"""The process's own record of its start and of its chunks (ISSUE 51).

`observability/trace.py::HostRecord` keeps a chip-owning process's host
spans on the wall clock from its first instruction, JAX's trace / lower /
compile events as intervals, and the collector's passes; the fused
launchers print it (`[<label>] start: ...` once, `[<label>] chunk <n>:
...` after every chunk) and `perfbench/start_read.py` turns a run's logs
into eleven per-layer metrics. Here:

- the record alone, on events handed to its listeners;
- ONE small launcher of each kind in a process of its own on the CPU (the
  three start together, once for the file), and what its log says;
- the benchmark's readers on those logs, on a log WITHOUT the record and
  on a run directory that holds a second process's log.
"""

import gc
import importlib.util
import json
import os
import re
import subprocess
import sys
import time

import pytest
from test_fused_setup import _SMALL

from distributed_reinforcement_learning_tpu.observability import scopes
from distributed_reinforcement_learning_tpu.observability.trace import (
    HostRecord,
    load_trace,
    merge_intervals,
    process_start_wall,
    split_by_first,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
TRACE, LOWER, COMPILE = HostRecord.KINDS  # JAX's event names, in that order


# -- the record alone -----------------------------------------------------------


def test_nested_and_overlapping_events_give_the_union_not_the_sum():
    rec = HostRecord(process_start=100.0)
    # one traced function of 10 s holding three nested ones, two that
    # overlap each other, one apart: 10 + 3 + 1 s of wall time, 22.5 summed
    for start, end, fun in [(1.0, 2.0, "inner"), (2.5, 4.0, "inner"),
                            (5.0, 9.0, "inner"), (0.0, 10.0, "chunk"),
                            (20.0, 22.0, "a"), (21.0, 23.0, "b"),
                            (30.0, 31.0, "c")]:
        rec._on_time_span(TRACE, 100.0 + start, 100.0 + end, fun_name=fun)
    rec._on_time_span(LOWER, 110.0, 112.0, fun_name="jit(chunk)")
    rec._on_time_span(COMPILE, 111.0, 115.0, fun_name="jit(chunk)")
    rec._on_time_span("/jax/other/duration", 0.0, 1e6, fun_name="x")
    seconds = rec.seconds()
    assert seconds["trace"] == pytest.approx(14.0)
    assert seconds["lower"] == pytest.approx(2.0)
    assert seconds["compile"] == pytest.approx(4.0)
    assert seconds["any"] == pytest.approx(14.0 + 5.0)  # 110..115 once
    assert rec.events == {"trace": 7, "lower": 1, "compile": 1}
    # a merged interval is named by its outermost event
    assert [iv[2] for iv in rec.intervals()["trace"]] == ["chunk", "a", "c"]


def test_the_union_survives_compaction_of_many_events():
    rec = HostRecord(process_start=0.0)
    n = 3 * HostRecord.COMPACT_EVERY + 7
    for i in range(n):  # disjoint halves of a second, then one over them all
        rec._on_time_span(TRACE, float(i), i + 0.5, fun_name="f")
    assert rec.seconds()["trace"] == pytest.approx(n / 2)
    rec._on_time_span(TRACE, 0.0, float(n), fun_name="outer")
    assert rec.seconds()["trace"] == pytest.approx(n)
    assert len(rec.intervals()["trace"]) == 1


def test_split_by_first_gives_every_instant_to_one_bucket():
    split = split_by_first(0.0, 20.0, [
        ("x", [(0.0, 5.0)]), ("y", [(3.0, 8.0), (18.0, 25.0)]),
        ("z", [(4.0, 9.0), (-3.0, 1.0)])])
    assert split == {"x": 5.0, "y": 5.0, "z": 1.0, "other": 9.0}
    assert sum(split.values()) == pytest.approx(20.0)
    assert merge_intervals([(3, 4, "b"), (0, 10, "a"), (12, 13, "c"),
                            (12.5, 14, "d")]) == [(0, 10, "a"), (12, 14, "c")]


def _chunk(rec, at, names=scopes.CHUNK_SPANS[:4]):
    for i, name in enumerate(names):
        parent = rec.enter(name)
        rec.leave(name, parent, at + i, 0.5)


def test_the_ring_keeps_the_starts_spans_and_drops_old_chunks():
    rec = HostRecord(process_start=0.0)
    rec.add(scopes.START_IMPORT, None, 0.0, 3.0)
    rec.add(scopes.START_INIT, None, 3.0, 2.0)
    rec._on_time_span(COMPILE, 5.0, 6.0, fun_name="jit(chunk)")
    _chunk(rec, 5.0)
    lines = rec.end_chunk("t")  # compiled: the start stays open
    assert len(lines) == 1 and rec.closed_at is None
    assert "compile x1 longest 1.00 s (jit(chunk))" in lines[0]
    _chunk(rec, 10.0)
    lines = rec.end_chunk("t")  # nothing compiled: this chunk closes it
    assert len(lines) == 2 and lines[1].startswith("[t] start: ")
    assert rec.closed_at is not None and rec.close_start("t") is None
    start_spans = list(rec.spans)
    for n in range(3 * HostRecord.CHUNK_RING):
        _chunk(rec, 20.0 + 5 * n)
        (line,) = rec.end_chunk("t")
    assert rec.spans == start_spans  # the start's, and no more
    assert [s[0] for s in start_spans[:2]] == [scopes.START_IMPORT,
                                               scopes.START_INIT]
    assert len(rec.chunks) == HostRecord.CHUNK_RING
    assert rec.chunks[-1]["chunk"] == 2 + 3 * HostRecord.CHUNK_RING
    chunk = json.loads(line[line.index("{"):])
    assert [s[0] for s in chunk["spans"]] == list(scopes.CHUNK_SPANS[:4])
    assert "compiled" not in chunk
    # a held chunk that was a recompile names itself, after the close too
    rec._on_time_span(TRACE, 900.0, 903.0, fun_name="_train_chunk")
    _chunk(rec, 900.0)
    (line,) = rec.end_chunk("t")
    assert "trace x1 longest 3.00 s (_train_chunk)" in line


def test_a_process_without_chunks_stays_bounded():
    rec = HostRecord(process_start=0.0)
    for i in range(4 * HostRecord.START_SPANS_MAX):
        rec.add("learn", None, float(i), 0.1)
    assert len(rec.spans) == HostRecord.START_SPANS_MAX
    assert len(rec._pending) == HostRecord.CHUNK_RING


def test_the_collectors_callback_is_gone_after_the_close():
    from jax._src import monitoring  # the public module cannot take a listener off

    rec = HostRecord()
    rec.begin()
    rec.begin()  # a second call registers nothing twice
    try:
        assert gc.callbacks.count(rec._on_gc) == 1
        assert monitoring.get_event_time_span_listeners().count(
            rec._on_time_span) == 1
        gc.collect()
        assert rec.gc_passes[2] >= 1 and rec.gc_seconds[2] > 0
        assert sum(rec.gc_by_second) == pytest.approx(sum(rec.gc_seconds))
        _chunk(rec, time.time())
        rec.end_chunk("t")
        assert rec.closed_at is not None
        assert rec._on_gc not in gc.callbacks
        # the compile listener stays: a later recompile is still seen
        assert rec._on_time_span in monitoring.get_event_time_span_listeners()
    finally:
        monitoring.unregister_event_time_span_listener(rec._on_time_span)
        monitoring.unregister_event_listener(rec._on_event)
        monitoring.unregister_event_duration_listener(rec._on_duration)
        rec._drop_gc_callback()


def test_the_process_start_is_the_kernels():
    started = process_start_wall()
    assert 0 < time.time() - started < 24 * 3600
    assert abs(process_start_wall() - started) < 0.05  # 10 ms ticks


# -- one small launcher of each kind, each in a process of its own --------------

_CALLS = {
    "anakin": ("train_anakin", "impala_cartpole",
               "num_updates=12, chunk=2, checkpoint_dir=sys.argv[3]"),
    "anakin-r2d2": ("train_anakin_r2d2", "r2d2", "num_updates=20, chunk=1"),
    "anakin-tokens": ("train_anakin_tokens", "ouro_looplm",
                      "num_updates=6, chunk=1, num_envs=4"),
}
_START_SPANS = {
    "anakin": (scopes.START_IMPORT, scopes.START_BACKEND, scopes.START_BUILD,
               scopes.START_INIT, scopes.START_RESTORE),
    "anakin-r2d2": (scopes.START_IMPORT, scopes.START_BACKEND,
                    scopes.START_BUILD, scopes.START_INIT,
                    scopes.START_RESTORE, scopes.START_WARM_COLLECT),
    "anakin-tokens": (scopes.START_IMPORT, scopes.START_BACKEND,
                      scopes.START_BUILD, scopes.START_INIT,
                      scopes.START_RESTORE),
}


@pytest.fixture(scope="module")
def logs(tmp_path_factory):
    """{label: {"log": path, "text", "start": record, "line", "chunks":
    [...], "telemetry": dir}}: the three launchers' logs, made together."""
    tmp = tmp_path_factory.mktemp("start_record")
    with open(os.path.join(ROOT, "config.json")) as f:
        config = json.load(f)
    config["ouro_looplm"] = dict(config["ouro_looplm"], envs_per_actor=4,
                                 dtype="float32", **_SMALL["ouro_looplm"])
    config_path = tmp / "config.json"
    config_path.write_text(json.dumps(config))
    procs = {}
    for label, (fn, section, kwargs) in _CALLS.items():
        code = ("import sys\n"
                "from distributed_reinforcement_learning_tpu.runtime import "
                "launch\n"
                f"launch.{fn}(sys.argv[1], sys.argv[2], {kwargs})\n")
        env = {**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu",
               "DRL_TELEMETRY_DIR": str(tmp / f"telemetry-{label}"),
               "XLA_FLAGS": ""}
        log = open(tmp / f"{label}.log", "w")
        procs[label] = (subprocess.Popen(
            [sys.executable, "-c", code, str(config_path), section,
             str(tmp / f"ckpt-{label}")],
            cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT), log)
    out = {}
    for label, (proc, log) in procs.items():
        try:
            rc = proc.wait(timeout=600)
        finally:
            proc.kill()
            log.close()
        text = (tmp / f"{label}.log").read_text()
        assert rc == 0, text[-3000:]
        starts = [ln for ln in text.splitlines() if f"[{label}] start: " in ln]
        chunks = [json.loads(ln[ln.index("{"):]) for ln in text.splitlines()
                  if re.match(rf"\[{label}\] chunk \d+: ", ln)]
        out[label] = {"log": str(tmp / f"{label}.log"), "text": text,
                      "lines": starts, "chunks": chunks,
                      "telemetry": str(tmp / f"telemetry-{label}"),
                      "start": json.loads(starts[0][starts[0].index("{"):])}
    return out


@pytest.mark.parametrize("label", list(_CALLS))
def test_the_start_line_is_printed_once_with_every_span(logs, label):
    run = logs[label]
    assert len(run["lines"]) == 1, run["text"][-2000:]
    start = run["start"]
    names = [s[0] for s in start["spans"]]
    for span in _START_SPANS[label]:
        assert names.count(span) == 1, (span, names)
    assert names.index(scopes.START_IMPORT) == 0
    by_name = {s[0]: s for s in start["spans"]}
    # the import runs from the kernel's start of the process
    assert by_name[scopes.START_IMPORT][2] == pytest.approx(
        start["process_start"], abs=1e-5)
    assert by_name[scopes.START_IMPORT][3] > 0.5  # the interpreter and JAX
    # each span where the work happens: in order, none inside another
    order = [by_name[n] for n in _START_SPANS[label][1:]]
    for a, b in zip(order, order[1:]):
        assert a[2] + a[3] <= b[2] + 1e-6, (a, b)
    # the chunk compiled inside the first dispatch, and the record saw it
    assert start["events"]["compile"] >= 1 and start["intervals"]["compile"]
    assert sum(start["gc"]["passes"]) > 0
    assert sum(start["gc"]["by_second"]) == pytest.approx(
        sum(start["gc"]["seconds"]), abs=1e-3)
    for kind in ("trace", "lower", "compile"):
        assert len(start["top"][kind]) <= HostRecord.TOP_FUNS
    assert any("chunk" in fun for fun, _ in start["top"]["trace"])


@pytest.mark.parametrize("label", list(_CALLS))
def test_the_lines_eight_buckets_sum_to_the_wall(logs, label):
    line = logs[label]["lines"][0]
    m = re.match(rf"\[{label}\] start: ([0-9.]+) s = (.*?) \{{", line)
    wall = float(m.group(1))
    buckets = dict(part.split(" ") for part in m.group(2).split(" + "))
    assert list(buckets) == ["import", "backend", "init", "trace", "lower",
                             "compile", "wait", "other"]
    assert sum(map(float, buckets.values())) == pytest.approx(wall, rel=0.01)
    start = logs[label]["start"]
    assert wall == pytest.approx(start["closed_at"] - start["process_start"],
                                 abs=0.01)
    # wall seconds: no kind can exceed the time it lies in
    for kind in ("trace", "lower", "compile"):
        assert sum(e - s for s, e in start["intervals"][kind]) <= wall


@pytest.mark.parametrize("label", list(_CALLS))
def test_every_chunk_has_its_spans_with_their_wall_start(logs, label):
    run = logs[label]
    want = list(scopes.CHUNK_SPANS[:4]) + (
        [scopes.CHECKPOINT] if label == "anakin" else [])
    steps = len(re.findall(rf"\[{re.escape(label)}\] step ", run["text"]))
    assert len(run["chunks"]) == steps >= 6
    assert [c["chunk"] for c in run["chunks"]] == list(range(1, steps + 1))
    for chunk in run["chunks"]:
        assert [s[0] for s in chunk["spans"]] == want
        for (_, w0, d0), (_, w1, _) in zip(chunk["spans"], chunk["spans"][1:]):
            assert w0 + d0 <= w1 + 1e-4  # on one clock, in order
    assert "compiled" in run["chunks"][0]  # the first dispatch compiles
    assert "compiled" not in run["chunks"][-1]
    # the chunk's line directly follows the program's own line of the chunk
    lines = [ln for ln in run["text"].splitlines() if ln.startswith(f"[{label}]")]
    for i, ln in enumerate(lines):
        if re.match(rf"\[{label}\] chunk \d+: ", ln):
            assert f"[{label}] step " in lines[i - 1]


def test_telemetrys_chrome_trace_begins_at_the_process_start(logs):
    run = logs["anakin"]
    events = [e for e in load_trace(os.path.join(
        run["telemetry"], "trace-anakin-0.json")) if e.get("ph") == "X"]
    names = [e["name"] for e in events]
    for span in _START_SPANS["anakin"]:
        assert names.count(span) == 1, (span, names)
    assert names.count(scopes.DISPATCH) == len(run["chunks"])
    first = min(events, key=lambda e: e["ts"])
    assert first["name"] == scopes.START_IMPORT
    assert first["ts"] == pytest.approx(run["start"]["process_start"] * 1e6,
                                        abs=10)


# -- the benchmark's readers ----------------------------------------------------


def _bench(module: str, kind: str = ""):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)  # the reducers import their neighbours
    path = os.path.join(BENCH, kind, f"{module}.py")
    spec = importlib.util.spec_from_file_location(f"perfbench_{module}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
NEW = ("start_import_s", "start_backend_s", "start_init_s", "start_trace_s",
       "start_lower_s", "start_compile_s", "start_warm_wait_s",
       "start_other_s", "start_gc_s", "loop_host_ms_per_chunk",
       "chunk_wall_max_over_median")


def _reduce(facts: dict) -> dict:
    out = {}
    for name in NEW:
        with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
            spec = json.load(f)
        out[name] = _bench(spec["reducer"], "reducers").reduce(facts, spec)
    return out


def _run_dir(tmp_path, run: dict, others: tuple = ()) -> dict:
    """A run directory as a mode leaves it: `config.json`, the timed
    child's log, and `t0` inside the dispatch of its third chunk (where the
    benchmark's observer opens the window after two warm chunks)."""
    (tmp_path / "config.json").write_text("{}")
    start = run["start"]["process_start"]
    os.utime(tmp_path / "config.json", (start - 0.25, start - 0.25))
    for i, path in enumerate((run["log"], *others)):
        with open(path) as f:
            (tmp_path / f"child{i}.log").write_text(f.read())
    third = {s[0]: s for s in run["chunks"][2]["spans"]}
    t0 = third[scopes.DISPATCH][1] + 1e-4
    last = run["chunks"][-1]["spans"][-1]
    return {"run_dir": str(tmp_path), "t0": t0, "t1": last[1] + last[2],
            "data_dir": BENCH, "notes": [],
            "chunk_seconds": [0.4, 0.5, 0.4, 1.2]}


@pytest.mark.parametrize("label", list(_CALLS))
def test_the_readers_buckets_sum_to_t0_less_the_process_start(
        logs, label, tmp_path):
    facts = _run_dir(tmp_path, logs[label])
    got = _reduce(facts)
    assert all(isinstance(v, float) and v >= 0 for v in got.values()), got
    eight = [got[n] for n in NEW[:8]]
    assert sum(eight) == pytest.approx(
        facts["t0"] - logs[label]["start"]["process_start"], abs=1e-6)
    by_name = {s[0]: s for s in logs[label]["start"]["spans"]}
    assert got["start_import_s"] == pytest.approx(
        by_name[scopes.START_IMPORT][3], abs=1e-5)
    assert got["start_backend_s"] == pytest.approx(
        by_name[scopes.START_BACKEND][3], abs=1e-5)
    assert got["start_init_s"] == pytest.approx(sum(
        by_name[n][3] for n in _START_SPANS[label][2:]), abs=1e-4)
    # the chunk's trace, lowering and compile lie in the first dispatch
    assert got["start_trace_s"] > 0 and got["start_compile_s"] > 0
    # two chunks were waited for before t0
    waits = [s[2] for c in logs[label]["chunks"][:2] for s in c["spans"]
             if s[0] == scopes.WAIT]
    assert got["start_warm_wait_s"] == pytest.approx(sum(waits), abs=1e-4)
    assert 0 <= got["start_gc_s"] <= sum(logs[label]["start"]["gc"]["seconds"]) + 1e-3
    # the window's chunks: those dispatched after t0, the fourth and later
    window = logs[label]["chunks"][3:]
    host = [sum(s[2] for s in c["spans"] if s[0] != scopes.WAIT)
            for c in window]
    assert got["loop_host_ms_per_chunk"] == pytest.approx(
        1e3 * sum(host) / len(host), rel=1e-6)
    assert got["chunk_wall_max_over_median"] >= 1.0
    assert any("held" in n and "over its median" in n for n in facts["notes"])


def test_the_profilers_own_chunks_are_marked_and_the_glue_is_counted(
        logs, tmp_path):
    run = logs["anakin-r2d2"]
    facts = _run_dir(tmp_path, run)
    start_read = _bench("start_read")
    chunks = start_read.window_chunks(facts)
    assert len(chunks) == len(run["chunks"]) - 3
    assert not any(c["profiler"] for c in chunks)
    # a chunk runs to the next chunk's step_read: the glue is `between`
    fourth, fifth = ({s[0]: s for s in c["spans"]} for c in run["chunks"][3:5])
    assert chunks[0]["wall"] == fourth[scopes.STEP_READ][1]
    assert chunks[0]["seconds"] == pytest.approx(
        fifth[scopes.STEP_READ][1] - fourth[scopes.STEP_READ][1], abs=1e-6)
    assert chunks[0]["spans"]["between"] == pytest.approx(
        fifth[scopes.STEP_READ][1]
        - (fourth[scopes.REPORT][1] + fourth[scopes.REPORT][2]), abs=1e-6)
    assert "between" not in chunks[-1]["spans"]  # nothing follows the last
    # the harness's profiler starts inside the fifth chunk's dispatch
    facts = _run_dir(tmp_path, run)
    facts["trace"] = {"start_wall": fifth[scopes.DISPATCH][1] + 1e-5,
                      "stop_wall": None}
    marked = [c for c in start_read.window_chunks(facts) if c["profiler"]]
    assert [c["wall"] for c in marked] == [fifth[scopes.STEP_READ][1]]
    # ... so that dispatch is left out of the dispatches' mean, and the
    # chunk out of the ratio
    got = _reduce(facts)
    window = run["chunks"][3:]
    mean = lambda name, skip=(): sum(  # noqa: E731
        s[2] for i, c in enumerate(window) for s in c["spans"]
        if s[0] == name and i not in skip) / (len(window) - len(skip))
    assert got["loop_host_ms_per_chunk"] == pytest.approx(1e3 * (
        mean(scopes.STEP_READ) + mean(scopes.DISPATCH, skip=(1,))
        + mean(scopes.REPORT)), rel=1e-6)
    assert any(f"{len(window) - 1} chunks" in n for n in facts["notes"])


def test_a_gap_between_two_chunks_is_named(logs, tmp_path):
    """What a held chunk looks like when no span held it: the loop's glue
    (seen on the chip in PR 51: 0.111 s between two R2D2 chunks of 0.31)."""
    run = logs["anakin-tokens"]
    late = _later(run["log"], 0.0, tmp_path / "same.txt")
    lines = open(late).read().splitlines(keepends=True)
    out, shift = [], 0.0
    for line in lines:
        if re.match(r"\[[^\]]+\] chunk \d+: ", line):
            head, rec = line[:line.index("{")], json.loads(line[line.index("{"):])
            if rec["chunk"] == 6:
                shift = 0.75  # the sixth chunk starts that much later
            for span in rec["spans"]:
                span[1] += shift
            line = head + json.dumps(rec) + "\n"
        out.append(line)
    (tmp_path / "held.txt").write_text("".join(out))
    held = dict(run, log=str(tmp_path / "held.txt"))
    facts = _run_dir(tmp_path, held)
    got = _reduce(facts)
    assert got["chunk_wall_max_over_median"] > 2.0
    assert any("between held 0.7" in n for n in facts["notes"]), facts["notes"]


def _later(log_path: str, by: float, into) -> str:
    """The log of a process that did the same `by` seconds later."""
    out = []
    with open(log_path) as f:
        for line in f:
            if re.match(r"\[[^\]]+\] (start|chunk \d+): ", line):
                head, rec = line[:line.index("{")], json.loads(
                    line[line.index("{"):])
                for key in ("process_start", "closed_at"):
                    if key in rec:
                        rec[key] += by
                for span in rec["spans"]:
                    span[-2] += by
                line = head + json.dumps(rec) + "\n"
            out.append(line)
    into.write_text("".join(out))
    return str(into)


@pytest.mark.parametrize("label", ["anakin-tokens", "anakin-r2d2"])
def test_the_timed_process_is_chosen_of_two(logs, label, tmp_path):
    """A token cell's run directory holds a second process's log (the
    reference check: it starts after the window, runs the same launcher
    up to its first chunk and prints a start line of its own): its record
    is not the one that is read, whichever file sorts first."""
    timed = logs[label]
    check = _later(logs["anakin"]["log"], 3600.0, tmp_path / "later.txt")
    start_read = _bench("start_read")
    for others in ((check,), ()):
        facts = _run_dir(tmp_path, timed, others=others)
        if others:  # `a_check.log` sorts before `child0.log`
            os.rename(tmp_path / "child1.log", tmp_path / "a_check.log")
            assert len(start_read._parse_log(
                str(tmp_path / "a_check.log"))["starts"]) == 1
        record = start_read.log_record(facts)
        assert record["start"]["pid"] == timed["start"]["pid"]
        assert record["log"].endswith("child0.log")
    # a run whose only record is of a process that started after t0: none
    facts = _run_dir(tmp_path, timed)
    facts["t0"] = timed["start"]["process_start"] - 1.0
    assert start_read.log_record(facts) is None


def test_a_log_without_the_record_reads_numbers_not_none(tmp_path):
    """The parent of PR 51 under these files: every named bucket 0.0, the
    whole interval in `other`, the ratio from the observer's seconds."""
    (tmp_path / "config.json").write_text("{}")
    now = time.time()
    os.utime(tmp_path / "config.json", (now - 60.0, now - 60.0))
    (tmp_path / "anakin.log").write_text(
        "[anakin] device: {'platform': 'cpu'}\n"
        "[anakin] step 8: mean_return 21.5 (86 episodes, loss 2506.25)\n")
    facts = {"run_dir": str(tmp_path), "t0": now - 10.0, "t1": now,
             "data_dir": BENCH, "notes": [], "chunk_updates": 2,
             "trace_updates": 4, "chunk_seconds": [0.4, 0.5, 0.4, 1.2],
             "trace": {"window_s": 1.0, "busy_s": 0.98},
             "scope_recording": {"hlo_stats": [], "host_spans": []}}
    got = _reduce(facts)
    assert got["start_other_s"] == pytest.approx(50.0, abs=1e-3)
    assert [got[n] for n in NEW[:7]] == [0.0] * 7 and got["start_gc_s"] == 0.0
    assert got["chunk_wall_max_over_median"] == pytest.approx(1.2 / 0.45)
    # no span on the host plane: the traced interval's idle time a chunk
    assert got["loop_host_ms_per_chunk"] == pytest.approx(1e3 * 0.02 / 2)
    assert sum("start_*: this program prints no record" in n
               for n in facts["notes"]) == 1
    # ... and from the profile's host plane where the converter hands it back
    facts["_scope_read"] = {}
    facts["scope_recording"]["host_spans"] = [
        ["anakin/dispatch", 0.0, 1500.0], ["anakin/report", 0.0, 500.0],
        ["anakin/wait", 0.0, 9e5]]
    assert _reduce(facts)["loop_host_ms_per_chunk"] == pytest.approx(2.0)
    # a loop without chunks (the host loop's cells) still reads a number
    del facts["chunk_seconds"]
    assert _reduce(facts)["chunk_wall_max_over_median"] == 1.0


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_a_traced_line_of_each_cell_passes_with_the_eleven(logs, cell, tmp_path):
    contract = _bench("contract")
    want = contract.cell_metrics(BENCHMARK, cell, traced=True)
    assert set(NEW) <= set(want)  # no `workloads` key: every cell
    for name in NEW:
        meta = want[name]
        assert meta["source"] == "program_span" and meta["better"] == "lower"
        assert meta["moves"] == ("setup_s" if name.startswith("start_")
                                 else "frames_learned_per_s")
    label = ("anakin" if cell.startswith("impala") else
             "anakin-r2d2" if cell.startswith("r2d2") else "anakin-tokens")
    got = _reduce(_run_dir(tmp_path, logs[label]))
    metrics = {name: {"value": 1.0, "unit": meta["unit"]}
               for name, meta in want.items()}
    metrics.update({name: {"value": got[name], "unit": want[name]["unit"]}
                    for name in NEW})
    line = {"correct": True, "attempted": 4, "failed": 0, "metrics": metrics,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 1, "busy_s": 0.9, "window_s": 1.0}}
    contract.check_line(line, BENCHMARK, cell, traced=True, chips=1)
    del line["metrics"]["start_gc_s"]
    with pytest.raises(contract.ContractError, match="start_gc_s"):
        contract.check_line(line, BENCHMARK, cell, traced=True, chips=1)

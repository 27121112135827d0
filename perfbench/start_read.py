"""A run's start and its chunks, read from the program's own log.

Since PR 51 the process that owns the chip keeps its host spans on the
wall clock from its first instruction (`distributed_reinforcement_
learning_tpu/observability/trace.py`, `HostRecord`) and a fused launcher
prints them: one line `[<label>] start: ... {json}` when its start
closes (the spans `start/import`, `start/backend`, `start/build`,
`start/init`, `start/restore`, `start/warm_collect` and the first
chunks'; JAX's trace / lower / compile events as merged intervals in
seconds since `process_start`; the collector's seconds by whole second
since `process_start`), and one line `[<label>] chunk <n>: ... {json}`
after every chunk (`anakin/step_read`, `dispatch`, `wait`, `report`,
`checkpoint`, each `[name, wall_start_s, duration_s]`). This module turns
the `*.log` under `facts["run_dir"]` into the numbers of the start-up and
host-side metrics. No JAX here: the reducers run in `run.py`'s parent.

`setup_s` is `t0 - run.py's own start`; what is read here is `[process
start of the timed child, t0]`, the same interval less the parent's few
hundred ms. Every instant of it goes to ONE bucket, first rule that
applies: inside `start/import`; inside `start/backend`; inside
`start/build|init|restore|warm_collect`; else inside a trace, a lower or
a compile interval, in that order; else inside an `anakin/wait`; else
`other`. The eight buckets sum to `t0 - process start`.

A program WITHOUT the record (every commit before PR 51) has no second
of its start under a span of its own: the named buckets read 0.0 and
`other` the whole interval from the child's start, as the run's files
show it (the mode writes `<run_dir>/config.json` just before it starts
the child), to `t0`. Each fallback says so in a note.

A test hands a log by putting it under `facts["run_dir"]`.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re

INIT_SPANS = ("start/build", "start/init", "start/restore",
              "start/warm_collect")
WAIT = "anakin/wait"
BUCKETS = ("import", "backend", "init", "trace", "lower", "compile",
           "warm_wait", "other")
_START = re.compile(r"^\[[^\]]+\] start: .*?(\{.*\})\s*$")
_CHUNK = re.compile(r"^\[[^\]]+\] chunk \d+: .*?(\{.*\})\s*$")


def _note(facts: dict, text: str) -> None:
    notes = facts.setdefault("notes", [])
    if text not in notes:
        notes.append(text)


def _parse_log(path: str) -> dict:
    """{"starts": [record, ...], "chunks": [[[name, wall, dur], ...], ...]}
    of one log; a line that does not parse is not the program's."""
    starts, chunks = [], []
    with open(path, errors="replace") as f:
        for line in f:
            for pattern, into in ((_START, starts), (_CHUNK, chunks)):
                m = pattern.match(line)
                if m:
                    try:
                        into.append(json.loads(m.group(1)))
                    except json.JSONDecodeError:
                        pass
    return {"starts": [s for s in starts if "process_start" in s],
            "chunks": [c["spans"] for c in chunks if "spans" in c]}


def log_record(facts: dict) -> dict | None:
    """The timed process's record: of the `*.log` under `run_dir` that
    hold a start line, the one whose spans bracket `t0` (the token cells'
    second process starts after the window; of several, the last to
    start). -> {"start": the start's record, "chunks": every chunk's
    spans, "log": its path}; None where no log holds one."""
    cache = facts.setdefault("_start_read", {})
    if "record" not in cache:
        best, t0 = None, facts.get("t0")
        for path in sorted(glob.glob(os.path.join(
                facts.get("run_dir") or "", "*.log"))) if t0 else ():
            parsed = _parse_log(path)
            last = max((w + d for c in parsed["chunks"] for _, w, d in c),
                       default=0.0)
            for start in parsed["starts"]:
                if start["process_start"] <= t0 <= max(
                        last, start["closed_at"]) and (
                        best is None or start["process_start"]
                        > best["start"]["process_start"]):
                    best = {"start": start, "chunks": parsed["chunks"],
                            "log": path}
        cache["record"] = best
    return cache["record"]


def _merge(intervals) -> list[tuple]:
    out: list[list] = []
    for lo, hi in sorted((iv[0], iv[1]) for iv in intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [tuple(iv) for iv in out]


def split_by_first(a: float, b: float, layers: list[tuple]) -> dict:
    """Seconds of `[a, b]` by the first of `layers` (`(name, [(start,
    end), ...])`, in order of precedence) that covers each instant;
    `other` is what none covers."""
    merged = [(name, _merge(ivs)) for name, ivs in layers]
    edges = sorted({a, b, *(min(max(t, a), b) for _, ivs in merged
                            for iv in ivs for t in iv)})
    out = {name: 0.0 for name, _ in merged}
    out["other"] = 0.0
    for lo, hi in zip(edges, edges[1:]):
        mid = (lo + hi) / 2
        for name, ivs in merged:
            i = bisect.bisect_right(ivs, (mid, float("inf"))) - 1
            if i >= 0 and ivs[i][1] > mid:
                out[name] += hi - lo
                break
        else:
            out["other"] += hi - lo
    return out


def _all_spans(record: dict) -> list:
    """[name, wall, dur] of the start's spans and of every chunk's line,
    each once (the start's record holds its own chunks' spans too)."""
    seen, out = set(), []
    spans = [[s[0], s[2], s[3]] for s in record["start"]["spans"]]
    for name, wall, dur in spans + [s for c in record["chunks"] for s in c]:
        if (name, wall) not in seen:
            seen.add((name, wall))
            out.append([name, wall, dur])
    return out


def child_start(facts: dict) -> float:
    """The wall-clock second the timed child started at, as the run's
    files show it: the mode writes `config.json` just before."""
    path = os.path.join(facts.get("run_dir") or "", "config.json")
    try:
        return min(os.path.getmtime(path), facts["t0"])
    except OSError:
        return facts["t0"]


def start_buckets(facts: dict) -> dict:
    """{bucket: seconds} over `[process start, t0]`, `gc` beside them
    (the collector's seconds before `t0`; it overlaps the eight)."""
    cache = facts.setdefault("_start_read", {})
    if "buckets" in cache:
        return cache["buckets"]
    record, t0 = log_record(facts), facts.get("t0")
    if record is None:
        _note(facts, "start_*: this program prints no record of its start "
                     "(before PR 51): no second of it is under a span of "
                     "its own, start_other_s is config.json's mtime -> t0"
              if t0 else "start_*: these facts hold no t0: nothing to read")
        out = {name: 0.0 for name in BUCKETS}
        out["other"] = t0 - child_start(facts) if t0 else 0.0
        out["gc"] = 0.0
        cache["buckets"] = out
        return out
    start = record["start"]
    p0 = start["process_start"]
    spans = _all_spans(record)

    def of(*names):
        return [(w, w + d) for n, w, d in spans if n in names]

    kinds = {kind: [(p0 + lo, p0 + hi) for lo, hi in ivs]
             for kind, ivs in start["intervals"].items()}
    out = split_by_first(p0, t0, [
        ("import", of("start/import")), ("backend", of("start/backend")),
        ("init", of(*INIT_SPANS)), ("trace", kinds.get("trace", [])),
        ("lower", kinds.get("lower", [])),
        ("compile", kinds.get("compile", [])), ("warm_wait", of(WAIT))])
    whole, part = divmod(t0 - p0, 1.0)
    by_second = start["gc"]["by_second"]
    out["gc"] = sum(by_second[:int(whole)]) + (
        by_second[int(whole)] * part if int(whole) < len(by_second) else 0.0)
    if start["closed_at"] < t0:
        _note(facts, f"start_*: the start closed "
                     f"{t0 - start['closed_at']:.3f} s before t0; compile "
                     f"events and collector passes after that are not in "
                     f"the record")
    cache["buckets"] = out
    return out


def traced_instants(facts: dict) -> list[float]:
    """Where the harness's own profiler started and stopped: both calls
    are made at a chunk's entry, inside the program's dispatch span."""
    trace = facts.get("trace") or {}
    return [trace[k] for k in ("start_wall", "stop_wall") if trace.get(k)]


def window_chunks(facts: dict) -> list[dict] | None:
    """The window's chunks by the program's own spans, in order:
    [{"wall": start, "seconds": extent, "spans": {name: seconds},
    "profiler": bool}, ...]: the chunks dispatched at or after `t0` (the
    observer opens the window INSIDE a dispatch, after its own opening
    work: that chunk's host side is set-up). A chunk runs from its
    `step_read` to the next chunk's, so `spans` holds `between` too: the
    loop's glue after the last span, which no span covers (the chunk's
    own line, the profiler's `on_step`, a collector pass). `profiler`
    marks the chunks in which the harness's own profiler started or
    stopped (inside their dispatch). None without a record or where the
    log holds no such chunk."""
    record = log_record(facts)
    if record is None:
        return None
    out = []
    chunks = [c for c in record["chunks"] if c]
    for spans, after in zip(chunks, chunks[1:] + [None]):
        dispatched = [w for n, w, _ in spans if n == "anakin/dispatch"]
        if not dispatched or dispatched[0] < facts["t0"]:
            continue
        lo = min(w for _, w, _ in spans)
        hi = max(w + d for _, w, d in spans)
        by_name: dict[str, float] = {}
        for name, _, dur in spans:
            by_name[name] = by_name.get(name, 0.0) + dur
        if after is not None:
            by_name["between"] = max(0.0, min(w for _, w, _ in after) - hi)
            hi += by_name["between"]
        out.append({"wall": lo, "seconds": hi - lo, "spans": by_name,
                    "profiler": any(lo <= t <= hi
                                    for t in traced_instants(facts))})
    return out or None

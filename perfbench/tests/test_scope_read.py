"""The readers of the program's scopes and spans (`scope_read.py` and
the reducers over it) on a recording of the chip's traced run: every
number the run printed is found again, the split adds up to the device's
busy time, and a recording without scopes reads as one."""

import copy
import json
import os

import pytest

import contract
import discover
import run
import scope_read

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(BENCH_DIR, "tests", "fixtures")
CELL = "impala_nature.anakin"
SPLIT = ("render_ms_per_update", "env_dynamics_ms_per_update",
         "act_ms_per_update", "layout_ms_per_update", "learn_ms_per_update")
WAITING = SPLIT + ("learn_step_mfu", "host_ms_per_chunk")
NEW = WAITING + ("device_unscoped_share",)


@pytest.fixture(scope="module")
def recording() -> dict:
    with open(os.path.join(FIXTURES, "impala_nature.anakin.scopes.json")) as f:
        return json.load(f)


def _facts(recording: dict) -> dict:
    with open(os.path.join(BENCH_DIR, "configs", "impala_nature.json")) as f:
        cfg = json.load(f)
    return {**copy.deepcopy(recording["facts"]), "data_dir": BENCH_DIR,
            "section": cfg[cfg["section"]],
            "scope_recording": recording["scope_recording"]}


@pytest.fixture()
def facts(recording) -> dict:
    return _facts(recording)


@pytest.fixture()
def unscoped_facts(recording) -> dict:
    """The same run as the parent commit's program would leave it: the
    same ops and times, no name of the vocabulary in any path."""
    rec = copy.deepcopy(recording)
    names = sorted(scope_read.vocabulary(BENCH_DIR), key=len, reverse=True)
    for row in rec["scope_recording"]["hlo_stats"]:
        for name in names:
            row[1] = row[1].replace(name + "/", "").replace(f"({name})", "(f)")
    rec["scope_recording"]["host_spans"] = [
        s for s in rec["scope_recording"]["host_spans"]
        if not s[0].startswith("anakin/")]
    return _facts(rec)


def _reduce(name: str, facts: dict):
    spec = discover.data(BENCH_DIR, "layer_metrics", name)
    return discover.module(BENCH_DIR, "reducers", spec["reducer"]).reduce(
        facts, spec)


@pytest.fixture(scope="module")
def bench_with_waiting(bench) -> dict:
    """BENCHMARK.json plus the entries that wait in scoped_entries.json,
    as the benchmark PR that lists them would leave it."""
    with open(os.path.join(FIXTURES, "scoped_entries.json")) as f:
        waiting = json.load(f)["per_layer"]
    assert sorted(m["name"] for m in waiting) == sorted(WAITING)
    return {**bench, "per_layer": bench["per_layer"] + waiting}


@pytest.mark.parametrize("name", NEW)
def test_reducer_gives_the_number_the_run_printed(facts, recording, name):
    assert _reduce(name, facts) == pytest.approx(
        recording["printed"][name], rel=1e-9)


def test_the_split_adds_up_to_the_devices_busy_time(facts):
    device_ms = _reduce("device_ms_per_update", facts)
    parts = sum(_reduce(name, facts) for name in SPLIT)
    unscoped = _reduce("device_unscoped_share", facts) / 100.0 * device_ms
    assert parts + unscoped == pytest.approx(device_ms, rel=0.01)
    assert _reduce("device_unscoped_share", facts) < 10.0
    assert (_reduce("learn_mfu", facts) < _reduce("learn_step_mfu", facts)
            < 105.0)


@pytest.mark.parametrize("name", WAITING)
def test_a_recording_without_scopes_reads_nothing(unscoped_facts, name):
    assert _reduce(name, unscoped_facts) is None


def test_a_recording_without_scopes_is_all_unscoped(unscoped_facts):
    # the table's self times over the trace's own busy seconds: 99.998 on
    # the chip for the parent commit's program (my chip run, PR 24)
    assert _reduce("device_unscoped_share", unscoped_facts) == pytest.approx(
        100.0, rel=1e-3)


def test_no_profile_reads_nothing(facts):
    del facts["scope_recording"]
    facts["run_dir"] = "/nonexistent"
    assert all(_reduce(name, facts) is None for name in NEW)


@pytest.mark.parametrize("path,scope", [
    ("jit(f)/while/body/collect/while/body/closed_call/collect/env/jit(step)"
     "/collect/env/render/vmap()/dot_general:", "collect/env/render"),
    ("jit(f)/collect/while/body/closed_call/collect/env/jit(step)/jit(_where)"
     "/select_n:", "collect/env"),
    ("jit(f)/collect/while/body/dynamic_update_slice", "collect"),
    ("jit(f)/learn/transpose(jvp(learn/loss))/torso/conv_general_dilated",
     "learn/loss"),
    ("jit(f)/learn/jvp(learn/loss)/learn/vtrace/jit(cumsum)/loss/add",
     "learn/vtrace"),
    ("jit(f)/while/body/closed_call/relearn/recollect/add", None),
    ("jit(f)/while:", None),
    ("", None),
])
def test_scope_of_is_the_deepest_whole_name(path, scope):
    names = ["collect", "collect/act", "collect/env", "collect/env/render",
             "learn", "learn/loss", "learn/vtrace", "to_batch_major"]
    assert scope_read.scope_of(path, names) == scope


def test_a_deeper_scope_is_a_file_and_its_parent_still_counts_it(
        data_copy, recording):
    """A later metric on `learn/loss` alone: one new file; `learn` keeps
    the whole of it."""
    dd = data_copy["dir"]
    facts = {**_facts(recording), "data_dir": dd}
    before = _reduce("learn_ms_per_update", facts)
    with open(os.path.join(dd, "layer_metrics", "loss_ms.json"), "w") as f:
        json.dump({"reducer": "scope_ms_per_update",
                   "source_detail": {"scopes": ["learn/loss"]}}, f)
    assert "learn/loss" in scope_read.vocabulary(dd)
    facts = {**_facts(recording), "data_dir": dd}
    spec = discover.data(dd, "layer_metrics", "loss_ms")
    loss = discover.module(dd, "reducers", spec["reducer"]).reduce(facts, spec)
    assert 0 < loss < before
    spec = discover.data(dd, "layer_metrics", "learn_ms_per_update")
    assert discover.module(dd, "reducers", spec["reducer"]).reduce(
        facts, spec) == pytest.approx(before)


def test_host_ms_per_chunk_leaves_out_the_wait_for_the_device(facts):
    spans = facts["scope_recording"]["host_spans"]
    wait_ms = [d / 1e3 for n, _s, d in spans if n == "anakin/wait"]
    assert wait_ms and min(wait_ms) > 1000  # a chunk of device work
    assert _reduce("host_ms_per_chunk", facts) < 50


def test_the_traced_line_holds_old_and_new_metrics(facts, bench_with_waiting,
                                                   recording):
    """`run.layer_metrics` over the recording, through the contract: the
    four metrics the cell had and the eight of ISSUE 24."""
    notes = []
    facts["setup_monitoring"] = {"seconds": {
        "/jax/core/compile/backend_compile_duration":
            recording["printed"]["compile_s"]}}
    metrics = run.layer_metrics(bench_with_waiting, BENCH_DIR, CELL, facts,
                                notes)
    assert notes == [] and len(metrics) == 12
    trace = facts["trace"]
    line = {"correct": True, "attempted": 80, "failed": 0, "metrics": metrics,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 8268448256, **trace}}
    contract.check_line(line, bench_with_waiting, CELL, traced=True, chips=1)


def test_the_committed_benchmark_lists_only_what_reads_on_any_program(bench):
    """Of the new readers only `device_unscoped_share` has a value on a
    program without scopes; the others return None there, which would
    fail the traced run of a parent under these files."""
    listed = {m["name"] for m in bench["per_layer"]}
    assert "device_unscoped_share" in listed
    assert not listed & set(WAITING)

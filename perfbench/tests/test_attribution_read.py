"""The per-layer metrics that read the program's own ledger
(`attribution_read.py`, `reducers/resolved_scope_ms_per_update.py`,
`reducers/unresolved_share.py`) on recordings cut from the chip's traced
runs of PR 34: the decode body's async copies of the granite cell with
their consumers, and the unpack's passes of the R2D2 cell. Each fixture
holds the rows of the chosen ops, the part of the module's optimized HLO
that the resolver walks to place them, where the WHOLE run's resolver put
them (`placed`), and what the reducers make of the cut (`printed`)."""

import copy
import json
import os

import pytest

import attribution_read
import contract
import discover
import run
import scope_read

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(BENCH_DIR, "tests", "fixtures")
NEW = ("hybridlm_decode_resolved_ms_per_update",
       "hybridlm_state_staging_ms_per_update",
       "replay_io_resolved_ms_per_update", "device_unresolved_share")
LISTED_NOW = ("render_ms_per_update", "env_dynamics_ms_per_update",
              "act_ms_per_update", "layout_ms_per_update",
              "learn_ms_per_update", "learn_step_mfu", "host_ms_per_chunk")
GRANITE = "granite_hybrid.decode_copies.json"
R2D2 = "r2d2_atari.unpack_passes.json"
IMPALA = "impala_nature.anakin.scopes.json"


def _recording(name: str) -> dict:
    with open(os.path.join(FIXTURES, name)) as f:
        return json.load(f)


def _facts(recording: dict, **more) -> dict:
    return {**copy.deepcopy(recording["facts"]), "data_dir": BENCH_DIR,
            "scope_recording": recording["scope_recording"], **more}


def _reduce(name: str, facts: dict):
    spec = discover.data(BENCH_DIR, "layer_metrics", name)
    return discover.module(BENCH_DIR, "reducers", spec["reducer"]).reduce(
        facts, spec)


@pytest.mark.parametrize("name", NEW)
def test_new_layer_metric_names_an_existing_reducer(bench, name):
    spec = discover.data(BENCH_DIR, "layer_metrics", name)
    assert set(spec) == {"reducer", "source_detail"}
    assert os.path.exists(os.path.join(BENCH_DIR, "reducers",
                                       f"{spec['reducer']}.py"))
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["source"] == "device_trace"
    assert entry["moves"] == "frames_learned_per_s"
    cells = {w["name"] for w in bench["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    # no new name in the vocabulary: the old readers see what they saw
    names = scope_read.vocabulary(BENCH_DIR)
    for key in ("scopes", "less"):
        assert set(spec["source_detail"].get(key, ())) <= set(names)


def test_the_benchmark_lists_what_waited_since_pr_24(bench):
    """The seven readers of the program's scopes and spans that waited in
    `fixtures/scoped_entries.json` are entries now (every parent a PR can
    meet has the scopes); `host_ms_per_chunk` in the three cells whose
    traced run gives the host plane back (the granite cell's holds one
    `anakin/wait` and nothing else: it waits there, PERF.md section 7);
    the unresolved share in all four beside each cell's unscoped one."""
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert set(LISTED_NOW) <= set(listed)
    cells = [w["name"] for w in bench["workloads"]]
    assert listed["host_ms_per_chunk"]["workloads"] == cells[:3]
    assert listed["device_unresolved_share"]["workloads"] == cells
    with open(os.path.join(FIXTURES, "scoped_entries.json")) as f:
        waiting = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in LISTED_NOW[:-1]:
        assert listed[name] == waiting[name]
    for m in bench["per_layer"]:  # no second truth in a metric's file
        assert set(discover.data(BENCH_DIR, "layer_metrics", m["name"])) == {
            "reducer", "source_detail"}


@pytest.mark.parametrize("fixture", [GRANITE, R2D2])
def test_the_cut_resolves_as_the_whole_run_did(fixture):
    rec = _recording(fixture)
    resolver = attribution_read.resolver()
    names = scope_read.vocabulary(BENCH_DIR)
    (text,) = rec["scope_recording"]["hlo_text"].values()
    placed = resolver.resolve(resolver.parse_hlo(text), names)
    assert rec["placed"] and len(rec["placed"]) == len(
        rec["scope_recording"]["hlo_rows"])
    for op, (scope, rule) in rec["placed"].items():
        assert placed[op] == (scope, rule), op


def test_granite_decode_copies_go_to_the_scope_they_serve():
    """The decode body's `copy-done` / `slice-done` waits carry no name
    of their own; the ledger gives them to their consumer or producer,
    the decode metric holds them and the staging metric is those that
    land under `collect/act/ssm`."""
    rec = _recording(GRANITE)
    facts = _facts(rec)
    for name in NEW[:2] + NEW[3:]:
        assert _reduce(name, facts) == pytest.approx(rec["printed"][name],
                                                     rel=1e-9), name
    staging = _reduce("hybridlm_state_staging_ms_per_update", facts)
    resolved = _reduce("hybridlm_decode_resolved_ms_per_update", facts)
    own = _reduce("hybridlm_decode_ms_per_update", facts)
    assert 0 < staging <= resolved - own
    rules = {rule for _scope, rule in rec["placed"].values()}
    assert "serves" in rules
    kinds = {row[1].split(".")[0] for row in rec["scope_recording"]["hlo_rows"]}
    assert {"copy-done", "slice-done"} <= kinds


def test_r2d2_unpack_passes_go_to_the_ring():
    rec = _recording(R2D2)
    facts = _facts(rec)
    resolved = _reduce("replay_io_resolved_ms_per_update", facts)
    assert resolved == pytest.approx(
        rec["printed"]["replay_io_resolved_ms_per_update"], rel=1e-9)
    assert resolved > (_reduce("replay_io_ms_per_update", facts) or 0.0)
    assert _reduce("device_unresolved_share", facts) == pytest.approx(
        rec["printed"]["device_unresolved_share"], abs=1e-12)


@pytest.mark.parametrize("fixture, unscoped", [
    (GRANITE, "hybridlm_unscoped_share"), (R2D2, "replay_unscoped_share"),
    (IMPALA, "device_unscoped_share")])
def test_a_program_without_a_resolver_reads_its_own_names(fixture, unscoped):
    """The parent commit under these files: the ledger is the own view,
    so the resolved metrics equal the ones that read own names, the
    staging is 0 and the unresolved share is the unscoped one: numbers,
    not None (`contract.check_line` fails a line that lacks a metric)."""
    facts = _facts(_recording(fixture), resolver_path="/nonexistent")
    facts.setdefault("trace", {"busy_s": 1.0, "window_s": 1.0})
    assert attribution_read.resolver("/nonexistent") is None
    assert _reduce("hybridlm_decode_resolved_ms_per_update", facts) == \
        pytest.approx(_reduce("hybridlm_decode_ms_per_update", facts) or 0.0)
    assert _reduce("replay_io_resolved_ms_per_update", facts) == \
        pytest.approx(_reduce("replay_io_ms_per_update", facts) or 0.0)
    assert _reduce("hybridlm_state_staging_ms_per_update", facts) == 0.0
    assert _reduce("device_unresolved_share", facts) == pytest.approx(
        _reduce(unscoped, facts))


def test_rows_with_no_hlo_behind_them_read_as_their_own_names():
    """The recorded IMPALA run of PR 24 has no HLO: nothing is resolved,
    and the share is the unscoped one."""
    facts = _facts(_recording(IMPALA))
    led = attribution_read.ledger(facts)
    assert led["by_rule"]["inside"] == led["by_rule"]["serves"] == {}
    assert led["scopes"] == led["own"]
    assert _reduce("device_unresolved_share", facts) == pytest.approx(
        _reduce("device_unscoped_share", facts))
    assert attribution_read.ledger(facts) is led  # made once a run


def test_no_profile_reads_nothing():
    facts = {"data_dir": BENCH_DIR, "run_dir": "/nonexistent",
             "trace": {"busy_s": 1.0, "window_s": 1.0}, "trace_updates": 2}
    assert all(_reduce(name, facts) is None for name in NEW)
    facts["resolver_path"] = "/nonexistent"
    facts.pop("_scope_read")
    assert all(_reduce(name, facts) is None for name in NEW)


def test_the_traced_line_of_the_recorded_run_holds_every_listed_metric(bench):
    """`run.layer_metrics` over the recorded IMPALA run under the
    committed BENCHMARK.json, through the contract: the four metrics of
    every cell, the unscoped share, the seven that waited and the
    unresolved share."""
    rec = _recording(IMPALA)
    with open(os.path.join(BENCH_DIR, "configs", "impala_nature.json")) as f:
        cfg = json.load(f)
    facts = _facts(rec, section=cfg[cfg["section"]])
    facts["setup_monitoring"] = {"seconds": {
        "/jax/core/compile/backend_compile_duration":
            rec["printed"]["compile_s"]}}
    notes = []
    cell = "impala_nature.anakin"
    metrics = run.layer_metrics(bench, BENCH_DIR, cell, facts, notes)
    assert notes == [] and len(metrics) == 13
    line = {"correct": True, "attempted": 80, "failed": 0, "metrics": metrics,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 8268448256, **facts["trace"]}}
    contract.check_line(line, bench, cell, traced=True, chips=1)

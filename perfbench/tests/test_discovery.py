"""A later PR adds a cell, a configuration, a per-layer metric, a
reducer or a mode as files and entries of its own: the harness finds
them by name, and no file that was there is edited (`data_copy` in
conftest.py asserts that while it builds the copy)."""

import json
import os

import pytest

import contract
import discover
import flops
import run


def test_added_workload_and_configuration_are_found(data_copy):
    cell = run.load_cell(data_copy["bench"], data_copy["dir"],
                         "tiny_r2d2.hostloop")
    assert cell["config"]["section"] == "r2d2_tiny"
    assert cell["traffic"]["mode"] == "hostloop"
    # the cell's overrides lie over the shared traffic file
    assert cell["traffic"]["warm_updates"] == 3
    assert cell["traffic"]["env"]["DRL_REPLAY_SPILL"] == "0"
    assert cell["traffic"]["trace_seconds"] == 1.0


def test_added_layer_metric_and_reducer_are_found(data_copy):
    notes = []
    facts = {"span_total_ms": {"ingest_dequeue": 12.5},
             "setup_monitoring": {"seconds": {
                 "/jax/core/compile/backend_compile_duration": 2.0}},
             "t0": 0.0, "t1": 1.0, "updates": 10, "telemetry_dir": "/nonexistent",
             "trace": {"busy_s": 0.2, "window_s": 1.0}, "trace_updates": 4}
    got = run.layer_metrics(data_copy["bench"], data_copy["dir"],
                            "tiny_r2d2.hostloop", facts, notes)
    assert got["ingest_ms"] == {"value": 12.5, "unit": "ms"}
    assert got["compile_s"]["value"] == 2.0
    assert got["device_ms_per_update"]["value"] == 50.0
    assert got["device_idle_share"]["value"] == 80.0
    # readers that found nothing to read are left out, and said so
    assert "batch_wait_ms" not in got
    assert any("batch_wait_ms" in n for n in notes)


def test_added_mode_is_found(data_copy, tmp_path):
    with open(os.path.join(data_copy["dir"], "modes", "echo.py"), "w") as f:
        f.write("def run(ctx):\n    return {'mode': 'echo', 'chips': ctx['chips']}\n")
    mode = discover.module(data_copy["dir"], "modes", "echo")
    assert mode.run({"chips": 4}) == {"mode": "echo", "chips": 4}


def test_added_family_and_torso_are_found(data_copy):
    """A third family over a torso that is not the Nature stack, both
    dropped in as files: the parent finds its launcher, and `learn_mfu`
    (which every cell reports) counts its operations."""
    dd = data_copy["dir"]
    section = {"algorithm": "toy", "model_input": [8, 8, 2], "torso": "slab",
               "batch_size": 4}
    algo, family = discover.family(dd, "whatever_name", section)
    assert algo == "toy" and family.LAUNCHER == "train_impala.py"
    assert discover.family(dd, "r2d2_tiny", {})[0] == "r2d2"  # name prefix
    assert flops.torso_macs(dd, section) == (8 * 8 * 2 * 16, 16)
    facts = {"data_dir": dd, "algorithm": "toy", "section": section,
             "chips": 1, "device": {"kind": "TPU v5 lite"},
             "trace": {"busy_s": 1e-6, "window_s": 1.0}, "trace_updates": 1}
    spec = discover.data(dd, "layer_metrics", "learn_mfu")
    mfu = discover.module(dd, "reducers", spec["reducer"]).reduce(facts, spec)
    per_update = 3 * 2 * (2048 + 16) * 4
    assert mfu == pytest.approx(100 * per_update / (1e-6 * 197e12))
    # over the traced interval's BUSY seconds: an idle device costs nothing
    facts["trace"]["window_s"] = 50.0
    assert discover.module(dd, "reducers", spec["reducer"]).reduce(
        facts, spec) == pytest.approx(mfu)


def test_a_family_or_torso_no_file_brings_fails_the_run_by_name(data_copy, bench):
    dd = data_copy["dir"]
    with_mfu = dict(data_copy["bench"], per_layer=[
        m for m in bench["per_layer"] if m["name"] == "learn_mfu"])
    with pytest.raises(FileNotFoundError, match="families/apex.py"):
        discover.family(dd, "apex_nature", {})
    with pytest.raises(FileNotFoundError, match="torsos/resnet.py"):
        flops.torso_macs(dd, {"model_input": [84, 84, 4], "torso": "resnet"})
    facts = {"data_dir": dd, "algorithm": "impala", "chips": 1,
             "section": {"model_input": [84, 84, 4], "torso": "resnet"},
             "device": {"kind": "TPU v5 lite"}, "t0": 0.0, "t1": 1.0,
             "trace": {"busy_s": 0.1, "window_s": 1.0}, "trace_updates": 1}
    with pytest.raises(run.RunFailed, match="learn_mfu.*torsos/resnet.py"):
        run.layer_metrics(with_mfu, dd, "tiny_impala.anakin", facts, [])


def test_every_committed_name_resolves(bench):
    """Each cell, configuration, traffic mix, per-layer metric, reducer
    and mode that BENCHMARK.json names has its file."""
    here = os.path.dirname(os.path.abspath(run.__file__))
    for w in bench["workloads"]:
        cell = run.load_cell(bench, here, w["name"])
        assert os.path.exists(os.path.join(
            here, "modes", f"{cell['traffic']['mode']}.py"))
        section = cell["config"][cell["config"]["section"]]
        assert section["publish_interval"] == \
            cell["config"]["guarantees"]["publish_interval"] == 1
    for cfg in bench["configs"]:
        with open(os.path.join(os.path.dirname(here), cfg["file"])) as f:
            data = json.load(f)
        assert data["source"] == cfg["source"]
        assert data["reduced"] == cfg["reduced"]
    for m in bench["per_layer"]:
        spec = discover.data(here, "layer_metrics", m["name"])
        assert set(spec) == {"reducer", "source_detail"}  # no second truth
        assert os.path.exists(os.path.join(
            here, "reducers", f"{spec['reducer']}.py"))


def test_benchmark_json_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for g in ("end_to_end", "per_layer") for m in bench[g]]
    assert len(names) == len(set(names))
    for g in ("end_to_end", "per_layer"):
        for m in bench[g]:
            assert contract.NAME_RE.match(m["name"])
            assert contract.UNIT_RE.match(m["unit"])
            assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] == "host_clock"
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] == 1
    layers = {m["layer"] for m in bench["per_layer"]}
    assert all(len(x) <= 200 and "\n" not in x for x in layers)
    assert 1 <= bench["run_seconds"] <= 51

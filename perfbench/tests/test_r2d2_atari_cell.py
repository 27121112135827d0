"""The cell `r2d2_atari.anakin` (ISSUE 26): its mode rehearsed on the CPU
end to end through `run.py` at a tiny size, the early exit on a program
that cannot run the configuration, and the family's operation count by
hand. Files and entries are ADDED to `data_copy`'s copy; none is edited.
"""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

import contract
import discover
import flops
from conftest import BENCH_DIR, ROOT

NEW_METRICS = ("replay_score_ms_per_update", "replay_io_ms_per_update",
               "seq_learn_ms_per_update", "lstm_unroll_ms_per_update",
               "replay_collect_ms_per_update", "replay_unscoped_share")
CELL = "tiny_r2d2_atari.anakin_replay"


@pytest.fixture()
def tiny_cell(data_copy):
    """The published configuration's code paths (Breakout frames, the
    conv torso, dueling streams, 3-step targets, K = 2) at widths a CPU
    compiles in seconds, as a cell of its own in the copy."""
    with open(os.path.join(BENCH_DIR, "configs", "r2d2_atari.json")) as f:
        published = json.load(f)
    section = dict(published["r2d2_atari"], lstm_size=16, dueling_hidden=8,
                   seq_len=6, burn_in=2, batch_size=4, n_step=3,
                   target_sync_interval=4)
    dd = data_copy["dir"]

    def dump(rel, obj):
        path = os.path.join(dd, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            json.dump(obj, f)

    dump("configs/tiny_r2d2_atari.json", {
        "name": "tiny_r2d2_atari", "section": "r2d2_tiny_atari",
        "kernels": {"tpu_custom_call": 0}, "r2d2_tiny_atari": section})
    dump(f"workloads/{CELL}.json", {
        "config": "tiny_r2d2_atari", "traffic": "anakin_replay",
        "overrides": {"num_envs": 4, "capacity": 8, "updates_per_call": 2,
                      "train_start_factor": 2, "chunk_updates": 1}})
    bench = data_copy["bench"]
    bench["workloads"].append({"name": CELL, "config": "tiny_r2d2_atari",
                               "traffic": "anakin_replay", "chips": 1,
                               "why": "test"})
    # The six metrics by scope stay off this cell's list: XLA:CPU's
    # profile has no `hlo_stats`, so their readers find nothing here
    # (`test_scope_metrics_read_the_chunks_own_names` runs them on a
    # recording of the chunk's own op names instead).
    with open(data_copy["benchmark"], "w") as f:
        json.dump(bench, f)
    return data_copy


def _run(copy, trace, seconds="2"):
    cmd = [sys.executable, os.path.join(BENCH_DIR, "run.py"),
           "--workload", CELL, "--seed", "3000000019", "--seconds", seconds,
           "--trace", str(trace), "--data-dir", copy["dir"],
           "--benchmark", copy["benchmark"], "--expect-platform", "cpu"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"}
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_rehearsal_prints_a_contract_line(tiny_cell, trace):
    proc = _run(tiny_cell, trace)
    assert proc.returncode == 0, (proc.stderr[-3000:], proc.stdout[-3000:])
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    contract.check_line(line, tiny_cell["bench"], CELL, bool(trace), chips=1)
    assert line["correct"] is True, proc.stdout[-4000:]
    assert line["device"]["platform"] == "cpu"  # never published
    assert line["attempted"] > 0 and line["failed"] == 0
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert {"compile_s", "device_ms_per_update", "device_idle_share"} \
            <= set(line["metrics"])
    else:
        assert line["metrics"]["frames_learned_per_s"]["value"] > 0
        assert line["metrics"]["setup_s"]["value"] > 0
    assert "NOT CORRECT" not in proc.stdout
    assert "'target_syncs'" in proc.stdout  # the counters ride in the notes


def test_scope_metrics_read_the_chunks_own_names(bench):
    """The six metrics by scope on a recording made of the op names of a
    tiny `AnakinR2D2.train_chunk` compiled here, 1 us each: every one
    reads something, the recurrence lies inside the learn step, and the
    four parts and the unscoped rest add up to the whole."""
    import re

    import jax

    import run
    from distributed_reinforcement_learning_tpu.agents.r2d2 import (
        R2D2Agent, R2D2Config)
    from distributed_reinforcement_learning_tpu.envs.cartpole import (
        pomdp_project)
    from distributed_reinforcement_learning_tpu.runtime.anakin_r2d2 import (
        AnakinR2D2)

    cfg = R2D2Config(obs_shape=(2,), num_actions=2, seq_len=6, burn_in=2,
                     lstm_size=16, n_step=3, dueling_hidden=8,
                     priority_eta=0.9)
    an = AnakinR2D2(R2D2Agent(cfg), num_envs=4, capacity=16, batch_size=4,
                    obs_transform=pomdp_project, updates_per_collect=2)
    text = an.train_chunk.lower(an.init(jax.random.PRNGKey(0)), 1) \
        .compile().as_text()
    names = sorted(set(re.findall(r'op_name="([^"]+)"', text)))
    rows = [[f"op.{i}", name, 1.0] for i, name in enumerate(names)]
    facts = {"data_dir": BENCH_DIR, "trace_updates": 1,
             "trace": {"busy_s": len(rows) / 1e6, "window_s": 1.0},
             "scope_recording": {"hlo_stats": rows, "host_spans": []}}
    only = dict(bench, per_layer=[m for m in bench["per_layer"]
                                  if m["name"] in NEW_METRICS])
    got = {k: v["value"] for k, v in run.layer_metrics(
        only, BENCH_DIR, "r2d2_atari.anakin", facts, []).items()}
    assert set(got) == set(NEW_METRICS)
    assert all(got[n] > 0 for n in NEW_METRICS)
    assert got["lstm_unroll_ms_per_update"] < got["seq_learn_ms_per_update"]
    parts = (got["replay_score_ms_per_update"] + got["replay_io_ms_per_update"]
             + got["seq_learn_ms_per_update"]
             + got["replay_collect_ms_per_update"])
    whole = 1e3 * facts["trace"]["busy_s"]
    assert parts + got["replay_unscoped_share"] / 100 * whole \
        == pytest.approx(whole)
    assert got["replay_unscoped_share"] < 50


def _mode():
    spec = importlib.util.spec_from_file_location(
        "anakin_r2d2_mode", os.path.join(BENCH_DIR, "modes", "anakin_r2d2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_program_without_n_step_is_told_apart_before_anything_is_built(
        monkeypatch, tmp_path):
    """Every commit before PR 26: `load_config` ignores `n_step` and
    `dueling_hidden`, and its `R2D2Config` has no such field."""
    from distributed_reinforcement_learning_tpu.utils import config

    mode = _mode()
    section = {"n_step": 5, "dueling_hidden": 512}
    monkeypatch.setattr(config, "load_config",
                        lambda path, name: (types.SimpleNamespace(), None))
    why = mode._unsupported("unused.json", "r2d2_atari", section)
    assert "n_step" in why and "cannot run this configuration" in why
    monkeypatch.setattr(config, "load_config", lambda path, name: (
        types.SimpleNamespace(n_step=5, dueling_hidden=None), None))
    assert "dueling_hidden" in mode._unsupported("unused.json", "r2d2_atari",
                                                 section)
    monkeypatch.setattr(config, "load_config", lambda path, name: (
        types.SimpleNamespace(n_step=5, dueling_hidden=512), None))
    assert mode._unsupported("unused.json", "r2d2_atari", section) is None


def test_the_unsupported_exit_leaves_no_result_line(tiny_cell, monkeypatch):
    """`run` turns the child's `EXIT_UNSUPPORTED` into a failed run that
    quotes the child's one line."""
    mode = _mode()

    class Exited:
        def __init__(self, cmd, stdout=None, **kw):
            stdout.write("[perfbench] UNSUPPORTED: no `n_step`\n")
            stdout.flush()

        def wait(self, timeout=None):
            return mode.EXIT_UNSUPPORTED

        def poll(self):
            return mode.EXIT_UNSUPPORTED

    monkeypatch.setattr(mode.subprocess, "Popen", Exited)

    class RunFailed(Exception):
        pass

    with open(os.path.join(tiny_cell["dir"], "configs",
                           "tiny_r2d2_atari.json")) as f:
        cfg = json.load(f)
    out = os.path.join(tiny_cell["dir"], "out")
    os.makedirs(out)
    ctx = {"config": cfg, "out_dir": out, "root": ROOT, "bench_dir": BENCH_DIR,
           "data_dir": tiny_cell["dir"], "chips": 1, "t_start": 0.0,
           "traffic": {"updates_per_call": 2, "train_start_factor": 2},
           "args": types.SimpleNamespace(seed=1, seconds=1.0, trace=0,
                                         expect_platform="cpu"),
           "RunFailed": RunFailed, "NoDevice": RuntimeError}
    with pytest.raises(RunFailed, match="UNSUPPORTED: no `n_step`"):
        mode.run(ctx)


def test_operation_count_by_hand():
    """One frame of the published network, multiply-adds: convolutions
    20*20*32*8*8*4 + 9*9*64*4*4*32 + 7*7*64*3*3*64 = 7,737,344; action
    embedding 18*256 + 256*256 = 70,144; LSTM (3136 + 256 + 512) * 2048 =
    7,995,392; value stream 512*512 + 512, advantage stream 512*512 +
    512*18 = 534,016. One update: K = 4 learn steps x (3 + 1) forwards x
    64 sequences x 120 steps."""
    family = discover.module(BENCH_DIR, "families", "r2d2_atari")
    with open(os.path.join(BENCH_DIR, "configs", "r2d2_atari.json")) as f:
        section = json.load(f)["r2d2_atari"]
    torso = flops.torso_macs(BENCH_DIR, section)
    assert torso == (7_737_344, 3136)
    macs = 7_737_344 + 70_144 + 7_995_392 + 534_016
    assert family.forward_flops_per_frame(section, torso) == 2 * macs
    assert family.learn_flops_per_update(section, torso) \
        == 4 * 4 * 2 * macs * 64 * 120
    assert family.learn_flops_per_update(section, torso, 32) \
        == 4 * 4 * 2 * macs * 32 * 120


def test_committed_cell_resolves_and_mirrors_the_table(bench):
    import run

    cell = run.load_cell(bench, BENCH_DIR, "r2d2_atari.anakin")
    assert cell["traffic"]["mode"] == "anakin_r2d2"
    assert {k: cell["traffic"][k] for k in (
        "num_envs", "capacity", "updates_per_call", "train_start_factor",
        "chunk_updates")} == {"num_envs": 256, "capacity": 2048,
                              "updates_per_call": 4, "train_start_factor": 32,
                              "chunk_updates": 2}
    section = cell["config"]["r2d2_atari"]
    # the traffic's K and warm-up are the section's own
    assert all(section[k] == cell["traffic"][k]
               for k in ("updates_per_call", "train_start_factor"))
    listed = {m["name"] for m in bench["per_layer"]
              if m.get("workloads") == ["r2d2_atari.anakin"]}
    assert listed == set(NEW_METRICS)
    traced = contract.cell_metrics(bench, "r2d2_atari.anakin", traced=True)
    assert len(traced) == 10

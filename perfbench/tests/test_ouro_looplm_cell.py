"""The cell `ouro_looplm.anakin_tokens` (ISSUE 30): its mode rehearsed on
the CPU end to end through `run.py` at a tiny size, the early exit on a
program that cannot run the configuration, the family's operation count
by hand, the configuration file against the catalog's published keys,
and the four metrics by scope on the chunk's own op names. Files and
entries are ADDED to `data_copy`'s copy; none is edited.
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
from conftest import BENCH_DIR, ROOT

NEW_METRICS = ("looplm_decode_ms_per_update", "looplm_stack_ms_per_update",
               "looplm_heads_ms_per_update", "looplm_unscoped_share")
CELL = "tiny_looplm.anakin_tokens"
PUBLISHED = {  # huggingface.co/ByteDance/Ouro-2.6B config.json
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "max_window_layers": 48, "model_type": "ouro",
    "num_attention_heads": 16, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False, "total_ut_steps": 4,
    "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152}


def _published_config():
    with open(os.path.join(BENCH_DIR, "configs", "ouro_looplm.json")) as f:
        return json.load(f)


@pytest.fixture()
def tiny_cell(data_copy):
    """The published configuration's code paths (four passes, the gate,
    the cache, the blocked head) at widths a CPU compiles in seconds."""
    section = dict(_published_config()["ouro_looplm"], hidden_size=64,
                   num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                   intermediate_size=176, vocab_size=512,
                   available_action=[512], num_hidden_layers=2, trajectory=16,
                   envs_per_actor=4, dtype="float32")
    dd = data_copy["dir"]

    def dump(rel, obj):
        path = os.path.join(dd, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            json.dump(obj, f)

    dump("configs/tiny_looplm.json", {
        "name": "tiny_looplm", "section": "tiny_looplm", "kernels": {},
        "frames_per_update": 64, "tiny_looplm": section})
    dump(f"workloads/{CELL}.json", {
        "config": "tiny_looplm", "traffic": "anakin_tokens",
        "overrides": {"num_envs": 4, "chunk_updates": 2}})
    bench = data_copy["bench"]
    bench["workloads"].append({"name": CELL, "config": "tiny_looplm",
                               "traffic": "anakin_tokens", "chips": 1,
                               "why": "test"})
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
    assert "'exit_cdf_pass3'" in proc.stdout  # the counters ride in the notes
    assert "'loop_passes': 4" in proc.stdout and "chunk {" in proc.stdout


def test_scope_metrics_read_the_chunks_own_names(bench):
    """The four metrics by scope on a recording made of the op names of
    a tiny `AnakinTokens.train_chunk` compiled here, 1 us each: every one
    reads something, and the named scopes of the token loop all appear."""
    import re

    import jax
    import jax.numpy as jnp

    import run
    from distributed_reinforcement_learning_tpu.agents.looplm import (
        LoopLMAgent, LoopLMConfig)
    from distributed_reinforcement_learning_tpu.envs.token_recall_jax import (
        TokenRecall)
    from distributed_reinforcement_learning_tpu.observability import scopes
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
        AnakinTokens)

    cfg = LoopLMConfig(vocab_size=128, hidden_size=32, num_attention_heads=2,
                       head_dim=16, intermediate_size=48, num_hidden_layers=2,
                       trajectory=8, dtype=jnp.float32, head_block=16)
    an = AnakinTokens(LoopLMAgent(cfg), 4, TokenRecall(128, 8))
    text = an.train_chunk.lower(an.init(jax.random.PRNGKey(0)), 1) \
        .compile().as_text()
    names = sorted(set(re.findall(r'op_name="([^"]+)"', text)))
    for scope in scopes.TOKENS_CHUNK_SCOPES:
        assert any(scope in n for n in names), scope
    rows = [[f"op.{i}", name, 1.0] for i, name in enumerate(names)]
    facts = {"data_dir": BENCH_DIR, "trace_updates": 1,
             "trace": {"busy_s": len(rows) / 1e6, "window_s": 1.0},
             "scope_recording": {"hlo_stats": rows, "host_spans": []}}
    only = dict(bench, per_layer=[m for m in bench["per_layer"]
                                  if m["name"] in NEW_METRICS])
    got = {k: v["value"] for k, v in run.layer_metrics(
        only, BENCH_DIR, "ouro_looplm.anakin_tokens", facts, []).items()}
    assert set(got) == set(NEW_METRICS)
    assert all(got[n] > 0 for n in NEW_METRICS)
    assert got["looplm_unscoped_share"] < 50


def test_new_metrics_read_nothing_on_a_program_without_the_scopes(bench):
    """The parent's program has no such scope: each reader returns None
    (the line leaves the metric out) and does not raise."""
    import run

    rows = [["op.0", "jit(_train_chunk_s3)/while/body/collect/act/dot", 5.0],
            ["op.1", "jit(_train_chunk_s3)/while/body/learn/loss/conv", 5.0]]
    facts = {"data_dir": BENCH_DIR, "trace_updates": 1,
             "trace": {"busy_s": 1e-5, "window_s": 1.0},
             "scope_recording": {"hlo_stats": rows, "host_spans": []}}
    only = dict(bench, per_layer=[
        m for m in bench["per_layer"]
        if m["name"] in ("looplm_stack_ms_per_update",
                         "looplm_heads_ms_per_update")])
    notes: list = []
    got = run.layer_metrics(only, BENCH_DIR, "ouro_looplm.anakin_tokens",
                            facts, notes)
    assert all(v["value"] == 0 for v in got.values())
    no_profile = {"data_dir": BENCH_DIR, "trace_updates": 1, "trace": None}
    assert run.layer_metrics(only, BENCH_DIR, "ouro_looplm.anakin_tokens",
                             no_profile, notes) == {}


def _mode():
    spec = importlib.util.spec_from_file_location(
        "anakin_tokens_mode", os.path.join(BENCH_DIR, "modes", "anakin_tokens.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_program_without_the_family_is_told_apart_before_anything_is_built(
        monkeypatch):
    """Every commit before PR 30: `load_config` raises on the section's
    algorithm, and `runtime/launch.py` has no `train_anakin_tokens`."""
    from distributed_reinforcement_learning_tpu.runtime import launch
    from distributed_reinforcement_learning_tpu.utils import config

    mode = _mode()

    def old_load_config(path, name):
        raise ValueError("unknown algorithm 'looplm'")

    monkeypatch.setattr(config, "load_config", old_load_config)
    why = mode._unsupported("unused.json", "ouro_looplm")
    assert "unknown algorithm 'looplm'" in why
    assert "cannot run this configuration" in why
    monkeypatch.setattr(config, "load_config", lambda path, name: (None, None))
    assert mode._unsupported("unused.json", "ouro_looplm") is None
    monkeypatch.delattr(launch, "train_anakin_tokens")
    assert "train_anakin_tokens" in mode._unsupported("unused.json",
                                                      "ouro_looplm")


def test_the_unsupported_exit_leaves_no_result_line(tiny_cell, monkeypatch):
    mode = _mode()

    class Exited:
        def __init__(self, cmd, stdout=None, **kw):
            stdout.write("[perfbench] UNSUPPORTED: no `looplm`\n")
            stdout.flush()

        def wait(self, timeout=None):
            return mode.EXIT_UNSUPPORTED

        def poll(self):
            return mode.EXIT_UNSUPPORTED

    monkeypatch.setattr(mode.subprocess, "Popen", Exited)

    class RunFailed(Exception):
        pass

    with open(os.path.join(tiny_cell["dir"], "configs", "tiny_looplm.json")) as f:
        cfg = json.load(f)
    out = os.path.join(tiny_cell["dir"], "out")
    os.makedirs(out)
    ctx = {"config": cfg, "out_dir": out, "root": ROOT, "bench_dir": BENCH_DIR,
           "data_dir": tiny_cell["dir"], "chips": 1, "t_start": 0.0,
           "traffic": {"num_envs": 4, "chunk_updates": 2},
           "args": types.SimpleNamespace(seed=1, seconds=1.0, trace=0,
                                         expect_platform="cpu"),
           "RunFailed": RunFailed, "NoDevice": RuntimeError}
    with pytest.raises(RunFailed, match="UNSUPPORTED: no `looplm`"):
        mode.run(ctx)


def test_operation_count_by_hand():
    """One token forward: the stack's matmuls 8 x (4 x 2048^2 + 3 x 2048 x
    5632) = 411,041,792 multiply-adds x 4 passes; attention 4 passes x 8
    layers x (q k^T and p v) x 2 x mean causal length 64.5 (129 / 2) x 2048;
    four head passes of 2048 x (49,152 + 2). Learn = 3 x forward x 32 x 128."""
    family = discover.module(BENCH_DIR, "families", "looplm")
    section = _published_config()["ouro_looplm"]
    assert family.stack_matmul_params(section) == 411_041_792
    forward = (4 * 2 * 411_041_792 + 4 * 8 * 2 * 2 * 129 * 2048 // 2
               + 4 * 2 * 2048 * 49_154)
    assert family.forward_flops_per_token(section) == forward
    assert 4.10e9 < forward < 4.12e9
    assert family.learn_flops_per_update(section, None) == 3 * forward * 4096
    assert family.learn_flops_per_update(section, (0, 0), 16) \
        == 3 * forward * 16 * 128


def test_configuration_file_keeps_every_published_key():
    cfg = _published_config()
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert len(cfg["layer_types"]) == 48  # copied whole from the source
    assert set(cfg["layer_types"]) == {"full_attention"}
    assert cfg["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert cfg["num_hidden_layers"] == 8 and cfg["max_position_embeddings"] == 128
    section = cfg["ouro_looplm"]
    for key in ("head_dim", "hidden_size", "intermediate_size",
                "num_attention_heads", "num_key_value_heads", "vocab_size",
                "total_ut_steps", "early_exit_threshold", "rope_theta",
                "rms_norm_eps", "num_hidden_layers", "tie_word_embeddings"):
        assert section[key] == cfg[key], key
    assert section["trajectory"] == cfg["max_position_embeddings"]
    assert section["dtype"] == "bfloat16"
    with open(os.path.join(ROOT, "config.json")) as f:
        assert json.load(f)["ouro_looplm"] == section  # the same values
    for key in ("norm_placement", "biases", "value_head", "initializer_range",
                "exit_gate", "env", "loss", "optimizer", "dtype"):
        assert key in cfg["assumed"], key


def test_reference_copies_are_identical_and_import_nothing_of_the_program():
    with open(os.path.join(BENCH_DIR, "references", "ouro_looplm.py")) as f:
        copy = f.read()
    with open(os.path.join(ROOT, "distributed_reinforcement_learning_tpu",
                           "reference", "ouro_looplm.py")) as f:
        assert f.read() == copy
    imports = [line for line in copy.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations",
                       "import functools", "import jax",
                       "import jax.numpy as jnp"]
    assert 'default_matmul_precision("highest")' in copy


def test_committed_cell_resolves_and_mirrors_the_table(bench):
    import run

    cell = run.load_cell(bench, BENCH_DIR, "ouro_looplm.anakin_tokens")
    assert cell["traffic"]["mode"] == "anakin_tokens"
    assert {k: cell["traffic"][k] for k in ("num_envs", "chunk_updates")} \
        == {"num_envs": 32, "chunk_updates": 2}
    section = cell["config"]["ouro_looplm"]
    assert section["trajectory"] == 128 and section["recall_distance"] == 8
    assert cell["config"]["frames_per_update"] == 32 * 128
    assert cell["config"]["kernels"] == {"tpu_custom_call": 6}
    listed = {m["name"] for m in bench["per_layer"]
              if m.get("workloads") == ["ouro_looplm.anakin_tokens"]}
    assert listed == set(NEW_METRICS)
    traced = contract.cell_metrics(bench, "ouro_looplm.anakin_tokens", traced=True)
    assert set(traced) == set(NEW_METRICS) | {
        "compile_s", "device_ms_per_update", "learn_mfu", "device_idle_share"}
    entry = next(c for c in bench["configs"] if c["name"] == "ouro_looplm")
    assert entry["source"] == cell["config"]["source"]
    assert entry["source"].startswith("https://huggingface.co/ByteDance/Ouro-2.6B")

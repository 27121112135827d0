"""The cell `granite_hybrid.anakin_tokens_1k` (ISSUE 32): its mode
rehearsed on the CPU end to end through `run.py` at a tiny size, the early
exit on a program that cannot run the configuration, the rule for leaves
whose step is under float32's last bit, the family's operation count by
hand, the configuration file against the catalog's published keys, and
the six metrics by scope on the chunk's own op names. Files and entries
are ADDED to `data_copy`'s copy; none is edited.
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

NEW_METRICS = ("hybridlm_decode_ms_per_update", "hybridlm_ssm_act_ms_per_update",
               "hybridlm_stack_ms_per_update", "hybridlm_ssd_ms_per_update",
               "hybridlm_heads_ms_per_update", "hybridlm_unscoped_share")
REAL_CELL = "granite_hybrid.anakin_tokens_1k"
CELL = "tiny_hybrid.anakin_tokens_1k"
ORDER = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
PUBLISHED = {  # huggingface.co/ibm-granite/granite-4.0-h-micro config.json
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "logits_scaling": 8, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 64, "mamba_proj_bias": False,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True}


def _published_config():
    with open(os.path.join(BENCH_DIR, "configs", "granite_hybrid.json")) as f:
        return json.load(f)


@pytest.fixture()
def tiny_cell(data_copy):
    """The published configuration's code paths (two kinds of layer in a
    run each, three kinds of state, the chunked scan over four chunks, the
    blocked head) at widths a CPU compiles in seconds."""
    section = dict(_published_config()["granite_hybrid"], hidden_size=32,
                   num_attention_heads=4, num_key_value_heads=2,
                   intermediate_size=48, shared_intermediate_size=48,
                   mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
                   mamba_chunk_size=8, vocab_size=96, available_action=[96],
                   layer_types=["mamba", "mamba", "attention", "mamba"],
                   num_hidden_layers=4, trajectory=32, envs_per_actor=4,
                   dtype="float32")
    dd = data_copy["dir"]

    def dump(rel, obj):
        path = os.path.join(dd, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            json.dump(obj, f)

    dump("configs/tiny_hybrid.json", {
        "name": "tiny_hybrid", "section": "tiny_hybrid", "kernels": {},
        "frames_per_update": 128, "tiny_hybrid": section})
    dump(f"workloads/{CELL}.json", {
        "config": "tiny_hybrid", "traffic": "anakin_tokens_1k",
        "overrides": {"num_envs": 4, "chunk_updates": 1}})
    bench = data_copy["bench"]
    bench["workloads"].append({"name": CELL, "config": "tiny_hybrid",
                               "traffic": "anakin_tokens_1k", "chips": 1,
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
                          text=True, timeout=900)


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
    for said in ("'dt_mean'", "'decay_min'", "'state_norm_mean'",
                 "'ssm_state_bytes'", "'conv_state_bytes'", "'kv_cache_bytes'",
                 "'layer_order': ['mamba', 'mamba', 'attention', 'mamba']",
                 "chunk {", "'step_over_last_bit'"):
        assert said in proc.stdout, said


def test_scope_metrics_read_the_chunks_own_names(bench):
    """The six metrics by scope on a recording made of the op names of a
    tiny hybrid `AnakinTokens.train_chunk` compiled here, 1 us each: every
    one reads something, and the named scopes all appear."""
    import re

    import jax
    import jax.numpy as jnp

    import run
    from distributed_reinforcement_learning_tpu.agents.hybridlm import (
        HybridLMAgent, HybridLMConfig)
    from distributed_reinforcement_learning_tpu.envs.token_recall_jax import (
        TokenRecall)
    from distributed_reinforcement_learning_tpu.observability import scopes
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
        AnakinTokens)

    cfg = HybridLMConfig(
        vocab_size=64, hidden_size=32, layer_types=("mamba", "attention", "mamba"),
        num_attention_heads=4, num_key_value_heads=2, shared_intermediate_size=48,
        mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8, mamba_chunk_size=8,
        trajectory=16, dtype=jnp.float32, head_block=16, row_block=2)
    an = AnakinTokens(HybridLMAgent(cfg), 4, TokenRecall(64, 16))
    text = an.train_chunk.lower(an.init(jax.random.PRNGKey(0)), 1) \
        .compile().as_text()
    names = sorted(set(re.findall(r'op_name="([^"]+)"', text)))
    for scope in scopes.HYBRID_CHUNK_SCOPES:
        assert any(scope in n for n in names), scope
    rows = [[f"op.{i}", name, 1.0] for i, name in enumerate(names)]
    facts = {"data_dir": BENCH_DIR, "trace_updates": 1,
             "trace": {"busy_s": len(rows) / 1e6, "window_s": 1.0},
             "scope_recording": {"hlo_stats": rows, "host_spans": []}}
    only = dict(bench, per_layer=[m for m in bench["per_layer"]
                                  if m["name"] in NEW_METRICS])
    got = {k: v["value"] for k, v in run.layer_metrics(
        only, BENCH_DIR, REAL_CELL, facts, []).items()}
    assert set(got) == set(NEW_METRICS)
    assert all(got[n] > 0 for n in NEW_METRICS)
    assert got["hybridlm_ssm_act_ms_per_update"] < got["hybridlm_decode_ms_per_update"]
    assert got["hybridlm_ssd_ms_per_update"] < got["hybridlm_stack_ms_per_update"]
    assert got["hybridlm_unscoped_share"] < 50


def test_new_metrics_read_nothing_on_a_program_without_the_scopes(bench):
    """The parent's program has no such scope: each reader returns None or
    0 (the line leaves the metric out or reads nothing) and does not raise."""
    import run

    rows = [["op.0", "jit(_train_chunk_s4)/while/body/collect/act/dot", 5.0],
            ["op.1", "jit(_train_chunk_s4)/while/body/learn/loss/loop/dot", 5.0]]
    facts = {"data_dir": BENCH_DIR, "trace_updates": 1,
             "trace": {"busy_s": 1e-5, "window_s": 1.0},
             "scope_recording": {"hlo_stats": rows, "host_spans": []}}
    only = dict(bench, per_layer=[
        m for m in bench["per_layer"]
        if m["name"] in ("hybridlm_ssm_act_ms_per_update",
                         "hybridlm_stack_ms_per_update",
                         "hybridlm_ssd_ms_per_update")])
    notes: list = []
    got = run.layer_metrics(only, BENCH_DIR, REAL_CELL, facts, notes)
    assert all(v["value"] == 0 for v in got.values())
    no_profile = {"data_dir": BENCH_DIR, "trace_updates": 1, "trace": None}
    assert run.layer_metrics(only, BENCH_DIR, REAL_CELL, no_profile, notes) == {}


def _mode():
    spec = importlib.util.spec_from_file_location(
        "anakin_tokens_hybrid_mode",
        os.path.join(BENCH_DIR, "modes", "anakin_tokens_hybrid.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_program_without_the_family_is_told_apart_before_anything_is_built(
        monkeypatch):
    """Every commit before PR 32: `load_config` raises on the section's
    algorithm."""
    from distributed_reinforcement_learning_tpu.utils import config

    base = _mode()._base()

    def old_load_config(path, name):
        raise ValueError("unknown algorithm 'hybridlm'")

    monkeypatch.setattr(config, "load_config", old_load_config)
    why = base._unsupported("unused.json", "granite_hybrid")
    assert "unknown algorithm 'hybridlm'" in why
    assert "cannot run this configuration" in why
    assert base.COUNTERS == _mode().COUNTERS and "dt_mean" in base.COUNTERS


def test_the_unsupported_exit_leaves_no_result_line(tiny_cell, monkeypatch):
    mode = _mode()

    class Exited:
        def __init__(self, cmd, stdout=None, **kw):
            assert cmd[1].endswith("anakin_tokens_hybrid.py")  # THIS mode's child
            stdout.write("[perfbench] UNSUPPORTED: no `hybridlm`\n")
            stdout.flush()

        def wait(self, timeout=None):
            return 5

        def poll(self):
            return 5

    monkeypatch.setattr(mode.subprocess, "Popen", Exited)

    class RunFailed(Exception):
        pass

    with open(os.path.join(tiny_cell["dir"], "configs", "tiny_hybrid.json")) as f:
        cfg = json.load(f)
    out = os.path.join(tiny_cell["dir"], "out")
    os.makedirs(out)
    ctx = {"config": cfg, "out_dir": out, "root": ROOT, "bench_dir": BENCH_DIR,
           "data_dir": tiny_cell["dir"], "chips": 1, "t_start": 0.0,
           "traffic": {"num_envs": 4, "chunk_updates": 1},
           "args": types.SimpleNamespace(seed=1, seconds=1.0, trace=0,
                                         expect_platform="cpu"),
           "RunFailed": RunFailed, "NoDevice": RuntimeError}
    with pytest.raises(RunFailed, match="UNSUPPORTED: no `hybridlm`"):
        mode.run(ctx)


@pytest.mark.parametrize("stuck, bits, allowed", [
    ([], [5.0, 0.2], None),  # nothing stayed: nothing to allow
    ([1], [5.0, 0.2], [1]),  # the reference's own step is under the last bit
    ([0], [5.0, 0.2], None),  # the reference moves this leaf: a fault
    ([0, 1], [0.3, 0.2], None),  # nothing moved at all
    ([1], [5.0, 1.5], None),  # over the last bit: it should have moved
])
def test_a_leaf_may_stay_only_under_the_last_bit(stuck, bits, allowed):
    res = {"leaves_stuck": stuck, "chunk": {"step_over_last_bit": bits}}
    assert _mode().under_the_last_bit(res) == allowed


def test_a_state_in_another_precision_or_order_is_refused_by_its_bytes():
    """The chunk's own `static_facts` at the published sizes pass; a
    bfloat16 recurrent state, a missing window, another order do not."""
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.hybridlm import (
        HybridLMAgent)
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    mode = _mode()
    section = _published_config()["granite_hybrid"]
    cfg, _ = load_config(os.path.join(ROOT, "config.json"), "granite_hybrid")
    agent = HybridLMAgent(cfg)
    facts = agent.state_facts(32)
    assert mode.state_problems(facts, section, 32) == []
    assert mode.state_problems(facts, section, 16)  # other sizes
    import dataclasses
    agent.model = dataclasses.replace(agent.model, state_dtype=jnp.bfloat16)
    said = mode.state_problems(agent.state_facts(32), section, 32)
    assert len(said) == 1 and "ssm_state_bytes" in said[0]
    assert mode.state_problems({**facts, "layer_order": ORDER[::-1]}, section, 32)
    assert mode.state_problems({**facts, "conv_state_bytes": 0}, section, 32)


def test_operation_count_by_hand():
    """One token forward. A state-space layer: in_proj 2048 x 8512 and
    out_proj 4096 x 2048 (25,821,184 multiply-adds), the scan's einsums as
    computed (C.B^T 256 x 128, the decay-weighted product 64 x 256 x 64,
    the chunk's state and the read of the carried one 64 x 64 x 128 each:
    2,129,920), the MLP 3 x 2048 x 8192 (50,331,648). The attention layer:
    2 x 2048^2 + 2 x 2048 x 512 and q k^T, p v over the mean causal length
    512.5. The tied head 2048 x 12,544 and the value."""
    family = discover.module(BENCH_DIR, "families", "hybridlm")
    section = _published_config()["granite_hybrid"]
    mamba = 2 * (25_821_184 + 50_331_648) + 2 * (256 * 128 + 64 * 256 * 64
                                                 + 2 * 64 * 64 * 128)
    attention = (2 * (2 * 2048 ** 2 + 2 * 2048 * 512 + 50_331_648)
                 + 2 * 2 * 1025 * 2048 // 2)
    forward = 9 * mamba + attention + 2 * 2048 * 12_545
    assert family.forward_flops_per_token(section) == forward
    assert 1.58e9 < forward < 1.60e9
    assert family.learn_flops_per_update(section, None) == 3 * forward * 32_768
    assert family.learn_flops_per_update(section, (0, 0), 16) \
        == 3 * forward * 16 * 1024


def test_configuration_file_keeps_every_published_key():
    cfg = _published_config()
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert len(cfg["layer_types"]) == 40  # copied whole from the source
    assert cfg["layer_types"] == ORDER * 4
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size",
                              "max_position_embeddings"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (10, 12544, 1024)
    assert 12544 * 8 == 100_352  # an eighth of the published vocabulary
    section = cfg["granite_hybrid"]
    for key in PUBLISHED:
        if key in section:
            assert section[key] == cfg[key], key
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "shared_intermediate_size", "mamba_n_heads", "mamba_d_head",
                "mamba_d_state", "mamba_d_conv", "mamba_chunk_size",
                "embedding_multiplier", "residual_multiplier",
                "attention_multiplier", "logits_scaling", "vocab_size",
                "num_hidden_layers"):
        assert section[key] == cfg[key], key
    assert section["layer_types"] == ORDER == cfg["layer_types"][:10]
    assert section["trajectory"] == cfg["max_position_embeddings"]
    assert section["dtype"] == "bfloat16" and section["algorithm"] == "hybridlm"
    with open(os.path.join(ROOT, "config.json")) as f:
        assert json.load(f)["granite_hybrid"] == section  # the same values
    for key in ("value_head", "initializer", "act_state_dtype", "env", "loss",
                "optimizer", "dtype"):
        assert key in cfg["assumed"], key
    for key in cfg["reduced"]:
        assert key in cfg["reduced_why"], key


def test_reference_copies_are_identical_and_import_nothing_of_the_program():
    with open(os.path.join(BENCH_DIR, "references", "granite_hybrid.py")) as f:
        copy = f.read()
    with open(os.path.join(ROOT, "distributed_reinforcement_learning_tpu",
                           "reference", "granite_hybrid.py")) as f:
        assert f.read() == copy
    imports = [line for line in copy.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations",
                       "import functools", "import jax",
                       "import jax.numpy as jnp"]
    assert 'default_matmul_precision("highest")' in copy
    assert "ssd" not in copy.replace("ops/ssd.py", "")  # the recurrence, not chunks


def test_committed_cell_resolves_and_mirrors_the_table(bench):
    import run

    cell = run.load_cell(bench, BENCH_DIR, REAL_CELL)
    assert cell["traffic"]["mode"] == "anakin_tokens_hybrid"
    assert {k: cell["traffic"][k] for k in ("num_envs", "chunk_updates")} \
        == {"num_envs": 32, "chunk_updates": 1}
    section = cell["config"]["granite_hybrid"]
    assert section["trajectory"] == 1024 and section["recall_distance"] == 8
    assert cell["config"]["frames_per_update"] == 32 * 1024
    assert cell["config"]["kernels"] == {"tpu_custom_call": 6}
    listed = {m["name"] for m in bench["per_layer"]
              if m.get("workloads") == [REAL_CELL]}
    assert listed == set(NEW_METRICS)
    traced = contract.cell_metrics(bench, REAL_CELL, traced=True)
    assert set(traced) == set(NEW_METRICS) | {
        "compile_s", "device_ms_per_update", "learn_mfu", "device_idle_share"}
    entry = next(c for c in bench["configs"] if c["name"] == "granite_hybrid")
    assert entry["source"] == cell["config"]["source"]
    assert entry["source"].startswith(
        "https://huggingface.co/ibm-granite/granite-4.0-h-micro")
    assert entry["reduced"] == cell["config"]["reduced"]
    assert len(bench["workloads"]) == 4 and len(bench["configs"]) == 4

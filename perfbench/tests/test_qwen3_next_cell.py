"""The cell `qwen3_next.anakin_tokens_moe_1k` (ISSUE 36): its mode
rehearsed on the CPU end to end through `run.py` at a tiny size, the early
exit on a program that cannot run the configuration, what the chunk is
held to (its layers, the bytes of its state, its share of the experts),
the family's operation count by hand, the configuration file against the
catalog's published keys, and the nine metrics by scope on the chunk's
own op names. Files and entries are ADDED to `data_copy`'s copy; none is
edited.
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

NEW_METRICS = ("moelm_decode_resolved_ms_per_update",
               "moelm_experts_act_ms_per_update", "moelm_gdn_act_ms_per_update",
               "moelm_stack_ms_per_update", "moelm_route_ms_per_update",
               "moelm_experts_ms_per_update", "moelm_gdn_ms_per_update",
               "moelm_heads_ms_per_update", "moelm_unscoped_share")
BY_OWN_NAMES = NEW_METRICS[3:]  # the `_resolved` readers want a profile
REAL_CELL = "qwen3_next.anakin_tokens_moe_1k"
CELL = "tiny_moe.anakin_tokens_moe_1k"
ORDER = ["linear_attention"] * 3 + ["full_attention"]
PUBLISHED = {  # huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct config.json
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "mlp_only_layers": [],
    "model_type": "qwen3_next", "moe_intermediate_size": 512,
    "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts_per_tok": 10, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False}
CUT = {"num_hidden_layers": (48, 4), "num_experts": (512, 32),
       "vocab_size": (151936, 18992), "max_position_embeddings": (262144, 1024)}


def _published_config():
    with open(os.path.join(BENCH_DIR, "configs", "qwen3_next.json")) as f:
        return json.load(f)


@pytest.fixture()
def tiny_cell(data_copy):
    """The published configuration's code paths (two kinds of mixer in a
    run each, three kinds of state, the chunked rule over four chunks, a
    router over 16 experts of which 4 are held, the blocked head) at
    widths a CPU compiles in seconds."""
    section = dict(_published_config()["qwen3_next"], hidden_size=32,
                   num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                   linear_num_key_heads=2, linear_num_value_heads=4,
                   linear_key_head_dim=8, linear_value_head_dim=8,
                   num_experts=4, router_width=16, first_expert=4,
                   num_experts_per_tok=3, moe_intermediate_size=16,
                   shared_expert_intermediate_size=16, vocab_size=96,
                   available_action=[96], trajectory=32, envs_per_actor=4,
                   dtype="float32")
    dd = data_copy["dir"]

    def dump(rel, obj):
        path = os.path.join(dd, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            json.dump(obj, f)

    dump("configs/tiny_moe.json", {
        "name": "tiny_moe", "section": "tiny_moe", "kernels": {},
        "frames_per_update": 128, "tiny_moe": section})
    dump(f"workloads/{CELL}.json", {
        "config": "tiny_moe", "traffic": "anakin_tokens_moe_1k",
        "overrides": {"num_envs": 4, "chunk_updates": 1}})
    bench = data_copy["bench"]
    bench["workloads"].append({"name": CELL, "config": "tiny_moe",
                               "traffic": "anakin_tokens_moe_1k", "chips": 1,
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
    assert line["correct"] is True, proc.stdout[-6000:]
    assert line["device"]["platform"] == "cpu"  # never published
    assert line["attempted"] > 0 and line["failed"] == 0
    contract.check_line(line, tiny_cell["bench"], CELL, bool(trace), chips=1)
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert {"compile_s", "device_ms_per_update", "device_idle_share"} \
            <= set(line["metrics"])
    else:
        assert line["metrics"]["frames_learned_per_s"]["value"] > 0
        assert line["metrics"]["setup_s"]["value"] > 0
    assert "NOT CORRECT" not in proc.stdout
    for said in ("'held_pair_share'", "'dropped_pairs': 0.0", "'experts_untouched'",
                 "'router_entropy'", "'beta_mean'", "'decay_min'",
                 "'state_norm_mean'", "'gdn_state_bytes'", "'conv_state_bytes'",
                 "'kv_cache_bytes'", "'experts_held': 4", "'router_width': 16",
                 "'first_expert': 4", "'route_flip_share'", "'flips_over_margin': 0",
                 "'router_prob'", "chunk {", "'step_over_last_bit'"):
        assert said in proc.stdout, said


def test_scope_metrics_read_the_chunks_own_names(bench):
    """The metrics by own names on a recording made of the op names of a
    tiny `AnakinTokens.train_chunk` of this family compiled here, 1 us
    each: every one reads something, and every scope of the family's
    vocabulary appears."""
    import re

    import jax
    import jax.numpy as jnp

    import run
    from distributed_reinforcement_learning_tpu.agents.moelm import (
        MoELMAgent, MoELMConfig)
    from distributed_reinforcement_learning_tpu.envs.token_recall_jax import (
        TokenRecall)
    from distributed_reinforcement_learning_tpu.observability import scopes
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
        AnakinTokens)

    cfg = MoELMConfig(
        vocab_size=64, hidden_size=32, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8,
        num_experts=4, router_width=16, first_expert=4, num_experts_per_tok=3,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        trajectory=16, gdn_chunk=8, dtype=jnp.float32, head_block=16, row_block=2)
    an = AnakinTokens(MoELMAgent(cfg), 4, TokenRecall(64, 16))
    text = an.train_chunk.lower(an.init(jax.random.PRNGKey(0)), 1) \
        .compile().as_text()
    names = sorted(set(re.findall(r'op_name="([^"]+)"', text)))
    for scope in scopes.MOE_CHUNK_SCOPES:
        assert any(scope in n for n in names), scope
    rows = [[f"op.{i}", name, 1.0] for i, name in enumerate(names)]
    facts = {"data_dir": BENCH_DIR, "trace_updates": 1,
             "trace": {"busy_s": len(rows) / 1e6, "window_s": 1.0},
             "scope_recording": {"hlo_stats": rows, "host_spans": []}}
    only = dict(bench, per_layer=[m for m in bench["per_layer"]
                                  if m["name"] in BY_OWN_NAMES])
    got = {k: v["value"] for k, v in run.layer_metrics(
        only, BENCH_DIR, REAL_CELL, facts, []).items()}
    assert set(got) == set(BY_OWN_NAMES)
    assert all(got[n] > 0 for n in BY_OWN_NAMES)
    for part in ("route", "experts", "gdn"):
        assert got[f"moelm_{part}_ms_per_update"] < got["moelm_stack_ms_per_update"]
    assert got["moelm_unscoped_share"] < 50


def test_new_metrics_read_nothing_on_a_program_without_the_scopes(bench):
    """The parent's program has no such scope: each reader returns None or
    0 (the line leaves the metric out or reads nothing) and does not raise;
    without a profile every one of the nine returns None."""
    import run

    rows = [["op.0", "jit(_train_chunk_s4)/while/body/collect/act/dot", 5.0],
            ["op.1", "jit(_train_chunk_s4)/while/body/learn/loss/loop/dot", 5.0]]
    facts = {"data_dir": BENCH_DIR, "trace_updates": 1,
             "trace": {"busy_s": 1e-5, "window_s": 1.0},
             "scope_recording": {"hlo_stats": rows, "host_spans": []}}
    of = lambda names: dict(bench, per_layer=[
        m for m in bench["per_layer"] if m["name"] in names])
    notes: list = []
    got = run.layer_metrics(of(BY_OWN_NAMES[:-2]), BENCH_DIR, REAL_CELL, facts, notes)
    assert all(v["value"] == 0 for v in got.values())
    no_profile = {"data_dir": BENCH_DIR, "trace_updates": 1, "trace": None}
    assert run.layer_metrics(of(NEW_METRICS), BENCH_DIR, REAL_CELL, no_profile,
                             notes) == {}


def _mode():
    spec = importlib.util.spec_from_file_location(
        "anakin_tokens_moe_mode",
        os.path.join(BENCH_DIR, "modes", "anakin_tokens_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_program_without_the_family_is_told_apart_before_anything_is_built(
        monkeypatch):
    """Every commit before PR 36: `load_config` raises on the section's
    algorithm."""
    from distributed_reinforcement_learning_tpu.utils import config

    base = _mode()._base()

    def old_load_config(path, name):
        raise ValueError("unknown algorithm 'moelm'")

    monkeypatch.setattr(config, "load_config", old_load_config)
    why = base._unsupported("unused.json", "qwen3_next")
    assert "unknown algorithm 'moelm'" in why
    assert "cannot run this configuration" in why
    assert base.COUNTERS == _mode().COUNTERS and "held_pair_share" in base.COUNTERS


def _ctx(tiny_cell, run_failed):
    with open(os.path.join(tiny_cell["dir"], "configs", "tiny_moe.json")) as f:
        cfg = json.load(f)
    out = os.path.join(tiny_cell["dir"], "out")
    os.makedirs(out)
    return {"config": cfg, "out_dir": out, "root": ROOT, "bench_dir": BENCH_DIR,
            "data_dir": tiny_cell["dir"], "chips": 1, "t_start": 0.0,
            "traffic": {"num_envs": 4, "chunk_updates": 1},
            "args": types.SimpleNamespace(seed=1, seconds=1.0, trace=0,
                                          expect_platform="cpu"),
            "RunFailed": run_failed, "NoDevice": RuntimeError}


def test_the_unsupported_exit_leaves_no_result_line(tiny_cell, monkeypatch):
    mode = _mode()

    class Exited:
        def __init__(self, cmd, stdout=None, **kw):
            assert cmd[1].endswith("anakin_tokens_moe.py")  # THIS mode's child
            stdout.write("[perfbench] UNSUPPORTED: no `moelm`\n")
            stdout.flush()

        def wait(self, timeout=None):
            return 5

        def poll(self):
            return 5

    monkeypatch.setattr(subprocess, "Popen", Exited)

    class RunFailed(Exception):
        pass

    with pytest.raises(RunFailed, match="UNSUPPORTED: no `moelm`"):
        mode.run(_ctx(tiny_cell, RunFailed))


def test_the_parent_program_exits_unsupported_on_the_real_cell(tmp_path):
    """The mode's child on a program WITHOUT the family (this tree's
    `load_config` with the branch cut out, as every commit before PR 36):
    exit code 5 and one line, within seconds, nothing built."""
    root = tmp_path / "old"
    pkg = root / "distributed_reinforcement_learning_tpu"
    import shutil

    shutil.copytree(os.path.join(ROOT, "distributed_reinforcement_learning_tpu"),
                    pkg, ignore=shutil.ignore_patterns("__pycache__"))
    config_py = pkg / "utils" / "config.py"
    config_py.write_text(config_py.read_text().replace(
        'elif algorithm == "moelm":', 'elif algorithm == "no such family":'))
    cfg = _published_config()
    run_cfg = tmp_path / "config.json"
    run_cfg.write_text(json.dumps({"qwen3_next": cfg["qwen3_next"]}))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(root), BENCH_DIR])}
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "modes", "anakin_tokens_moe.py"),
         "--config", str(run_cfg), "--section", "qwen3_next", "--seed", "1",
         "--seconds", "1", "--trace", "0", "--out", str(tmp_path), "--params", "{}",
         "--expect-platform", "cpu", "--chips", "1", "--data-dir", BENCH_DIR],
        cwd=str(root), env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 5, proc.stderr[-2000:]
    said = [line for line in proc.stderr.splitlines() if "[perfbench]" in line]
    assert len(said) == 1 and "UNSUPPORTED" in said[0] and "moelm" in said[0]


def test_a_dropped_pair_in_the_window_is_not_correct(monkeypatch):
    mode = _mode()
    result = {"correct": True, "notes": [],
              "facts": {"counters": {"dropped_pairs": 0.5}}}

    class Hybrid:
        run = staticmethod(lambda ctx: result)

    monkeypatch.setattr(mode, "_hybrid", lambda: Hybrid)
    out = mode.run({})
    assert out["correct"] is False and "dropped_pairs 0.5" in out["notes"][-1]
    result.update(correct=True, notes=[], facts={"counters": {"dropped_pairs": 0.0}})
    assert mode.run({})["correct"] is True


def test_a_state_or_a_share_other_than_the_files_is_refused():
    """The chunk's own `static_facts` at the published sizes pass; a
    bfloat16 recurrent state, a missing window, another order, another
    share of the experts do not."""
    import dataclasses

    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.moelm import MoELMAgent
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    mode = _mode()
    section = _published_config()["qwen3_next"]
    cfg, _ = load_config(os.path.join(ROOT, "config.json"), "qwen3_next")
    agent = MoELMAgent(cfg)
    facts = agent.state_facts(32)
    assert (facts["gdn_state_bytes"], facts["conv_state_bytes"],
            facts["kv_cache_bytes"]) == (201_326_592, 9_437_184, 67_108_864)
    assert mode.state_problems(facts, section, 32) == []
    assert mode.state_problems(facts, section, 16)  # other sizes
    agent.model = dataclasses.replace(agent.model, state_dtype=jnp.bfloat16)
    said = mode.state_problems(agent.state_facts(32), section, 32)
    assert len(said) == 1 and "gdn_state_bytes" in said[0]
    assert mode.state_problems({**facts, "layer_order": ORDER[::-1]}, section, 32)
    assert mode.state_problems({**facts, "conv_state_bytes": 0}, section, 32)
    for key, other in (("experts_held", 64), ("router_width", 32), ("first_expert", 32)):
        said = mode.state_problems({**facts, key: other}, section, 32)
        assert len(said) == 1 and key in said[0]


def test_operation_count_by_hand():
    """One token forward. Every layer's expert MLP: the router 2048 x 512,
    the shared expert 3 x 2048 x 512 and its gate 2048, and 0.625 held
    experts of 3 x 2048 x 512 (10 x 32 / 512). A delta-rule layer: W_qkvz
    2048 x 12,288, W_ba 2048 x 64, W_o 4096 x 2048, and per value head the
    chunk's products at C = 64. The attention layer: W_q 2048 x 8192, W_kv
    2048 x 1024, W_o 4096 x 2048 and q k^T, p v over the mean causal
    length 512.5. The untied head 2048 x 18,992 and the value."""
    family = discover.module(BENCH_DIR, "families", "moelm")
    section = _published_config()["qwen3_next"]
    moe = 2 * (2048 * 512 + 3 * 2048 * 512 + 2048) + 0.625 * 2 * 3 * 2048 * 512
    delta = (2 * (2048 * 12_288 + 2048 * 64 + 4096 * 2048)
             + 32 * (4 * 64 * 128 + 64 * 256 + 4 * 128 * 128 + 2 * 64 * 128
                     + 2 * 128 * 128))
    attention = (2 * (2048 * 8192 + 2048 * 1024 + 4096 * 2048)
                 + 2 * 2 * 1025 * 4096 // 2)
    forward = 3 * (delta + moe) + attention + moe + 2 * 2048 * 18_993
    assert family.forward_flops_per_token(section) == int(forward)
    assert 3.6e8 < forward < 4.2e8
    assert family.learn_flops_per_update(section, None) == 3 * int(forward) * 32_768
    assert family.learn_flops_per_update(section, (0, 0), 16) \
        == 3 * int(forward) * 16 * 1024


def test_configuration_file_keeps_every_published_key():
    cfg = _published_config()
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == list(CUT)
    for key, (published, here) in CUT.items():
        assert cfg[key] == here and cfg["published"][key] == published, key
        assert key in cfg["reduced_why"], key
    assert 18_992 * 8 == 151_936 and 32 * 16 == 512
    section = cfg["qwen3_next"]
    for key in (*PUBLISHED, *CUT):
        if key != "max_position_embeddings":
            assert section[key] == cfg[key], key
    assert section["layer_types"] == ORDER
    assert (section["router_width"], section["first_expert"]) == (512, 0)
    assert section["trajectory"] == cfg["max_position_embeddings"]
    assert section["dtype"] == "bfloat16" and section["algorithm"] == "moelm"
    with open(os.path.join(ROOT, "config.json")) as f:
        assert json.load(f)["qwen3_next"] == section  # the same values
    for key in ("value_head", "initializer", "act_state_dtype", "env", "loss",
                "optimizer", "dtype"):
        assert key in cfg["assumed"], key
    assert set(cfg["departures"]) == {"absent_experts", "multi_token_prediction",
                                      "auxiliary_loss"}
    assert "16 chips" in cfg["published"]["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the row the driver drew, number for number
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert cfg[key] == value or key in cfg["reduced"], key


def test_reference_copies_are_identical_and_import_nothing_of_the_program():
    with open(os.path.join(BENCH_DIR, "references", "qwen3_next.py")) as f:
        copy = f.read()
    with open(os.path.join(ROOT, "distributed_reinforcement_learning_tpu",
                           "reference", "qwen3_next.py")) as f:
        assert f.read() == copy
    imports = [line for line in copy.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations",
                       "import functools", "import jax",
                       "import jax.numpy as jnp"]
    assert 'default_matmul_precision("highest")' in copy
    assert "ragged_dot" not in copy and "solve_triangular" not in copy


def test_committed_cell_resolves_and_mirrors_the_table(bench):
    import run

    cell = run.load_cell(bench, BENCH_DIR, REAL_CELL)
    assert cell["traffic"]["mode"] == "anakin_tokens_moe"
    assert {k: cell["traffic"][k] for k in ("num_envs", "chunk_updates")} \
        == {"num_envs": 32, "chunk_updates": 1}
    section = cell["config"]["qwen3_next"]
    assert section["trajectory"] == 1024 and section["recall_distance"] == 8
    assert cell["config"]["frames_per_update"] == 32 * 1024
    assert cell["config"]["kernels"] == {"tpu_custom_call": 6}
    listed = {m["name"] for m in bench["per_layer"]
              if m.get("workloads") == [REAL_CELL]}
    assert listed == set(NEW_METRICS)
    traced = contract.cell_metrics(bench, REAL_CELL, traced=True)
    assert set(traced) == set(NEW_METRICS) | {
        "compile_s", "device_ms_per_update", "learn_mfu", "device_idle_share"}
    entry = next(c for c in bench["configs"] if c["name"] == "qwen3_next")
    assert entry["source"] == cell["config"]["source"]
    assert entry["reduced"] == cell["config"]["reduced"]
    assert bench["workloads"][-1]["name"] == REAL_CELL  # appended, last
    assert bench["configs"][-1]["name"] == "qwen3_next"
    assert [m["name"] for m in bench["per_layer"][-9:]] == list(NEW_METRICS)

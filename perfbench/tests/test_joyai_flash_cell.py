"""The cell `joyai_flash.anakin_tokens_mla_2k` (ISSUE 40): its mode
rehearsed on the CPU end to end through `run.py` at a tiny size, the early
exit on a program that cannot run the configuration, what the chunk is
held to (its layers, the bytes of its latent cache, its share of the
experts), the family's operation counts by hand, the configuration file
against the catalog's published keys, and the new metrics by scope on
the chunk's own op names. Files and entries are ADDED to `data_copy`'s
copy; none is edited.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

import contract
import discover
from conftest import BENCH_DIR, ROOT

NEW_METRICS = ("mlalm_decode_resolved_ms_per_update",
               "mlalm_attend_act_ms_per_update", "mlalm_experts_act_ms_per_update",
               "mlalm_stack_ms_per_update", "mlalm_attend_ms_per_update",
               "mlalm_project_ms_per_update", "mlalm_route_ms_per_update",
               "mlalm_experts_ms_per_update", "mlalm_mtp_ms_per_update",
               "mlalm_heads_ms_per_update", "mlalm_unresolved_share",
               "mla_flash_roofline")
BY_OWN_NAMES = NEW_METRICS[3:10]  # the resolved readers want a profile
REAL_CELL = "joyai_flash.anakin_tokens_mla_2k"
CELL = "tiny_mla.anakin_tokens_mla_2k"
ORDER = ["dense"] + ["moe"] * 4
CUT = {"num_hidden_layers": (40, 5), "n_routed_experts": (256, 16),
       "vocab_size": (129280, 16160), "max_position_embeddings": (131072, 2048)}


def _published_config():
    with open(os.path.join(BENCH_DIR, "configs", "joyai_flash.json")) as f:
        return json.load(f)


@pytest.fixture()
def tiny_cell(data_copy):
    """The published configuration's code paths (a dense run and an
    expert run, the latent cache, a router over 16 experts of which 4 are
    held, the prediction module, the blocked head) at widths a CPU
    compiles in seconds."""
    section = dict(_published_config()["joyai_flash"], hidden_size=32,
                   num_hidden_layers=3, num_attention_heads=4,
                   num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
                   qk_head_dim=12, qk_nope_head_dim=8, qk_rope_head_dim=4,
                   v_head_dim=8, intermediate_size=48, n_routed_experts=4,
                   router_width=16, first_expert=4, num_experts_per_tok=3,
                   moe_intermediate_size=16, vocab_size=96,
                   available_action=[96], trajectory=32, envs_per_actor=4,
                   dtype="float32", start_learning_rate=1e-3,
                   initializer_range=0.3)
    dd = data_copy["dir"]

    def dump(rel, obj):
        path = os.path.join(dd, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            json.dump(obj, f)

    dump("configs/tiny_mla.json", {
        "name": "tiny_mla", "section": "tiny_mla", "kernels": {},
        "frames_per_update": 128, "tiny_mla": section})
    dump(f"workloads/{CELL}.json", {
        "config": "tiny_mla", "traffic": "anakin_tokens_mla_2k",
        "overrides": {"num_envs": 4, "chunk_updates": 1}})
    bench = data_copy["bench"]
    bench["workloads"].append({"name": CELL, "config": "tiny_mla",
                               "traffic": "anakin_tokens_mla_2k", "chips": 1,
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
                 "'router_score_mean'", "'bias_abs_max'", "'mtp_loss'",
                 "'mtp_agreement'", "'router_load_max_over_mean'",
                 "'latent_cache_bytes'", "'cache_bytes_per_token'",
                 "'experts_held': 4", "'router_width': 16", "'first_expert': 4",
                 "'route_flip_share'", "'flips_over_margin': 0", "'router_prob'",
                 "chunk {", "'step_over_last_bit'"):
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
    from distributed_reinforcement_learning_tpu.agents.mlalm import (
        MLALMAgent, MLALMConfig)
    from distributed_reinforcement_learning_tpu.envs.token_recall_jax import (
        TokenRecall)
    from distributed_reinforcement_learning_tpu.observability import scopes
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
        AnakinTokens)

    cfg = MLALMConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=3, num_attention_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, intermediate_size=48, n_routed_experts=4, router_width=16,
        first_expert=4, num_experts_per_tok=3, moe_intermediate_size=16,
        trajectory=16, dtype=jnp.float32, head_block=16, row_block=2)
    an = AnakinTokens(MLALMAgent(cfg), 4, TokenRecall(64, 16))
    text = an.train_chunk.lower(an.init(jax.random.PRNGKey(0)), 1) \
        .compile().as_text()
    names = sorted(set(re.findall(r'op_name="([^"]+)"', text)))
    for scope in scopes.MLA_CHUNK_SCOPES:
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
    for part in ("attend", "project", "route", "experts"):
        assert got[f"mlalm_{part}_ms_per_update"] < got["mlalm_stack_ms_per_update"]
    # the prediction module's layer is the module's, not the stack's
    mtp = [n for n in names if "learn/loss/mtp/mla/attend" in n]
    assert mtp and all(scope_of(n) == "learn/loss/mtp" for n in mtp)


def scope_of(op_path):
    import scope_read

    return scope_read.scope_of(op_path, scope_read.vocabulary(BENCH_DIR))


def test_new_metrics_read_nothing_on_a_program_without_the_scopes(bench):
    """The parent's program has no such scope and no such kernel: each
    reader returns None or 0 and does not raise; without a profile every
    one of the twelve returns None."""
    import run

    rows = [["op.0", "jit(_train_chunk_s4)/while/body/collect/act/dot", 5.0],
            ["op.1", "jit(_train_chunk_s4)/while/body/learn/loss/loop/dot", 5.0]]
    facts = {"data_dir": BENCH_DIR, "trace_updates": 1, "chips": 1,
             "device": {"kind": "TPU v5 lite"},
             "section": _published_config()["joyai_flash"],
             "trace": {"busy_s": 1e-5, "window_s": 1.0},
             "scope_recording": {"hlo_stats": rows, "host_spans": []}}
    of = lambda names: dict(bench, per_layer=[
        m for m in bench["per_layer"] if m["name"] in names])
    notes: list = []
    got = run.layer_metrics(of((*BY_OWN_NAMES, "mla_flash_roofline")), BENCH_DIR,
                            REAL_CELL, facts, notes)
    assert "mla_flash_roofline" not in got
    assert all(v["value"] == 0 for v in got.values())
    no_profile = {"data_dir": BENCH_DIR, "trace_updates": 1, "trace": None}
    assert run.layer_metrics(of(NEW_METRICS), BENCH_DIR, REAL_CELL, no_profile,
                             notes) == {}


def test_the_kernels_roofline_share_from_a_recording(bench):
    """117.3 ms of kernel time an update would be the whole peak: the
    count is causal, by hand."""
    import run

    reducer = discover.module(BENCH_DIR, "reducers", "mla_flash_roofline")
    section = _published_config()["joyai_flash"]
    pairs = 2048 * 2049 // 2
    per_pair = 2 * (2 * (192 + 128) + (2 * 192 + 128) + (2 * 192 + 2 * 128))
    assert per_pair == 3584
    want = 6 * 16 * 32 * pairs * per_pair
    assert reducer.kernel_flops_per_update(section, 16) == want
    assert 2.3e13 < want < 2.32e13
    name = ("jit(_train_chunk_s4)/while/body/learn/transpose(jvp(learn/loss))/"
            "learn/loss/layers/while/body/checkpoint/learn/loss/layers/mla/attend/"
            "pallas_call")
    rows = [["attend.1", name + ":", 400_000.0], ["fusion.2", name + "/mul", 9e6],
            ["vtrace.3", "jit(x)/learn/loss/vtrace/jit(vtrace_pallas)/pallas_call", 9.0]]
    facts = {"data_dir": BENCH_DIR, "trace_updates": 2, "chips": 1,
             "device": {"kind": "TPU v5 lite"}, "section": section,
             "learn_batch": 16, "trace": {"busy_s": 10.0, "window_s": 10.0},
             "scope_recording": {"hlo_stats": rows, "host_spans": []}}
    only = dict(bench, per_layer=[m for m in bench["per_layer"]
                                  if m["name"] == "mla_flash_roofline"])
    got = run.layer_metrics(only, BENCH_DIR, REAL_CELL, facts, [])
    share = got["mla_flash_roofline"]["value"]
    assert abs(share - 100 * want * 2 / (0.4 * 197e12)) < 1e-9 and 50 < share < 65


def _mode():
    spec = importlib.util.spec_from_file_location(
        "anakin_tokens_mla_mode",
        os.path.join(BENCH_DIR, "modes", "anakin_tokens_mla.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_program_without_the_family_is_told_apart_before_anything_is_built(
        monkeypatch):
    """Every commit before PR 40: `load_config` raises on the section's
    algorithm."""
    from distributed_reinforcement_learning_tpu.utils import config

    base = _mode()._base()

    def old_load_config(path, name):
        raise ValueError("unknown algorithm 'mlalm'")

    monkeypatch.setattr(config, "load_config", old_load_config)
    why = base._unsupported("unused.json", "joyai_flash")
    assert "unknown algorithm 'mlalm'" in why
    assert "cannot run this configuration" in why
    assert base.COUNTERS == _mode().COUNTERS and "mtp_loss" in base.COUNTERS


def test_the_parent_program_exits_unsupported_on_the_real_cell(tmp_path):
    """The mode's child on a program WITHOUT the family (this tree's
    `load_config` with the branch cut out, as every commit before PR 40):
    exit code 5 and one line, within seconds, nothing built."""
    root = tmp_path / "old"
    pkg = root / "distributed_reinforcement_learning_tpu"
    import shutil

    shutil.copytree(os.path.join(ROOT, "distributed_reinforcement_learning_tpu"),
                    pkg, ignore=shutil.ignore_patterns("__pycache__"))
    config_py = pkg / "utils" / "config.py"
    config_py.write_text(config_py.read_text().replace(
        'elif algorithm == "mlalm":', 'elif algorithm == "no such family":'))
    cfg = _published_config()
    run_cfg = tmp_path / "config.json"
    run_cfg.write_text(json.dumps({"joyai_flash": cfg["joyai_flash"]}))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(root), BENCH_DIR])}
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "modes", "anakin_tokens_mla.py"),
         "--config", str(run_cfg), "--section", "joyai_flash", "--seed", "1",
         "--seconds", "1", "--trace", "0", "--out", str(tmp_path), "--params", "{}",
         "--expect-platform", "cpu", "--chips", "1", "--data-dir", BENCH_DIR],
        cwd=str(root), env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 5, proc.stderr[-2000:]
    said = [line for line in proc.stderr.splitlines() if "[perfbench]" in line]
    assert len(said) == 1 and "UNSUPPORTED" in said[0] and "mlalm" in said[0]


def test_a_dropped_pair_in_the_window_is_not_correct(monkeypatch):
    """`anakin_tokens_moe.run`'s rule reaches this mode's runs too."""
    mode = _mode()
    result = {"correct": True, "notes": [],
              "facts": {"counters": {"dropped_pairs": 0.5}}}
    moe = mode._moe()

    class Hybrid:
        run = staticmethod(lambda ctx: result)

    moe._hybrid = lambda: Hybrid
    monkeypatch.setattr(mode, "_moe", lambda: moe)
    out = mode.run({})
    assert out["correct"] is False and "dropped_pairs 0.5" in out["notes"][-1]
    assert Hybrid.state_problems is mode.state_problems  # this stack's account


def test_a_cache_or_a_share_other_than_the_files_is_refused():
    """The chunk's own `static_facts` at the published sizes pass; a
    float32 cache, a cache of expanded keys and values, another order,
    another share of the experts do not."""
    import dataclasses

    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.mlalm import MLALMAgent
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    mode = _mode()
    section = _published_config()["joyai_flash"]
    cfg, _ = load_config(os.path.join(ROOT, "config.json"), "joyai_flash")
    facts = MLALMAgent(cfg).state_facts(16)
    assert facts["latent_cache_bytes"] == 188_743_680
    assert (facts["cache_bytes_per_token"],
            facts["expanded_cache_bytes_per_token"]) == (5_760, 102_400)
    assert mode.state_problems(facts, section, 16) == []
    assert mode.state_problems(facts, section, 32)  # other sizes
    wide = MLALMAgent(dataclasses.replace(cfg, dtype=jnp.float32)).state_facts(16)
    said = mode.state_problems(wide, section, 16)
    assert len(said) == 2 and "latent_cache_bytes" in said[0]
    expanded = {**facts, "latent_cache_bytes": 16 * 2048 * 102_400,
                "cache_bytes_per_token": 102_400}
    assert len(mode.state_problems(expanded, section, 16)) == 2
    assert mode.state_problems({**facts, "layer_order": ORDER[::-1]}, section, 16)
    for key, other in (("experts_held", 32), ("router_width", 16), ("first_expert", 16)):
        said = mode.state_problems({**facts, key: other}, section, 16)
        assert len(said) == 1 and key in said[0]


def test_operation_count_by_hand():
    """One token forward. Latent attention in each of the 5 layers and in
    the prediction module: W_qa 2048 x 1536, W_qb 1536 x 6144, W_kva 2048
    x 576, W_kvb 512 x 8192, W_o 4096 x 2048, and q k^T (192) and p v
    (128) over the mean causal length 1024.5 for 32 heads. The dense
    layer: 3 x 2048 x 7168. An expert layer: the router 2048 x 256, the
    shared expert 3 x 2048 x 768 and 0.5 held experts of 3 x 2048 x 768
    (8 x 16 / 256). The module besides: W_p 4096 x 2048 and the head
    2048 x 16,160. The untied head 2048 x 16,160 and the value."""
    family = discover.module(BENCH_DIR, "families", "mlalm")
    section = _published_config()["joyai_flash"]
    mla = (2 * (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048)
           + 2 * 2049 * 32 * 320 // 2)
    moe = 2 * (2048 * 256 + 3 * 2048 * 768) + 0.5 * 2 * 3 * 2048 * 768
    forward = ((mla + 2 * 3 * 2048 * 7168) + 4 * (mla + moe)
               + (2 * 4096 * 2048 + mla + moe + 2 * 2048 * 16_160)
               + 2 * 2048 * 16_161)
    assert family.forward_flops_per_token(section) == int(forward)
    assert 7.4e8 < forward < 7.7e8
    assert family.learn_flops_per_update(section, None) == 3 * int(forward) * 32_768
    assert family.learn_flops_per_update(section, (0, 0), 8) \
        == 3 * int(forward) * 8 * 2048


def test_configuration_file_keeps_every_published_key():
    cfg = _published_config()
    assert cfg["reduced"] == list(CUT)
    for key, (published, here) in CUT.items():
        assert cfg[key] == here and cfg["published"][key] == published, key
        assert key in cfg["reduced_why"], key
    assert 16_160 * 8 == 129_280 and 16 * 16 == 256
    section = cfg["joyai_flash"]
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "q_lora_rank", "kv_lora_rank", "qk_head_dim", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "head_dim", "num_attention_heads",
              "num_key_value_heads", "num_experts_per_tok", "n_shared_experts",
              "routed_scaling_factor", "rope_theta", "first_k_dense_replace")
    for key in (*widths, *CUT):
        if key != "max_position_embeddings":
            assert section[key] == cfg[key], key
    assert not set(widths) & set(cfg["reduced"])
    assert (section["router_width"], section["first_expert"]) == (256, 0)
    assert section["trajectory"] == cfg["max_position_embeddings"]
    assert section["dtype"] == "bfloat16" and section["algorithm"] == "mlalm"
    with open(os.path.join(ROOT, "config.json")) as f:
        assert json.load(f)["joyai_flash"] == section  # the same values
    for key in ("value_head", "initializer", "bias_update_speed", "mtp_loss_coef",
                "mtp_input", "act_state_dtype", "env", "loss", "optimizer", "dtype"):
        assert key in cfg["assumed"], key
    assert set(cfg["departures"]) == {"absent_experts", "multi_token_prediction",
                                      "pipeline_ends", "mtp_reduction"}
    assert "16 chips" in cfg["published"]["deployment"]
    assert "both ends" in cfg["published"]["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the row the driver drew, number for number
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "JoyAI-LLM-Flash")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert cfg[key] == value or key in cfg["reduced"], key


def test_reference_copies_are_identical_and_import_nothing_of_the_program():
    with open(os.path.join(BENCH_DIR, "references", "joyai_flash.py")) as f:
        copy = f.read()
    with open(os.path.join(ROOT, "distributed_reinforcement_learning_tpu",
                           "reference", "joyai_flash.py")) as f:
        assert f.read() == copy
    imports = [line for line in copy.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations",
                       "import functools", "import jax",
                       "import jax.numpy as jnp"]
    assert 'default_matmul_precision("highest")' in copy
    assert "ragged_dot" not in copy and "pallas" not in copy


def test_committed_cell_resolves_and_mirrors_the_table(bench):
    import run

    cell = run.load_cell(bench, BENCH_DIR, REAL_CELL)
    assert cell["traffic"]["mode"] == "anakin_tokens_mla"
    assert {k: cell["traffic"][k] for k in ("num_envs", "chunk_updates")} \
        == {"num_envs": 16, "chunk_updates": 1}
    section = cell["config"]["joyai_flash"]
    assert section["trajectory"] == 2048 and section["recall_distance"] == 8
    assert cell["config"]["frames_per_update"] == 16 * 2048
    assert cell["config"]["kernels"] == {"tpu_custom_call": 14}
    listed = {m["name"] for m in bench["per_layer"]
              if m.get("workloads") == [REAL_CELL]}
    assert listed == set(NEW_METRICS)
    traced = contract.cell_metrics(bench, REAL_CELL, traced=True)
    assert set(traced) == set(NEW_METRICS) | {
        "compile_s", "device_ms_per_update", "learn_mfu", "device_idle_share"}
    entry = next(c for c in bench["configs"] if c["name"] == "joyai_flash")
    assert entry["source"] == cell["config"]["source"]
    assert entry["reduced"] == cell["config"]["reduced"]
    assert REAL_CELL in [w["name"] for w in bench["workloads"]]
    names = [m["name"] for m in bench["per_layer"]]
    assert names[names.index(NEW_METRICS[0]):][:12] == list(NEW_METRICS)
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])

"""The cell `lfm2_moe.anakin_tokens_conv_1k` (ISSUE 46): its mode
rehearsed on the CPU end to end through `run.py` at a tiny size, the early
exit on a program that cannot run the configuration, what the chunk is
held to (its layers, the bytes of its windows and of its one cache, its
share of the experts), the family's operation counts and the decode
step's weight bytes by hand, the configuration file against the catalog's
published keys, and the new metrics by scope on the chunk's own op names.
Files and entries are ADDED to `data_copy`'s copy; none is edited.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

import contract
import discover
from conftest import BENCH_DIR, ROOT

NEW_METRICS = ("convlm_decode_resolved_ms_per_update", "convlm_conv_act_ms_per_update",
               "convlm_experts_act_ms_per_update", "convlm_stack_ms_per_update",
               "convlm_conv_ms_per_update", "convlm_attend_ms_per_update",
               "convlm_route_ms_per_update", "convlm_experts_ms_per_update",
               "convlm_heads_ms_per_update", "convlm_unresolved_share",
               "convlm_decode_weight_read_share")
BY_OWN_NAMES = NEW_METRICS[3:9]  # the resolved readers want a profile
REAL_CELL = "lfm2_moe.anakin_tokens_conv_1k"
CELL = "tiny_conv.anakin_tokens_conv_1k"
ORDER = ["conv+dense", "full_attention+moe", "conv+moe", "conv+moe", "conv+moe"]
CUT = {"num_hidden_layers": (40, 5), "num_dense_layers": (2, 1),
       "num_experts": (64, 16), "vocab_size": (65536, 16384),
       "max_position_embeddings": (128000, 1024)}


def _published_config():
    with open(os.path.join(BENCH_DIR, "configs", "lfm2_moe.json")) as f:
        return json.load(f)


def _tiny_section() -> dict:
    """The published configuration's code paths (three runs of two mixers
    and two MLPs, four windows and one cache, a router over 16 experts of
    which 4 are held, no shared expert, the blocked tied head) at widths a
    CPU compiles in seconds: `config.json`'s small section."""
    with open(os.path.join(ROOT, "config.json")) as f:
        small = json.load(f)["lfm2_moe_small"]
    return dict(small, vocab_size=96, available_action=[96])


@pytest.fixture()
def tiny_cell(data_copy):
    dd = data_copy["dir"]

    def dump(rel, obj):
        path = os.path.join(dd, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            json.dump(obj, f)

    dump("configs/tiny_conv.json", {
        "name": "tiny_conv", "section": "tiny_conv", "kernels": {},
        "frames_per_update": 128, "tiny_conv": _tiny_section()})
    dump(f"workloads/{CELL}.json", {
        "config": "tiny_conv", "traffic": "anakin_tokens_conv_1k",
        "overrides": {"num_envs": 4, "chunk_updates": 1}})
    bench = data_copy["bench"]
    bench["workloads"].append({"name": CELL, "config": "tiny_conv",
                               "traffic": "anakin_tokens_conv_1k", "chips": 1,
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
                 "'router_score_mean'", "'bias_abs_max'", "'conv_gate_abs_mean'",
                 "'conv_state_abs_max'", "'router_load_max_over_mean'",
                 "'pair_slabs_mean'", "'conv_state_bytes'", "'kv_cache_bytes'",
                 "'act_weight_bytes'", "'experts_held': 4", "'router_width': 16",
                 "'first_expert': 4", "'layer_order': ['conv+dense'",
                 "'route_flip_share'", "'flips_over_margin': 0", "'router_prob'",
                 "chunk {", "'step_over_last_bit'"):
        assert said in proc.stdout, said


def _tiny_chunk_names():
    import re

    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.convlm import (
        ConvLMAgent, ConvLMConfig)
    from distributed_reinforcement_learning_tpu.envs.token_recall_jax import (
        TokenRecall)
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
        AnakinTokens)

    cfg = ConvLMConfig(
        vocab_size=64, hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        rope_theta=1e4, intermediate_size=48, num_experts=4, router_width=16,
        first_expert=4, num_experts_per_tok=3, moe_intermediate_size=16,
        trajectory=16, dtype=jnp.float32, head_block=16, row_block=2)
    an = AnakinTokens(ConvLMAgent(cfg), 4, TokenRecall(64, 16))
    text = an.train_chunk.lower(an.init(jax.random.PRNGKey(0)), 1) \
        .compile().as_text()
    return sorted(set(re.findall(r'op_name="([^"]+)"', text)))


def test_scope_metrics_read_the_chunks_own_names(bench):
    """The metrics by own names on a recording made of the op names of a
    tiny `AnakinTokens.train_chunk` of this family compiled here, 1 us
    each: every one reads something, and every scope of the family's
    vocabulary appears."""
    import run
    from distributed_reinforcement_learning_tpu.observability import scopes

    names = _tiny_chunk_names()
    for scope in scopes.CONV_CHUNK_SCOPES:
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
    for part in ("conv", "attend", "route", "experts"):
        assert got[f"convlm_{part}_ms_per_update"] < got["convlm_stack_ms_per_update"]
    # the act-time convolution is the act path's, not the learner's
    act = [n for n in names if "collect/act/conv" in n]
    assert act and all(scope_of(n) == "collect/act/conv" for n in act)


def scope_of(op_path):
    import scope_read

    return scope_read.scope_of(op_path, scope_read.vocabulary(BENCH_DIR))


def test_new_metrics_read_nothing_on_a_program_without_the_scopes(bench):
    """The parent's program has no such scope: each reader by own names
    returns 0 and does not raise; the weight-read share, which needs a
    section of this family, returns None on another's; without a profile
    every one of the eleven returns None."""
    import run

    rows = [["op.0", "jit(_train_chunk_s4)/while/body/collect/env/dot", 5.0],
            ["op.1", "jit(_train_chunk_s4)/while/body/learn/loss/loop/dot", 5.0]]
    with open(os.path.join(BENCH_DIR, "configs", "joyai_flash.json")) as f:
        other = json.load(f)["joyai_flash"]
    facts = {"data_dir": BENCH_DIR, "trace_updates": 1, "chips": 1,
             "device": {"kind": "TPU v5 lite"}, "section": other,
             "trace": {"busy_s": 1e-5, "window_s": 1.0},
             "scope_recording": {"hlo_stats": rows, "host_spans": []}}
    of = lambda names: dict(bench, per_layer=[
        m for m in bench["per_layer"] if m["name"] in names])
    notes: list = []
    got = run.layer_metrics(of((*BY_OWN_NAMES, NEW_METRICS[-1])), BENCH_DIR,
                            REAL_CELL, facts, notes)
    assert NEW_METRICS[-1] not in got
    assert all(v["value"] == 0 for v in got.values())
    # this family's section, and still no op under `collect/act`: nothing to read
    facts["section"] = _published_config()["lfm2_moe"]
    facts.pop("_scope_read", None)
    assert run.layer_metrics(of(NEW_METRICS[-1:]), BENCH_DIR, REAL_CELL, facts,
                             notes) == {}
    no_profile = {"data_dir": BENCH_DIR, "trace_updates": 1, "trace": None}
    assert run.layer_metrics(of(NEW_METRICS), BENCH_DIR, REAL_CELL, no_profile,
                             notes) == {}


def test_the_decode_steps_weight_read_share_from_a_recording(bench):
    """The bytes by hand, and the share of a recording: 2,048 decode
    steps in 5 s of `collect/act` and below are 2.442 ms a step where the
    weights alone take 1.924."""
    import run

    reducer = discover.module(BENCH_DIR, "reducers", "decode_weight_read_share")
    section = _published_config()["lfm2_moe"]
    conv = 2048 * 6144 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 8 * 64
    experts = 2048 * 64 + 16 * 3 * 2048 * 1536
    want = 2 * (4 * conv + attention + 3 * 2048 * 11776 + 4 * experts + 16384 * 2048)
    assert reducer.act_weight_bytes(section) == want == 1_576_009_728
    # a lower bound on what the program says a step reads (the routers in float32)
    from distributed_reinforcement_learning_tpu.agents.convlm import ConvLMAgent
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    cfg, _ = load_config(os.path.join(ROOT, "config.json"), "lfm2_moe")
    said = ConvLMAgent(cfg).state_facts(64)["act_weight_bytes"]
    assert 0 <= said - want == 4 * 2048 * 64 * 2
    act = "jit(_train_chunk_s4)/while/body/collect/while/body/collect/act/"
    rows = [["dot.1", act + "collect/act/layers/dot_general", 3_000_000.0],
            ["mul.2", act + "collect/act/layers/collect/act/conv/mul", 1_500_000.0],
            ["sort.3", act + "collect/act/layers/collect/act/moe/collect/act/moe/"
             "experts/sort", 500_000.0],
            ["dot.4", "jit(_train_chunk_s4)/while/body/learn/learn/loss/dot", 9e6]]
    facts = {"data_dir": BENCH_DIR, "trace_updates": 2, "chips": 1,
             "device": {"kind": "TPU v5 lite"}, "section": section,
             "trace": {"busy_s": 14.0, "window_s": 14.0}, "notes": (notes := []),
             "scope_recording": {"hlo_stats": rows, "host_spans": []}}
    only = dict(bench, per_layer=[m for m in bench["per_layer"] if m["name"] in (
        NEW_METRICS[-1], NEW_METRICS[0], NEW_METRICS[1])])
    got = {k: v["value"] for k, v in run.layer_metrics(
        only, BENCH_DIR, REAL_CELL, facts, notes).items()}
    assert abs(got[NEW_METRICS[0]] - 2500.0) < 1e-6
    assert abs(got[NEW_METRICS[1]] - 750.0) < 1e-6
    share = got[NEW_METRICS[-1]]
    assert abs(share - 100 * want * 2048 / 819e9 / 5.0) < 1e-9 and 78 < share < 79
    assert any("1.924 ms a step" in n and "2.441 ms a step" in n for n in notes), notes
    entry = next(m for m in bench["per_layer"] if m["name"] == NEW_METRICS[-1])
    assert (entry["unit"], entry["better"]) == ("%", "higher")


def _mode():
    spec = importlib.util.spec_from_file_location(
        "anakin_tokens_conv_mode",
        os.path.join(BENCH_DIR, "modes", "anakin_tokens_conv.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_program_without_the_family_is_told_apart_before_anything_is_built(
        monkeypatch):
    """Every commit before PR 46: `load_config` raises on the section's
    algorithm."""
    from distributed_reinforcement_learning_tpu.utils import config

    base = _mode()._base()

    def old_load_config(path, name):
        raise ValueError("unknown algorithm 'convlm'")

    monkeypatch.setattr(config, "load_config", old_load_config)
    why = base._unsupported("unused.json", "lfm2_moe")
    assert "unknown algorithm 'convlm'" in why
    assert "cannot run this configuration" in why
    assert base.COUNTERS == _mode().COUNTERS and "conv_gate_abs_mean" in base.COUNTERS


def test_the_parent_program_exits_unsupported_on_the_real_cell(tmp_path):
    """The mode's child on a program WITHOUT the family (this tree with the
    family's row and import cut out of `agents/token_families.py`, and
    without its model and agent files: every commit before PR 46): exit
    code 5 and one line that names the family, within seconds, nothing
    built and no device opened."""
    root = tmp_path / "old"
    pkg = root / "distributed_reinforcement_learning_tpu"
    shutil.copytree(os.path.join(ROOT, "distributed_reinforcement_learning_tpu"),
                    pkg, ignore=shutil.ignore_patterns("__pycache__"))
    table = pkg / "agents" / "token_families.py"
    kept = [line for line in table.read_text().splitlines(keepends=True)
            if "convlm" not in line and "ConvLM" not in line]
    table.write_text("".join(kept))
    assert "convlm" not in table.read_text()
    os.remove(pkg / "agents" / "convlm.py")
    os.remove(pkg / "models" / "conv_moe_lm.py")
    cfg = _published_config()
    run_cfg = tmp_path / "config.json"
    run_cfg.write_text(json.dumps({"lfm2_moe": cfg["lfm2_moe"]}))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(root), BENCH_DIR])}
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "modes", "anakin_tokens_conv.py"),
         "--config", str(run_cfg), "--section", "lfm2_moe", "--seed", "1",
         "--seconds", "1", "--trace", "0", "--out", str(tmp_path), "--params", "{}",
         "--expect-platform", "cpu", "--chips", "1", "--data-dir", BENCH_DIR],
        cwd=str(root), env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 5, proc.stderr[-2000:]
    said = [line for line in proc.stderr.splitlines() if "[perfbench]" in line]
    assert len(said) == 1 and "UNSUPPORTED" in said[0] and "convlm" in said[0]
    assert "device:" not in proc.stdout + proc.stderr  # the chip was never opened


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


def test_windows_a_cache_or_a_share_other_than_the_files_are_refused():
    """The chunk's own `static_facts` at the published sizes pass;
    float32 windows and cache, a window of three columns, a cache of the
    query heads, another order, another share of the experts do not."""
    import dataclasses

    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.convlm import ConvLMAgent
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    mode = _mode()
    section = _published_config()["lfm2_moe"]
    cfg, _ = load_config(os.path.join(ROOT, "config.json"), "lfm2_moe")
    facts = ConvLMAgent(cfg).state_facts(64)
    assert (facts["conv_state_bytes"], facts["kv_cache_bytes"]) == (
        2_097_152, 134_217_728)
    assert list(facts["layer_order"]) == ORDER
    assert mode.state_problems(facts, section, 64) == []
    assert mode.state_problems(facts, section, 32)  # other sizes
    wide = ConvLMAgent(dataclasses.replace(cfg, dtype=jnp.float32)).state_facts(64)
    said = mode.state_problems(wide, section, 64)
    assert len(said) == 2 and "conv_state_bytes" in said[0] and "kv_cache_bytes" in said[1]
    three = {**facts, "conv_state_bytes": facts["conv_state_bytes"] * 3 // 2}
    assert len(mode.state_problems(three, section, 64)) == 1
    every_head = {**facts, "kv_cache_bytes": facts["kv_cache_bytes"] * 4}
    assert len(mode.state_problems(every_head, section, 64)) == 1
    assert mode.state_problems({**facts, "layer_order": ORDER[::-1]}, section, 64)
    two_dense = dict(section, num_dense_layers=2)
    assert "conv+dense" in mode.state_problems(facts, two_dense, 64)[0]
    for key, other in (("experts_held", 8), ("router_width", 16), ("first_expert", 16)):
        said = mode.state_problems({**facts, key: other}, section, 64)
        assert len(said) == 1 and key in said[0]


def test_operation_count_by_hand():
    """One token forward. A convolution mixer: W_in 2048 x 6144, W_out
    2048 x 2048 and three taps of 2048; the attention mixer: q and o 2048
    x 2048, k and v 2048 x 512, and q k^T and p v (64 wide, 32 heads) over
    the mean causal length 512.5. The dense layer: 3 x 2048 x 11,776. An
    expert layer: the router 2048 x 64 and 1.0 held experts of 3 x 2048 x
    1,536 (4 x 16 / 64), and NO shared expert. The tied head 2048 x 16,384
    and the value."""
    family = discover.module(BENCH_DIR, "families", "convlm")
    section = _published_config()["lfm2_moe"]
    conv = 2 * (2048 * 6144 + 2048 * 2048) + 2 * 3 * 2048
    attention = 2 * (2 * 2048 * 2048 + 2 * 2048 * 512) + 2 * 2 * 1025 * 2048 // 2
    moe = 2 * 2048 * 64 + 1.0 * 2 * 3 * 2048 * 1536
    forward = ((conv + 2 * 3 * 2048 * 11776) + (attention + moe) + 3 * (conv + moe)
               + 2 * 2048 * 16_385)
    assert family.forward_flops_per_token(section) == int(forward)
    assert 4.4e8 < forward < 4.6e8
    assert family.learn_flops_per_update(section, None) == 3 * int(forward) * 65_536
    assert family.learn_flops_per_update(section, (0, 0), 8) \
        == 3 * int(forward) * 8 * 1024


def test_configuration_file_keeps_every_published_key():
    cfg = _published_config()
    assert cfg["reduced"] == list(CUT)
    for key, (published, here) in CUT.items():
        assert cfg[key] == here and cfg["published"][key] == published, key
        assert key in cfg["reduced_why"], key
    assert 16_384 * 4 == 65_536 and 16 * 4 == 64
    section = cfg["lfm2_moe"]
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "num_key_value_heads", "num_experts_per_tok",
              "conv_L_cache", "routed_scaling_factor", "norm_eps")
    for key in (*widths, *CUT, "rope_parameters", "conv_bias", "use_expert_bias",
                "norm_topk_prob"):
        if key != "max_position_embeddings":
            assert section[key] == cfg[key], key
    assert not set(widths) & set(cfg["reduced"])
    assert len(cfg["layer_types"]) == 40  # the published order, copied whole
    assert section["layer_types"] == cfg["layer_types"][0:1] + cfg["layer_types"][2:6]
    assert [f"{m}+{'dense' if i < 1 else 'moe'}"
            for i, m in enumerate(section["layer_types"])] == ORDER
    assert section["rope_theta"] == cfg["rope_parameters"]["rope_theta"]
    assert (section["router_width"], section["first_expert"]) == (64, 0)
    assert section["trajectory"] == cfg["max_position_embeddings"]
    assert section["dtype"] == "bfloat16" and section["algorithm"] == "convlm"
    with open(os.path.join(ROOT, "config.json")) as f:
        assert json.load(f)["lfm2_moe"] == section  # the same values
    for key in ("tie_word_embeddings", "weight_eps", "value_head", "initializer",
                "bias_update_speed", "act_state_dtype", "env", "loss", "optimizer",
                "dtype"):
        assert key in cfg["assumed"], key
    assert set(cfg["departures"]) == {"absent_experts", "pipeline_ends",
                                      "second_dense_layer"}
    assert "4 chips" in cfg["published"]["deployment"]
    assert "both ends" in cfg["published"]["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the row the driver drew, number for number
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert cfg[key] == value or key in cfg["reduced"], key


def test_reference_copies_are_identical_and_import_nothing_of_the_program():
    with open(os.path.join(BENCH_DIR, "references", "lfm2_moe.py")) as f:
        copy = f.read()
    with open(os.path.join(ROOT, "distributed_reinforcement_learning_tpu",
                           "reference", "lfm2_moe.py")) as f:
        assert f.read() == copy
    imports = [line for line in copy.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations",
                       "import functools", "import jax",
                       "import jax.numpy as jnp"]
    assert 'default_matmul_precision("highest")' in copy
    assert "ragged_dot" not in copy and "pallas" not in copy


def test_committed_cell_resolves_and_lists_its_own_metrics_in_order(bench):
    import run

    cell = run.load_cell(bench, BENCH_DIR, REAL_CELL)
    assert cell["traffic"]["mode"] == "anakin_tokens_conv"
    assert {k: cell["traffic"][k] for k in ("num_envs", "chunk_updates")} \
        == {"num_envs": 64, "chunk_updates": 1}
    section = cell["config"]["lfm2_moe"]
    assert section["trajectory"] == 1024 and section["recall_distance"] == 8
    assert cell["config"]["frames_per_update"] == 64 * 1024
    assert cell["config"]["kernels"] == {"tpu_custom_call": 6}
    # membership and order of ITS OWN metrics only: another cell's are not this test's
    own = [m["name"] for m in bench["per_layer"] if m["name"].startswith("convlm_")]
    assert own == list(NEW_METRICS)
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert REAL_CELL in m["workloads"] and m["source"] == "device_trace"
            assert m["moves"] == "frames_learned_per_s"
    traced = contract.cell_metrics(bench, REAL_CELL, traced=True)
    assert set(NEW_METRICS) | {"compile_s", "device_ms_per_update", "learn_mfu",
                               "device_idle_share"} <= set(traced)
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2_moe")
    assert entry["source"] == cell["config"]["source"]
    assert entry["reduced"] == cell["config"]["reduced"]
    assert entry["file"] == "perfbench/configs/lfm2_moe.json"
    listed = next(w for w in bench["workloads"] if w["name"] == REAL_CELL)
    assert listed["chips"] == 1 and len(listed["why"]) <= 200

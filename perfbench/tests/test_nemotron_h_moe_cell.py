"""The cell `nemotron_h_moe.anakin_tokens_ssm_2k` (ISSUE 53): its mode
rehearsed on the CPU end to end through `run.py` at a tiny size (`ME*ME`:
every kind of layer, four chunks of the scan an episode), the early exit
on a program that cannot run the configuration, what the chunk is held to
(its layers as the published string, the bytes of its recurrent states,
windows and one cache, its share of the experts), the family's operation
counts and the decode step's bytes by hand, the configuration file
against the catalog's published keys, and the new metrics by scope on the
chunk's own op names. Files and entries are ADDED to `data_copy`'s copy;
none is edited.
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

NEW_METRICS = ("ssmoelm_decode_resolved_ms_per_update", "ssmoelm_ssm_act_ms_per_update",
               "ssmoelm_experts_act_ms_per_update", "ssmoelm_stack_ms_per_update",
               "ssmoelm_ssd_ms_per_update", "ssmoelm_attend_ms_per_update",
               "ssmoelm_route_ms_per_update", "ssmoelm_experts_ms_per_update",
               "ssmoelm_heads_ms_per_update", "ssmoelm_unresolved_share",
               "ssmoelm_decode_read_share")
BY_OWN_NAMES = NEW_METRICS[3:9]  # the resolved readers want a profile
REAL_CELL = "nemotron_h_moe.anakin_tokens_ssm_2k"
CELL = "tiny_ssmoe.anakin_tokens_ssm_2k"
ORDER = "MEMEM*EME"
CUT = {"num_hidden_layers": (52, 9), "n_routed_experts": (128, 8),
       "vocab_size": (131072, 16384), "max_position_embeddings": (262144, 2048)}
SPANS = tuple(range(256, 2049, 256))


def _published_config():
    with open(os.path.join(BENCH_DIR, "configs", "nemotron_h_moe.json")) as f:
        return json.load(f)


def _tiny_section() -> dict:
    """The published configuration's code paths (three kinds of layer of
    one sublayer each, grouped B and C, a router over 16 experts of which
    4 are held, ungated experts beside a shared one, the blocked untied
    head) at widths a CPU compiles in seconds: `config.json`'s small
    section."""
    with open(os.path.join(ROOT, "config.json")) as f:
        small = json.load(f)["nemotron_h_moe_small"]
    return dict(small, vocab_size=96, available_action=[96])


@pytest.fixture()
def tiny_cell(data_copy):
    dd = data_copy["dir"]

    def dump(rel, obj):
        path = os.path.join(dd, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            json.dump(obj, f)

    dump("configs/tiny_ssmoe.json", {
        "name": "tiny_ssmoe", "section": "tiny_ssmoe", "kernels": {},
        "frames_per_update": 128, "tiny_ssmoe": _tiny_section()})
    dump(f"workloads/{CELL}.json", {
        "config": "tiny_ssmoe", "traffic": "anakin_tokens_ssm_2k",
        "overrides": {"num_envs": 4, "chunk_updates": 1}})
    bench = data_copy["bench"]
    bench["workloads"].append({"name": CELL, "config": "tiny_ssmoe",
                               "traffic": "anakin_tokens_ssm_2k", "chips": 1,
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
                 "'relu2_zero_share'", "'held_experts_touched_mean'", "'dt_mean'",
                 "'bias_abs_max'", "'state_norm_mean'",
                 "'router_load_max_over_mean'", "'pair_slabs_mean'",
                 "'ssm_state_bytes'", "'conv_state_bytes'", "'kv_cache_bytes'",
                 "'route_record_bytes'", "'act_weight_bytes'", "'experts_held': 4",
                 "'router_width': 16", "'first_expert': 4", "'layer_order': 'ME*ME'",
                 "'route_flip_share'", "'flips_over_margin': 0", "'router_prob'",
                 "'relu2_zero'", "'state'", "chunk {", "'step_over_last_bit'"):
        assert said in proc.stdout, said


def _tiny_chunk_names():
    import dataclasses
    import re

    import jax

    from distributed_reinforcement_learning_tpu.agents.ssmoelm import SSMoELMAgent
    from distributed_reinforcement_learning_tpu.envs.token_recall_jax import (
        TokenRecall)
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
        AnakinTokens)
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    cfg = dataclasses.replace(
        load_config(os.path.join(ROOT, "config.json"), "nemotron_h_moe_small")[0],
        trajectory=16, head_block=16)
    an = AnakinTokens(SSMoELMAgent(cfg), 4, TokenRecall(64, 16))
    text = an.train_chunk.lower(an.init(jax.random.PRNGKey(0)), 1) \
        .compile().as_text()
    return sorted(set(re.findall(r'op_name="([^"]+)"', text)))


def scope_of(op_path):
    import scope_read

    return scope_read.scope_of(op_path, scope_read.vocabulary(BENCH_DIR))


def test_scope_metrics_read_the_chunks_own_names(bench):
    """The metrics by own names on a recording made of the op names of a
    tiny `AnakinTokens.train_chunk` of this family compiled here, 1 us
    each: every one reads something, every scope of the family's
    vocabulary appears, and the three kinds of layer are told apart."""
    import run
    from distributed_reinforcement_learning_tpu.observability import scopes

    names = _tiny_chunk_names()
    for scope in scopes.SSMOE_CHUNK_SCOPES:
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
    for part in ("ssd", "attend", "route", "experts"):
        assert got[f"ssmoelm_{part}_ms_per_update"] < got["ssmoelm_stack_ms_per_update"]
    for scope in ("collect/act/ssm", "collect/act/cache",
                  "learn/loss/layers/ssd", "learn/loss/layers/conv",
                  "learn/loss/layers/global_attention", "learn/loss/layers/moe/shared"):
        own = [n for n in names if n.endswith(scope) or scope + "/" in n]
        assert own and all(scope_of(n) == scope for n in own), scope


def test_new_metrics_read_nothing_on_a_program_without_the_scopes(bench):
    """The parent's program has no such scope: each reader by own names
    returns 0 and does not raise; the share, which needs a section of this
    family and the run's counter, returns None on another's; without a
    profile every one of the eleven returns None."""
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
    # this family's section, its counter and spans, and still no op under
    # `collect/act`: nothing to read
    facts.update(section=_published_config()["nemotron_h_moe"],
                 static={"decode_spans": SPANS},
                 counters={"held_experts_touched_mean": 4.2})
    facts.pop("_scope_read", None)
    assert run.layer_metrics(of(NEW_METRICS[-1:]), BENCH_DIR, REAL_CELL, facts,
                             notes) == {}
    no_profile = {"data_dir": BENCH_DIR, "trace_updates": 1, "trace": None}
    assert run.layer_metrics(of(NEW_METRICS), BENCH_DIR, REAL_CELL, no_profile,
                             notes) == {}


def test_the_decode_steps_bytes_by_hand_and_its_share_of_a_recording(bench):
    """The bytes a decode step must move, by hand from the published
    shapes, and the share of a recording: 2,048 decode steps in 6.144 s
    under `collect/act` are 3 ms a step where the traffic alone takes
    1.5."""
    import run

    reads = discover.module(BENCH_DIR, "reducers", "ssm_decode_read_share")
    section = _published_config()["nemotron_h_moe"]
    parts = reads.step_bytes(section, 16, SPANS, 4.0)
    position = 2 * 16 * 2 * 128 * 2
    assert parts == {
        "mixers": 2 * 4 * (2688 * 10_304 + 4096 * 2688),
        "attention": 2 * (2 * 2688 * 4096 + 2 * 2688 * 256),
        "shared": 2 * 4 * 2 * 2688 * 3712, "routers": 4 * 4 * 2688 * 128,
        "experts": 2 * 4 * 4.0 * 2 * 2688 * 1856, "head": 2 * 16_384 * 2688,
        "state": 2 * 134_217_728, "windows": 2 * 4 * 16 * 3 * 6144 * 4,
        "cache": position * 256 * sum(SPANS) / 2048}
    assert parts["cache"] == 18_874_368 and 1.2e9 < sum(parts.values()) < 1.25e9
    # what the program says a step could read whole: every held expert, no state
    from distributed_reinforcement_learning_tpu.agents.ssmoelm import SSMoELMAgent
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    cfg, _ = load_config(os.path.join(ROOT, "config.json"), "nemotron_h_moe")
    whole = reads.step_bytes(section, 16, SPANS, 8.0)
    assert SSMoELMAgent(cfg).state_facts(16)["act_weight_bytes"] == sum(
        whole[k] for k in ("mixers", "attention", "shared", "routers", "experts",
                           "head"))
    act = "jit(_train_chunk_s4)/while/body/collect/while/body/collect/act/"
    rows = [["dot.1", act + "collect/act/layers/dot_general", 3_000_000.0],
            ["mul.2", act + "collect/act/layers/collect/act/ssm/mul", 2_000_000.0],
            ["dus.3", act + "collect/act/layers/collect/act/attend/collect/act/cache/"
             "dynamic_update_slice", 144_000.0],
            ["sort.4", act + "collect/act/layers/collect/act/moe/experts/sort",
             1_000_000.0]]
    facts = {"data_dir": BENCH_DIR, "trace_updates": 1, "chips": 1, "num_envs": 16,
             "device": {"kind": "TPU v5 lite"}, "section": section,
             "static": {"decode_spans": list(SPANS)},
             "counters": {"held_experts_touched_mean": 4.0},
             "trace": {"busy_s": 10.0, "window_s": 10.0}, "notes": (notes := []),
             "scope_recording": {"hlo_stats": rows, "host_spans": []}}
    only = dict(bench, per_layer=[m for m in bench["per_layer"] if m["name"] in (
        *NEW_METRICS[:3], NEW_METRICS[-1])])
    got = {k: v["value"] for k, v in run.layer_metrics(
        only, BENCH_DIR, REAL_CELL, facts, notes).items()}
    assert abs(got[NEW_METRICS[0]] - 6_144.0) < 1e-6
    assert abs(got[NEW_METRICS[1]] - 2_000.0) < 1e-6
    assert abs(got[NEW_METRICS[2]] - 1_000.0) < 1e-6
    size = sum(parts.values())
    share = got["ssmoelm_decode_read_share"]
    assert abs(share - 100 * size * 2048 / 819e9 / 6.144) < 1e-9 and 45 < share < 55
    entry = next(m for m in bench["per_layer"] if m["name"] == NEW_METRICS[-1])
    assert (entry["unit"], entry["better"]) == ("%", "higher")


def _mode():
    spec = importlib.util.spec_from_file_location(
        "anakin_tokens_ssmoe_mode",
        os.path.join(BENCH_DIR, "modes", "anakin_tokens_ssmoe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_program_without_the_family_is_told_apart_before_anything_is_built(
        monkeypatch):
    """Every commit before PR 53: `load_config` raises on the section's
    algorithm. And the traced interval is one chunk here."""
    from distributed_reinforcement_learning_tpu.utils import config

    base = _mode()._base()

    def old_load_config(path, name):
        raise ValueError("unknown algorithm 'ssmoelm'")

    monkeypatch.setattr(config, "load_config", old_load_config)
    why = base._unsupported("unused.json", "nemotron_h_moe")
    assert "unknown algorithm 'ssmoelm'" in why
    assert "cannot run this configuration" in why
    assert base.COUNTERS == _mode().COUNTERS and "relu2_zero_share" in base.COUNTERS
    anakin_mode = discover.module(BENCH_DIR, "modes", "anakin")
    assert anakin_mode.TRACE_CHUNKS == 2

    class Family:
        param_sample = staticmethod(lambda params: [])

    base._watch_class(anakin_mode, Family)
    assert anakin_mode.TRACE_CHUNKS == _mode().TRACE_CHUNKS == 1


def test_the_parent_program_exits_unsupported_on_the_real_cell(tmp_path):
    """The mode's child on a program WITHOUT the family (this tree with the
    family's row and import cut out of `agents/token_families.py`, and
    without its model and agent files: every commit before PR 53): exit
    code 5 and one line that names the family, within seconds, nothing
    built and no device opened."""
    root = tmp_path / "old"
    pkg = root / "distributed_reinforcement_learning_tpu"
    shutil.copytree(os.path.join(ROOT, "distributed_reinforcement_learning_tpu"),
                    pkg, ignore=shutil.ignore_patterns("__pycache__"))
    table = pkg / "agents" / "token_families.py"
    kept = table.read_text().replace(
        "from distributed_reinforcement_learning_tpu.agents.ssmoelm import (\n"
        "    SSMoELMAgent, SSMoELMConfig)\n", "")
    kept = "".join(line for line in kept.splitlines(keepends=True)
                   if "ssmoelm" not in line)
    table.write_text(kept)
    assert "ssmoelm" not in table.read_text() and "SSMoELM" not in table.read_text()
    os.remove(pkg / "agents" / "ssmoelm.py")
    os.remove(pkg / "models" / "ssm_moe_lm.py")
    cfg = _published_config()
    run_cfg = tmp_path / "config.json"
    run_cfg.write_text(json.dumps({"nemotron_h_moe": cfg["nemotron_h_moe"]}))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(root), BENCH_DIR])}
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "modes", "anakin_tokens_ssmoe.py"),
         "--config", str(run_cfg), "--section", "nemotron_h_moe", "--seed", "1",
         "--seconds", "1", "--trace", "0", "--out", str(tmp_path), "--params", "{}",
         "--expect-platform", "cpu", "--chips", "1", "--data-dir", BENCH_DIR],
        cwd=str(root), env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 5, proc.stderr[-2000:]
    said = [line for line in proc.stderr.splitlines() if "[perfbench]" in line]
    assert len(said) == 1 and "UNSUPPORTED" in said[0] and "ssmoelm" in said[0]
    assert "device:" not in proc.stdout + proc.stderr  # the chip was never opened


def test_a_dropped_pair_in_the_window_is_not_correct(monkeypatch):
    """`anakin_tokens_moe.run`'s rule reaches this mode's runs too."""
    mode = _mode()
    result = {"correct": True, "notes": [],
              "facts": {"counters": {"dropped_pairs": 0.5}}}
    moe = mode._mode("anakin_tokens_moe")

    class Hybrid:
        run = staticmethod(lambda ctx: result)

    moe._hybrid = lambda: Hybrid
    monkeypatch.setattr(mode, "_mode", lambda name: moe)
    out = mode.run({})
    assert out["correct"] is False and "dropped_pairs 0.5" in out["notes"][-1]
    assert Hybrid.state_problems is mode.state_problems  # this stack's account


def test_a_state_a_cache_or_a_share_other_than_the_files_are_refused():
    """The chunk's own `static_facts` at the published sizes pass; a
    bfloat16 recurrent state, a float32 cache, a cache of the query heads,
    another order, another share of the experts do not."""
    import dataclasses

    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.ssmoelm import SSMoELMAgent
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    mode = _mode()
    section = _published_config()["nemotron_h_moe"]
    cfg, _ = load_config(os.path.join(ROOT, "config.json"), "nemotron_h_moe")
    agent = SSMoELMAgent(cfg)
    facts = agent.state_facts(16)
    assert (facts["ssm_state_bytes"], facts["conv_state_bytes"],
            facts["kv_cache_bytes"], facts["route_record_bytes"]) == (
        134_217_728, 4_718_592, 33_554_432, 1_572_864)
    assert facts["layer_order"] == ORDER
    assert mode.state_problems(facts, section, 16) == []
    assert mode.state_problems(facts, section, 8)  # other sizes
    wide = SSMoELMAgent(dataclasses.replace(cfg, dtype=jnp.float32)).state_facts(16)
    said = mode.state_problems(wide, section, 16)
    assert len(said) == 1 and "kv_cache_bytes" in said[0]
    agent.model = dataclasses.replace(agent.model, state_dtype=jnp.bfloat16)
    said = mode.state_problems(agent.state_facts(16), section, 16)
    assert len(said) == 1 and "ssm_state_bytes" in said[0]
    every_head = {**facts, "kv_cache_bytes": facts["kv_cache_bytes"] * 16}
    assert len(mode.state_problems(every_head, section, 16)) == 1
    assert mode.state_problems({**facts, "layer_order": ORDER[::-1]}, section, 16)
    other = dict(section, hybrid_override_pattern="MEMEM*EMM")
    assert "MEMEM*EMM" in mode.state_problems(facts, other, 16)[0]
    for key, value in (("experts_held", 16), ("router_width", 64), ("first_expert", 8)):
        said = mode.state_problems({**facts, key: value}, section, 16)
        assert len(said) == 1 and key in said[0]


def test_operation_count_by_hand():
    """One token forward. An M layer: in 2688 x 10,304 and out 4,096 x
    2688, four taps over 6,144 channels, and the scan at chunk 128: the
    scores 2 x 8 x 128 x 128, scores x xdt 2 x 4,096 x 128, the read of the
    past and the chunk's state 2 x 4,096 x 128 each. The * layer: q and o
    2688 x 4,096, k and v 2688 x 256, q k^T and p v over 32 heads of 128
    and a mean of 1,024.5 keys. An E layer: the router 2688 x 128, the
    shared expert 2 x 2688 x 3,712 and 0.375 held experts of 2 x 2688 x
    1,856 (6 x 8 / 128). The untied head 2688 x 16,384 and the value."""
    family = discover.module(BENCH_DIR, "families", "ssmoelm")
    section = _published_config()["nemotron_h_moe"]
    mamba = (2 * (2688 * 10_304 + 4096 * 2688) + 2 * 4 * 6144
             + 2 * 8 * 128 * 128 + 2 * 4096 * 128 + 2 * 2 * 4096 * 128)
    star = 2 * (2 * 2688 * 4096 + 2 * 2688 * 256) + 2 * 2 * 2049 * 4096 // 2
    expert = 2 * 2688 * 128 + 2 * 2 * 2688 * 3712 + 0.375 * 2 * 2 * 2688 * 1856
    forward = 4 * mamba + star + 4 * expert + 2 * 2688 * 16_385
    assert family.forward_flops_per_token(section) == int(forward)
    assert 6.6e8 < forward < 6.7e8  # 333.7 M multiply-adds: the 318.6 M ISSUE 53 counts, the scan and the attention core
    assert family.learn_flops_per_update(section, None) == 3 * int(forward) * 32_768
    assert family.learn_flops_per_update(section, (0, 0), 2) \
        == 3 * int(forward) * 2 * 2048


def test_configuration_file_keeps_every_published_key():
    cfg = _published_config()
    assert cfg["reduced"] == list(CUT)
    for key, (published, here) in CUT.items():
        assert cfg[key] == here and cfg["published"][key] == published, key
        assert key in cfg["reduced_why"], key
    assert 16_384 * 8 == 131_072 and 8 * 16 == 128
    section = cfg["nemotron_h_moe"]
    widths = ("hidden_size", "head_dim", "mamba_num_heads", "mamba_head_dim",
              "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
              "num_attention_heads", "num_key_value_heads", "num_experts_per_tok",
              "moe_intermediate_size", "moe_shared_expert_intermediate_size",
              "routed_scaling_factor", "layer_norm_epsilon", "expand")
    for key in (*widths, *CUT, "mlp_hidden_act", "tie_word_embeddings", "n_group",
                "topk_group", "norm_topk_prob", "model_type", "use_conv_bias",
                "time_step_min", "time_step_max", "time_step_floor"):
        assert section[key] == cfg[key], key
    assert not set(widths) & set(cfg["reduced"])
    # the published order, copied whole; the section runs its first nine layers
    assert len(cfg["hybrid_override_pattern"]) == 52
    assert [cfg["hybrid_override_pattern"].count(c) for c in "ME*"] == [23, 23, 6]
    assert section["hybrid_override_pattern"] == cfg["hybrid_override_pattern"][:9] \
        == ORDER
    assert (section["router_width"], section["first_expert"]) == (128, 0)
    assert section["trajectory"] == cfg["max_position_embeddings"] == 2048
    assert section["dtype"] == "bfloat16" and section["algorithm"] == "ssmoelm"
    with open(os.path.join(ROOT, "config.json")) as f:
        assert json.load(f)["nemotron_h_moe"] == section  # the same values
    for key in ("d_inner", "nope", "dt", "value_head", "bias_update", "initializer",
                "act_state_dtype", "env", "loss", "optimizer", "dtype"):
        assert key in cfg["assumed"], key
    assert set(cfg["departures"]) == {"absent_experts", "pipeline_ends", "no_mtp",
                                      "repeated_kv_heads"}
    assert "16 chips" in cfg["published"]["deployment"]
    assert "both ends" in cfg["published"]["deployment"]
    assert "666,965,633" in cfg["bytes"]["parameters"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the row the driver drew, number for number
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert cfg[key] == value or key in cfg["reduced"], key


def test_reference_copies_are_identical_and_import_nothing_of_the_program():
    with open(os.path.join(BENCH_DIR, "references", "nemotron_h_moe.py")) as f:
        copy = f.read()
    with open(os.path.join(ROOT, "distributed_reinforcement_learning_tpu",
                           "reference", "nemotron_h_moe.py")) as f:
        assert f.read() == copy
    imports = [line for line in copy.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations",
                       "import functools", "import jax",
                       "import jax.numpy as jnp"]
    assert 'default_matmul_precision("highest")' in copy
    assert "ragged_dot" not in copy and "pallas_call" not in copy
    assert "jnp.square(jax.nn.relu(up))" in copy and "cumsum" not in copy


def test_committed_cell_resolves_and_lists_its_own_metrics_in_order(bench):
    import run

    cell = run.load_cell(bench, BENCH_DIR, REAL_CELL)
    assert cell["traffic"]["mode"] == "anakin_tokens_ssmoe"
    assert {k: cell["traffic"][k] for k in ("num_envs", "chunk_updates")} \
        == {"num_envs": 16, "chunk_updates": 1}
    section = cell["config"]["nemotron_h_moe"]
    assert section["trajectory"] == 2048 and section["recall_distance"] == 8
    assert cell["config"]["frames_per_update"] == 16 * 2048
    assert cell["config"]["kernels"] == {"tpu_custom_call": 6}
    # membership and order of ITS OWN metrics only: another cell's are not this test's
    own = [m["name"] for m in bench["per_layer"] if m["name"].startswith("ssmoelm_")]
    assert own == list(NEW_METRICS)
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [REAL_CELL] and m["source"] == "device_trace"
            assert m["moves"] == "frames_learned_per_s"
    traced = contract.cell_metrics(bench, REAL_CELL, traced=True)
    assert set(NEW_METRICS) | {"compile_s", "device_ms_per_update", "learn_mfu",
                               "device_idle_share"} <= set(traced)
    entry = next(c for c in bench["configs"] if c["name"] == "nemotron_h_moe")
    assert entry["source"] == cell["config"]["source"]
    assert entry["reduced"] == cell["config"]["reduced"]
    assert entry["file"] == "perfbench/configs/nemotron_h_moe.json"
    listed = next(w for w in bench["workloads"] if w["name"] == REAL_CELL)
    assert listed["chips"] == 1 and len(listed["why"]) <= 200

"""The cell `smallthinker_moe.anakin_tokens_swa_8k` (ISSUE 49): its mode
rehearsed on the CPU end to end through `run.py` at a tiny size (an
episode of four windows, so the rings wrap), the early exit on a program
that cannot run the configuration, what the chunk is held to (its layers
by attention kind, the bytes of its rings and of its one full cache, its
share of the experts), the family's operation counts, the visible pairs
and the decode step's bytes by hand, the configuration file against the
catalog's published keys, and the new metrics by scope on the chunk's own
op names. Files and entries are ADDED to `data_copy`'s copy; none is
edited.
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

NEW_METRICS = ("swalm_decode_resolved_ms_per_update", "swalm_cache_act_ms_per_update",
               "swalm_experts_act_ms_per_update", "swalm_stack_ms_per_update",
               "swalm_window_attend_ms_per_update", "swalm_global_attend_ms_per_update",
               "swalm_route_ms_per_update", "swalm_experts_ms_per_update",
               "swalm_heads_ms_per_update", "swalm_unresolved_share",
               "swa_flash_roofline", "swalm_decode_read_share")
BY_OWN_NAMES = NEW_METRICS[3:9]  # the resolved readers want a profile
REAL_CELL = "smallthinker_moe.anakin_tokens_swa_8k"
CELL = "tiny_swa.anakin_tokens_swa_8k"
ORDER = ["global", "window", "window", "window"]
CUT = {"num_hidden_layers": (52, 4), "moe_num_primary_experts": (64, 16),
       "vocab_size": (151936, 37984), "max_position_embeddings": (16384, 8192)}
SPANS = tuple(range(1024, 8193, 1024))


def _published_config():
    with open(os.path.join(BENCH_DIR, "configs", "smallthinker_moe.json")) as f:
        return json.load(f)


def _tiny_section() -> dict:
    """The published configuration's code paths (two runs of two kinds of
    layer, one full cache and three rings of a window a quarter of the
    episode, a router over 16 experts of which 4 are held, ReGLU, no
    shared expert, the blocked untied head) at widths a CPU compiles in
    seconds: `config.json`'s small section."""
    with open(os.path.join(ROOT, "config.json")) as f:
        small = json.load(f)["smallthinker_moe_small"]
    return dict(small, vocab_size=96, available_action=[96])


@pytest.fixture()
def tiny_cell(data_copy):
    dd = data_copy["dir"]

    def dump(rel, obj):
        path = os.path.join(dd, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            json.dump(obj, f)

    dump("configs/tiny_swa.json", {
        "name": "tiny_swa", "section": "tiny_swa", "kernels": {},
        "frames_per_update": 128, "tiny_swa": _tiny_section()})
    dump(f"workloads/{CELL}.json", {
        "config": "tiny_swa", "traffic": "anakin_tokens_swa_8k",
        "overrides": {"num_envs": 4, "chunk_updates": 1}})
    bench = data_copy["bench"]
    bench["workloads"].append({"name": CELL, "config": "tiny_swa",
                               "traffic": "anakin_tokens_swa_8k", "chips": 1,
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
                 "'relu_gate_zero_share'", "'held_experts_touched_mean'",
                 "'ring_read_share'", "'window_pair_share'",
                 "'router_load_max_over_mean'", "'pair_slabs_mean'",
                 "'ring_bytes'", "'kv_cache_bytes'", "'ring_positions': 8",
                 "'act_weight_bytes'", "'experts_held': 4", "'router_width': 16",
                 "'first_expert': 4", "'layer_order': ['global', 'window'",
                 "'route_flip_share'", "'flips_over_margin': 0", "'router_prob'",
                 "'relu_zero'", "chunk {", "'step_over_last_bit'"):
        assert said in proc.stdout, said


def _tiny_chunk_names():
    import re

    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.swalm import (
        SwaLMAgent, SwaLMConfig)
    from distributed_reinforcement_learning_tpu.envs.token_recall_jax import (
        TokenRecall)
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
        AnakinTokens)

    cfg = SwaLMConfig(
        vocab_size=64, hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, rope_theta=1e4, sliding_window_size=8,
        moe_num_primary_experts=4, router_width=16, first_expert=4,
        moe_num_active_primary_experts=3, moe_ffn_hidden_size=16,
        trajectory=16, dtype=jnp.float32, head_block=16)
    an = AnakinTokens(SwaLMAgent(cfg), 4, TokenRecall(64, 16))
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
    vocabulary appears, and the two kinds of attention layer and of
    act-time cache are told apart."""
    import run
    from distributed_reinforcement_learning_tpu.observability import scopes

    names = _tiny_chunk_names()
    for scope in scopes.SWA_CHUNK_SCOPES:
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
    for part in ("window_attend", "global_attend", "route", "experts"):
        assert got[f"swalm_{part}_ms_per_update"] < got["swalm_stack_ms_per_update"]
    for scope in ("collect/act/ring", "collect/act/cache",
                  "learn/loss/layers/window_attention",
                  "learn/loss/layers/global_attention"):
        own = [n for n in names if n.endswith(scope) or scope + "/" in n]
        assert own and all(scope_of(n) == scope for n in own), scope
    # the router runs ahead of attention, outside either attention's scope
    route = [n for n in names if "learn/loss/layers/moe/route" in n]
    assert route and not any("_attention" in n.split("moe/route")[-1] for n in route)


def test_new_metrics_read_nothing_on_a_program_without_the_scopes(bench):
    """The parent's program has no such scope: each reader by own names
    returns 0 and does not raise; the two shares, which need a section of
    this family (and the kernels' names, the run's counter), return None
    on another's; without a profile every one of the twelve returns None."""
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
    got = run.layer_metrics(of((*BY_OWN_NAMES, *NEW_METRICS[-2:])), BENCH_DIR,
                            REAL_CELL, facts, notes)
    assert not set(NEW_METRICS[-2:]) & set(got)
    assert all(v["value"] == 0 for v in got.values())
    # this family's section, its counter and spans, and still no kernel of the
    # two scopes and no op under `collect/act`: nothing to read
    facts.update(section=_published_config()["smallthinker_moe"],
                 static={"decode_spans": SPANS},
                 counters={"held_experts_touched_mean": 8.7})
    facts.pop("_scope_read", None)
    assert run.layer_metrics(of(NEW_METRICS[-2:]), BENCH_DIR, REAL_CELL, facts,
                             notes) == {}
    no_profile = {"data_dir": BENCH_DIR, "trace_updates": 1, "trace": None}
    assert run.layer_metrics(of(NEW_METRICS), BENCH_DIR, REAL_CELL, no_profile,
                             notes) == {}


def test_the_two_shares_by_hand_from_a_recording(bench):
    """The visible pairs, the operations, the bytes by hand, and both
    shares of a recording: 8,192 decode steps in 16.384 s under
    `collect/act` are 2 ms a step where the reads alone take 1.24; the
    eight kernels of an update in 2 s against 68.8 TFLOP over the visible
    pairs."""
    import run

    flash = discover.module(BENCH_DIR, "reducers", "window_flash_roofline")
    reads = discover.module(BENCH_DIR, "reducers", "decode_read_share")
    section = _published_config()["smallthinker_moe"]
    assert flash.visible_pairs(8192, None) == 8192 * 8193 // 2 == 33_558_528
    assert flash.visible_pairs(8192, 4096) == 4096 * 4097 // 2 + 4096 * 4096 \
        == 25_167_872
    assert flash.visible_pairs(24, 8) == sum(min(t + 1, 8) for t in range(24))
    assert flash.visible_pairs(6, 8) == 21
    assert flash.pair_flops(128, 128) == 2816
    work = flash.kernel_flops_per_update(section, 8)
    assert work == {"window": 3 * 25_167_872 * 8 * 28 * 2816,
                    "global": 33_558_528 * 8 * 28 * 2816}
    assert 6.87e13 < sum(work.values()) < 6.89e13
    parts = reads.step_bytes(section, 8, SPANS, 8.0)
    position = 2 * 8 * 4 * 128 * 2
    assert parts == {
        "attention": 2 * 4 * (2 * 2560 * 3584 + 2 * 2560 * 512),
        "head": 2 * 37984 * 2560, "routers": 4 * 4 * 2560 * 64,
        "experts": 2 * 4 * 8.0 * 3 * 2560 * 768,
        "global_cache": position * 1024 * sum(SPANS) / 8192,
        "rings": 3 * position * 1024 * (1024 + 2048 + 3072 + 5 * 4096) / 8192}
    assert parts["global_cache"] == 75_497_472 and parts["rings"] == 163_577_856
    # what the program says a step could read whole: every held expert, no cache
    from distributed_reinforcement_learning_tpu.agents.swalm import SwaLMAgent
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    cfg, _ = load_config(os.path.join(ROOT, "config.json"), "smallthinker_moe")
    whole = reads.step_bytes(section, 8, SPANS, 16.0)
    assert SwaLMAgent(cfg).state_facts(8)["act_weight_bytes"] == sum(
        whole[k] for k in ("attention", "head", "routers", "experts"))
    act = "jit(_train_chunk_s4)/while/body/collect/while/body/collect/act/"
    learn = ("jit(_train_chunk_s4)/while/body/closed_call/learn/jvp(learn/loss)/"
             "learn/loss/layers/while/body/closed_call/learn/loss/layers/")
    rows = [["dot.1", act + "collect/act/layers/dot_general", 10_000_000.0],
            ["dus.2", act + "collect/act/layers/collect/act/ring/dynamic_update_slice",
             3_000_000.0],
            ["dus.3", act + "collect/act/layers/collect/act/cache/dynamic_update_slice",
             1_384_000.0],
            ["sort.4", act + "collect/act/layers/collect/act/moe/experts/sort",
             2_000_000.0],
            ["call.5", learn + "window_attention/pallas_call:", 1_200_000.0],
            ["call.6", learn + "global_attention/pallas_call", 800_000.0],
            ["dot.7", learn + "window_attention/dot_general", 9e6]]
    facts = {"data_dir": BENCH_DIR, "trace_updates": 1, "chips": 1, "num_envs": 8,
             "device": {"kind": "TPU v5 lite"}, "section": section,
             "static": {"decode_spans": list(SPANS)},
             "counters": {"held_experts_touched_mean": 8.0},
             "trace": {"busy_s": 30.0, "window_s": 30.0}, "notes": (notes := []),
             "scope_recording": {"hlo_stats": rows, "host_spans": []}}
    only = dict(bench, per_layer=[m for m in bench["per_layer"] if m["name"] in (
        *NEW_METRICS[:3], *NEW_METRICS[-2:])])
    got = {k: v["value"] for k, v in run.layer_metrics(
        only, BENCH_DIR, REAL_CELL, facts, notes).items()}
    assert abs(got[NEW_METRICS[0]] - 16_384.0) < 1e-6
    assert abs(got[NEW_METRICS[1]] - 4_384.0) < 1e-6  # ring and cache together
    assert abs(got[NEW_METRICS[2]] - 2_000.0) < 1e-6
    size = sum(parts.values())
    share = got["swalm_decode_read_share"]
    assert abs(share - 100 * size * 8192 / 819e9 / 16.384) < 1e-9 and 55 < share < 65
    roof = got["swa_flash_roofline"]
    assert abs(roof - 100 * sum(work.values()) / (2.0 * 197e12)) < 1e-9
    assert 17 < roof < 18
    assert any("window 1200.00 ms" in n and "global 800.00 ms" in n for n in notes)
    for name in NEW_METRICS[-2:]:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert (entry["unit"], entry["better"]) == ("%", "higher")


def _mode():
    spec = importlib.util.spec_from_file_location(
        "anakin_tokens_swa_mode",
        os.path.join(BENCH_DIR, "modes", "anakin_tokens_swa.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_program_without_the_family_is_told_apart_before_anything_is_built(
        monkeypatch):
    """Every commit before PR 49: `load_config` raises on the section's
    algorithm. And the traced interval is one chunk here."""
    from distributed_reinforcement_learning_tpu.utils import config

    base = _mode()._base()

    def old_load_config(path, name):
        raise ValueError("unknown algorithm 'swalm'")

    monkeypatch.setattr(config, "load_config", old_load_config)
    why = base._unsupported("unused.json", "smallthinker_moe")
    assert "unknown algorithm 'swalm'" in why
    assert "cannot run this configuration" in why
    assert base.COUNTERS == _mode().COUNTERS and "ring_read_share" in base.COUNTERS
    anakin_mode = discover.module(BENCH_DIR, "modes", "anakin")
    assert anakin_mode.TRACE_CHUNKS == 2

    class Family:
        param_sample = staticmethod(lambda params: [])

    base._watch_class(anakin_mode, Family)
    assert anakin_mode.TRACE_CHUNKS == _mode().TRACE_CHUNKS == 1


def test_the_parent_program_exits_unsupported_on_the_real_cell(tmp_path):
    """The mode's child on a program WITHOUT the family (this tree with the
    family's row and import cut out of `agents/token_families.py`, and
    without its model and agent files: every commit before PR 49): exit
    code 5 and one line that names the family, within seconds, nothing
    built and no device opened."""
    root = tmp_path / "old"
    pkg = root / "distributed_reinforcement_learning_tpu"
    shutil.copytree(os.path.join(ROOT, "distributed_reinforcement_learning_tpu"),
                    pkg, ignore=shutil.ignore_patterns("__pycache__"))
    table = pkg / "agents" / "token_families.py"
    kept = [line for line in table.read_text().splitlines(keepends=True)
            if "swalm" not in line and "SwaLM" not in line]
    table.write_text("".join(kept))
    assert "swalm" not in table.read_text()
    os.remove(pkg / "agents" / "swalm.py")
    os.remove(pkg / "models" / "window_moe_lm.py")
    cfg = _published_config()
    run_cfg = tmp_path / "config.json"
    run_cfg.write_text(json.dumps({"smallthinker_moe": cfg["smallthinker_moe"]}))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(root), BENCH_DIR])}
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "modes", "anakin_tokens_swa.py"),
         "--config", str(run_cfg), "--section", "smallthinker_moe", "--seed", "1",
         "--seconds", "1", "--trace", "0", "--out", str(tmp_path), "--params", "{}",
         "--expect-platform", "cpu", "--chips", "1", "--data-dir", BENCH_DIR],
        cwd=str(root), env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 5, proc.stderr[-2000:]
    said = [line for line in proc.stderr.splitlines() if "[perfbench]" in line]
    assert len(said) == 1 and "UNSUPPORTED" in said[0] and "swalm" in said[0]
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


def test_rings_a_cache_or_a_share_other_than_the_files_are_refused():
    """The chunk's own `static_facts` at the published sizes pass; float32
    rings and cache, a full cache where a ring is stated, a ring of the
    query heads, another order, another share of the experts do not."""
    import dataclasses

    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.swalm import SwaLMAgent
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    mode = _mode()
    section = _published_config()["smallthinker_moe"]
    cfg, _ = load_config(os.path.join(ROOT, "config.json"), "smallthinker_moe")
    facts = SwaLMAgent(cfg).state_facts(8)
    assert (facts["kv_cache_bytes"], facts["ring_bytes"]) == (
        134_217_728, 3 * 67_108_864)
    assert list(facts["layer_order"]) == ORDER and facts["ring_positions"] == 4096
    assert mode.state_problems(facts, section, 8) == []
    assert mode.state_problems(facts, section, 16)  # other sizes
    wide = SwaLMAgent(dataclasses.replace(cfg, dtype=jnp.float32)).state_facts(8)
    said = mode.state_problems(wide, section, 8)
    assert len(said) == 2 and "kv_cache_bytes" in said[0] and "ring_bytes" in said[1]
    # a window as long as the episode: every layer holds a full cache
    full = SwaLMAgent(dataclasses.replace(
        cfg, sliding_window_size=8192)).state_facts(8)
    said = mode.state_problems(full, section, 8)
    assert len(said) == 2 and "ring_bytes" in said[0] and "ring_positions" in said[1]
    every_head = {**facts, "ring_bytes": facts["ring_bytes"] * 7}
    assert len(mode.state_problems(every_head, section, 8)) == 1
    assert mode.state_problems({**facts, "layer_order": ORDER[::-1]}, section, 8)
    all_window = dict(section, sliding_window_layout=[1, 1, 1, 1])
    assert "'window', 'window', 'window', 'window'" in mode.state_problems(
        facts, all_window, 8)[0]
    for key, other in (("experts_held", 8), ("router_width", 16), ("first_expert", 16)):
        said = mode.state_problems({**facts, key: other}, section, 8)
        assert len(said) == 1 and key in said[0]


def test_operation_count_by_hand():
    """One token forward. A layer outside its attention core: q and o 2560
    x 3584, k and v 2560 x 512, the router 2560 x 64 and 1.5 held experts
    of 3 x 2560 x 768 (6 x 16 / 64), NO shared expert. The core, q k^T and
    p v over 28 heads of 128: a global layer's mean visible keys 4,096.5, a
    window layer's 25,167,872 / 8,192 = 3,072.25. The untied head 2560 x
    37,984 and the value."""
    family = discover.module(BENCH_DIR, "families", "swalm")
    section = _published_config()["smallthinker_moe"]
    layer = (2 * (2 * 2560 * 3584 + 2 * 2560 * 512) + 2 * 2560 * 64
             + 1.5 * 2 * 3 * 2560 * 768)
    core = 2 * 2 * 3584 * (4096.5 + 3 * 3072.25)
    forward = 4 * layer + core + 2 * 2560 * 37_985
    assert family.forward_flops_per_token(section) == int(forward)
    assert 6.2e8 < forward < 6.4e8
    assert family.learn_flops_per_update(section, None) == 3 * int(forward) * 65_536
    assert family.learn_flops_per_update(section, (0, 0), 2) \
        == 3 * int(forward) * 2 * 8192


def test_configuration_file_keeps_every_published_key():
    cfg = _published_config()
    assert cfg["reduced"] == list(CUT)
    for key, (published, here) in CUT.items():
        assert cfg[key] == here and cfg["published"][key] == published, key
        assert key in cfg["reduced_why"], key
    assert 37_984 * 4 == 151_936 and 16 * 4 == 64
    section = cfg["smallthinker_moe"]
    widths = ("hidden_size", "head_dim", "moe_ffn_hidden_size",
              "num_attention_heads", "num_key_value_heads",
              "moe_num_active_primary_experts", "sliding_window_size",
              "rope_theta", "rms_norm_eps")
    for key in (*widths, *CUT, "rope_scaling", "tie_word_embeddings",
                "moe_primary_router_apply_softmax", "norm_topk_prob", "model_name"):
        assert section[key] == cfg[key], key
    assert not set(widths) & set(cfg["reduced"])
    # the published orders, copied whole; the section runs their first period
    assert len(cfg["sliding_window_layout"]) == len(cfg["rope_layout"]) == 52
    assert cfg["sliding_window_layout"] == cfg["rope_layout"] == 13 * [0, 1, 1, 1]
    assert section["sliding_window_layout"] == cfg["sliding_window_layout"][:4]
    assert section["rope_layout"] == cfg["rope_layout"][:4]
    assert ["window" if w else "global"
            for w in section["sliding_window_layout"]] == ORDER
    assert (section["router_width"], section["first_expert"]) == (64, 0)
    assert section["trajectory"] == cfg["max_position_embeddings"] \
        == 2 * cfg["sliding_window_size"]
    assert section["dtype"] == "bfloat16" and section["algorithm"] == "swalm"
    with open(os.path.join(ROOT, "config.json")) as f:
        assert json.load(f)["smallthinker_moe"] == section  # the same values
    for key in ("router_input", "expert_activation", "expert_levels",
                "rotary_pairing", "value_head", "initializer", "act_state_dtype",
                "env", "loss", "optimizer", "dtype"):
        assert key in cfg["assumed"], key
    assert set(cfg["departures"]) == {"absent_experts", "pipeline_ends",
                                      "repeated_kv_heads", "embedding_range"}
    # the embedding's range is a key of the section and a departure with its
    # readings, not an assumption about the published model
    assert section["embedding_initializer_range"] == 1.0
    assert section["initializer_range"] == 0.02
    assert "departures.embedding_range" in cfg["assumed"]["initializer"]
    assert "0.71 %" in cfg["departures"]["embedding_range"]
    assert "4 chips" in cfg["published"]["deployment"]
    assert "both ends" in cfg["published"]["deployment"]
    assert "656,532,481" in cfg["bytes"]["parameters"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the row the driver drew, number for number
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert cfg[key] == value or key in cfg["reduced"], key


def test_reference_copies_are_identical_and_import_nothing_of_the_program():
    with open(os.path.join(BENCH_DIR, "references", "smallthinker_moe.py")) as f:
        copy = f.read()
    with open(os.path.join(ROOT, "distributed_reinforcement_learning_tpu",
                           "reference", "smallthinker_moe.py")) as f:
        assert f.read() == copy
    imports = [line for line in copy.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations",
                       "import functools", "import jax",
                       "import jax.numpy as jnp"]
    assert 'default_matmul_precision("highest")' in copy
    assert "ragged_dot" not in copy and "pallas_call" not in copy
    assert "jax.nn.relu" in copy and "jax.nn.silu" not in copy


def test_committed_cell_resolves_and_lists_its_own_metrics_in_order(bench):
    import run

    cell = run.load_cell(bench, BENCH_DIR, REAL_CELL)
    assert cell["traffic"]["mode"] == "anakin_tokens_swa"
    assert {k: cell["traffic"][k] for k in ("num_envs", "chunk_updates")} \
        == {"num_envs": 8, "chunk_updates": 1}
    section = cell["config"]["smallthinker_moe"]
    assert section["trajectory"] == 8192 and section["recall_distance"] == 8
    assert cell["config"]["frames_per_update"] == 8 * 8192
    assert cell["config"]["kernels"] == {"tpu_custom_call": 8}
    # membership and order of ITS OWN metrics only: another cell's are not this test's
    own = [m["name"] for m in bench["per_layer"]
           if m["name"].startswith(("swalm_", "swa_"))]
    assert own == list(NEW_METRICS)
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [REAL_CELL] and m["source"] == "device_trace"
            assert m["moves"] == "frames_learned_per_s"
    traced = contract.cell_metrics(bench, REAL_CELL, traced=True)
    assert set(NEW_METRICS) | {"compile_s", "device_ms_per_update", "learn_mfu",
                               "device_idle_share"} <= set(traced)
    entry = next(c for c in bench["configs"] if c["name"] == "smallthinker_moe")
    assert entry["source"] == cell["config"]["source"]
    assert entry["reduced"] == cell["config"]["reduced"]
    assert entry["file"] == "perfbench/configs/smallthinker_moe.json"
    listed = next(w for w in bench["workloads"] if w["name"] == REAL_CELL)
    assert listed["chips"] == 1 and len(listed["why"]) <= 200
    assert bench["workloads"][-1] == listed and bench["configs"][-1] == entry

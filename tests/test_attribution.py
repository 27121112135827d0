"""`observability/attribution.py`: each rule on a hand-written HLO
module, conservation, the `own` view against the benchmark's reader on
the recorded IMPALA run, a compiled chunk end to end, and what the
module may import (ISSUE 34)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from distributed_reinforcement_learning_tpu.observability import attribution
from distributed_reinforcement_learning_tpu.observability import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = ["collect", "collect/act", "collect/act/layers", "collect/act/ssm",
         "learn", "learn/optimizer"]

# One decode loop as memory-space assignment leaves it: the weights are
# prefetched before the loop (`copy-start.0`) and again inside it
# (`slice-start.1`, whose `-done` inherited the `while`'s metadata), the
# state is evicted to the carry (`copy-start.2`), and a fusion the
# compiler made out of two scopes' instructions has no name.
LOOP = """
HloModule jit_f, is_scheduled=true

%fused.ssm (p0: f32[64], p1: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %p1 = f32[64]{0} parameter(1)
  ROOT %m = f32[64]{0} multiply(%p0, %p1), metadata={op_name="jit(f)/collect/while/body/collect/act/ssm/mul"}
}

%fused.two (q0: f32[1024], q1: f32[8]) -> (f32[1024], f32[8]) {
  %q0 = f32[1024]{0} parameter(0)
  %q1 = f32[8]{0} parameter(1)
  %a = f32[1024]{0} multiply(%q0, %q0), metadata={op_name="jit(f)/collect/while/body/collect/act/ssm/mul"}
  %b = f32[8]{0} add(%q1, %q1), metadata={op_name="jit(f)/collect/while/body/collect/act/layers/add"}
  ROOT %t = (f32[1024]{0}, f32[8]{0}) tuple(%a, %b)
}

%fused.bare (r0: f32[8]) -> f32[8] {
  %r0 = f32[8]{0} parameter(0)
  ROOT %n = f32[8]{0} negate(%r0)
}

%body (arg: (s32[], f32[64], f32[64], f32[1024], f32[8])) -> (s32[], f32[64], f32[64], f32[1024], f32[8]) {
  %arg = (s32[], f32[64]{0}, f32[64]{0:S(1)}, f32[1024]{0}, f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %w = f32[64]{0} get-tuple-element(%arg), index=1
  %state = f32[64]{0:S(1)} get-tuple-element(%arg), index=2
  %big = f32[1024]{0} get-tuple-element(%arg), index=3
  %small = f32[8]{0} get-tuple-element(%arg), index=4
  %slice-start.1 = ((f32[64]{0}), f32[64]{0:S(1)}, s32[]{:S(2)}) slice-start(%w), slice={[0:64]}
  %slice-done.1 = f32[64]{0:S(1)} slice-done(%slice-start.1), metadata={op_name="jit(f)/collect/while"}
  %update = f32[64]{0:S(1)} fusion(%state, %slice-done.1), kind=kLoop, calls=%fused.ssm, metadata={op_name="jit(f)/collect/while/body/collect/act/ssm/mul"}
  %copy-start.2 = (f32[64]{0}, f32[64]{0:S(1)}, u32[]{:S(2)}) copy-start(%update)
  %copy-done.2 = f32[64]{0} copy-done(%copy-start.2)
  %fusion.7 = (f32[1024]{0}, f32[8]{0}) fusion(%big, %small), kind=kLoop, calls=%fused.two
  %big.1 = f32[1024]{0} get-tuple-element(%fusion.7), index=0
  %small.1 = f32[8]{0} get-tuple-element(%fusion.7), index=1
  %fusion.8 = f32[8]{0} fusion(%small.1), kind=kLoop, calls=%fused.bare
  %stack = f32[8]{0} dynamic-update-slice(%small, %fusion.8, %i), metadata={op_name="jit(f)/collect/while/body/dynamic_update_slice"}
  %next = s32[] add(%i, %i), metadata={op_name="jit(f)/collect/while/body/add"}
  ROOT %out = (s32[], f32[64]{0}, f32[64]{0}, f32[1024]{0}, f32[8]{0}) tuple(%next, %w, %copy-done.2, %big.1, %stack)
}

%cond (carg: (s32[], f32[64], f32[64], f32[1024], f32[8])) -> pred[] {
  %carg = (s32[], f32[64]{0}, f32[64]{0}, f32[1024]{0}, f32[8]{0}) parameter(0)
  %ci = s32[] get-tuple-element(%carg), index=0
  ROOT %lt = pred[] compare(%ci, %ci), direction=LT
}

ENTRY %main (p: f32[64], s0: f32[64], b0: f32[1024], c0: f32[8], q: f32[8]) -> (f32[64], f32[8]) {
  %p = f32[64]{0} parameter(0)
  %s0 = f32[64]{0} parameter(1)
  %b0 = f32[1024]{0} parameter(2)
  %c0 = f32[8]{0} parameter(3)
  %q = f32[8]{0} parameter(4)
  %zero = s32[] constant(0)
  %copy-start.0 = (f32[64]{0}, f32[64]{0}, u32[]{:S(2)}) copy-start(%p)
  %copy-done.0 = f32[64]{0} copy-done(%copy-start.0)
  %init = (s32[], f32[64]{0}, f32[64]{0}, f32[1024]{0}, f32[8]{0}) tuple(%zero, %copy-done.0, %s0, %b0, %c0)
  %while.1 = (s32[], f32[64]{0}, f32[64]{0}, f32[1024]{0}, f32[8]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(f)/collect/while"}
  %res = f32[64]{0} get-tuple-element(%while.1), index=2
  %copy.9 = f32[64]{0} copy(%res), metadata={op_name="jit(f)/learn/learn/optimizer/copy"}
  %copy.7 = f32[8]{0} copy(%q)
  ROOT %result = (f32[64]{0}, f32[8]{0}) tuple(%copy.9, %copy.7)
}
"""


@pytest.fixture(scope="module")
def placed() -> dict:
    return attribution.resolve(attribution.parse_hlo(LOOP), NAMES)


def test_parse_reads_names_operands_calls_and_metadata():
    insts = {i.name: i for i in attribution.parse_hlo(LOOP)}
    loop = insts["while.1"]
    assert loop.opcode == "while" and loop.operands == ("init",)
    assert dict(loop.calls) == {"condition": "cond", "body": "body"}
    assert loop.op_name == "jit(f)/collect/while" and loop.computation == "main"
    assert insts["small"].index == 4 and insts["q"].index == 4
    assert insts["fusion.7"].out_bytes == 4 * 1024 + 4 * 8
    assert insts["out"].root and not insts["update"].root
    assert insts["copy-start.2"].op_name == ""


@pytest.mark.parametrize("op, scope, rule", [
    # a deep own name is never moved, whatever the op's kind
    ("copy.9", "learn/optimizer", "own"),
    ("update", "collect/act/ssm", "own"),
    # the scan's own stacked write is the program's, not the compiler's
    ("stack", "collect", "own"),
    ("while.1", "collect", "own"),
    # a nameless fusion of two scopes' instructions: most output bytes
    ("fusion.7", "collect/act/ssm", "inside"),
    # a prefetch takes its first consumer; its -done follows the -start
    ("slice-start.1", "collect/act/ssm", "serves"),
    # a -done named only for the loop it sits in goes where its -start goes
    ("slice-done.1", "collect/act/ssm", "serves"),
    # a prefetch into a while body: through the tuple, the loop's
    # parameter and the copies inside
    ("copy-start.0", "collect/act/ssm", "serves"),
    ("copy-done.0", "collect/act/ssm", "serves"),
    # an eviction from the carry takes its producer
    ("copy-start.2", "collect/act/ssm", "serves"),
    ("copy-done.2", "collect/act/ssm", "serves"),
])
def test_rule_places_op(placed, op, scope, rule):
    assert placed[op] == (scope, rule)


@pytest.mark.parametrize("op, why", [
    ("copy.7", "a consumer chain that ends in a parameter"),
    ("fusion.8", "a fusion of nameless instructions"),
    ("lt", "no name of the vocabulary"),
])
def test_what_no_rule_places_stays_none_with_why(placed, op, why):
    assert placed[op] == (None, why)


def test_a_nameless_loop_is_its_own_self_time():
    text = LOOP.replace(', metadata={op_name="jit(f)/collect/while"}', "")
    placed = attribution.resolve(attribution.parse_hlo(text), NAMES)
    assert placed["while.1"] == (None, "while self time")
    # and the -done that had only the loop's name is nameless like its -start
    assert placed["slice-done.1"] == ("collect/act/ssm", "serves")


def test_instructions_inside_a_fused_computation_are_not_ops(placed):
    assert "m" not in placed and "a" not in placed and "n" not in placed
    assert "next" in placed and "lt" in placed  # loop body and condition run


ROWS = [["7", "update", "jit(f)/collect/while/body/collect/act/ssm/mul", 820.0],
        ["7", "copy-done.2", "", 18.4], ["7", "copy-start.2", "", 0.3],
        ["7", "slice-done.1", "jit(f)/collect/while", 18.9],
        ["7", "fusion.7", "", 25.7], ["7", "fusion.8", "", 1.1],
        ["7", "while.1", "jit(f)/collect/while", 4.55],
        ["7", "copy.9", "jit(f)/learn/learn/optimizer/copy", 2.0],
        ["7", "copy.7", "", 0.25], ["9", "fusion.1", "", 3.0]]


@pytest.fixture(scope="module")
def led() -> dict:
    return attribution.account(ROWS, {"7": attribution.parse_hlo(LOOP)}, NAMES)


def test_ledger_conserves_every_microsecond(led):
    total = sum(r[3] for r in ROWS) / 1e6
    assert led["total_s"] == pytest.approx(total, abs=1e-12)
    placed = sum(led["scopes"].values())
    left = sum(s for _n, s, _w in led["unresolved"])
    assert placed + left == pytest.approx(total, abs=1e-9)  # under 1 us
    assert sum(s for t in led["by_rule"].values() for s in t.values()) == \
        pytest.approx(placed, abs=1e-12)


def test_ledger_says_by_which_rule_and_what_stayed(led):
    ssm = "collect/act/ssm"
    assert led["by_rule"]["own"][ssm] == pytest.approx(820e-6)
    assert led["by_rule"]["serves"][ssm] == pytest.approx((18.4 + 0.3 + 18.9) / 1e6)
    assert led["by_rule"]["inside"][ssm] == pytest.approx(25.7e-6)
    # the own view is what a reader of own names sees: the root-named
    # wait under `collect`, nameless ops nowhere
    assert led["own"] == {"collect": pytest.approx((18.9 + 4.55) / 1e6),
                          ssm: pytest.approx(820e-6),
                          "learn/optimizer": pytest.approx(2e-6)}
    assert [(n, w) for n, _s, w in led["unresolved"]] == [
        ("fusion.1", "not in the HLO the profile holds"),
        ("fusion.8", "a fusion of nameless instructions"),
        ("copy.7", "a consumer chain that ends in a parameter")]


def test_what_no_rule_places_stays_where_its_row_names_it():
    """The converter labels an op without metadata with the loop it sits
    in: where no rule places such an op, a reader of own names still has
    it there, so the unresolved share never passes the unscoped one."""
    rows = [["7", "copy.7", "jit(f)/collect/while:", 0.25],
            ["7", "fusion.8", "jit(f)/while:", 1.1]]
    led = attribution.account(rows, {"7": attribution.parse_hlo(LOOP)}, NAMES)
    assert led["by_rule"]["own"] == {"collect": pytest.approx(0.25e-6)}
    assert [n for n, _s, _w in led["unresolved"]] == ["fusion.8"]


def test_a_fusion_of_two_scopes_is_one_op_and_the_ledger_says_so(led):
    """`fusion.7` is not apportioned: all of it under the scope with most
    bytes, and `holds_other_scopes` puts a number on the other."""
    assert led["holds_other_scopes"] == {
        "collect/act/ssm": {"collect/act/layers": pytest.approx(25.7e-6)}}
    text = attribution.table(led)
    assert "collect/act/layers 0.03" in text and "unresolved fusion.8" in text


def _perfbench(module: str):
    path = os.path.join(ROOT, "perfbench", f"{module}.py")
    spec = importlib.util.spec_from_file_location(f"_pb_{module}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def impala_recording() -> dict:
    with open(os.path.join(ROOT, "perfbench", "tests", "fixtures",
                           "impala_nature.anakin.scopes.json")) as f:
        return json.load(f)


def test_own_view_is_the_benchmarks_reading_of_the_recorded_run(impala_recording):
    """With no HLO behind the rows the ledger IS the own view, and that
    equals `scope_read.scope_seconds` for every name of the vocabulary."""
    scope_read = _perfbench("scope_read")
    data_dir = os.path.join(ROOT, "perfbench")
    names = scope_read.vocabulary(data_dir)
    rows = impala_recording["scope_recording"]["hlo_stats"]
    led = attribution.account([["", *r] for r in rows], {}, names)
    assert led["scopes"] == led["own"] == led["by_rule"]["own"]
    facts = {"data_dir": data_dir,
             "scope_recording": impala_recording["scope_recording"]}
    checked = 0
    for name in names:
        theirs = scope_read.scope_seconds(facts, [name])
        mine = sum(s for scope, s in led["own"].items()
                   if scope == name or scope.startswith(name + "/"))
        assert mine == pytest.approx(theirs or 0.0, abs=1e-8), name
        checked += mine > 0
    assert checked >= 6
    total = sum(r[2] for r in rows) / 1e6
    assert sum(led["scopes"].values()) + sum(
        s for _n, s, _w in led["unresolved"]) == pytest.approx(total, abs=1e-6)


def test_scope_of_is_the_benchmarks_scope_of(impala_recording):
    scope_read = _perfbench("scope_read")
    names = scope_read.vocabulary(os.path.join(ROOT, "perfbench"))
    paths = {r[1] for r in impala_recording["scope_recording"]["hlo_stats"]}
    paths |= {"jit(f)/learn/transpose(jvp(learn/loss))/torso/conv", "", "jit(f)/while:",
              "jit(f)/while/body/closed_call/relearn/recollect/add"}
    assert len(paths) > 100
    for path in paths:
        assert attribution.scope_of(path, names) == scope_read.scope_of(path, names)


def test_program_vocabulary_is_every_device_scope_of_scopes_py():
    names = attribution.program_vocabulary()
    assert set(scopes.HYBRID_CHUNK_SCOPES + scopes.R2D2_CHUNK_SCOPES
               + scopes.IMPALA_CHUNK_SCOPES) <= set(names)
    assert scopes.DISPATCH not in names and scopes.CACHE_TAG not in names


def test_a_compiled_hybrid_chunk_resolves_whole():
    """On the CPU, a small `hybridlm` chunk's optimized text: no
    exception, and under 5 % of the ops that run (by output bytes and by
    count) are left under no scope."""
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.hybridlm import (
        HybridLMAgent, HybridLMConfig)
    from distributed_reinforcement_learning_tpu.envs.token_recall_jax import TokenRecall
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import AnakinTokens

    cfg = HybridLMConfig(
        vocab_size=64, hidden_size=32, layer_types=("mamba", "attention", "mamba"),
        num_attention_heads=4, num_key_value_heads=2, shared_intermediate_size=48,
        mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8, mamba_chunk_size=8,
        trajectory=16, dtype=jnp.float32, head_block=16, row_block=2)
    anakin = AnakinTokens(HybridLMAgent(cfg), 4, TokenRecall(64, 16))
    text = anakin.train_chunk.lower(
        anakin.init(jax.random.PRNGKey(0)), 1).compile().as_text()
    insts = attribution.parse_hlo(text)
    placed = attribution.resolve(insts, attribution.program_vocabulary())
    work = [i for i in insts if i.name in placed and i.opcode not in (
        "parameter", "constant", "tuple", "get-tuple-element", "bitcast")]
    assert len(work) > 200
    left = [i for i in work if placed[i.name][0] is None]
    assert len(left) < 0.05 * len(work), sorted(
        (placed[i.name][1], i.opcode) for i in left)[:20]
    assert sum(i.out_bytes for i in left) < 0.05 * sum(i.out_bytes for i in work)
    rules = {placed[i.name][1] for i in work if placed[i.name][0]}
    assert "own" in rules and len(rules) > 1


def test_importing_attribution_loads_neither_jax_nor_flax():
    code = ("import sys\n"
            "import distributed_reinforcement_learning_tpu.observability.attribution as a\n"
            "a.resolve(a.parse_hlo(''), a.program_vocabulary())\n"
            "bad = [m for m in ('jax', 'flax', 'xprof') if m in sys.modules]\n"
            "assert not bad, bad\n")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_a_run_without_a_profile_dir_loads_neither_the_resolver_nor_xprof():
    """Tracing off, the training process runs what it ran: the ledger's
    modules are imported by `ProfilerSession.close()` only after a trace."""
    code = ("import sys\n"
            "from distributed_reinforcement_learning_tpu.runtime.launch import train_anakin\n"
            "train_anakin('config.json', 'impala_cartpole', num_updates=4, chunk=2)\n"
            "bad = [m for m in sys.modules if m.endswith('.attribution')\n"
            "       or m == 'xprof' or m.startswith('xprof.')]\n"
            "assert not bad, bad\n")
    env = {k: v for k, v in os.environ.items() if k != "DRL_PROFILE_DIR"}
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env={**env, "JAX_PLATFORMS": "cpu"})


def test_profiler_session_close_writes_the_ledger_beside_the_profile(
        tmp_path, monkeypatch, capsys, led):
    """`close()` after a trace: `scope_ledger.json` next to the
    `.xplane.pb` and the table on stdout; a profile with no device op
    line (the CPU's) says so and writes nothing."""
    import jax
    import numpy as np

    from distributed_reinforcement_learning_tpu.utils.profiling import ProfilerSession

    sess = ProfilerSession(str(tmp_path), start_step=0, num_steps=100)
    sess.on_step(0)
    jax.block_until_ready(jax.jit(lambda v: v * 2)(np.ones(8, np.float32)))
    sess.close()
    assert "no scope ledger: the profile holds no device op" in capsys.readouterr().out
    assert not list(tmp_path.rglob("scope_ledger.json"))
    sess.close()  # once: a second close has nothing to do
    assert capsys.readouterr().out == ""

    monkeypatch.setattr(attribution, "ledger", lambda d, names: led)
    sess._traced = True
    sess.close()
    out = capsys.readouterr().out
    (path,) = tmp_path.rglob("scope_ledger.json")
    assert list(path.parent.glob("*.xplane.pb"))
    assert json.loads(path.read_text())["unresolved"] == led["unresolved"]
    assert f"scope ledger written to {path}" in out and "collect/act/ssm" in out

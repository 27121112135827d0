"""What a fused loop's set-up traces, lowers and compiles (ISSUE 45).

`setup_s` is an end-to-end metric of every cell, and most of it is JAX's
own work before the first warm chunk: tracing the chunk, lowering it,
compiling it (or reading the compile cache). A second trace of the
chunk, a retrace at the second warm chunk, a second lowering are each
tens of seconds on the chip at the cells' sizes and invisible in any
device metric (PR 44 lost 16 s of `setup_s` in one cell that way, with
the device program unchanged to four digits). This file counts JAX's own
monitoring events, the ones the benchmark's `CompileClock` sums, at the
test widths of each family's own test file:

- built directly, as the benchmark's set-up goes: build, `init`,
  `train_chunk.lower(shapes, updates)` (`perfbench/childlib
  .kernels_in_lowered`), then three chunks. The first call after the
  lowering lowers nothing again and compiles once; the second and third
  add no event; the chunk's cache holds one entry.
- through `launch.train_anakin_tokens` on a small section for 3 updates
  in chunks of 1, watched the way `perfbench/modes/anakin_tokens
  ._launch_watched` watches: from the entry of the second chunk on, no
  event is added (the first chunk's report compiles its own small reads
  once) and the chunk's cache holds one entry.

`init` runs eager programs whose count depends on what the process
compiled before, so it is printed and held only under an upper limit
read in a FRESH process (`python -m pytest tests/test_fused_setup.py -s
-k <case>` alone prints it): a warm process counts fewer.
"""

import json
import threading

import jax
import pytest
from test_anakin import anakin_cfg
from test_anakin_r2d2 import make as make_r2d2
from test_granite_hybrid import CFG as HYBRID_CFG
from test_joyai_flash import CFG as MLA_CFG
from test_lfm2_moe import CFG as CONV_CFG
from test_nemotron_h_moe import CFG as SSMOE_CFG
from test_ouro_looplm import CFG as LOOP_CFG
from test_qwen3_next import CFG as MOE_CFG
from test_smallthinker_moe import CFG as SWA_CFG

from distributed_reinforcement_learning_tpu.agents.convlm import ConvLMAgent
from distributed_reinforcement_learning_tpu.agents.hybridlm import HybridLMAgent
from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent
from distributed_reinforcement_learning_tpu.agents.looplm import LoopLMAgent
from distributed_reinforcement_learning_tpu.agents.mlalm import MLALMAgent
from distributed_reinforcement_learning_tpu.agents.moelm import MoELMAgent
from distributed_reinforcement_learning_tpu.agents.ssmoelm import SSMoELMAgent
from distributed_reinforcement_learning_tpu.agents.swalm import SwaLMAgent
from distributed_reinforcement_learning_tpu.envs import breakout_jax
from distributed_reinforcement_learning_tpu.envs.token_recall_jax import TokenRecall
from distributed_reinforcement_learning_tpu.runtime import anakin_tokens, launch
from distributed_reinforcement_learning_tpu.runtime.anakin import AnakinImpala
from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import AnakinTokens

KINDS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
         "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
         "/jax/core/compile/backend_compile_duration": "compile"}
NOTHING = {"trace": 0, "lower": 0, "compile": 0}
N = 4  # envs, as the families' own fused-chunk tests take

_COUNTS = dict(NOTHING)
_LOCK = threading.Lock()  # compiles happen on worker threads too


def _on_duration(event: str, seconds: float, **_) -> None:
    if event in KINDS:
        with _LOCK:
            _COUNTS[KINDS[event]] += 1


# One listener for the module: jax.monitoring has no public way to take a
# listener off again, and the phases below read differences.
jax.monitoring.register_event_duration_secs_listener(_on_duration)


class Phases:
    """Events of the three kinds, phase by phase."""

    def __init__(self):
        self.last = self.now()
        self.seen: dict = {}

    @staticmethod
    def now() -> dict:
        with _LOCK:
            return dict(_COUNTS)

    def close(self, name: str) -> dict:
        now = self.now()
        self.seen[name] = {k: now[k] - self.last[k] for k in now}
        self.last = now
        return self.seen[name]


def _tokens(agent_cls, cfg):
    def build():
        return AnakinTokens(agent_cls(cfg), N, TokenRecall(
            cfg.vocab_size, cfg.trajectory, cfg.recall_distance))
    return build


def _impala():
    return AnakinImpala(ImpalaAgent(anakin_cfg()), num_envs=N)


def _impala_breakout():
    """The pixel cells' env: `reset` runs eagerly inside `init` and hands
    the first chunk its `obs`, the chunk hands the second its own."""
    cfg = anakin_cfg(obs_shape=(84, 84, 4), num_actions=4, trajectory=5,
                     lstm_size=16)
    return AnakinImpala(ImpalaAgent(cfg), num_envs=2, env=breakout_jax)


# (build the loop, chunk updates, upper limits read in a fresh process at
# the parent, c585750: `init`'s trace / lower / compile events and the trace
# events of the chunk's one lowering, which nest: one event a traced
# function). A second trace of a model's stack inside the chunk shows in
# the last.
LOOPS = {
    "impala": (_impala, 2, (415, 77, 77), 882),
    # read at PR 48's own tree: with the observation's layout pinned where
    # an eager `reset` could hand it on, "second" compiled the chunk again
    "impala_breakout": (_impala_breakout, 1, (515, 130, 130), 1967),
    "r2d2": (make_r2d2, 2, (904, 99, 99), 987),
    "looplm": (_tokens(LoopLMAgent, LOOP_CFG), 1, (549, 26, 26), 809),
    "hybridlm": (_tokens(HybridLMAgent, HYBRID_CFG), 1, (776, 39, 39), 1611),
    "moelm": (_tokens(MoELMAgent, MOE_CFG), 1, (983, 43, 43), 2305),
    "mlalm": (_tokens(MLALMAgent, MLA_CFG), 1, (1041, 46, 46), 2335),
    # read at PR 46's own tree: the family is new there
    "convlm": (_tokens(ConvLMAgent, CONV_CFG), 1, (765, 38, 38), 1908),
    # read at PR 49's own tree: the family is new there
    "swalm": (_tokens(SwaLMAgent, SWA_CFG), 1, (585, 30, 30), 1742),
    # read at PR 53's own tree: the family is new there
    "ssmoelm": (_tokens(SSMoELMAgent, SSMOE_CFG), 1, (687, 34, 34), 2029),
}


@pytest.mark.parametrize("loop", list(LOOPS))
def test_set_up_traces_lowers_and_compiles_the_chunk_once(loop):
    build, updates, init_limit, lowering_limit = LOOPS[loop]
    phases = Phases()
    anakin = build()
    state = anakin.init(jax.random.PRNGKey(0))
    if hasattr(anakin, "collect_chunk"):  # the replay loops' warm-up
        state, _ = anakin.collect_chunk(state, 2)
    jax.block_until_ready(state)
    init = phases.close("init")
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    anakin.train_chunk.lower(shapes, updates).as_text()
    phases.close("lowering")
    for name in ("first", "second", "third"):
        state, metrics = anakin.train_chunk(state, updates)
        jax.block_until_ready(metrics)
        phases.close(name)
    print(f"[fused-setup] {loop}: {phases.seen}")
    seen = phases.seen
    assert seen["lowering"]["lower"] == 1 and seen["lowering"]["compile"] == 0
    assert seen["lowering"]["trace"] <= lowering_limit, seen
    # The call after the lowering finds the chunk traced and lowered.
    assert seen["first"]["lower"] == 0, seen
    assert seen["first"]["compile"] == 1, seen
    assert seen["first"]["trace"] <= 1, seen
    assert seen["second"] == NOTHING and seen["third"] == NOTHING, seen
    assert anakin.train_chunk._cache_size() == 1
    assert all(init[k] <= limit for k, limit in zip(NOTHING, init_limit)), (
        init, init_limit)


# -- through the launcher -------------------------------------------------------

_SMALL = {
    "ouro_looplm": dict(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
        head_dim=16, intermediate_size=176, vocab_size=512,
        available_action=[512], num_hidden_layers=2, trajectory=16),
    "granite_hybrid": dict(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=48, shared_intermediate_size=48, mamba_n_heads=4,
        mamba_d_head=16, mamba_d_state=8, mamba_chunk_size=8, vocab_size=96,
        available_action=[96], num_hidden_layers=4, trajectory=32,
        layer_types=["mamba", "mamba", "attention", "mamba"]),
    "qwen3_next": dict(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8, num_experts=4,
        router_width=16, first_expert=4, num_experts_per_tok=3,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        vocab_size=96, available_action=[96], trajectory=32),
    "joyai_flash": dict(
        hidden_size=32, num_hidden_layers=3, num_attention_heads=4,
        num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_head_dim=12,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        intermediate_size=48, n_routed_experts=4, router_width=16,
        first_expert=4, num_experts_per_tok=3, moe_intermediate_size=16,
        vocab_size=96, available_action=[96], trajectory=32),
    "lfm2_moe": dict(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=48, num_experts=4, router_width=16, first_expert=4,
        num_experts_per_tok=3, moe_intermediate_size=16, vocab_size=96,
        available_action=[96], trajectory=32),
    "smallthinker_moe": dict(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        sliding_window_size=8, moe_num_primary_experts=4, router_width=16,
        first_expert=4, moe_num_active_primary_experts=3, moe_ffn_hidden_size=16,
        vocab_size=96, available_action=[96], trajectory=32),
    "nemotron_h_moe_small": dict(vocab_size=96, available_action=[96]),
}


@pytest.mark.parametrize("section", list(_SMALL))
def test_the_launchers_loop_adds_nothing_after_its_first_chunk(
        section, tmp_path, monkeypatch, capsys):
    with open("config.json") as f:
        small = dict(json.load(f)[section], envs_per_actor=N, dtype="float32",
                     **_SMALL[section])
    path = tmp_path / "config.json"
    path.write_text(json.dumps({section: small}))
    at_entry: list = []
    watched: list = []
    built = anakin_tokens.AnakinTokens.__init__

    def build_and_watch(self, *a, **kw):
        built(self, *a, **kw)
        jitted = self.train_chunk
        watched.append(jitted)

        def observed(state, updates):
            at_entry.append(Phases.now())
            return jitted(state, updates)

        self.train_chunk = observed

    monkeypatch.setattr(anakin_tokens.AnakinTokens, "__init__", build_and_watch)
    result = launch.train_anakin_tokens(
        str(path), section, num_updates=3, chunk=1, seed=0, num_envs=N)
    at_return = Phases.now()
    lines = capsys.readouterr().out
    print(f"[fused-setup] {section} through the launcher: entries {at_entry}, "
          f"at return {at_return}")
    assert len(at_entry) == 3 and len(result["chunk_mean_returns"]) == 3
    assert result["frames"] == 3 * N * small["trajectory"]
    assert at_entry[1] == at_entry[2] == at_return
    assert watched[0]._cache_size() == 1
    assert lines.count("[anakin-tokens] step ") == 3
    # the start-up line names the form of the held experts' calls, once
    assert lines.count("held experts at act time: sorted, one slab of") == (
        "router_width" in small)
    assert lines.count("; at learn time: dense, ") == ("router_width" in small)

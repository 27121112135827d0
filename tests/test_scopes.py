"""The profile vocabulary (`observability/scopes.py`): device scopes in
the compiled fused chunks, the fused loops' host spans on the
profiler's clock, and what went with them (ISSUE 24)."""

import dataclasses
import glob
import os
import re

import jax
import pytest

from distributed_reinforcement_learning_tpu.observability import (
    TELEMETRY,
    Telemetry,
    chip_span,
    load_trace,
    scopes,
)
from distributed_reinforcement_learning_tpu.utils.profiling import (
    ProfilerSession,
    StageTimer,
)

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "distributed_reinforcement_learning_tpu")


def _op_names(jitted, *args) -> set[str]:
    text = jitted.lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]+)"', text))


@pytest.fixture(scope="module")
def impala_chunk_names():
    """`op_name`s of a tiny `AnakinImpala.train_chunk` on the Breakout
    env (the renderer's scope lives in the env)."""
    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent
    from distributed_reinforcement_learning_tpu.envs import breakout_jax
    from distributed_reinforcement_learning_tpu.runtime.anakin import AnakinImpala
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    cfg, _ = load_config("config.json", "impala")
    anakin = AnakinImpala(ImpalaAgent(dataclasses.replace(cfg, trajectory=4)),
                          2, env=breakout_jax)
    return _op_names(anakin.train_chunk, anakin.init(jax.random.PRNGKey(0)), 1)


@pytest.mark.parametrize(
    "name", scopes.IMPALA_CHUNK_SCOPES + (f"transpose(jvp({scopes.LOSS}))",))
def test_impala_chunk_carries_scope(impala_chunk_names, name):
    assert any(name in n for n in impala_chunk_names), name


def test_one_chip_impala_chunk_swaps_nothing_to_batch_major(impala_chunk_names):
    """The learner takes the rollout as the scan wrote it (ISSUE 29): the
    readers that list `to_batch_major` sum a missing scope as 0."""
    assert not any(scopes.TO_BATCH_MAJOR in n for n in impala_chunk_names)


def test_mesh_impala_chunk_names_its_swap():
    """Under a mesh the fused loop keeps the batch-major handoff, and
    its swaps keep their name."""
    from distributed_reinforcement_learning_tpu.agents.impala import (
        ImpalaAgent, ImpalaConfig)
    from distributed_reinforcement_learning_tpu.parallel import make_mesh
    from distributed_reinforcement_learning_tpu.runtime.anakin import AnakinImpala

    anakin = AnakinImpala(ImpalaAgent(ImpalaConfig(
        obs_shape=(4,), num_actions=2, trajectory=4, lstm_size=16)), 8,
        mesh=make_mesh(8))
    # as lowered: XLA:CPU fuses these tiny swaps into their consumers
    text = anakin.train_chunk.lower(
        anakin.init(jax.random.PRNGKey(0)), 1).as_text(debug_info=True)
    assert f"{scopes.TO_BATCH_MAJOR}/transpose" in text


def test_impala_chunk_module_name_carries_the_cache_tag():
    """Metadata is not in the compile-cache key; the module's name is."""
    from distributed_reinforcement_learning_tpu.agents.impala import (
        ImpalaAgent, ImpalaConfig)
    from distributed_reinforcement_learning_tpu.runtime.anakin import AnakinImpala

    anakin = AnakinImpala(ImpalaAgent(ImpalaConfig(
        obs_shape=(4,), num_actions=2, trajectory=4, lstm_size=16)), 2)
    text = anakin.train_chunk.lower(anakin.init(jax.random.PRNGKey(0)), 1).as_text()
    assert f"_train_chunk_{scopes.CACHE_TAG}" in text.split("\n", 1)[0]


def _r2d2():
    from distributed_reinforcement_learning_tpu.agents.r2d2 import (
        R2D2Agent, R2D2Config)
    from distributed_reinforcement_learning_tpu.envs.cartpole import pomdp_project
    from distributed_reinforcement_learning_tpu.runtime.anakin_r2d2 import AnakinR2D2

    cfg = R2D2Config(obs_shape=(2,), num_actions=2, seq_len=6, burn_in=2,
                     lstm_size=16, learning_rate=1e-3)
    return AnakinR2D2(R2D2Agent(cfg), num_envs=4, capacity=16, batch_size=4,
                      obs_transform=pomdp_project, updates_per_collect=1)


def _apex():
    from distributed_reinforcement_learning_tpu.agents.apex import (
        ApexAgent, ApexConfig)
    from distributed_reinforcement_learning_tpu.runtime.anakin_apex import AnakinApex

    cfg = ApexConfig(obs_shape=(4,), num_actions=2, start_learning_rate=1e-3)
    return AnakinApex(ApexAgent(cfg), num_envs=4, steps_per_collect=4,
                      capacity=32, batch_size=8)


@pytest.fixture(scope="module", params=[_r2d2, _apex], ids=["r2d2", "apex"])
def replay_chunk_names(request):
    anakin = request.param()
    return _op_names(anakin.train_chunk, anakin.init(jax.random.PRNGKey(0)), 1)


@pytest.mark.parametrize("name", scopes.REPLAY_CHUNK_SCOPES)
def test_replay_chunk_carries_top_level_scope(replay_chunk_names, name):
    assert any(re.search(rf"(^|[/(]){name}(/|\)|$)", n)
               for n in replay_chunk_names), name


@pytest.fixture(scope="module")
def r2d2_chunk_names():
    return _op_names(_r2d2().train_chunk, _r2d2().init(jax.random.PRNGKey(0)), 1)


@pytest.mark.parametrize("name", scopes.R2D2_CHUNK_SCOPES)
def test_r2d2_chunk_carries_scope(r2d2_chunk_names, name):
    """The names `perfbench/layer_metrics/replay_*`, `seq_learn_*` and
    `lstm_unroll_*` read (ISSUE 26)."""
    assert any(name in n for n in r2d2_chunk_names), name


@pytest.fixture(scope="module")
def tokens_chunk():
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.looplm import (
        LoopLMAgent, LoopLMConfig)
    from distributed_reinforcement_learning_tpu.envs.token_recall_jax import TokenRecall
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import AnakinTokens

    cfg = LoopLMConfig(vocab_size=128, hidden_size=32, num_attention_heads=2,
                       head_dim=16, intermediate_size=48, num_hidden_layers=2,
                       trajectory=8, dtype=jnp.float32, head_block=16)
    anakin = AnakinTokens(LoopLMAgent(cfg), 4, TokenRecall(128, 8))
    return anakin, anakin.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens_chunk_names(tokens_chunk):
    anakin, state = tokens_chunk
    return _op_names(anakin.train_chunk, state, 1)


@pytest.mark.parametrize("name", scopes.TOKENS_CHUNK_SCOPES)
def test_tokens_chunk_carries_scope(tokens_chunk_names, name):
    """The names `perfbench/layer_metrics/looplm_*` read (ISSUE 30)."""
    assert any(name in n for n in tokens_chunk_names), name


def test_tokens_backward_stack_keeps_the_loop_name(tokens_chunk_names):
    """The rematerialised stack is entered again under the transpose:
    `transpose(jvp(learn/loss))/.../learn/loss/loop/...`."""
    assert any(f"transpose(jvp({scopes.LOSS}))" in n and scopes.LOOP in n
               for n in tokens_chunk_names)


def test_tokens_chunk_module_name_carries_the_cache_tag(tokens_chunk):
    anakin, state = tokens_chunk
    text = anakin.train_chunk.lower(state, 1).as_text()
    assert f"_train_chunk_{scopes.CACHE_TAG}" in text.split("\n", 1)[0]


@pytest.fixture(scope="module")
def hybrid_chunk_names():
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
    return _op_names(anakin.train_chunk, anakin.init(jax.random.PRNGKey(0)), 1)


@pytest.mark.parametrize("name", scopes.HYBRID_CHUNK_SCOPES)
def test_hybrid_chunk_carries_scope(hybrid_chunk_names, name):
    """The names `perfbench/layer_metrics/hybridlm_*` read (ISSUE 32)."""
    assert any(name in n for n in hybrid_chunk_names), name


@pytest.mark.parametrize("name", [scopes.LAYERS, scopes.SSD, scopes.ATTENTION])
def test_hybrid_backward_stack_keeps_its_names(hybrid_chunk_names, name):
    """The rematerialised blocks are entered again under the transpose."""
    assert any(f"transpose(jvp({scopes.LOSS}))" in n and name in n
               for n in hybrid_chunk_names)


def _moe_anakin(trajectory=16, row_block=2):
    """The small Qwen3-Next loop: 4 rows of `trajectory` tokens."""
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.moelm import (
        MoELMAgent, MoELMConfig)
    from distributed_reinforcement_learning_tpu.envs.token_recall_jax import TokenRecall
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import AnakinTokens

    cfg = MoELMConfig(
        vocab_size=64, hidden_size=32, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8,
        num_experts=4, router_width=16, first_expert=4, num_experts_per_tok=3,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        trajectory=trajectory, gdn_chunk=8, dtype=jnp.float32, head_block=16,
        row_block=row_block)
    return AnakinTokens(MoELMAgent(cfg), 4, TokenRecall(64, trajectory))


@pytest.fixture(scope="module")
def moe_chunk_names():
    anakin = _moe_anakin()
    return _op_names(anakin.train_chunk, anakin.init(jax.random.PRNGKey(0)), 1)


@pytest.mark.parametrize("name", scopes.MOE_CHUNK_SCOPES)
def test_moe_chunk_carries_scope(moe_chunk_names, name):
    """The names `perfbench/layer_metrics/moelm_*` read (ISSUE 36)."""
    assert any(name in n for n in moe_chunk_names), name


@pytest.mark.parametrize("name", [scopes.LAYERS, scopes.GDN, scopes.ATTENTION,
                                  scopes.MOE_ROUTE, scopes.MOE_EXPERTS,
                                  scopes.MOE_SHARED])
def test_moe_backward_stack_keeps_its_names(moe_chunk_names, name):
    """The rematerialised blocks are entered again under the transpose."""
    assert any(f"transpose(jvp({scopes.LOSS}))" in n and name in n
               for n in moe_chunk_names)


def _mla_anakin(trajectory=16, row_block=2):
    """The small JoyAI-LLM-Flash loop: 4 rows of `trajectory` tokens."""
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.mlalm import (
        MLALMAgent, MLALMConfig)
    from distributed_reinforcement_learning_tpu.envs.token_recall_jax import TokenRecall
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import AnakinTokens

    cfg = MLALMConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=3, num_attention_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, intermediate_size=48, n_routed_experts=4, router_width=16,
        first_expert=4, num_experts_per_tok=3, moe_intermediate_size=16,
        trajectory=trajectory, dtype=jnp.float32, head_block=16, row_block=row_block)
    return AnakinTokens(MLALMAgent(cfg), 4, TokenRecall(64, trajectory))


@pytest.fixture(scope="module")
def mla_chunk_names():
    anakin = _mla_anakin()
    return _op_names(anakin.train_chunk, anakin.init(jax.random.PRNGKey(0)), 1)


@pytest.mark.parametrize("name", scopes.MLA_CHUNK_SCOPES)
def test_mla_chunk_carries_scope(mla_chunk_names, name):
    """The names `perfbench/layer_metrics/mlalm_*` read (ISSUE 40)."""
    assert any(name in n for n in mla_chunk_names), name


@pytest.mark.parametrize("name", [scopes.LAYERS, scopes.MLA_PROJECT,
                                  scopes.MLA_ATTEND, scopes.DENSE, scopes.MOE_ROUTE,
                                  scopes.MOE_EXPERTS, scopes.MOE_SHARED, scopes.MTP,
                                  scopes.MLA_MTP["attend"], scopes.MLA_MTP["experts"]])
def test_mla_backward_stack_keeps_its_names(mla_chunk_names, name):
    """The rematerialised blocks are entered again under the transpose,
    the prediction module's under its own names."""
    assert any(f"transpose(jvp({scopes.LOSS}))" in n and name in n
               for n in mla_chunk_names)


def test_the_prediction_modules_layer_is_not_named_for_the_stack(mla_chunk_names):
    """Its ops end under `learn/loss/mtp/...`: a reader of
    `learn/loss/layers` does not count them."""
    module = [n for n in mla_chunk_names if scopes.MLA_MTP["attend"] in n]
    assert module and not any(scopes.MLA_ATTEND in n for n in module)
    assert not any(scopes.ACT in n and scopes.MTP in n for n in mla_chunk_names)


def _conv_anakin(trajectory=16, row_block=2):
    """The small LFM2 loop: 4 rows of `trajectory` tokens."""
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.convlm import (
        ConvLMAgent, ConvLMConfig)
    from distributed_reinforcement_learning_tpu.envs.token_recall_jax import TokenRecall
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import AnakinTokens

    cfg = ConvLMConfig(
        vocab_size=64, hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        rope_theta=1e4, intermediate_size=48, num_experts=4, router_width=16,
        first_expert=4, num_experts_per_tok=3, moe_intermediate_size=16,
        trajectory=trajectory, dtype=jnp.float32, head_block=16, row_block=row_block)
    return AnakinTokens(ConvLMAgent(cfg), 4, TokenRecall(64, trajectory))


@pytest.fixture(scope="module")
def conv_chunk_names():
    anakin = _conv_anakin()
    return _op_names(anakin.train_chunk, anakin.init(jax.random.PRNGKey(0)), 1)


@pytest.mark.parametrize("name", scopes.CONV_CHUNK_SCOPES)
def test_conv_chunk_carries_scope(conv_chunk_names, name):
    """The names `perfbench/layer_metrics/convlm_*` read (ISSUE 46)."""
    assert any(name in n for n in conv_chunk_names), name


@pytest.mark.parametrize("name", [scopes.LAYERS, scopes.CONV, scopes.ATTENTION,
                                  scopes.DENSE, scopes.MOE_ROUTE, scopes.MOE_EXPERTS])
def test_conv_backward_stack_keeps_its_names(conv_chunk_names, name):
    """The rematerialised blocks are entered again under the transpose."""
    assert any(f"transpose(jvp({scopes.LOSS}))" in n and name in n
               for n in conv_chunk_names)


def _walk_under(jaxpr, scope, above=""):
    """(equation, whether it holds a jaxpr itself) for every equation whose
    composed name stack holds `scope`, through every jaxpr inside."""
    for eqn in jaxpr.eqns:
        stack = f"{above}/{eqn.source_info.name_stack}"
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if scope in stack:
            yield eqn, bool(subs)
        for sub in subs:
            yield from _walk_under(sub, scope, stack)


def _equations_under(jaxpr, scope, loops=False):
    """The primitive of every equation under `scope`; with `loops`, the
    equations that hold a jaxpr themselves (`scan`, `pjit`, ...) as well."""
    return [eqn.primitive.name for eqn, holds in _walk_under(jaxpr, scope)
            if loops or not holds]


def test_every_equation_of_the_convolution_mixer_is_under_its_name():
    """In the learner the whole mixer is `learn/loss/layers/conv`: the
    in-projection and the out-projection (two products a convolution
    layer's block), the split, both gates, the pads and the three taps.
    At act time `collect/act/conv` holds what is the convolution's alone:
    the split, both gates, the window's shift (a concatenate and a slice)
    and the taps' product; the two projections stay with the decode step's
    other products under `collect/act/layers`. No shared expert's scope is
    anywhere in the chunk."""
    anakin = _conv_anakin()
    state = jax.eval_shape(anakin.init, jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(anakin.train_chunk, static_argnums=1)(state, 1).jaxpr
    learner = _equations_under(jaxpr, scopes.CONV)
    assert learner.count("dot_general") >= 2 * 2  # W_in, W_out in two convolution runs
    for primitive in ("split", "mul", "pad", "select_n"):
        assert primitive in learner, primitive
    act = _equations_under(jaxpr, scopes.ACT_CONV)
    for primitive in ("split", "mul", "concatenate", "slice", "dot_general"):
        assert primitive in act, primitive
    # the taps are the one product under the name: 4 convolution layers x 2 decode bodies
    assert act.count("dot_general") == 4 * len(anakin.decode_spans)
    assert not _equations_under(jaxpr, scopes.MOE_SHARED)
    assert not _equations_under(jaxpr, "collect/act/gdn")


def _ssmoe_anakin():
    """The small Nemotron-H loop (section `nemotron_h_moe_small`: `ME*ME`)."""
    import dataclasses

    from distributed_reinforcement_learning_tpu.agents.ssmoelm import SSMoELMAgent
    from distributed_reinforcement_learning_tpu.envs.token_recall_jax import TokenRecall
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import AnakinTokens
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    cfg = dataclasses.replace(load_config("config.json", "nemotron_h_moe_small")[0],
                              trajectory=16, head_block=16, row_block=2)
    return AnakinTokens(SSMoELMAgent(cfg), 4, TokenRecall(64, 16))


@pytest.fixture(scope="module")
def ssmoe_chunk():
    anakin = _ssmoe_anakin()
    state = anakin.init(jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(anakin.train_chunk, static_argnums=1)(
        jax.eval_shape(lambda: state), 1).jaxpr
    return _op_names(anakin.train_chunk, state, 1), jaxpr


@pytest.mark.parametrize("name", scopes.SSMOE_CHUNK_SCOPES)
def test_ssmoe_chunk_carries_scope(ssmoe_chunk, name):
    """The names `perfbench/layer_metrics/ssmoelm_*` read (ISSUE 53)."""
    assert any(name in n for n in ssmoe_chunk[0]), name


def test_a_layer_of_one_sublayer_has_its_sublayers_name_alone(ssmoe_chunk):
    """Two state-space layers, two expert layers and one attention layer a
    decode body: the state update and its read-out under `collect/act/ssm`,
    the cache's write under `collect/act/cache` inside `collect/act/attend`,
    the router's product and top-k under the route's name, the grouped
    products under the experts', the shared expert's two products under
    its own; the learner's scan, convolution, attention, route, experts and
    shared expert each under its own name, and the backward re-enters them."""
    names, jaxpr = ssmoe_chunk
    bodies = 1  # 16 steps: one scan
    assert _equations_under(jaxpr, scopes.ACT_CACHE).count(
        "dynamic_update_slice") == 2 * bodies
    assert "dot_general" in _equations_under(jaxpr, scopes.ACT_ATTEND)
    assert _equations_under(jaxpr, scopes.ACT_SSM).count("exp") >= 2 * bodies
    for route in (scopes.ACT_MOE_ROUTE, scopes.MOE_ROUTE):
        found = _equations_under(jaxpr, route)
        assert "dot_general" in found and "top_k" in found and "logistic" in found
    assert any("ragged_dot" in p for p in _equations_under(jaxpr, scopes.ACT_MOE_EXPERTS))
    assert _equations_under(jaxpr, scopes.MOE_SHARED).count("dot_general") >= 2
    assert "cumsum" in _equations_under(jaxpr, scopes.SSD)
    assert not any("ragged_dot" in p for p in _equations_under(jaxpr, scopes.SSD))
    for name in (scopes.LAYERS, scopes.SSD, scopes.CONV, scopes.GLOBAL_ATTENTION,
                 scopes.MOE_ROUTE, scopes.MOE_EXPERTS, scopes.MOE_SHARED):
        assert any(f"transpose(jvp({scopes.LOSS}))" in n and name in n
                   for n in names), name


def _swa_anakin(trajectory=16, row_block=2):
    """The small SmallThinker loop: 4 rows of `trajectory` tokens, a window
    of 8."""
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.swalm import (
        SwaLMAgent, SwaLMConfig)
    from distributed_reinforcement_learning_tpu.envs.token_recall_jax import TokenRecall
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import AnakinTokens

    cfg = SwaLMConfig(
        vocab_size=64, hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, rope_theta=1e4, sliding_window_size=8, moe_num_primary_experts=4,
        router_width=16, first_expert=4, moe_num_active_primary_experts=3,
        moe_ffn_hidden_size=16, trajectory=trajectory, dtype=jnp.float32,
        head_block=16, row_block=row_block)
    return AnakinTokens(SwaLMAgent(cfg), 4, TokenRecall(64, trajectory))


@pytest.fixture(scope="module")
def swa_chunk_names():
    anakin = _swa_anakin()
    return _op_names(anakin.train_chunk, anakin.init(jax.random.PRNGKey(0)), 1)


@pytest.mark.parametrize("name", scopes.SWA_CHUNK_SCOPES)
def test_swa_chunk_carries_scope(swa_chunk_names, name):
    """The names `perfbench/layer_metrics/swalm_*` read (ISSUE 49)."""
    assert any(name in n for n in swa_chunk_names), name


@pytest.mark.parametrize("name", [scopes.LAYERS, scopes.GLOBAL_ATTENTION,
                                  scopes.WINDOW_ATTENTION, scopes.MOE_ROUTE,
                                  scopes.MOE_EXPERTS])
def test_swa_backward_stack_keeps_its_names(swa_chunk_names, name):
    """The rematerialised blocks are entered again under the transpose."""
    assert any(f"transpose(jvp({scopes.LOSS}))" in n and name in n
               for n in swa_chunk_names)


def test_the_two_kinds_of_layer_and_of_cache_have_names_of_their_own():
    """The learner's window layers are `learn/loss/layers/window_attention`
    and its global layer `.../global_attention` (q, k, v, rotary where
    there is one, the core, W_o), never one inside the other, and the
    router's product, ahead of attention, is under neither. At act time a
    ring's write and read are `collect/act/ring` (three window layers a
    decode body) and the full cache's `collect/act/cache` (one)."""
    anakin = _swa_anakin()
    state = jax.eval_shape(anakin.init, jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(anakin.train_chunk, static_argnums=1)(state, 1).jaxpr
    for own in (scopes.WINDOW_ATTENTION, scopes.GLOBAL_ATTENTION):
        assert _equations_under(jaxpr, own).count("dot_general") >= 5  # q, kv, s, pv, o
    assert scopes.WINDOW_ATTENTION not in scopes.GLOBAL_ATTENTION
    assert scopes.GLOBAL_ATTENTION not in scopes.WINDOW_ATTENTION
    route = _equations_under(jaxpr, scopes.MOE_ROUTE)
    assert "dot_general" in route and "top_k" in route
    bodies = len(anakin.decode_spans)
    ring = _equations_under(jaxpr, scopes.ACT_RING)
    cache = _equations_under(jaxpr, scopes.ACT_CACHE)
    assert ring.count("dynamic_update_slice") == 2 * 3 * bodies  # k and v, three rings
    assert cache.count("dynamic_update_slice") == 2 * 1 * bodies
    assert "rem" in ring and "rem" not in cache  # t mod W is the ring's alone
    assert not _equations_under(jaxpr, scopes.MOE_SHARED)
    assert not _equations_under(jaxpr, scopes.ACT_CONV)


def test_r2d2_backward_recurrence_keeps_the_unroll_name(r2d2_chunk_names):
    """The inner scope is entered again inside the transposed outer one:
    `transpose(jvp(learn/loss))/.../learn/loss/unroll/...`."""
    assert any(f"transpose(jvp({scopes.LOSS}))" in n and scopes.UNROLL in n
               for n in r2d2_chunk_names)


def test_r2d2_scoring_unroll_is_not_named_for_the_learn_step(r2d2_chunk_names):
    """The same net scores the new sequences (time-major,
    `R2D2Net.unroll_time_major`); only the learn step's recurrence is
    `learn/loss/unroll`."""
    scoring = [n for n in r2d2_chunk_names if scopes.REPLAY_SCORE in n]
    assert scoring and not any(scopes.LEARN in n for n in scoring)


def _pixel_r2d2():
    """The pixel cell's shape of agent at 2 envs: Nature torso (three
    convolutions), dueling streams, 5-step targets."""
    from distributed_reinforcement_learning_tpu.agents.r2d2 import (
        R2D2Agent, R2D2Config)
    from distributed_reinforcement_learning_tpu.envs import breakout_jax
    from distributed_reinforcement_learning_tpu.runtime.anakin_r2d2 import AnakinR2D2

    cfg = R2D2Config(obs_shape=(84, 84, 4), num_actions=4, seq_len=6, burn_in=2,
                     n_step=5, lstm_size=16, torso="nature", dueling_hidden=32)
    return AnakinR2D2(R2D2Agent(cfg), num_envs=2, capacity=4, batch_size=2,
                      env=breakout_jax)


@pytest.mark.parametrize("chunk", ["train_chunk", "collect_chunk"])
def test_r2d2_scoring_pass_unrolls_one_net(chunk):
    """Under `replay/score` runs the target net alone: one torso and one
    recurrence. The online net's Q-values come out of the collect scan
    (ISSUE 50); with both nets there the counts are 6 and 2. Static, so
    every update of the training chunk and of the warm-up takes the path."""
    anakin = _pixel_r2d2()
    state = jax.eval_shape(anakin.init, jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(getattr(anakin, chunk), static_argnums=1)(state, 1).jaxpr
    scoring = _equations_under(jaxpr, scopes.REPLAY_SCORE, loops=True)
    assert scoring.count("conv_general_dilated") == 3
    assert scoring.count("scan") == 1
    acting = _equations_under(jaxpr, scopes.ACT)
    assert acting.count("conv_general_dilated") == 3


@pytest.mark.parametrize("chunk", ["train_chunk", "collect_chunk"])
def test_r2d2_scoring_pass_transposes_no_frames(chunk):
    """The score takes the rollout as the collect scan stacked it
    (ISSUE 52): under `replay/score` no `transpose` has an operand of the
    frame batch's rank and size (`[T, B, 84, 84, 4]`; the Q-values and the
    per-step scalars are swapped, and the recurrence's own order needs
    none), and it is still one net: three convolutions, one scan. The
    ring's `[B, T]` batch is made under `replay/write`."""
    anakin = _pixel_r2d2()
    state = jax.eval_shape(anakin.init, jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(getattr(anakin, chunk), static_argnums=1)(state, 1).jaxpr
    frames = 6 * 2 * 84 * 84 * 4
    names = _equations_under(jaxpr, scopes.REPLAY_SCORE, loops=True)
    assert names.count("conv_general_dilated") == 3 and names.count("scan") == 1
    swapped_under = lambda scope: [
        eqn.invars[0].aval for eqn, _ in _walk_under(jaxpr, scope)
        if eqn.primitive.name == "transpose"]
    swapped = swapped_under(scopes.REPLAY_SCORE)
    assert swapped  # the small fields, on their way to the TD arithmetic
    assert not [a for a in swapped if a.ndim >= 5 or a.size >= frames], swapped
    swaps_frames = lambda scope: any(
        a.shape == (6, 2, 84, 84, 4) for a in swapped_under(scope))
    assert swaps_frames(scopes.REPLAY_WRITE) and not swaps_frames(scopes.COLLECT)
    assert anakin.score_order == "time_major"


def test_r2d2_learn_step_still_unrolls_both_nets():
    """`_sequence_td` without `online_q` is the program it was: `_loss`
    lowers to the text of the forward written out here with both unrolls."""
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents import common

    anakin = _pixel_r2d2()
    agent, cfg = anakin.agent, anakin.agent.cfg
    train = jax.eval_shape(agent.init_state, jax.random.PRNGKey(0))
    entries = anakin._sequence_entries()
    batch = jax.tree.map(
        lambda e: jax.ShapeDtypeStruct((2, *e.shape), e.dtype), entries)
    weight = jax.ShapeDtypeStruct((2,), jnp.float32)

    def _loss(params, target_params, batch, is_weight):
        unroll = lambda p: agent.model.apply(
            p, agent._prep_obs(batch.state), batch.previous_action, batch.done,
            batch.initial_h, batch.initial_c, scopes.UNROLL,
            method=agent.model.unroll)
        discounts = (~batch.done).astype(jnp.float32) * cfg.discount_factor
        tv, sav = common.sequence_double_q_td(
            unroll(params), unroll(target_params), batch.action, batch.reward,
            discounts, burn_in=cfg.burn_in, rescale_eps=cfg.rescale_eps,
            n_step=cfg.n_step)
        per_seq = jnp.mean(jnp.square(tv - sav), axis=1)
        return jnp.mean(per_seq * is_weight) + 0.0, agent._seq_priority(tv, sav)

    args = (train.params, train.target_params, batch, weight)
    ours = jax.jit(agent._loss).lower(*args).as_text()
    assert ours.count("stablehlo.convolution") == 6
    assert ours == jax.jit(_loss).lower(*args).as_text()


def _host_events(profile_dir: str) -> list[str]:
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return [ev.name for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]


def test_train_anakin_profile_holds_one_span_per_chunk(tmp_path, monkeypatch,
                                                       capsys):
    """`DRL_PROFILE_DIR` on a fused loop: one profiler session (no
    Python tracer), and per chunk one dispatch / wait / report span on
    the host plane of the same `.xplane.pb` as the device ops."""
    from distributed_reinforcement_learning_tpu.runtime.launch import train_anakin

    calls = {"start": [], "stop": 0}
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace

    def counted_start(log_dir, *a, **kw):
        calls["start"].append(kw["profiler_options"].python_tracer_level)
        return start(log_dir, *a, **kw)

    def counted_stop():
        calls["stop"] += 1
        return stop()

    monkeypatch.setattr(jax.profiler, "start_trace", counted_start)
    monkeypatch.setattr(jax.profiler, "stop_trace", counted_stop)
    monkeypatch.setenv("DRL_PROFILE_DIR", str(tmp_path))
    monkeypatch.setenv("DRL_PROFILE_START", "0")
    monkeypatch.setenv("DRL_PROFILE_STEPS", "1000")
    train_anakin("config.json", "impala_cartpole", num_updates=4, chunk=2)
    # the static fact of the compiled chunk, once at start-up (ISSUE 29)
    assert capsys.readouterr().out.count(
        "[anakin] learn handoff: time_major, frames float32\n") == 1
    assert calls == {"start": [0], "stop": 1}
    names = _host_events(str(tmp_path))
    for span in (scopes.DISPATCH, scopes.WAIT, scopes.REPORT):
        assert names.count(span) == 2, (span, names.count(span))
    # the session starts after the first read: second chunk + the loop's exit
    assert names.count(scopes.STEP_READ) == 2
    assert scopes.CHECKPOINT not in names  # no checkpoint_dir was given


def test_stage_timer_is_silent_with_telemetry_off_and_emits_with_it_on(tmp_path):
    assert TELEMETRY.trace is None
    assert TELEMETRY.span("x") is TELEMETRY.span("y")  # the shared no-op
    timer = StageTimer(None, log_every=1)
    with timer.stage("learn"):
        pass
    timer.step_done(1)
    assert "learn" in timer.last_means_ms
    assert not list(tmp_path.iterdir())
    # with an emitter the same span also lands in the Chrome trace
    t = Telemetry()
    t.configure(str(tmp_path), "learner", rank=0, flush_interval=0)
    try:
        with chip_span("publish", t.trace):
            pass
    finally:
        t.close()
    events = load_trace(str(tmp_path / "trace-learner-0.json"))
    assert [e["name"] for e in events if e.get("ph") == "X"] == ["publish"]


def test_stage_timer_stage_lands_on_the_profilers_host_plane(tmp_path):
    timer = StageTimer(None, log_every=100)
    sess = ProfilerSession(str(tmp_path), start_step=0, num_steps=10)
    sess.on_step(0)
    with timer.stage("dequeue"):
        jax.block_until_ready(jax.jit(lambda v: v + 1)(1.0))
    sess.close()
    assert _host_events(str(tmp_path)).count("dequeue") == 1


def _program_sources():
    for path in glob.glob(os.path.join(PKG, "**", "*.py"), recursive=True):
        with open(path) as f:
            yield os.path.relpath(path, PKG), f.read()


@pytest.mark.parametrize("pattern", [
    r"stage/\{?\w*\}?_ms", "anakin/steps_per_s", "anakin/frames_per_s"])
def test_removed_gauges_have_no_emitter(pattern):
    hits = [rel for rel, text in _program_sources() if re.search(pattern, text)]
    assert hits == []


def test_scope_and_span_names_are_spelled_in_one_place():
    """No literal `named_scope("...")` / `chip_span("...")` outside
    `observability/scopes.py`: the program names them through it."""
    literal = re.compile(r"(named_scope|chip_span|TraceAnnotation)\(\s*[\"']")
    hits = [rel for rel, text in _program_sources()
            if rel != os.path.join("observability", "scopes.py")
            and literal.search(text)]
    assert hits == []


# -- the slab loop of the held experts (ISSUE 42) ------------------------------


def _loops_over_slabs(jaxpr, above=""):
    """[(the loop's own composed name stack, [that of every equation inside
    it])] for every `while` of `jaxpr` whose body runs a grouped product
    itself (not through a loop inside it): the slab loops."""
    def inside(jaxpr, above, loops_too):
        for eqn in jaxpr.eqns:
            stack = f"{above}/{eqn.source_info.name_stack}"
            yield eqn.primitive.name, stack
            if loops_too or eqn.primitive.name not in ("while", "scan"):
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from inside(sub, stack, loops_too)

    found = []
    for eqn in jaxpr.eqns:
        stack = f"{above}/{eqn.source_info.name_stack}"
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if eqn.primitive.name == "while" and any(
                name == "ragged_dot_general"
                for sub in subs for name, _ in inside(sub, stack, False)):
            found.append((stack, [s for sub in subs for _, s in inside(sub, stack, True)]))
        else:
            for sub in subs:
                found.extend(_loops_over_slabs(sub, stack))
    return found


@pytest.mark.parametrize("make,experts", [
    (_moe_anakin, [scopes.MOE_EXPERTS]),
    (_mla_anakin, [scopes.MOE_EXPERTS, scopes.MLA_MTP["experts"]]),
    (_conv_anakin, [scopes.MOE_EXPERTS]),
    (_swa_anakin, [scopes.MOE_EXPERTS])],
    ids=["qwen3_next", "joyai_flash", "lfm2_moe", "smallthinker_moe"])
def test_every_op_of_the_slab_loop_is_named_for_the_experts(make, experts):
    """4 rows x 64 tokens x 3 choices = 768 pairs in slabs of 512: the
    learner loops. Every equation of the loop, forward and in the loop's own
    backward (a custom VJP's, traced apart from the forward), carries the
    experts' scope, which `moelm_experts_` / `mlalm_experts_` read."""
    anakin = make(trajectory=64, row_block=4)
    assert anakin.static_facts["pair_slab_rows"] == 512
    state = jax.eval_shape(anakin.init, jax.random.PRNGKey(0))
    loops = _loops_over_slabs(
        jax.make_jaxpr(anakin.train_chunk, static_argnums=1)(state, 1).jaxpr)
    for scope in experts:
        own = [stacks for loop, stacks in loops if scope in loop]
        assert any("transpose(jvp(" in loop for loop, _ in loops if scope in loop)
        assert any("transpose(jvp(" not in loop for loop, _ in loops if scope in loop)
        assert all(scope in s for stacks in own for s in stacks)
    assert all(any(scope in loop for scope in experts) for loop, _ in loops)

"""The `granite_hybrid` configuration at a small size on the CPU: the
hybrid state-space / attention model (`models/hybrid_lm.py`), its chunked
scan (`ops/ssd.py`), the shared token-level loss (`agents/looplm.py`
through `agents/hybridlm.py`) and the fused loop
(`runtime/anakin_tokens.py`) against the plain reference
(`reference/granite_hybrid.py`), which imports nothing of the program.

Sizes: hidden 32, 4 query / 2 key-value heads of 8, SwiGLU 48, 4
state-space heads of 16 with a state of 8, chunks of 8, V 96, the order
mamba, mamba, attention, mamba, T 32, N 4; float32 so that the agreement
is the arithmetic's.
"""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.agents.hybridlm import (
    HybridLMAgent, HybridLMConfig)
from distributed_reinforcement_learning_tpu.agents.looplm import LoopLMBatch
from distributed_reinforcement_learning_tpu.envs.token_recall_jax import TokenRecall
from distributed_reinforcement_learning_tpu.models import hybrid_lm, looped_lm
from distributed_reinforcement_learning_tpu.ops import ssd
from distributed_reinforcement_learning_tpu.reference import granite_hybrid as ref
from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import AnakinTokens
from distributed_reinforcement_learning_tpu.utils.config import load_config

V, T, N = 96, 32, 4
ORDER = ("mamba", "mamba", "attention", "mamba")
CFG = HybridLMConfig(
    vocab_size=V, hidden_size=32, layer_types=ORDER, num_attention_heads=4,
    num_key_value_heads=2, shared_intermediate_size=48, mamba_n_heads=4,
    mamba_d_head=16, mamba_d_state=8, mamba_chunk_size=8, trajectory=T,
    dtype=jnp.float32, attention_backend="reference", row_block=2,
    head_block=32, start_learning_rate=1e-3, init_std=0.2)  # wide enough to see


def hyper(cfg: HybridLMConfig) -> dict:
    return dict(num_heads=cfg.num_attention_heads,
                num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
                attention_multiplier=cfg.attention_multiplier,
                residual_multiplier=cfg.residual_multiplier,
                embedding_multiplier=cfg.embedding_multiplier,
                logits_scaling=cfg.logits_scaling,
                mamba_n_heads=cfg.mamba_n_heads, mamba_d_head=cfg.mamba_d_head,
                mamba_d_state=cfg.mamba_d_state, rms_eps=cfg.rms_norm_eps,
                layer_order=tuple(cfg.layer_types), discount=cfg.discount_factor,
                baseline_loss_coef=cfg.baseline_loss_coef,
                entropy_coef=cfg.entropy_coef,
                reward_clipping=cfg.reward_clipping,
                gradient_clip_norm=cfg.gradient_clip_norm,
                learning_rate=cfg.start_learning_rate,
                end_learning_rate=cfg.end_learning_rate,
                learning_frame=cfg.learning_frame)


def seeded_batch(seed: int, mid_episode_end: bool = True) -> dict:
    r = np.random.RandomState(seed)
    done = np.zeros((N, T), bool)
    done[:, -1] = True
    if mid_episode_end:
        done[0, 11] = True  # inside the second chunk of 8
        done[2, 7] = True  # the last step of the first chunk
    return {"tokens": r.randint(0, V, (N, T)).astype(np.int32),
            "action": r.randint(0, V, (N, T)).astype(np.int32),
            "behaviour_logp": (np.log(1.0 / V) + 0.3 * r.normal(size=(N, T))
                               ).astype(np.float32),
            "reward": r.choice([0.0, 0.0, 1.0, 2.0], size=(N, T)).astype(np.float32),
            "done": done}


def perturbed(params, seed=1):
    """Norm scales, biases and the skip off their initial 1 and 0."""
    key = jax.random.PRNGKey(seed)
    count = [0]

    def move(path, x):
        if path[-1].key not in ("norms", "final_norm", "b_value", "conv_b",
                                "gate_norm", "D"):
            return x
        count[0] += 1
        return x + 0.1 * jax.random.normal(jax.random.fold_in(key, count[0]),
                                           x.shape, x.dtype)

    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def agent():
    return HybridLMAgent(CFG)


@pytest.fixture(scope="module")
def params(agent):
    return perturbed(agent.init_state(jax.random.PRNGKey(0)).params)


@pytest.fixture(scope="module")
def reference_out(params):
    return ref.evaluate(params, seeded_batch(0), hyper(CFG))


def _program(agent, params, nb):
    model = agent.model

    def run(p):
        batch = LoopLMBatch(**{k: jnp.asarray(v) for k, v in nb.items()})
        (_, metrics), grads = jax.value_and_grad(agent._loss, has_aux=True)(p, batch)
        hs, _ = model.apply(p, batch.tokens, batch.done, method=model.trunk)
        logits, _, value = model.apply(p, hs, method=model.logits)
        logp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                   batch.action[None, ..., None], -1)[..., 0]
        return {"logits": logits, "value": value, "logp": logp, "grads": grads,
                "grad_norm": common.global_norm(grads), **metrics}

    with jax.default_matmul_precision("highest"):
        return jax.device_get(jax.jit(run)(params))


@pytest.fixture(scope="module")
def program_out(agent, params):
    return _program(agent, params, seeded_batch(0))


def _logits(agent, params, nb):
    """The forward alone: what a planted fault is read from (`_program`
    compiles the loss, its gradients and a second forward beside it)."""
    model = agent.model

    def run(p):
        hs, _ = model.apply(p, jnp.asarray(nb["tokens"]), jnp.asarray(nb["done"]),
                            method=model.trunk)
        return model.apply(p, hs, method=model.logits)[0]

    with jax.default_matmul_precision("highest"):
        return jax.device_get(jax.jit(run)(params))


# -- the chunked scan against the step-by-step recurrence ----------------------


def _scan_inputs(seed, steps, boundary):
    r = np.random.RandomState(seed)
    b, h, p, n = 2, 3, 4, 5
    done = np.zeros((b, steps), bool)
    if boundary is not None:
        done[0, boundary] = True
    seg = np.concatenate([np.zeros((b, 1), np.int32),
                          np.cumsum(done, 1)[:, :-1].astype(np.int32)], 1)
    start = np.concatenate([np.ones((b, 1), bool), done[:, :-1]], 1)
    return (jnp.asarray(r.normal(size=(b, steps, h, p)), jnp.float32),
            jnp.asarray(r.uniform(0.01, 0.5, size=(b, steps, h)), jnp.float32),
            -jnp.asarray(r.uniform(0.5, 4.0, size=(h,)), jnp.float32),
            jnp.asarray(r.normal(size=(b, steps, n)), jnp.float32),
            jnp.asarray(r.normal(size=(b, steps, n)), jnp.float32),
            jnp.asarray(seg), jnp.asarray(start))


@pytest.mark.parametrize("boundary", [None, 2, 7, 8],
                         ids=["one_episode", "inside_a_chunk", "chunk_end",
                              "chunk_start"])
@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_chunked_scan_equals_the_recurrence_forward_and_backward(chunks, boundary):
    steps = 8 * chunks
    if boundary is not None and boundary >= steps - 1:
        pytest.skip("the boundary lies past this episode")
    x, dt, a, bmat, cmat, seg, start = _scan_inputs(chunks, steps, boundary)
    weight = jnp.asarray(np.random.RandomState(9).normal(size=x.shape), jnp.float32)

    def chunked(x, dt, a, bmat, cmat):
        y, state = ssd.ssd_chunked(x, dt, a, bmat, cmat, seg, 8, jnp.float32)
        return jnp.sum(y * weight) + jnp.sum(state), (y, state)

    def stepwise(x, dt, a, bmat, cmat):
        y, state = ref.recurrence(x, dt, a, bmat, cmat, start)
        return jnp.sum(y * weight) + jnp.sum(state), (y, state)

    with jax.default_matmul_precision("highest"):
        (_, (y, state)), grads = jax.value_and_grad(
            chunked, argnums=(0, 1, 2, 3, 4), has_aux=True)(x, dt, a, bmat, cmat)
        (_, (y_ref, state_ref)), grads_ref = jax.value_and_grad(
            stepwise, argnums=(0, 1, 2, 3, 4), has_aux=True)(x, dt, a, bmat, cmat)
    np.testing.assert_allclose(y, y_ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(state, state_ref, atol=2e-5, rtol=2e-5)
    for g, g_ref in zip(grads, grads_ref):
        np.testing.assert_allclose(g, g_ref, atol=1e-4, rtol=1e-4)


def test_scan_refuses_steps_that_are_not_whole_chunks():
    x, dt, a, bmat, cmat, seg, _ = _scan_inputs(0, 12, None)
    with pytest.raises(ValueError, match="whole chunks"):
        ssd.ssd_chunked(x, dt, a, bmat, cmat, seg, 8)
    assert ssd.chunk_length(6, 256) == 6  # a short episode is one chunk


def test_dropping_the_carried_state_is_seen():
    """A chunk boundary that drops H_{c-1} differs wherever the episode is
    longer than a chunk, and nowhere in the first chunk."""
    x, dt, a, bmat, cmat, seg, _ = _scan_inputs(3, 16, None)
    whole, _ = ssd.ssd_chunked(x, dt, a, bmat, cmat, seg, 8, jnp.float32)
    halves = jnp.concatenate([ssd.ssd_chunked(
        x[:, s], dt[:, s], a, bmat[:, s], cmat[:, s], seg[:, s], 8,
        jnp.float32)[0] for s in (slice(0, 8), slice(8, 16))], axis=1)
    np.testing.assert_allclose(whole[:, :8], halves[:, :8], atol=1e-6)
    assert np.abs(whole[:, 8:] - halves[:, 8:]).max() > 1e-2


def _grouped_inputs(seed, steps, boundary, groups=4):
    """`_scan_inputs` with 8 heads in `groups` groups of B and C."""
    r = np.random.RandomState(seed)
    seg, start = _scan_inputs(seed, steps, boundary)[-2:]
    b, n = seg.shape[0], 5
    normal = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)
    return (normal(b, steps, 8, 4),
            jnp.asarray(r.uniform(0.01, 0.5, size=(b, steps, 8)), jnp.float32),
            -jnp.asarray(r.uniform(0.5, 4.0, size=(8,)), jnp.float32),
            normal(b, steps, groups, n), normal(b, steps, groups, n), seg, start)


@pytest.mark.parametrize("boundary", [None, 10, 7],
                         ids=["one_episode", "inside_a_chunk", "chunk_end"])
def test_grouped_scan_equals_the_recurrence_forward_and_backward(boundary):
    """Eight heads in FOUR B/C groups (head h reads group h // 2) over
    three chunks of 8 against `reference/nemotron_h_moe.py`'s step-by-step
    recurrence, values, the last state and all five gradients (ISSUE 53)."""
    from distributed_reinforcement_learning_tpu.reference import nemotron_h_moe

    x, dt, a, bmat, cmat, seg, start = _grouped_inputs(5, 24, boundary)
    weight = jnp.asarray(np.random.RandomState(9).normal(size=x.shape), jnp.float32)

    def chunked(x, dt, a, bmat, cmat):
        y, state = ssd.ssd_chunked(x, dt, a, bmat, cmat, seg, 8, jnp.float32)
        return jnp.sum(y * weight) + jnp.sum(state), (y, state)

    def stepwise(x, dt, a, bmat, cmat):
        y, state = nemotron_h_moe.recurrence(x, dt, a, bmat, cmat, start)
        return jnp.sum(y * weight) + jnp.sum(state), (y, state)

    with jax.default_matmul_precision("highest"):
        (_, (y, state)), grads = jax.value_and_grad(
            chunked, argnums=(0, 1, 2, 3, 4), has_aux=True)(x, dt, a, bmat, cmat)
        (_, (y_ref, state_ref)), grads_ref = jax.value_and_grad(
            stepwise, argnums=(0, 1, 2, 3, 4), has_aux=True)(x, dt, a, bmat, cmat)
    np.testing.assert_allclose(y, y_ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(state, state_ref, atol=2e-5, rtol=2e-5)
    for g, g_ref in zip(grads, grads_ref):
        np.testing.assert_allclose(g, g_ref, atol=1e-4, rtol=1e-4)


def test_one_group_is_the_ungrouped_call_and_a_wrong_grouping_is_seen():
    """`bmat, cmat [B, T, N]` (granite's call) traces to a program with no
    group axis in it, and returns what `[B, T, 1, N]` returns; heads
    reading group h % G, or group 0 for every head, are another result."""
    x, dt, a, bmat, cmat, seg, _ = _scan_inputs(3, 16, 6)
    run = lambda b, c, x=x, dt=dt, a=a: ssd.ssd_chunked(
        x, dt, a, b, c, seg, 8, jnp.float32)
    text = str(jax.make_jaxpr(lambda: run(bmat, cmat))())
    assert "bgij" not in text and f"{x.shape[0]},1,{bmat.shape[-1]}" not in text
    for got, want in zip(run(bmat[:, :, None], cmat[:, :, None]), run(bmat, cmat)):
        np.testing.assert_allclose(got, want, atol=1e-6)
    x, dt, a, bmat, cmat, seg, _ = _grouped_inputs(5, 16, 6)
    right, _ = run(bmat, cmat, x, dt, a)
    modulo = jnp.tile(bmat, (1, 1, 2, 1))  # eight groups of one head: h -> h % 4
    for wrong in (modulo, jnp.repeat(bmat[:, :, :1], 8, 2)):
        got, _ = run(wrong, jnp.repeat(cmat, 2, 2), x, dt, a)
        assert float(jnp.abs(got - right).max()) > 1e-2
    np.testing.assert_allclose(
        run(jnp.repeat(bmat, 2, 2), jnp.repeat(cmat, 2, 2), x, dt, a)[0], right,
        atol=1e-5)  # eight groups of one head, each a copy of its group's: the same
    with pytest.raises(ValueError, match="whole groups"):
        run(bmat[:, :, :3], cmat[:, :, :3], x, dt, a)


# -- the whole model and the loss against the reference -------------------------


@pytest.mark.parametrize("what", ["logits", "value", "logp"])
def test_forward_matches_reference(program_out, reference_out, what):
    np.testing.assert_allclose(program_out[what], reference_out[what],
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("term", ["total_loss", "pi_loss", "baseline_loss",
                                  "entropy", "dt_mean", "decay_min"])
def test_loss_terms_and_counters_match_reference(program_out, reference_out, term):
    np.testing.assert_allclose(program_out[term], reference_out[term], rtol=2e-5)


def test_one_pass_without_a_gate_is_the_plain_loss(program_out):
    """`LoopLMAgent._loss` at R = 1: the exit distribution is 1, its
    entropy 0, and no exit counter is logged."""
    assert float(program_out["exit_entropy"]) == 0.0
    assert not [k for k in program_out if k.startswith("exit_cdf")]
    np.testing.assert_array_equal(
        looped_lm.exit_distribution(jnp.full((1, 2, 3), 0.3)), np.ones((1, 2, 3)))


def test_gradients_match_reference(agent, params, program_out):
    _, theirs = ref.loss_and_grads(params, seeded_batch(0), hyper(CFG))
    theirs = jax.tree.leaves(ref.stacked(theirs))
    ours = jax.tree.leaves(program_out["grads"])
    assert len(ours) == len(theirs) == len(jax.tree.leaves(params))
    for a, b in zip(ours, theirs):
        scale = max(1e-6, float(np.abs(b).max()))
        assert float(np.abs(np.asarray(a) - np.asarray(b)).max()) / scale < 2e-4


def test_gradient_norm_and_update_norm_match_reference(agent, params,
                                                       program_out, reference_out):
    np.testing.assert_allclose(program_out["grad_norm"],
                               reference_out["grad_norm"], rtol=2e-5)
    state = common.TrainState.create(params, agent.tx)
    batch = LoopLMBatch(**{k: jnp.asarray(v) for k, v in seeded_batch(0).items()})
    with jax.default_matmul_precision("highest"):
        new, _ = jax.jit(agent._learn)(state, batch)
    moved = common.global_norm(jax.tree.map(lambda a, b: a - b, new.params, params))
    np.testing.assert_allclose(moved, reference_out["update_norm"], rtol=1e-3)


def test_rekey_and_stacked_are_inverses(params):
    back = ref.stacked(ref.rekey(params, ORDER))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="the configuration says"):
        ref.rekey(params, ("mamba",) * 4)


MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_planted_wrong_is_seen(params, reference_out, name):
    """Set to 1, each of the four published scalars moves the logits by
    orders more than the 2e-5 the right program is held to."""
    wrong = HybridLMAgent(dataclasses.replace(CFG, **{name: 1.0}))
    out = _logits(wrong, params, seeded_batch(0))
    scale = np.abs(reference_out["logits"]).max()
    assert np.abs(out - reference_out["logits"]).max() / scale > 1e-2


class _Wrong(hybrid_lm.HybridLM):
    """Wrong programs, by name: each overrides one small method."""

    fault: str = ""

    def _rate(self, lp):
        rate = super()._rate(lp)
        return -rate if self.fault == "decay_sign" else rate

    def _step_size(self, dt, lp):
        if self.fault == "dt_without_bias":
            return jax.nn.softplus(dt)
        return super()._step_size(dt, lp)

    def _gated_out(self, y, x, z, lp):
        if self.fault != "gate_after_norm":
            return super()._gated_out(y, x, z, lp)
        g = (y + lp["D"][:, None] * x).reshape(*z.shape)
        return self._mm(looped_lm.rms_norm(g, lp["gate_norm"], self.rms_eps)
                        * jax.nn.silu(z), lp["out_proj"])

    def _grouped(self, q):
        if self.fault != "heads_not_grouped":
            return super()._grouped(q)
        groups = self.num_heads // self.num_kv_heads  # head i reads i % KV
        return jnp.swapaxes(q.reshape(*q.shape[:-2], groups, self.num_kv_heads,
                                      self.head_dim), -3, -2)


def _wrong_agent(fault, **replace):
    agent = HybridLMAgent(dataclasses.replace(CFG, **replace))
    fields = {f.name: getattr(agent.model, f.name)
              for f in dataclasses.fields(agent.model)}
    agent.model = dataclasses.make_dataclass(
        "Wrong", [], bases=(_Wrong,), frozen=True, namespace={"fault": fault})(
            **fields)
    return agent


@pytest.mark.parametrize("fault", ["decay_sign", "dt_without_bias",
                                   "gate_after_norm"])
def test_a_wrong_state_space_layer_is_seen(params, reference_out, fault):
    out = _logits(_wrong_agent(fault), params, seeded_batch(0))
    scale = np.abs(reference_out["logits"]).max()
    assert np.abs(out - reference_out["logits"]).max() / scale > 1e-3


# -- acting as decode through the three kinds of state ---------------------------


def _decode_all(agent, params, tokens, spans=None, model=None):
    """Every step's logits `[N, T, V]` by decode, and the final state."""
    model = model or agent.model
    act = agent.for_acting(params)
    state = agent.init_cache(tokens.shape[0])
    spans = spans or (tokens.shape[1],)
    out = []
    step = jax.jit(lambda s, tok, t, span: model.apply(
        act, tok, t, s, span, method=model.decode), static_argnums=(3,))
    with jax.default_matmul_precision("highest"):
        for lo, span in zip((0, *spans), spans):
            for t in range(lo, span):
                h, state = step(state, tokens[:, t], jnp.int32(t), span)
                out.append(model.apply(act, h, method=model.logits)[0])
    return jnp.stack(out, axis=1), state


@pytest.fixture(scope="module")
def whole_episode_forward(params):
    nb = seeded_batch(2, mid_episode_end=False)
    return nb, ref.forward(params, nb["tokens"], nb["done"], hyper(CFG))


@pytest.mark.parametrize("segments", [1, 2, 4])
def test_decode_through_state_equals_full_forward_at_every_step(
        agent, params, whole_episode_forward, segments):
    """The recurrent state, the convolution window and the key/value
    cache together reproduce the full forward at every t, across every
    boundary of `decode_spans`."""
    nb, want = whole_episode_forward
    spans = looped_lm.decode_spans(T, segments)
    got, state = _decode_all(agent, params, jnp.asarray(nb["tokens"]), spans)
    np.testing.assert_allclose(got, want["logits"][0], atol=3e-5, rtol=3e-5)
    states = [s for s in state.ssm if s is not None]
    assert len(states) == len(want["states"]) == 3
    for ours, theirs in zip(states, want["states"]):
        np.testing.assert_allclose(ours, theirs, atol=1e-6, rtol=1e-4)


def test_state_is_of_three_kinds_side_by_side(agent):
    state = agent.init_cache(N)
    kinds = [(s is not None, c is not None, k is not None)
             for s, c, k in zip(state.ssm, state.conv, state.k)]
    assert kinds == [(True, True, False), (True, True, False),
                     (False, False, True), (True, True, False)]
    assert state.ssm[0].shape == (N, 4, 16, 8) and state.ssm[0].dtype == jnp.float32
    assert state.conv[0].shape == (N, 3, 64 + 2 * 8)
    assert state.k[2].shape == (N, T, 2, 8)
    facts = agent.state_facts(N)
    assert facts == {"ssm_state_bytes": 3 * N * 4 * 16 * 8 * 4,
                     "conv_state_bytes": 3 * N * 3 * 80 * 4,
                     "kv_cache_bytes": 2 * N * T * 2 * 8 * 4,
                     "layer_order": ORDER}


def test_a_window_shifted_by_one_is_seen(agent, params, whole_episode_forward):
    class Shifted(hybrid_lm.HybridLM):
        def _decode_mamba(self, h, lp, state, window):
            h, state, window = super()._decode_mamba(h, lp, state, window)
            return h, state, jnp.roll(window, 1, axis=1)

    nb, want = whole_episode_forward
    model = Shifted(**{f.name: getattr(agent.model, f.name)
                       for f in dataclasses.fields(agent.model)})
    got, _ = _decode_all(agent, params, jnp.asarray(nb["tokens"]), model=model)
    assert np.abs(got - want["logits"][0]).max() > 1e-3


def test_heads_not_grouped_is_seen_in_decode(params, whole_episode_forward):
    nb, want = whole_episode_forward
    wrong = _wrong_agent("heads_not_grouped")
    got, _ = _decode_all(wrong, params, jnp.asarray(nb["tokens"]))
    assert np.abs(got - want["logits"][0]).max() > 1e-3


def test_a_span_past_the_cache_is_refused(agent, params):
    with pytest.raises(ValueError, match="span"):
        _decode_all(agent, params, jnp.zeros((N, T), jnp.int32), spans=(T + 1,))


# -- the configuration ------------------------------------------------------------


def _section(**changes):
    from tests.test_tpu_compile import CONFIG

    with open(CONFIG) as f:
        return dict(json.load(f)["granite_hybrid"], **changes)


def test_load_config_reads_the_section(tmp_path):
    path = tmp_path / "c.json"
    path.write_text(json.dumps({"granite_hybrid": _section()}))
    cfg, rt = load_config(str(path), "granite_hybrid")
    assert isinstance(cfg, HybridLMConfig)
    assert cfg.layer_types == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads) == (2048, 64, 8)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_chunk_size) == (64, 64, 128, 256)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (12, 0.22, 0.015625, 8)
    assert cfg.vocab_size == cfg.num_actions == 12544 and cfg.trajectory == 1024
    assert rt.num_actors * rt.envs_per_actor == 32
    model = HybridLMAgent(cfg).model
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    count = sum(x.size for x in jax.tree.leaves(shapes))
    layer = 2048 * 8512 + 4352 * 5 + 3 * 64 + 4096 + 4096 * 2048 + 3 * 2048 * 8192
    attention = 2 * 2048 ** 2 + 2 * 2048 * 512 + 3 * 2048 * 8192
    assert count == (9 * layer + attention + 10 * 2 * 2048  # the layers' norms
                     + 12544 * 2048 + 2048 + 2048 + 1)


@pytest.mark.parametrize("changes, message", [
    ({"layer_types": ["mamba"] * 9 + ["moe"]}, "unknown layer type"),
    ({"layer_types": ["mamba"] * 9}, "layer_types"),
    ({"mamba_n_groups": 8}, "mamba_n_groups"),
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"num_local_experts": 4}, "num_local_experts"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
])
def test_load_config_refuses_what_is_not_computed(tmp_path, changes, message):
    path = tmp_path / "c.json"
    path.write_text(json.dumps({"s": _section(**changes)}))
    with pytest.raises(ValueError, match=message):
        load_config(str(path), "s")


def test_load_config_refuses_a_missing_width(tmp_path):
    section = _section()
    del section["mamba_d_state"]
    path = tmp_path / "c.json"
    path.write_text(json.dumps({"s": section}))
    with pytest.raises(KeyError):
        load_config(str(path), "s")


# -- the fused loop -----------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _chunk(updates: int = 2):
    agent = HybridLMAgent(CFG)
    anakin = AnakinTokens(agent, N, TokenRecall(vocab=V, episode_len=T, distance=3))
    state = anakin.init(jax.random.PRNGKey(5))
    before = jax.device_get(state.train.params)
    with jax.default_matmul_precision("highest"):
        state, metrics = anakin.train_chunk(state, updates)
    return anakin, before, jax.device_get(state), jax.device_get(metrics)


def test_fused_chunk_losses_are_finite_and_every_leaf_moves():
    anakin, before, state, metrics = _chunk()
    assert np.all(np.isfinite(metrics["total_loss"]))
    assert np.all(metrics["grad_norm"] > 0)
    assert metrics["rollout"]["tokens"].shape == (2, N, T)
    for key in ("dt_mean", "decay_min", "state_norm_mean", "rho_clipped_share",
                "behaviour_logp_mean"):
        assert metrics[key].shape == (2,), key
    assert np.all((0 < metrics["decay_min"]) & (metrics["decay_min"] < 1))
    assert metrics["state_sample"].shape[0] == 2
    moved = [bool(np.any(a != b)) for a, b in zip(
        jax.tree.leaves(before), jax.tree.leaves(state.train.params))]
    assert all(moved), moved
    assert anakin.static_facts["layer_order"] == ORDER
    assert anakin.static_facts["loop_passes"] == 1


@pytest.mark.parametrize("update", [0, 1])
def test_collect_logp_is_the_reference_forward_from_zero_state(update):
    """Every update's log mu(a_t), written through the three kinds of
    state, is the reference's full forward from ZERO state under the
    parameters that update collected with: the state is zeroed between
    updates (update 1 would otherwise start from update 0's)."""
    anakin, before, _, metrics = _chunk()
    rollout = {k: v[update] for k, v in metrics["rollout"].items()}
    params = before
    hp = hyper(CFG)
    if update == 1:  # the reference's own step from update 0's rollout
        first = {k: v[0] for k, v in metrics["rollout"].items()}
        _, grads = ref.loss_and_grads(params, first, hp)
        params, _ = ref.rmsprop_step(ref.rekey(params), None, grads, hp, 0)
    want = ref.taken_logp(params, rollout["tokens"], rollout["action"],
                          rollout["done"], hp)
    np.testing.assert_allclose(rollout["behaviour_logp"], want, atol=5e-5)


def test_a_state_not_reset_between_updates_is_seen():
    """The same loop starting an update from a state that is not zero (as
    one carried over from the update before would be): its log mu is no
    longer the forward from zero."""
    class Stale(HybridLMAgent):
        def init_cache(self, num_rows):
            return jax.tree.map(lambda x: x + jnp.asarray(0.05, x.dtype),
                                super().init_cache(num_rows))

    anakin = AnakinTokens(Stale(CFG), N,
                          TokenRecall(vocab=V, episode_len=T, distance=3))
    state = anakin.init(jax.random.PRNGKey(5))
    params = jax.device_get(state.train.params)
    with jax.default_matmul_precision("highest"):
        _, metrics = anakin.train_chunk(state, 1)
    rollout = {k: np.asarray(v[0]) for k, v in metrics["rollout"].items()}
    want = ref.taken_logp(params, rollout["tokens"], rollout["action"],
                          rollout["done"], hyper(CFG))
    assert np.abs(rollout["behaviour_logp"] - want).max() > 1e-3


def test_final_state_logged_is_the_reference_s():
    anakin, before, _, metrics = _chunk()
    rollout = {k: v[0] for k, v in metrics["rollout"].items()}
    out = ref.forward(before, rollout["tokens"], rollout["done"], hyper(CFG))
    states = [np.asarray(s) for s in out["states"]]
    every = max(1, sum(s.size for s in states) // 16384)
    want = np.concatenate([s.reshape(-1)[::every] for s in states])
    np.testing.assert_allclose(metrics["state_sample"][0], want, atol=1e-6,
                               rtol=1e-4)
    norms = np.concatenate([np.sqrt((s ** 2).sum((-2, -1))).reshape(-1)
                            for s in states])
    np.testing.assert_allclose(metrics["state_norm_mean"][0], norms.mean(),
                               rtol=1e-4)

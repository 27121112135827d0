"""The `ouro_looplm` configuration at a small size on the CPU: the
looped decoder (`models/looped_lm.py`), its V-trace loss
(`agents/looplm.py`), the token env and the fused loop
(`runtime/anakin_tokens.py`) against the plain reference
(`reference/ouro_looplm.py`), which imports nothing of the program.

Sizes (ISSUE 30): hidden 64, 4 heads of 16, SwiGLU 176, V 512, L 2,
R 4, T 16, N 4; float32 so that the agreement is the arithmetic's.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.agents.looplm import (
    LoopLMAgent, LoopLMBatch, LoopLMConfig)
from distributed_reinforcement_learning_tpu.envs.token_recall_jax import TokenRecall
from distributed_reinforcement_learning_tpu.models import looped_lm
from distributed_reinforcement_learning_tpu.ops import vtrace
from distributed_reinforcement_learning_tpu.reference import ouro_looplm as ref
from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import AnakinTokens

V, T, N = 512, 16, 4
CFG = LoopLMConfig(
    vocab_size=V, hidden_size=64, num_attention_heads=4, head_dim=16,
    intermediate_size=176, num_hidden_layers=2, total_ut_steps=4,
    trajectory=T, dtype=jnp.float32, head_block=32, start_learning_rate=1e-3,
    init_std=0.2)  # wide enough that the passes differ visibly


def hyper(cfg: LoopLMConfig) -> dict:
    return dict(num_heads=cfg.num_attention_heads, head_dim=cfg.head_dim,
                rope_theta=cfg.rope_theta, rms_eps=cfg.rms_norm_eps,
                loop_passes=cfg.total_ut_steps, discount=cfg.discount_factor,
                baseline_loss_coef=cfg.baseline_loss_coef,
                entropy_coef=cfg.entropy_coef,
                exit_entropy_coef=cfg.exit_entropy_coef,
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
        done[0, 5] = True
        done[2, 9] = True
    return {"tokens": r.randint(0, V, (N, T)).astype(np.int32),
            "action": r.randint(0, V, (N, T)).astype(np.int32),
            "behaviour_logp": (np.log(1.0 / V) + 0.3 * r.normal(size=(N, T))
                               ).astype(np.float32),
            "reward": r.choice([0.0, 0.0, 1.0, 2.0], size=(N, T)).astype(np.float32),
            "done": done}


@pytest.fixture(scope="module")
def agent():
    return LoopLMAgent(CFG)


@pytest.fixture(scope="module")
def params(agent):
    p = agent.init_state(jax.random.PRNGKey(3)).params
    # norm scales and head biases that are not their initial 1 and 0
    noisy = lambda x, k: x + 0.1 * jax.random.normal(jax.random.PRNGKey(k), x.shape)
    q = dict(p["params"])
    for k, name in enumerate(("norms", "final_norm", "b_exit", "b_value")):
        q[name] = noisy(q[name], k)
    return {"params": q}


@pytest.fixture(scope="module")
def reference_out(params):
    return ref.evaluate(ref.rekey(params), seeded_batch(0), hyper(CFG))


@pytest.fixture(scope="module")
def program_out(agent, params):
    nb = seeded_batch(0)
    batch = LoopLMBatch(**nb)
    model = agent.model

    def run(p):
        (loss, metrics), grads = jax.value_and_grad(agent._loss, has_aux=True)(p, batch)
        hs = model.apply(p, batch.tokens, batch.done, method=model.trunk)
        logits, gate, value = model.apply(p, hs, method=model.logits)
        stats = agent._stats(p, batch)
        return {"logits": logits, "gate": gate, "value": value,
                "logp": stats["logp"], "grads": grads, **metrics}

    return jax.device_get(jax.jit(run)(params))


# -- forward and loss against the plain reference -------------------------


@pytest.mark.parametrize("what", ["logits", "gate", "value", "logp"])
@pytest.mark.parametrize("loop_pass", range(4))
def test_forward_of_every_pass_matches_reference(program_out, reference_out,
                                                 what, loop_pass):
    got, want = program_out[what][loop_pass], reference_out[what][loop_pass]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("term", ["total_loss", "pi_loss", "baseline_loss",
                                  "entropy"])
def test_loss_terms_match_reference(program_out, reference_out, term):
    scale = max(1.0, float(reference_out["pi_scale"]))
    assert abs(float(program_out[term]) - float(reference_out[term])) <= 1e-4 * scale


def test_exit_entropy_matches_reference(program_out, reference_out):
    # the program logs the mean over positions, the reference the sum
    positions = N * (T - 2)
    np.testing.assert_allclose(program_out["exit_entropy"] * positions,
                               reference_out["exit_entropy"], rtol=1e-4)


def test_gradients_match_reference(agent, params, program_out):
    nb = seeded_batch(0)
    want = jax.grad(lambda p: ref.loss(p, {k: jnp.asarray(v) for k, v in nb.items()}, hyper(CFG))[0])(ref.rekey(params))
    got = ref.rekey({"params": program_out["grads"]["params"]})
    flat_w, _ = jax.tree.flatten(want)
    flat_g, _ = jax.tree.flatten(got)
    biggest = max(float(jnp.max(jnp.abs(w))) for w in flat_w)
    for g, w in zip(flat_g, flat_w):
        np.testing.assert_allclose(g, w, atol=2e-4 * biggest, rtol=2e-3)


def test_gradient_norm_and_update_norm_match_reference(agent, params,
                                                       reference_out):
    batch = LoopLMBatch(**seeded_batch(0))
    state = agent.init_state(jax.random.PRNGKey(3)).replace(params=params)
    new, metrics = jax.jit(agent._learn)(state, batch)
    np.testing.assert_allclose(metrics["grad_norm"], reference_out["grad_norm"],
                               rtol=1e-3)
    moved = jnp.sqrt(sum(jnp.sum(jnp.square(a - b)) for a, b in zip(
        jax.tree.leaves(new.params), jax.tree.leaves(params))))
    np.testing.assert_allclose(moved, reference_out["update_norm"], rtol=1e-3)


# -- the loop over passes ---------------------------------------------------


def _unrolled_trunk(agent, copies, batch):
    """`LoopedLM.trunk` through an UNROLLED R x L stack: pass r reads its
    own copy of the stack's parameters."""
    model, cfg = agent.model, agent.cfg
    segs = looped_lm.episode_segments(batch.done)
    pos = looped_lm.episode_positions(batch.done)
    h = copies[0]["params"]["embed"][batch.tokens].astype(cfg.dtype)
    hs = []
    for r in range(cfg.total_ut_steps):
        pr = copies[r]["params"]
        for i in range(cfg.num_hidden_layers):
            lp = {k: pr[k][i] for k in ("wqkv", "wo", "wgu", "wd", "norms")}
            h = model.apply(copies[0], h, lp, segs, pos, method=model._layer)
        hs.append(h)
    return jnp.stack(hs)


def test_looped_gradient_is_the_sum_over_tied_unrolled_copies(agent, params):
    """d/dW of the looped stack == the sum over the R uses of an
    unrolled R x L stack whose copies hold the same values."""
    batch = LoopLMBatch(**seeded_batch(1))
    model = agent.model
    score = lambda hs: jnp.sum(jnp.sin(hs.astype(jnp.float32)))
    looped = jax.grad(lambda p: score(model.apply(
        p, batch.tokens, batch.done, method=model.trunk)))(params)
    copies = [params] * CFG.total_ut_steps
    unrolled = jax.grad(lambda cs: score(_unrolled_trunk(agent, cs, batch)))(copies)
    for key in ("wqkv", "wo", "wgu", "wd", "norms"):
        summed = sum(c["params"][key] for c in unrolled)
        np.testing.assert_allclose(  # float32 sums in another order
            looped["params"][key], summed, rtol=2e-3,
            atol=2e-5 * float(jnp.max(jnp.abs(summed))))


def test_one_pass_is_a_plain_decoder(params):
    """R = 1: the looped model is an L-layer decoder; its one pass equals
    the first pass of the R = 4 model (same parameters)."""
    one = LoopLMAgent(dataclasses.replace(CFG, total_ut_steps=1))
    nb = seeded_batch(2)
    got = ref.forward(ref.rekey(params), nb["tokens"], nb["done"],
                      {**hyper(CFG), "loop_passes": 1})
    hs = one.model.apply(params, nb["tokens"], nb["done"], method=one.model.trunk)
    assert hs.shape[0] == 1
    logits, _, _ = one.model.apply(params, hs, method=one.model.logits)
    np.testing.assert_allclose(logits[0], got["logits"][0], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("seed", range(3))
def test_exit_distribution_sums_to_one(seed):
    gate = jax.random.uniform(jax.random.PRNGKey(seed), (4, 5, 7))
    p = looped_lm.exit_distribution(gate)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    np.testing.assert_allclose(p, ref.exit_distribution(gate), atol=1e-6)
    assert (p >= 0).all()


def test_threshold_one_runs_every_pass():
    """Threshold 1, the published value, runs every pass (the chunk's
    static `loop_passes`); an early exit is not built, and a threshold
    under 1 is refused, not ignored."""
    assert AnakinTokens(LoopLMAgent(CFG), N, TokenRecall(V, T, 8)) \
        .static_facts["loop_passes"] == CFG.total_ut_steps
    with pytest.raises(ValueError, match="early_exit_threshold"):
        LoopLMAgent(dataclasses.replace(CFG, early_exit_threshold=0.5))


# -- acting as decode ---------------------------------------------------------


def _decode_all(agent, params, tokens, model=None, spans=None):
    """Token by token through the cache -> last-pass logits `[N, T, V]`;
    step t reads the first of `spans` that covers it (default: the
    full-length path, `decode` without `span`)."""
    model = model or agent.model
    cache = agent.init_cache(tokens.shape[0])
    out = []
    step = jax.jit(lambda tok, t, c, span: model.apply(
        params, tok, t, c, span, method=model.decode), static_argnums=3)
    for t in range(tokens.shape[1]):
        span = spans and next(s for s in spans if s > t)
        h, cache = step(tokens[:, t], jnp.int32(t), cache, span)
        out.append(model.apply(params, h, method=model.logits)[0])
    return jnp.stack(out, axis=1)


@pytest.fixture(scope="module")
def whole_episode_forward(agent, params):
    nb = seeded_batch(4, mid_episode_end=False)
    hs = agent.model.apply(params, nb["tokens"], nb["done"],
                           method=agent.model.trunk)
    return nb, agent.model.apply(params, hs, method=agent.model.logits)[0][-1]


def test_decode_through_per_pass_cache_equals_full_forward(
        agent, params, whole_episode_forward):
    nb, full = whole_episode_forward
    got = _decode_all(agent, params, jnp.asarray(nb["tokens"]))
    np.testing.assert_allclose(got, full, rtol=2e-4, atol=2e-4)  # every position
    want = ref.forward(ref.rekey(params), nb["tokens"], nb["done"], hyper(CFG))
    np.testing.assert_allclose(got, want["logits"][-1], rtol=2e-4, atol=2e-4)


def _model_as(cls, model):
    return cls(**{f.name: getattr(model, f.name)
                  for f in dataclasses.fields(model)
                  if f.name not in ("parent", "name")})


class ShortRead(looped_lm.LoopedLM):
    """The planted fault: a prefix one position short, so that the last
    step of every segment does not see the key it has just written."""

    def _decode_layer(self, carry, xs, slot, t, span=None):
        span = carry[1].k.shape[3] if span is None else span
        return super()._decode_layer(carry, xs, slot, t, span - 1)


# 16 steps: every segment whole; 14: the last of four is cut to 2 steps
@pytest.mark.parametrize("length", [16, 14])
@pytest.mark.parametrize("segments", [1, 2, 4])
def test_decode_through_spans_equals_full_length_decode(
        params, whole_episode_forward, segments, length):
    nb, full = whole_episode_forward
    agent = LoopLMAgent(dataclasses.replace(CFG, trajectory=length))
    tokens = jnp.asarray(nb["tokens"][:, :length])
    spans = looped_lm.decode_spans(length, segments)
    assert len(spans) == segments and spans[-1] == length
    whole = _decode_all(agent, params, tokens)
    got = _decode_all(agent, params, tokens, spans=spans)
    # What a span drops are exact zeros of the float32 sums; what is left
    # is the ORDER of the sums over the prefix, which the CPU changes with
    # the length: 0 at spans of 8 and 16, at most 1.4e-5 on logits up to
    # 5.9 at spans of 4 and under (a last bit, through 8 layer passes).
    np.testing.assert_allclose(got, whole, rtol=0, atol=5e-5)
    # causal: the first `length` positions of the 16-token forward
    np.testing.assert_allclose(got, full[:, :length], rtol=2e-4, atol=2e-4)
    want = ref.forward(ref.rekey(params), nb["tokens"], nb["done"], hyper(CFG))
    np.testing.assert_allclose(got, want["logits"][-1][:, :length],
                               rtol=2e-4, atol=2e-4)
    short = _decode_all(agent, params, tokens, spans=spans,
                        model=_model_as(ShortRead, agent.model))
    ends = [s - 1 for s in spans]  # each segment's last step
    assert float(jnp.min(jnp.max(jnp.abs(short - whole)[:, ends],
                                 axis=(0, 2)))) > 1e-2


@pytest.mark.parametrize("length, spans, share", [
    (16, (16,), 1.0), (31, (31,), 1.0), (32, (16, 32), 0.75),
    (100, (17, 34, 51, 68, 85, 100), 0.5835),
    (128, (16, 32, 48, 64, 80, 96, 112, 128), 0.5625),
    (2048, tuple(range(256, 2049, 256)), 0.5625)])
def test_spans_follow_from_the_episode_length(length, spans, share):
    """S = 8 from 128 steps on, segments of at least 16 below, one span
    under 32; `static_facts` says what the chunk was compiled with."""
    assert looped_lm.decode_spans(length) == spans
    facts = AnakinTokens(
        LoopLMAgent(dataclasses.replace(CFG, trajectory=length)), N,
        TokenRecall(V, length, 8)).static_facts
    assert facts["decode_spans"] == spans
    assert facts["cache_read_share"] == pytest.approx(share, abs=1e-9)


def test_cache_shared_between_passes_is_wrong(agent, params,
                                              whole_episode_forward):
    class Shared(looped_lm.LoopedLM):
        def _decode_layer(self, carry, xs, slot, t):
            return super()._decode_layer(carry, xs, slot * 0, t)

    nb, full = whole_episode_forward
    shared = _model_as(Shared, agent.model)
    got = _decode_all(agent, params, jnp.asarray(nb["tokens"]), model=shared)
    # position 0 attends only to itself; from position 1 on the passes
    # read each other's keys
    np.testing.assert_allclose(got[:, 0], full[:, 0], rtol=2e-4, atol=2e-4)
    assert float(jnp.max(jnp.abs(got[:, 1:] - full[:, 1:]))) > 1e-2


def test_segments_mask_across_an_episode_boundary(agent, params):
    """Tokens before an episode's end do not reach the steps after it."""
    nb = seeded_batch(5)
    model = agent.model
    fwd = jax.jit(lambda tok: model.apply(
        params, model.apply(params, tok, nb["done"], method=model.trunk),
        method=model.logits)[0])
    other = nb["tokens"].copy()
    other[0, :6] = (other[0, :6] + 7) % V  # row 0's first episode ends at t = 5
    a, b = fwd(nb["tokens"]), fwd(other)
    np.testing.assert_array_equal(a[:, 0, 6:], b[:, 0, 6:])
    assert float(jnp.max(jnp.abs(a[:, 0, :6] - b[:, 0, :6]))) > 1e-3
    pos = looped_lm.episode_positions(jnp.asarray(nb["done"]))
    _, want_pos = ref.episode_positions(jnp.asarray(nb["done"]))
    np.testing.assert_array_equal(pos, want_pos)


# -- V-trace on taken-action log-probabilities -----------------------------------


@pytest.mark.parametrize("seed", range(2))
def test_importance_weights_on_logp_equal_from_softmax(seed):
    a, b, t = 6, 5, 9
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    mu = jax.nn.softmax(jax.random.normal(k[0], (b, t, a)))
    pi = jax.nn.softmax(jax.random.normal(k[1], (b, t, a)))
    act = jax.random.randint(k[2], (b, t), 0, a)
    disc = 0.99 * (jax.random.uniform(k[3], (b, t)) > 0.1)
    rew, val = jax.random.normal(k[4], (b, t)), jax.random.normal(k[5], (b, t + 1))
    want = vtrace.from_softmax(mu, pi, act, disc, rew, val[:, :-1], val[:, 1:])
    logp = lambda p: jnp.log(jnp.take_along_axis(p, act[..., None], -1)[..., 0])
    got = vtrace.from_importance_weights(
        (logp(pi) - logp(mu)).T, disc.T, rew.T, val[:, :-1].T, val[:, -1])
    np.testing.assert_allclose(got.vs.T, want.vs, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got.clipped_rhos.T, want.clipped_rhos, rtol=1e-6)
    ref_vs, ref_rho = ref.vtrace(logp(pi) - logp(mu), disc, rew, val[:, :-1],
                                 val[:, -1])
    np.testing.assert_allclose(ref_vs, want.vs, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(ref_rho, want.clipped_rhos, rtol=1e-6)


# -- the env --------------------------------------------------------------------


def _play(env, seed, answers):
    state, obs = env.reset(jax.random.PRNGKey(seed), 3)
    shown, rewards, dones, returns = [obs], [], [], []
    for t in range(answers.shape[0]):
        state, obs, r, d, ret = env.step(state, answers[t], None)
        shown.append(obs)
        rewards.append(r)
        dones.append(d)
        returns.append(ret)
    return (np.stack(shown), np.stack(rewards), np.stack(dones),
            np.stack(returns))


def test_env_reward_rule_and_reset():
    env = TokenRecall(vocab=V, episode_len=T, distance=8)
    # first play it blind to learn what it shows (its draws ignore actions)
    blind = np.zeros((2 * T, 3), np.int32)
    shown, _, dones, _ = _play(env, 0, blind)
    assert shown.min() >= 0 and shown.max() < V
    assert dones[T - 1].all() and dones[2 * T - 1].all() and dones.sum() == 6
    # answer x_{t-8} in env 0 always, in env 1 never, in env 2 only at t < 8
    steps = np.arange(2 * T)
    recall = np.where((steps % T >= 8)[:, None], np.roll(shown[:-1], 8, axis=0), 0)
    answers = blind.copy()
    answers[:, 0] = recall[:, 0]
    answers[:, 1] = (recall[:, 1] + 1) % V
    _, rewards, _, returns = _play(env, 0, answers)
    assert (rewards[:, 0] == (steps % T >= 8)).all()
    assert rewards[:, 1].sum() == 0 and rewards[:, 2].sum() == 0
    assert returns[T - 1, 0] == T - 8 and returns[:T - 1].sum() == 0


@pytest.mark.parametrize("seed", [0, 2 ** 31 - 5])
def test_env_is_deterministic_in_its_seed(seed):
    env = TokenRecall(vocab=V, episode_len=T, distance=8)
    answers = np.zeros((T + 3, 3), np.int32)
    a, b = _play(env, seed, answers), _play(env, seed, answers)
    np.testing.assert_array_equal(a[0], b[0])
    other = _play(env, seed - 1, answers)
    assert (a[0] != other[0]).any()
    # a fresh key every episode: the second episode shows other tokens
    assert (a[0][:3] != a[0][T:T + 3]).any()


# -- one fused chunk ----------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _chunk_of(agent, segments):
    """Two updates of the fused loop from one seed, collected through
    `segments` spans (None: what `decode_spans` derives from T = 16, one)."""
    env = TokenRecall(vocab=V, episode_len=T, distance=8)
    anakin = AnakinTokens(agent, N, env)
    if segments is not None:
        anakin.decode_spans = looped_lm.decode_spans(T, segments)
    state = anakin.init(jax.random.PRNGKey(7))
    before = jax.tree.map(np.asarray, state.train.params)
    state, metrics = anakin.train_chunk(state, 2)
    return anakin, before, state, jax.device_get(metrics)


@pytest.fixture(scope="module", params=[None, 4], ids=["one_span", "four_spans"])
def chunk(agent, request):
    return _chunk_of(agent, request.param)


def test_fused_chunk_losses_are_finite_and_parameters_move(chunk):
    _, before, state, m = chunk
    assert np.isfinite(m["total_loss"]).all() and (m["grad_norm"] > 0).all()
    assert int(state.train.step) == 2
    moved = [float(np.max(np.abs(a - np.asarray(b)))) for a, b in zip(
        jax.tree.leaves(before), jax.tree.leaves(state.train.params))]
    assert max(moved) > 0
    assert m["rollout"]["tokens"].shape == (2, N, T)
    assert (m["episodes_done"] == N).all()
    for key in ("exit_cdf_pass1", "exit_cdf_pass2", "exit_cdf_pass3",
                "exit_entropy", "rho_clipped_share", "behaviour_logp_mean"):
        assert np.isfinite(m[key]).all(), key
    assert (np.diff([m[f"exit_cdf_pass{i}"][0] for i in (1, 2, 3)]) > 0).all()


def test_collect_logp_equals_learn_logp_with_unchanged_weights(chunk):
    """rho = 1: with the weights the first update collected under, the
    learner's log pi^(R)(a_t) on the rollout equals the log mu(a_t) that
    acting through the cache recorded; and both equal the reference's."""
    anakin, before, _, m = chunk
    roll = {k: v[0] for k, v in m["rollout"].items()}  # the first update's
    batch = LoopLMBatch(**roll)
    logp = anakin.agent._stats(before, batch)["logp"][-1]
    np.testing.assert_allclose(logp, roll["behaviour_logp"], rtol=1e-4, atol=1e-4)
    want = ref.taken_logp(ref.rekey(before), roll["tokens"], roll["action"],
                          roll["done"], hyper(CFG))
    np.testing.assert_allclose(roll["behaviour_logp"], want, rtol=1e-4, atol=1e-4)
    spans = anakin.decode_spans
    assert spans in ((16,), (4, 8, 12, 16))
    assert anakin.static_facts == {
        "loop_passes": 4, "compute_dtype": "float32",
        "kv_cache_bytes": 2 * 4 * 2 * N * T * 4 * 16 * 4,
        "decode_spans": spans,
        "cache_read_share": {1: 1.0, 4: 0.625}[len(spans)]}
    # four times a plain decoder's of the same depth
    plain = LoopLMAgent(dataclasses.replace(CFG, total_ut_steps=1))
    assert anakin.agent.kv_cache_bytes == 4 * plain.kv_cache_bytes


@pytest.mark.parametrize("segments", [2, 4])
def test_rollout_is_the_same_through_spans_and_through_one(agent, segments):
    """One seed, two updates: the segmented collection shows, answers and
    rewards what the one-scan collection does, update for update."""
    _, _, one_state, one = _chunk_of(agent, None)
    anakin, _, state, got = _chunk_of(agent, segments)
    assert len(anakin.decode_spans) == segments
    for field in ("tokens", "action", "reward", "done"):
        np.testing.assert_array_equal(got["rollout"][field],
                                      one["rollout"][field], err_msg=field)
    np.testing.assert_allclose(got["rollout"]["behaviour_logp"],
                               one["rollout"]["behaviour_logp"],
                               rtol=0, atol=5e-5)  # the order of the sums
    np.testing.assert_allclose(got["total_loss"], one["total_loss"], rtol=1e-5)
    np.testing.assert_array_equal(got["episode_return_sum"],
                                  one["episode_return_sum"])
    for a, b in zip(jax.tree.leaves(state.train.params),
                    jax.tree.leaves(one_state.train.params)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("what", ["segment_past_its_span", "span_past_the_cache",
                                  "spans_short_of_the_episode"])
def test_a_span_that_does_not_cover_its_steps_is_refused(agent, params, what):
    """At trace time, not as a wrong answer: `t` is traced, so the static
    bounds are held where they are known."""
    anakin = AnakinTokens(agent, N, TokenRecall(vocab=V, episode_len=T, distance=8))
    if what == "segment_past_its_span":
        with pytest.raises(ValueError, match="cannot see what it wrote"):
            anakin._collect(params, None, 0, 16, 8)
    elif what == "span_past_the_cache":
        with pytest.raises(ValueError, match="span 17 of a cache of 16"):
            jax.eval_shape(lambda: agent.model.apply(
                params, jnp.zeros(N, jnp.int32), jnp.int32(0),
                agent.init_cache(N), T + 1, method=agent.model.decode))
    else:
        anakin.decode_spans = (8, 12)
        state = jax.eval_shape(anakin.init, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="do not end at the episode"):
            jax.eval_shape(anakin._update, state, None)

"""One V-trace loss, two entries (ISSUE 29).

`ImpalaAgent._loss` / `_learn` take a batch-major `ImpalaBatch`
(`[B, T, ...]`, what a queue delivers and what the benchmark's reference
check calls); `_loss_time_major` / `_learn_time_major` take the same
fields as the fused loop's scan wrote them (`ImpalaRollout`,
`[T, B, ...]`). Both end in `_vtrace_loss`. Fed the transposed arrays
the two entries must agree to the order of summation: 1e-6 of the
largest magnitude, float32.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.agents.impala import (
    ImpalaAgent, ImpalaBatch, ImpalaConfig, ImpalaRollout)
from distributed_reinforcement_learning_tpu.models.impala_net import (
    apply_stored_state)
from distributed_reinforcement_learning_tpu.ops import vtrace
from distributed_reinforcement_learning_tpu.runtime.anakin import AnakinImpala
from distributed_reinforcement_learning_tpu.utils.synthetic import (
    synthetic_impala_batch)

RTOL = 1e-6  # of the largest magnitude compared
LOSS_TERMS = ("pi_loss", "baseline_loss", "entropy", "total_loss")

# Pixel frames as the benchmark's cell holds them (uint8 84x84x4, 18
# logits, LSTM 256) and CartPole's vector observations.
OBS = {
    "pixel": dict(obs_shape=(84, 84, 4), num_actions=18, lstm_size=256,
                  obs_dtype=np.uint8, B=3, T=5),
    "vector": dict(obs_shape=(4,), num_actions=2, lstm_size=32,
                   obs_dtype=np.float32, B=6, T=9),
}


def _close(got, want, scale=None, what=""):
    """`got` within RTOL x `scale` (default: `want`'s largest magnitude)
    of `want`."""
    got, want = np.asarray(got), np.asarray(want)
    scale = float(np.max(np.abs(want))) if scale is None else scale
    worst = float(np.max(np.abs(got - want)))
    assert worst <= RTOL * scale, (what, worst, scale)


def _close_trees(got, want):
    """Every leaf, relative to the largest magnitude in the tree (a
    float32 sum in another order moves a small leaf by more than 1e-6
    of ITSELF: conv0's bias gradient sums 400 positions x N frames)."""
    scale = max(float(np.max(np.abs(w))) for w in jax.tree.leaves(want))
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_leaves_with_path(want)]
    for path, g, w in zip(paths, jax.tree.leaves(got), jax.tree.leaves(want),
                          strict=True):
        _close(g, w, scale, path)


@pytest.fixture(scope="module", params=sorted(OBS))
def case(request):
    """(agent, train state, batch-major batch, the same data time-major)."""
    o = OBS[request.param]
    cfg = ImpalaConfig(obs_shape=o["obs_shape"], num_actions=o["num_actions"],
                       trajectory=o["T"], lstm_size=o["lstm_size"],
                       learning_frame=10 ** 6)
    agent = ImpalaAgent(cfg)
    state = agent.init_state(jax.random.PRNGKey(29))
    batch = jax.tree.map(jnp.asarray, synthetic_impala_batch(
        o["B"], o["T"], o["obs_shape"], o["num_actions"], o["lstm_size"],
        seed=29, obs_dtype=o["obs_dtype"], uniform_behavior=False))
    # rewards beyond +-1 so the clip matters, as in the benchmark's batch
    batch = batch._replace(reward=4.0 * batch.reward - 2.0)
    rollout = ImpalaRollout(*(jnp.swapaxes(x, 0, 1) for x in batch))
    return agent, state, batch, rollout


def test_rollout_is_the_batch_with_its_leading_axes_swapped(case):
    _, _, batch, rollout = case
    assert ImpalaRollout._fields == ImpalaBatch._fields
    for b, r in zip(batch, rollout):
        assert r.shape == (b.shape[1], b.shape[0], *b.shape[2:])


def test_apply_stored_state_hands_back_the_callers_layout(case):
    """Flattened as they lie: `[B, T]` in gives `[B, T, A]` / `[B, T]`
    (the benchmark's call), `[T, B]` in gives `[T, B, A]` / `[T, B]`."""
    agent, state, batch, rollout = case
    forward = jax.jit(functools.partial(apply_stored_state, agent.model))
    policy, value = forward(state.params, agent._prep_obs(batch.state),
                            batch.previous_action, batch.initial_h,
                            batch.initial_c)
    policy_t, value_t = forward(state.params, agent._prep_obs(rollout.state),
                                rollout.previous_action, rollout.initial_h,
                                rollout.initial_c)
    B, T = batch.action.shape
    assert policy.shape == (B, T, agent.cfg.num_actions) and value.shape == (B, T)
    assert policy_t.shape == (T, B, agent.cfg.num_actions)
    _close(jnp.swapaxes(policy_t, 0, 1), policy, what="policy")
    _close(jnp.swapaxes(value_t, 0, 1), value, what="value")


def test_time_major_loss_terms_match_batch_major(case):
    agent, state, batch, rollout = case
    total, metrics = jax.jit(agent._loss)(state.params, batch)
    total_t, metrics_t = jax.jit(agent._loss_time_major)(state.params, rollout)
    assert set(metrics) == set(metrics_t) == set(LOSS_TERMS)
    # two-signed sums: relative to the summed magnitude of the one-signed
    # terms the total is made of, as the benchmark's reference check does
    scale = sum(abs(float(metrics[k])) for k in LOSS_TERMS[:3])
    for k in LOSS_TERMS:
        assert abs(float(metrics_t[k]) - float(metrics[k])) <= RTOL * scale, k
    assert float(total_t) == float(metrics_t["total_loss"])


def test_time_major_gradient_matches_batch_major(case):
    agent, state, batch, rollout = case
    grad = lambda loss: jax.jit(jax.grad(loss, has_aux=True))
    g, _ = grad(agent._loss)(state.params, batch)
    g_t, _ = grad(agent._loss_time_major)(state.params, rollout)
    _close_trees(g_t, g)


def test_time_major_learn_matches_batch_major(case):
    """Same updated parameters, optimizer state, step and metrics."""
    agent, state, batch, rollout = case
    new, metrics = jax.jit(agent._learn)(state, batch)
    new_t, metrics_t = jax.jit(agent._learn_time_major)(state, rollout)
    assert int(new_t.step) == int(new.step) == 1
    _close_trees(new_t.params, new.params)
    _close_trees(new_t.opt_state, new.opt_state)
    assert set(metrics_t) == set(metrics)
    _close(metrics_t["grad_norm"], metrics["grad_norm"], what="grad_norm")
    assert float(metrics_t["learning_rate"]) == float(metrics["learning_rate"])


def test_from_softmax_time_major_is_from_softmax_without_the_swaps():
    rng = np.random.default_rng(0)
    B, T, A = 5, 7, 4
    soft = lambda x: np.exp(x) / np.exp(x).sum(-1, keepdims=True)
    args = dict(
        behavior_policy=soft(rng.normal(size=(B, T, A))).astype(np.float32),
        target_policy=soft(rng.normal(size=(B, T, A))).astype(np.float32),
        actions=rng.integers(0, A, (B, T)).astype(np.int32),
        discounts=(0.99 * (rng.random((B, T)) > 0.1)).astype(np.float32),
        rewards=rng.normal(size=(B, T)).astype(np.float32),
        values=rng.normal(size=(B, T)).astype(np.float32),
        next_values=rng.normal(size=(B, T)).astype(np.float32))
    want = vtrace.from_softmax(**args)
    got = vtrace.from_softmax_time_major(
        **{k: np.swapaxes(v, 0, 1) for k, v in args.items()})
    np.testing.assert_array_equal(np.swapaxes(got.vs, 0, 1), want.vs)
    np.testing.assert_array_equal(np.swapaxes(got.clipped_rhos, 0, 1),
                                  want.clipped_rhos)
    first, middle, last = vtrace.split_time_major(args["values"].T)
    for t, b in zip((first, middle, last), vtrace.split_data(args["values"])):
        np.testing.assert_array_equal(t.T, b)


def test_anakin_update_matches_collect_swap_learn():
    """`AnakinImpala._update` on CartPole against the parent's
    formulation written out: collect, swap every field to `[B, T]`,
    `agent._learn`."""
    cfg = ImpalaConfig(obs_shape=(4,), num_actions=2, trajectory=16,
                       lstm_size=32, learning_frame=10 ** 9)
    agent = ImpalaAgent(cfg)
    anakin = AnakinImpala(agent, num_envs=8)
    assert anakin.handoff == "time_major, frames float32"  # CartPole: floats
    state = anakin.init(jax.random.PRNGKey(3))

    def parent_update(state):
        carry = (state.env, state.obs, state.prev_action, state.h, state.c,
                 state.rng)
        carry, rec = jax.lax.scan(
            functools.partial(anakin._env_step, state.train.params), carry,
            None, length=cfg.trajectory)
        batch = ImpalaBatch(**{f: jnp.swapaxes(rec[f], 0, 1)
                               for f in ImpalaBatch._fields})
        train, metrics = agent._learn(state.train, batch)
        return train, metrics, carry

    train, metrics, carry = jax.jit(parent_update)(state)
    new, got = jax.jit(lambda s: anakin._update(s, None))(state)
    _close_trees(new.train.params, train.params)
    _close_trees(new.train.opt_state, train.opt_state)
    scale = sum(abs(float(metrics[k])) for k in LOSS_TERMS[:3])
    for k in LOSS_TERMS:
        assert abs(float(got[k]) - float(metrics[k])) <= RTOL * scale, k
    _close(got["grad_norm"], metrics["grad_norm"], what="grad_norm")
    # the rollout itself is the same: same keys, same carry out
    for a, b in zip(jax.tree.leaves((new.env, new.obs, new.prev_action,
                                     new.h, new.c, new.rng)),
                    jax.tree.leaves(carry), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mesh_keeps_the_batch_major_handoff():
    """A mesh shards B; `[T, B/n]` does not flatten shard-contiguously
    (the compile for a described v5e:2x2 all-gathers the frames,
    tests/test_tpu_compile.py), so the code separates by what it
    observes: `mesh is not None`."""
    from distributed_reinforcement_learning_tpu.parallel import make_mesh

    cfg = ImpalaConfig(obs_shape=(4,), num_actions=2, trajectory=4,
                       lstm_size=16)
    assert AnakinImpala(ImpalaAgent(cfg), 8, mesh=make_mesh(8)).handoff \
        == "batch_major, frames float32"

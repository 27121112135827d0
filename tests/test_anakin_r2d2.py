"""On-device replay R2D2 (`runtime/anakin_r2d2.py`) tests.

`data/replay.py` + `runtime/r2d2_runner.py` are the semantics source:
same priority transform, stratified sampling, IS weights, beta anneal,
per-episode epsilon decay — expressed as a device-resident ring.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.agents.r2d2 import R2D2Agent, R2D2Config
from distributed_reinforcement_learning_tpu.envs.cartpole import pomdp_project
from distributed_reinforcement_learning_tpu.runtime.anakin_r2d2 import (
    PER_ALPHA,
    PER_EPS,
    AnakinR2D2,
    _priority,
)


def make(num_envs=4, capacity=16, batch_size=4, **kw):
    cfg = R2D2Config(obs_shape=(2,), num_actions=2, seq_len=6, burn_in=2,
                     lstm_size=16, learning_rate=1e-3)
    agent = R2D2Agent(cfg)
    defaults = dict(obs_transform=pomdp_project, updates_per_collect=1)
    defaults.update(kw)
    return AnakinR2D2(agent, num_envs=num_envs, capacity=capacity,
                      batch_size=batch_size, **defaults)


class TestDeviceReplay:
    def test_ring_write_wrap_and_size_cap(self):
        an = make(num_envs=4, capacity=8)
        st = an.init(jax.random.PRNGKey(0))
        assert int(st.replay.size) == 0
        # Three collects of 4 into capacity 8: wraps once, size caps.
        st, _ = an.collect_chunk(st, 3)
        assert int(st.replay.size) == 8
        assert int(st.replay.ptr) == 4
        assert (np.asarray(st.replay.priorities) > 0).all()

    def test_priority_transform_matches_host_replay(self):
        errs = jnp.asarray([0.0, 0.5, 2.0])
        got = np.asarray(_priority(errs))
        want = np.power(np.abs(np.asarray(errs)) + PER_EPS, PER_ALPHA)
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_sample_indices_respect_priorities(self):
        an = make(num_envs=4, capacity=8, batch_size=16)
        st = an.init(jax.random.PRNGKey(0))
        st, _ = an.collect_chunk(st, 2)  # fill all 8 slots
        # Concentrate all mass on slot 5.
        pri = np.full(8, 1e-6, np.float32)
        pri[5] = 100.0
        replay = st.replay._replace(priorities=jnp.asarray(pri))
        _, batch, idx, weights = an._sample(replay, jax.random.PRNGKey(1))
        idx = np.asarray(idx)
        assert (idx == 5).mean() > 0.9
        assert np.all(np.asarray(weights) <= 1.0 + 1e-6)
        assert np.asarray(weights).max() == 1.0

    def test_beta_anneals_per_sample(self):
        an = make()
        st = an.init(jax.random.PRNGKey(0))
        st, _ = an.collect_chunk(st, 2)
        b0 = float(st.replay.beta)
        replay, *_ = an._sample(st.replay, jax.random.PRNGKey(1))
        assert abs(float(replay.beta) - (b0 + 0.001)) < 1e-6


class TestAnakinR2D2:
    def test_train_chunk_mechanics(self):
        an = make(num_envs=4, capacity=16, batch_size=4)
        st = an.init(jax.random.PRNGKey(0))
        st, _ = an.collect_chunk(st, 4)  # warm-up fills the ring
        st, m = an.train_chunk(st, 3)
        assert int(st.train.step) == 3
        assert np.isfinite(np.asarray(m["loss"])).all()
        assert float(m["replay_size"][-1]) == 16
        # Same compiled program serves subsequent chunks.
        st, _ = an.train_chunk(st, 2)
        assert int(st.train.step) == 5

    def test_target_sync_cadence(self):
        an = make(target_sync_interval=2)
        st = an.init(jax.random.PRNGKey(0))
        st, _ = an.collect_chunk(st, 4)
        st, _ = an.train_chunk(st, 2)  # step hits 2 -> sync fires
        tp = jax.device_get(st.train.target_params)
        p = jax.device_get(st.train.params)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), tp, p)

    def test_updates_per_collect_syncs_on_interval(self):
        """K=2 with interval 3: the steps-since-last cadence still syncs
        (a naive step-modulo would wait for step 6)."""
        an = make(updates_per_collect=2, target_sync_interval=3)
        st = an.init(jax.random.PRNGKey(0))
        st, _ = an.collect_chunk(st, 4)
        st, m = an.train_chunk(st, 2)  # steps 2, 4: since-last 4 >= 3 at 4
        assert int(st.train.step) == 4
        assert int(st.last_sync) == 4
        tp = jax.device_get(st.train.target_params)
        p = jax.device_get(st.train.params)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), tp, p)

    def test_k_exceeding_interval_rejected(self):
        import pytest

        with pytest.raises(ValueError):
            make(updates_per_collect=8, target_sync_interval=4)

    def test_epsilon_decays_per_episode(self):
        an = make(epsilon_floor=0.02)
        st = an.init(jax.random.PRNGKey(0))
        eps0 = float(an._epsilon(st.episodes).mean())
        assert eps0 == 1.0
        st, _ = an.collect_chunk(st, 30)  # plenty of episode ends
        assert int(np.asarray(st.episodes).sum()) > 0
        eps1 = float(an._epsilon(st.episodes).mean())
        assert eps1 < 1.0
        assert float(an._epsilon(st.episodes).min()) >= 0.02

    def test_learns_cartpole_pomdp_on_device(self):
        """Same learning bar family as the host-loop e2e: well above the
        ~20 random baseline within a small budget."""
        cfg = R2D2Config(obs_shape=(2,), num_actions=2, seq_len=10,
                         burn_in=5, lstm_size=32, learning_rate=2e-3)
        an = AnakinR2D2(R2D2Agent(cfg), num_envs=8, capacity=512,
                        batch_size=32, target_sync_interval=25,
                        epsilon_floor=0.02, obs_transform=pomdp_project)
        st = an.init(jax.random.PRNGKey(0))
        st, _ = an.collect_chunk(st, 16)
        st, _ = an.train_chunk(st, 350)  # burn-in
        st, m = an.train_chunk(st, 50)  # late window
        episodes = float(m["episodes_done"].sum())
        mean_return = float(m["episode_return_sum"].sum()) / max(episodes, 1.0)
        assert episodes > 0
        assert mean_return > 45, f"late mean return {mean_return}"


class TestPixelR2D2:
    def test_breakout_sequences_train_and_eval(self):
        """Conv-torso R2D2 (`models/r2d2_net.py` torso="nature") + uint8
        sequence ring + pixel env: compiled updates run, stay finite, and
        the greedy-eval rollout executes (VERDICT r4 item 2's in-suite
        pixel-R2D2 coverage)."""
        from distributed_reinforcement_learning_tpu.envs import breakout_jax

        cfg = R2D2Config(obs_shape=(84, 84, 4), num_actions=4, seq_len=4,
                         burn_in=2, lstm_size=16, torso="nature",
                         priority_eta=0.9)
        an = AnakinR2D2(R2D2Agent(cfg), num_envs=2, capacity=8,
                        batch_size=2, env=breakout_jax)
        st = an.init(jax.random.PRNGKey(0))
        # The ring holds the stacks as words; what is sampled is uint8.
        assert st.replay.storage.state.words.dtype == jnp.uint32
        st, _ = an.collect_chunk(st, 1)
        sampled = an._sample(st.replay, jax.random.PRNGKey(2))[1].state
        assert (sampled.dtype, sampled.shape) == (jnp.uint8, (2, 4, 84, 84, 4))
        st, m = an.train_chunk(st, 1)
        assert np.isfinite(np.asarray(m["loss"])).all()
        ev = an.greedy_eval(st.train.params, 2, 8, jax.random.PRNGKey(1))
        assert "mean_return" in ev


def _pixel_anakin(priority_eta, n_step=5):
    """The pixel cell's shape of agent (Nature torso, dueling streams,
    5-step targets) at 4 envs; env 0's ball is put on its way out of the
    field, so a life is lost, and `done` set, inside the next sequence."""
    from distributed_reinforcement_learning_tpu.envs import breakout_jax

    cfg = R2D2Config(obs_shape=(84, 84, 4), num_actions=4, seq_len=12,
                     burn_in=4, n_step=n_step, lstm_size=16, torso="nature",
                     dueling_hidden=32, priority_eta=priority_eta)
    an = AnakinR2D2(R2D2Agent(cfg), num_envs=4, capacity=8, batch_size=2,
                    env=breakout_jax)

    def lose_a_ball(env):
        first = lambda leaf, v: leaf.at[0].set(v)
        return env._replace(
            ball_dead=first(env.ball_dead, False),
            paddle_x=first(env.paddle_x, 136.0),
            ball_x=first(env.ball_x, 12.0), vx=first(env.vx, 0.0),
            ball_y=first(env.ball_y, 150.0), vy=first(env.vy, 3.0))

    return an, lose_a_ball


def _mlp_anakin(priority_eta, n_step=5):
    """The reference's CartPole net; a random policy (epsilon 1 before
    the first episode ends) drops the pole inside 24 steps."""
    cfg = R2D2Config(obs_shape=(2,), num_actions=2, seq_len=12, burn_in=4,
                     n_step=n_step, lstm_size=16, priority_eta=priority_eta)
    an = AnakinR2D2(R2D2Agent(cfg), num_envs=4, capacity=8, batch_size=2,
                    obs_transform=pomdp_project)
    return an, lambda env: env


def _second_rollout(an, before_second):
    """-> (state, rollout) of the SECOND collect from seed 3, with a
    target net of its own (equal nets would hide a mixed-up argument), a
    stored start state that is not zeros and a `done` inside a sequence."""
    st = an.init(jax.random.PRNGKey(3))
    other = an.agent.init_state(jax.random.PRNGKey(4)).params
    st = st._replace(train=st.train.replace(target_params=other))
    collect = jax.jit(an._collect)
    st = collect(st)[0]
    st = st._replace(env=before_second(st.env))
    st, rollout, _ = collect(st)
    assert float(jnp.abs(rollout.initial_h).max()) > 0
    assert np.asarray(rollout.done)[:-1].any(), "no reset inside a sequence"
    return st, rollout


@pytest.mark.parametrize("priority_eta", [None, 0.9], ids=["ref", "eta0.9"])
@pytest.mark.parametrize("build", [_pixel_anakin, _mlp_anakin],
                         ids=["nature", "mlp"])
def test_acting_q_scores_the_new_sequences(build, priority_eta):
    """The collect scan's Q-values ARE the online net's unroll over the
    batch it records (same params, inputs, stored start state and
    resets), so `_ingest` may score with them and unroll the target net
    alone (ISSUE 50)."""
    an, before_second = build(priority_eta)
    agent = an.agent
    st, rollout = _second_rollout(an, before_second)
    batch, online_q = rollout.batch(), jnp.swapaxes(rollout.online_q, 0, 1)

    unrolled = agent.model.apply(
        st.train.params, agent._prep_obs(batch.state), batch.previous_action,
        batch.done, batch.initial_h, batch.initial_c,
        method=agent.model.unroll)
    assert online_q.shape == unrolled.shape == (4, 12, agent.cfg.num_actions)
    scale = float(jnp.abs(unrolled).max())
    np.testing.assert_allclose(online_q, unrolled, rtol=0, atol=1e-5 * scale)

    both = np.asarray(agent.td_error(st.train, batch))
    np.testing.assert_allclose(agent.td_error(st.train, batch, online_q),
                               both, rtol=1e-5, atol=1e-5 * both.max())
    # And the online values are read: another net's give another score.
    assert not np.allclose(agent.td_error(st.train, batch, online_q[::-1]),
                           both, rtol=1e-3)


@pytest.mark.parametrize("priority_eta", [None, 0.9], ids=["ref", "eta0.9"])
@pytest.mark.parametrize("n_step", [1, 5])
@pytest.mark.parametrize("build", [_pixel_anakin, _mlp_anakin],
                         ids=["nature", "mlp"])
def test_the_score_takes_the_rollout_as_the_scan_wrote_it(build, n_step,
                                                          priority_eta):
    """One net, two axis orders: `unroll_time_major` over `[T, B, ...]`
    is `unroll` over the swapped `[B, T, ...]`, and the fused loop's score
    of a rollout is `_td_error` of its `[B, T]` batch (ISSUE 52)."""
    an, before_second = build(priority_eta, n_step)
    agent = an.agent
    st, rollout = _second_rollout(an, before_second)
    batch, online_q = rollout.batch(), jnp.swapaxes(rollout.online_q, 0, 1)
    assert rollout.state.shape[:2] == (12, 4) and batch.state.shape[:2] == (4, 12)

    def q(method, seq):
        return agent.model.apply(
            st.train.target_params, agent._prep_obs(seq.state),
            seq.previous_action, seq.done, seq.initial_h, seq.initial_c,
            method=method)

    batch_major = q(agent.model.unroll, batch)
    time_major = q(agent.model.unroll_time_major, rollout)
    assert time_major.shape == (12, 4, agent.cfg.num_actions)
    scale = float(jnp.abs(batch_major).max())
    np.testing.assert_allclose(jnp.swapaxes(time_major, 0, 1), batch_major,
                               rtol=0, atol=1e-5 * scale)

    want = np.asarray(agent.td_error(st.train, batch, online_q))
    got = jax.jit(agent._td_error_time_major)(st.train, rollout)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * want.max())
    # The order is read: the same rollout through the other entry is not it.
    square = jax.tree.map(lambda x: x[:4], rollout)  # T = B = 4: shapes agree
    assert not np.allclose(
        q(agent.model.unroll_time_major, square),
        q(agent.model.unroll, square), atol=1e-3 * scale)


@pytest.mark.parametrize("build", [_pixel_anakin, _mlp_anakin],
                         ids=["nature", "mlp"])
def test_the_ring_holds_the_same_bytes_whichever_order_scored_them(build):
    """After one `_collect_only` the ring is what the batch-major path
    writes from the same seed (collect, swap every field to `[B, T]`,
    `_td_error`, `device_replay.ingest`): the stored leaves to the bit,
    the priorities to float32 rounding."""
    from distributed_reinforcement_learning_tpu.data import device_replay

    an, _ = build(0.9)
    agent = an.agent
    st = an.init(jax.random.PRNGKey(5))
    other = agent.init_state(jax.random.PRNGKey(4)).params
    st = st._replace(train=st.train.replace(target_params=other))

    def batch_major(state):
        state, rollout, _ = an._collect(state)
        batch = rollout.batch()
        errs = agent._td_error(state.train, batch,
                               jnp.swapaxes(rollout.online_q, 0, 1))
        return device_replay.ingest(state.replay, batch, errs)

    want = jax.jit(batch_major)(st)
    got = jax.jit(an._collect_only)(st, None)[0].replay
    assert int(got.ptr) == int(want.ptr) == 4 and int(got.size) == 4
    for ours, theirs in zip(jax.tree.leaves(got.storage),
                            jax.tree.leaves(want.storage)):
        np.testing.assert_array_equal(ours, theirs)
    assert np.asarray(jax.tree.leaves(got.storage)[0]).any()
    np.testing.assert_allclose(got.priorities, want.priorities, rtol=1e-5)
    assert (np.asarray(got.priorities)[:4] > 0).all()

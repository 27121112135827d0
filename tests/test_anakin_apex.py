"""On-device Ape-X (`runtime/anakin_apex.py`) tests: ring mechanics on
flat transitions, cadences, CartPole learning, and a pixel-env smoke."""

import jax
import jax.numpy as jnp
import numpy as np

from distributed_reinforcement_learning_tpu.agents.apex import ApexAgent, ApexConfig
from distributed_reinforcement_learning_tpu.data import device_replay
from distributed_reinforcement_learning_tpu.runtime.anakin_apex import AnakinApex


def make(num_envs=4, steps=4, capacity=32, batch_size=8, **kw):
    cfg = ApexConfig(obs_shape=(4,), num_actions=2, start_learning_rate=1e-3)
    return AnakinApex(ApexAgent(cfg), num_envs=num_envs,
                      steps_per_collect=steps, capacity=capacity,
                      batch_size=batch_size, **kw)


class TestMechanics:
    def test_ring_write_width_and_wrap(self):
        an = make(num_envs=4, steps=4, capacity=32)  # width 16
        st = an.init(jax.random.PRNGKey(0))
        st, _ = an.collect_chunk(st, 3)  # 48 transitions -> wraps
        assert int(st.replay.size) == 32
        assert int(st.replay.ptr) == 16
        assert (np.asarray(st.replay.priorities) > 0).all()

    def test_capacity_alignment_guard(self):
        import pytest

        with pytest.raises(ValueError):
            make(num_envs=4, steps=4, capacity=40)  # not a multiple of 16

    def test_train_chunk_mechanics(self):
        an = make()
        st = an.init(jax.random.PRNGKey(0))
        st, _ = an.collect_chunk(st, 2)
        st, m = an.train_chunk(st, 3)
        assert int(st.train.step) == 3
        assert np.isfinite(np.asarray(m["loss"])).all()
        st, _ = an.train_chunk(st, 2)
        assert int(st.train.step) == 5

    def test_target_sync_steps_since_last(self):
        an = make(target_sync_interval=2, updates_per_collect=2)
        st = an.init(jax.random.PRNGKey(0))
        st, _ = an.collect_chunk(st, 2)
        st, _ = an.train_chunk(st, 1)  # 2 steps -> sync fires
        assert int(st.last_sync) == 2
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
            jax.device_get(st.train.target_params),
            jax.device_get(st.train.params))

    def test_epsilon_reference_schedule(self):
        an = make()
        eps = an._epsilon(jnp.asarray([0, 20, 100]))
        np.testing.assert_allclose(
            np.asarray(eps), [1.0, 1.0 / 2.0, 1.0 / 6.0], rtol=1e-6)


class TestLearning:
    def test_learns_cartpole_on_device(self):
        """Same bar family as the host e2e: late mean return well above
        the ~20 random baseline."""
        cfg = ApexConfig(obs_shape=(4,), num_actions=2,
                         start_learning_rate=1e-3)
        # updates_per_collect=4 puts the sampled-to-collected ratio at
        # 1.0 (the host learner trains whenever the queue allows).
        an = AnakinApex(ApexAgent(cfg), num_envs=8, steps_per_collect=16,
                        capacity=8192, batch_size=32, updates_per_collect=4,
                        target_sync_interval=25, epsilon_floor=0.02)
        st = an.init(jax.random.PRNGKey(0))
        st, _ = an.collect_chunk(st, 8)
        st, _ = an.train_chunk(st, 250)
        st, m = an.train_chunk(st, 50)
        episodes = float(m["episodes_done"].sum())
        mean_return = float(m["episode_return_sum"].sum()) / max(episodes, 1.0)
        assert episodes > 0
        assert mean_return > 60, f"late mean return {mean_return}"


class TestPixelSmoke:
    def test_breakout_transitions_train(self):
        """Dueling conv net + uint8 transition ring + pixel env: one
        compiled update runs and stays finite."""
        from distributed_reinforcement_learning_tpu.envs import breakout_jax

        cfg = ApexConfig(obs_shape=(84, 84, 4), num_actions=4)
        an = AnakinApex(ApexAgent(cfg), num_envs=2, steps_per_collect=3,
                        capacity=12, batch_size=4, env=breakout_jax)
        st = an.init(jax.random.PRNGKey(0))
        # The ring holds the stacks as words; what is sampled is uint8.
        assert st.replay.storage.state.words.dtype == jnp.uint32
        st, _ = an.collect_chunk(st, 1)
        sampled = device_replay.sample(st.replay, jax.random.PRNGKey(2), 4)[1]
        assert (sampled.state.dtype, sampled.next_state.shape) == (
            jnp.uint8, (4, 84, 84, 4))
        st, m = an.train_chunk(st, 1)
        assert np.isfinite(np.asarray(m["loss"])).all()

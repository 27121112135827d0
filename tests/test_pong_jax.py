"""JAX Pong (`envs.pong_jax`) parity + Anakin integration tests.

`envs.pong_sim` + the host preprocessing pipeline is the semantics
source, exactly as `tests/test_breakout_jax.py` does for Breakout.
"""

import jax
import jax.numpy as jnp
import numpy as np

from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent, ImpalaConfig
from distributed_reinforcement_learning_tpu.envs import pong_jax, pong_sim
from distributed_reinforcement_learning_tpu.envs.atari import AtariPreprocessor, preprocess_frame
from distributed_reinforcement_learning_tpu.envs.pong_sim import PongSimRaw
from distributed_reinforcement_learning_tpu.runtime.anakin import AnakinImpala


def rally(core: pong_sim.PongCore, x=80.0, y=100.0, vx=2.0, vy=1.0):
    """Put a numpy core into a deterministic mid-rally state."""
    core._ball_dead = False
    core.ball_x, core.ball_y = x, y
    core.vx, core.vy = vx, vy


def jax_rally(state, x=80.0, y=100.0, vx=2.0, vy=1.0):
    n = state.frames.shape[0]
    return state._replace(
        ball_dead=jnp.zeros(n, bool),
        ball_x=jnp.full(n, x, jnp.float32),
        ball_y=jnp.full(n, y, jnp.float32),
        vx=jnp.full(n, vx, jnp.float32),
        vy=jnp.full(n, vy, jnp.float32),
    )


class TestRenderParity:
    def test_frame_matches_numpy_render_below_score_strip(self):
        core = pong_sim.PongCore(seed=3)
        core.reset()
        core.player_y = 60
        core.enemy_y = 150
        rally(core, x=100.0, y=120.0)
        want = core.render()

        state, _ = pong_jax.reset(jax.random.PRNGKey(0), 1)
        state = state._replace(
            player_y=jnp.asarray([60], jnp.int32),
            enemy_y=jnp.asarray([150], jnp.int32))
        state = jax_rally(state, x=100.0, y=120.0)
        got = np.asarray(jax.vmap(pong_jax._render)(
            state.player_y, state.enemy_y, state.ball_dead,
            state.ball_x, state.ball_y))[0]

        # Scanlines below the score strip (everything the crop can see)
        # must match exactly; the strip region renders as background.
        top = pong_sim.FIELD_TOP - pong_sim.BOUND_H
        np.testing.assert_array_equal(got[top:], want[top:])
        assert (got[:top] == np.asarray(pong_sim.BACKGROUND, np.uint8)).all()

    def test_preprocess_matches_host_pipeline(self):
        core = pong_sim.PongCore(seed=5)
        core.reset()
        rally(core)
        frame = core.render()
        want = preprocess_frame(frame).astype(np.int32)
        got = np.asarray(pong_jax._preprocess(jnp.asarray(frame))).astype(np.int32)
        assert np.abs(got - want).max() <= 1


class TestDynamicsParity:
    def test_tracks_host_pipeline_until_first_point(self):
        """Same mid-rally state + same actions -> identical rewards and
        observations until the first point (serves are the only
        randomness; a rally is deterministic)."""
        pre = AtariPreprocessor(PongSimRaw(seed=0, frameskip=4),
                                fire_reset=False)
        obs_h = pre.reset()
        core = pre.env._core
        rally(core)

        state, obs_j = pong_jax.reset(jax.random.PRNGKey(0), 1)
        state = jax_rally(state)
        assert np.abs(np.asarray(obs_j[0], np.int32)
                      - obs_h.astype(np.int32)).max() <= 1

        rng = np.random.default_rng(11)
        actions = rng.choice([pong_sim.NOOP, pong_sim.RIGHT, pong_sim.LEFT],
                             size=60)
        saw_point = False
        for t, a in enumerate(actions):
            obs_h, r_h, done_h, info_h = pre.step(int(a))
            state, obs_j, r_j, done_j, _ = pong_jax.step(
                state, jnp.asarray([a]), jax.random.PRNGKey(100 + t))
            assert float(r_j[0]) == r_h, f"step {t}: {float(r_j[0])} != {r_h}"
            assert int(state.player_score[0]) == core.player_score, f"step {t}"
            assert int(state.enemy_score[0]) == core.enemy_score, f"step {t}"
            assert np.abs(np.asarray(obs_j[0], np.int32)
                          - obs_h.astype(np.int32)).max() <= 1, f"step {t}"
            if r_h != 0.0:
                saw_point = True
                break  # post-point serves draw from different rngs
        assert saw_point, "60 steps without a point; horizon too short"


class TestEpisodeSemantics:
    def _near_win(self, player=20, enemy=0, **ball):
        state, _ = pong_jax.reset(jax.random.PRNGKey(0), 1)
        state = state._replace(
            player_score=jnp.asarray([player], jnp.int32),
            enemy_score=jnp.asarray([enemy], jnp.int32),
            returns=jnp.asarray([float(player - enemy)], jnp.float32))
        return jax_rally(state, **ball)

    def test_winning_point_ends_and_resets(self):
        # Ball about to cross the LEFT edge: agent scores point 21.
        state = self._near_win(player=20, x=3.0, y=100.0, vx=-2.0, vy=0.0)
        # Move the enemy paddle away from the ball's path.
        state = state._replace(enemy_y=jnp.asarray([170], jnp.int32))
        state, obs, r, done, ep = pong_jax.step(
            state, jnp.asarray([pong_sim.NOOP]), jax.random.PRNGKey(1))
        assert float(r[0]) == 1.0
        assert bool(done[0])
        assert float(ep[0]) == 21.0
        assert int(state.player_score[0]) == 0  # fresh game
        assert (np.asarray(obs[0, :, :, :3]) == 0).all()

    def test_losing_point_is_negative_and_nonterminal(self):
        state = self._near_win(player=5, enemy=3,
                               x=156.0, y=60.0, vx=2.0, vy=0.0)
        # Agent paddle far from the ball: it scores on the right edge.
        state = state._replace(player_y=jnp.asarray([170], jnp.int32))
        state, obs, r, done, ep = pong_jax.step(
            state, jnp.asarray([pong_sim.NOOP]), jax.random.PRNGKey(1))
        assert float(r[0]) == -1.0
        assert not bool(done[0])
        assert int(state.enemy_score[0]) == 4
        assert bool(state.ball_dead[0])

    def test_auto_serve_after_timer(self):
        state, _ = pong_jax.reset(jax.random.PRNGKey(0), 1)
        assert bool(state.ball_dead[0])
        # SERVE_DELAY emulated frames / 4 per step = 9 steps to serve.
        for t in range(pong_sim.SERVE_DELAY // 4 + 1):
            state, *_ = pong_jax.step(
                state, jnp.asarray([pong_sim.NOOP]), jax.random.PRNGKey(t))
        assert not bool(state.ball_dead[0])

    def test_fire_serves_immediately(self):
        state, _ = pong_jax.reset(jax.random.PRNGKey(0), 1)
        state, *_ = pong_jax.step(
            state, jnp.asarray([pong_sim.FIRE]), jax.random.PRNGKey(1))
        assert not bool(state.ball_dead[0])


class TestAnakinPong:
    def test_train_chunk_runs_and_is_finite(self):
        cfg = ImpalaConfig(obs_shape=(84, 84, 4), num_actions=6, trajectory=5,
                           lstm_size=16, entropy_coef=0.01,
                           start_learning_rate=1e-3, end_learning_rate=1e-3)
        anakin = AnakinImpala(ImpalaAgent(cfg), num_envs=2, env=pong_jax)
        st = anakin.init(jax.random.PRNGKey(0))
        st, m = anakin.train_chunk(st, 2)
        assert int(st.train.step) == 2
        assert np.isfinite(np.asarray(m["total_loss"])).all()

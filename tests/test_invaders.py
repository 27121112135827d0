"""Space-Invaders sim + JAX env tests: host-vs-device parity, episode
semantics, registry routing, and Anakin integration (VERDICT r4 item 8).

`envs.invaders_sim.InvadersCore` + the host preprocessing pipeline is
the semantics source; `envs.invaders_jax` must reproduce frames,
physics, rewards, and observations from a matched state. Bomb spawns
are the one RNG-dependent mechanic, so exact-parity tests run with
`bomb_prob=0` on both sides (deterministic march/missile/shield
dynamics) and a separate statistical test exercises bombs.
"""

import jax
import jax.numpy as jnp
import numpy as np

from distributed_reinforcement_learning_tpu.envs import invaders_jax, invaders_sim
from distributed_reinforcement_learning_tpu.envs.atari import AtariPreprocessor, preprocess_frame
from distributed_reinforcement_learning_tpu.envs.invaders_sim import InvadersCore, InvadersSimRaw


class _NoBombs:
    """RandomState stub: the host core never rolls a bomb."""

    def random(self):
        return 1.0

    def choice(self, a):
        return a[0]


def _jax_render(st, i=0):
    return np.asarray(invaders_jax._render(
        st.aliens[i], st.grid_x[i], st.grid_y[i], st.cannon_x[i],
        st.missile_live[i], st.missile_x[i], st.missile_y[i],
        st.bomb_live[i], st.bomb_x[i], st.bomb_y[i], st.shield_hp[i]))


class TestRenderParity:
    def test_reset_frame_matches_numpy_render(self):
        core = InvadersCore(seed=0)
        want = core.reset()
        st, _ = invaders_jax.reset(jax.random.PRNGKey(0), 1)
        got = _jax_render(st)
        # Score strip (< scanline 20) deliberately unrendered (cropped).
        np.testing.assert_array_equal(got[20:], want[20:])

    def test_mid_game_frame_matches(self):
        """Thinned grid + eroded shield + in-flight projectiles."""
        core = InvadersCore(seed=0)
        core.reset()
        core.aliens[0, :3] = False
        core.aliens[4, 4] = False
        core.grid_x, core.grid_y = 33.0, 64.0
        core.cannon_x = 100.0
        core.shield_hp[1] = 3
        core.missile_live = True
        core.missile_x, core.missile_y = 104.0, 120.0
        core.bomb_live[0] = True
        core.bomb_x[0], core.bomb_y[0] = 50.0, 140.0
        want = core.render()

        st, _ = invaders_jax.reset(jax.random.PRNGKey(0), 1)
        st = st._replace(
            aliens=jnp.asarray(core.aliens)[None],
            grid_x=jnp.asarray([33.0]), grid_y=jnp.asarray([64.0]),
            cannon_x=jnp.asarray([100.0]),
            shield_hp=jnp.asarray(core.shield_hp)[None].astype(jnp.int32),
            missile_live=jnp.asarray([True]),
            missile_x=jnp.asarray([104.0]), missile_y=jnp.asarray([120.0]),
            bomb_live=jnp.asarray(core.bomb_live)[None],
            bomb_x=jnp.asarray(core.bomb_x)[None].astype(jnp.float32),
            bomb_y=jnp.asarray(core.bomb_y)[None].astype(jnp.float32))
        np.testing.assert_array_equal(_jax_render(st)[20:], want[20:])

    def test_preprocess_matches_host_pipeline(self):
        core = InvadersCore(seed=2)
        frame = core.reset()
        want = preprocess_frame(frame).astype(np.int32)
        got = np.asarray(invaders_jax._preprocess(jnp.asarray(frame))).astype(np.int32)
        assert np.abs(got - want).max() <= 1


class TestDynamicsParity:
    def test_tracks_host_pipeline_with_bombs_off(self):
        """Same actions, bombs disabled -> identical rewards, lives,
        dones, and stacked observations for 80 steps (march + missiles +
        shields + alien kills all exercised)."""
        pre = AtariPreprocessor(InvadersSimRaw(seed=0, frameskip=4),
                                fire_reset=False)
        obs_h = pre.reset()
        pre.env._core._rng = _NoBombs()

        st, obs_j = invaders_jax.reset(jax.random.PRNGKey(0), 1)
        assert np.abs(np.asarray(obs_j[0], np.int32)
                      - obs_h.astype(np.int32)).max() <= 1

        rng = np.random.default_rng(3)
        actions = rng.integers(0, 6, size=80)
        total = 0.0
        for t, a in enumerate(actions):
            obs_h, r_h, done_h, info_h = pre.step(int(a))
            st, obs_j, r_j, done_j, _ = invaders_jax.step(
                st, jnp.asarray([a]), jax.random.PRNGKey(100 + t),
                life_loss=False, bomb_prob=0.0)
            assert float(r_j[0]) == r_h, f"step {t}"
            assert int(st.lives[0]) == info_h["lives"], f"step {t}"
            assert bool(done_j[0]) == done_h, f"step {t}"
            assert np.abs(np.asarray(obs_j[0], np.int32)
                          - obs_h.astype(np.int32)).max() <= 1, f"step {t}"
            total += r_h
            if done_h:
                break
        assert total > 0, "pattern never killed an alien; test is vacuous"

    def test_bombs_cost_lives_and_erode_shields(self):
        """Statistical (jax-only): with bombs on, life-loss dones occur,
        shields erode, and games complete under a random policy."""
        st, _ = invaders_jax.reset(jax.random.PRNGKey(0), 8)
        rng = jax.random.PRNGKey(1)
        acts = np.random.default_rng(0)
        eps = dones = 0
        min_hp = invaders_sim.SHIELD_HP
        for t in range(300):
            rng, k = jax.random.split(rng)
            a = jnp.asarray(acts.integers(0, 6, size=8))
            st, _, r, done, ep = invaders_jax.step(st, a, k)
            eps += int((ep != 0).sum())
            dones += int(done.sum())
            min_hp = min(min_hp, int(st.shield_hp.min()))
        assert eps > 0, "no game ever completed"
        assert dones > eps, "no life-loss boundaries fired"
        assert min_hp < invaders_sim.SHIELD_HP, "shields never eroded"


class TestEpisodeSemantics:
    def test_life_loss_shaping_and_completed_mask(self):
        """A bomb hit surfaces done with reward -1 (non-terminal), the
        game continues (no grid reset), and completed_episode_mask stays
        False until a true game over."""
        st, _ = invaders_jax.reset(jax.random.PRNGKey(0), 1)
        # Plant a bomb just above the cannon, dead-center.
        cx = float(st.cannon_x[0])
        st = st._replace(
            bomb_live=jnp.asarray([[True, False]]),
            bomb_x=jnp.asarray([[cx + 2.0, 0.0]], jnp.float32),
            bomb_y=jnp.asarray([[invaders_sim.CANNON_Y - 8.0, 0.0]],
                               jnp.float32),
            aliens=st.aliens.at[0, :, :3].set(False))  # mark the grid
        st2, _, r, done, ep = invaders_jax.step(
            st, jnp.asarray([invaders_sim.NOOP]), jax.random.PRNGKey(0),
            bomb_prob=0.0)
        assert bool(done[0]) and float(r[0]) == -1.0 and float(ep[0]) == 0.0
        assert int(st2.lives[0]) == 2
        # No auto-reset: the thinned grid is still thinned.
        assert not bool(st2.aliens[0, 0, 0])
        assert not bool(invaders_jax.completed_episode_mask(done, st2)[0])

    def test_game_over_resets_and_reports_return(self):
        st, _ = invaders_jax.reset(jax.random.PRNGKey(0), 1)
        cx = float(st.cannon_x[0])
        st = st._replace(
            lives=jnp.asarray([1], jnp.int32),
            returns=jnp.asarray([120.0], jnp.float32),
            bomb_live=jnp.asarray([[True, False]]),
            bomb_x=jnp.asarray([[cx + 2.0, 0.0]], jnp.float32),
            bomb_y=jnp.asarray([[invaders_sim.CANNON_Y - 8.0, 0.0]],
                               jnp.float32))
        st2, _, r, done, ep = invaders_jax.step(
            st, jnp.asarray([invaders_sim.NOOP]), jax.random.PRNGKey(0),
            bomb_prob=0.0)
        assert bool(done[0]) and float(ep[0]) == 120.0
        # Terminal life keeps the raw reward (host-parity convention).
        assert float(r[0]) == 0.0
        # Auto-reset: fresh lives/grid.
        assert int(st2.lives[0]) == 3 and bool(st2.aliens.all())
        assert bool(invaders_jax.completed_episode_mask(done, st2)[0])

    def test_one_missile_in_flight(self):
        """The 2600's signature constraint: FIRE while a missile flies
        does not spawn a second one."""
        st, _ = invaders_jax.reset(jax.random.PRNGKey(0), 1)
        # Fire from the gap between shields (a shot from under a shield
        # erodes it from below — the real game's mechanic).
        st = st._replace(cannon_x=jnp.asarray([56.0], jnp.float32))
        st, *_ = invaders_jax.step(st, jnp.asarray([invaders_sim.FIRE]),
                                   jax.random.PRNGKey(0), bomb_prob=0.0)
        assert bool(st.missile_live[0])
        y0 = float(st.missile_y[0])
        st, *_ = invaders_jax.step(st, jnp.asarray([invaders_sim.FIRE]),
                                   jax.random.PRNGKey(1), bomb_prob=0.0)
        # Still the SAME missile (kept rising, not re-spawned at cannon).
        assert float(st.missile_y[0]) < y0


class TestRegistry:
    def test_spaceinvaders_names_route_to_sim(self):
        from distributed_reinforcement_learning_tpu.envs.registry import make_env

        env = make_env("SpaceInvadersDeterministic-v4", seed=0)
        obs = env.reset()
        assert obs.shape == (84, 84, 4) and obs.dtype == np.uint8
        assert env.num_actions == 6
        obs, r, done, info = env.step(1)
        assert "lives" in info


class TestAnakinInvaders:
    def test_impala_train_chunk_runs_and_is_finite(self):
        from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent, ImpalaConfig
        from distributed_reinforcement_learning_tpu.runtime.anakin import AnakinImpala

        cfg = ImpalaConfig(obs_shape=(84, 84, 4), num_actions=6,
                           trajectory=4, lstm_size=16)
        an = AnakinImpala(ImpalaAgent(cfg), num_envs=2, env=invaders_jax)
        state = an.init(jax.random.PRNGKey(0))
        state, m = an.train_chunk(state, 1)
        assert np.isfinite(np.asarray(m["total_loss"])).all()

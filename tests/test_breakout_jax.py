"""JAX Breakout (`envs.breakout_jax`) parity + Anakin integration tests.

The numpy simulator (`envs.breakout_sim`) plus the host preprocessing
pipeline (`envs.atari.AtariPreprocessor`) is the semantics source; the
JAX env must reproduce frames, physics, rewards, and the stacked
observation stream from a matched state.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent, ImpalaConfig
from distributed_reinforcement_learning_tpu.envs import (
    breakout_jax, breakout_sim, invaders_jax, pixel_jax, pong_jax)
from distributed_reinforcement_learning_tpu.envs.atari import AtariPreprocessor, preprocess_frame
from distributed_reinforcement_learning_tpu.envs.breakout_sim import BreakoutSimRaw
from distributed_reinforcement_learning_tpu.runtime.anakin import AnakinImpala


def launched(core: breakout_sim.BreakoutCore, x=80.0, y=150.0, vx=1.0, vy=-3.0):
    """Put a numpy core into a deterministic post-launch state."""
    core._ball_dead = False
    core.ball_x, core.ball_y = x, y
    core.vx, core.vy = vx, vy


def jax_launched(state, x=80.0, y=150.0, vx=1.0, vy=-3.0):
    n = state.lives.shape[0]
    return state._replace(
        ball_dead=jnp.zeros(n, bool),
        ball_x=jnp.full(n, x, jnp.float32),
        ball_y=jnp.full(n, y, jnp.float32),
        vx=jnp.full(n, vx, jnp.float32),
        vy=jnp.full(n, vy, jnp.float32),
    )


class TestRenderParity:
    def test_frame_matches_numpy_render_below_score_strip(self):
        core = breakout_sim.BreakoutCore(seed=3)
        core.reset()
        core.bricks[2, 5] = False
        core.bricks[0, :4] = False
        core.paddle_x = 40
        launched(core, x=100.0, y=120.0)
        want = core.render()

        state, _ = breakout_jax.reset(jax.random.PRNGKey(0), 1)
        state = state._replace(
            bricks=jnp.asarray(core.bricks)[None],
            paddle_x=jnp.asarray([40.0], jnp.float32))
        state = jax_launched(state, x=100.0, y=120.0)
        got = np.asarray(jax.vmap(breakout_jax._render)(
            state.bricks, state.paddle_x, state.ball_dead,
            state.ball_x, state.ball_y))[0]

        # The score strip (scanlines < WALL_TOP) is deliberately unrendered:
        # the crop removes it from every observation.
        np.testing.assert_array_equal(got[breakout_sim.WALL_TOP:],
                                      want[breakout_sim.WALL_TOP:])
        assert (got[:breakout_sim.WALL_TOP] == 0).all()

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_random_boards_match_numpy_render(self, seed):
        """Any board, paddle and ball (over bricks, beside walls, dead):
        the frame made from `repeat` + `pad` masks is the simulator's."""
        rng = np.random.default_rng(seed)
        n = 16
        bricks = rng.random((n, 6, 18)) < 0.5
        paddle_x = rng.integers(breakout_sim.WALL_SIDE,
                                breakout_sim.W - breakout_sim.WALL_SIDE
                                - breakout_sim.PADDLE_W + 1, n)
        dead = rng.random(n) < 0.25
        ball_x = rng.uniform(breakout_sim.WALL_SIDE, breakout_sim.W - 10, n)
        ball_y = rng.uniform(breakout_sim.WALL_TOP + 4, breakout_sim.H - 2, n)
        ball_y[:4] = rng.uniform(breakout_sim.BRICK_TOP, breakout_sim.BRICK_TOP + 36, 4)
        got = np.asarray(jax.vmap(breakout_jax._render)(
            jnp.asarray(bricks), jnp.asarray(paddle_x, jnp.float32),
            jnp.asarray(dead), jnp.asarray(ball_x, jnp.float32),
            jnp.asarray(ball_y, jnp.float32)))
        core = breakout_sim.BreakoutCore(seed=0)
        for i in range(n):
            core.reset()
            core.bricks[:] = bricks[i]
            core.paddle_x = int(paddle_x[i])
            core._ball_dead = bool(dead[i])
            core.ball_x, core.ball_y = float(np.float32(ball_x[i])), float(np.float32(ball_y[i]))
            np.testing.assert_array_equal(
                got[i, breakout_sim.WALL_TOP:],
                core.render()[breakout_sim.WALL_TOP:], err_msg=f"env {i}")

    def test_preprocess_matches_host_pipeline(self):
        """luma+resize+crop on device == `atari.preprocess_frame` (u8 +-1
        from float-association differences in the resize matmuls)."""
        core = breakout_sim.BreakoutCore(seed=5)
        core.reset()
        launched(core)
        frame = core.render()
        want = preprocess_frame(frame).astype(np.int32)
        got = np.asarray(pixel_jax.preprocess(jnp.asarray(frame))).astype(np.int32)
        assert np.abs(got - want).max() <= 1


class TestDynamicsParity:
    def test_tracks_host_pipeline_for_40_steps(self):
        """Same launched state + same actions -> same rewards, lives, and
        stacked observations as BreakoutSimRaw under AtariPreprocessor."""
        pre = AtariPreprocessor(BreakoutSimRaw(seed=0, frameskip=4),
                                fire_reset=False)
        obs_h = pre.reset()
        core = pre.env._core
        launched(core)
        # The JAX env keeps no raw frame: its 2-frame max redraws the last
        # one from the state it is handed, so to that env the ball was in
        # flight in the last frame too. Show the host the same last frame.
        pre._raw_buffer[-1] = core.render()

        state, obs_j = breakout_jax.reset(jax.random.PRNGKey(0), 1)
        state = jax_launched(state)
        assert np.abs(np.asarray(obs_j[0], np.int32)
                      - obs_h.astype(np.int32)).max() <= 1

        rng = np.random.default_rng(7)
        actions = rng.choice([breakout_sim.NOOP, breakout_sim.RIGHT,
                              breakout_sim.LEFT], size=40)
        total_h = total_j = 0.0
        for t, a in enumerate(actions):
            obs_h, r_h, done_h, info_h = pre.step(int(a))
            state, obs_j, r_j, done_j, _ = breakout_jax.step(
                state, jnp.asarray([a]), jax.random.PRNGKey(100 + t),
                life_loss=False)
            total_h += r_h
            total_j += float(r_j[0])
            assert float(r_j[0]) == r_h, f"step {t}: reward {r_j[0]} != {r_h}"
            assert int(state.lives[0]) == info_h["lives"], f"step {t}"
            assert bool(done_j[0]) == done_h, f"step {t}"
            assert np.abs(np.asarray(obs_j[0], np.int32)
                          - obs_h.astype(np.int32)).max() <= 1, f"step {t}"
            if done_h:
                break
        assert total_j == total_h
        assert total_j > 0, "pattern never hit a brick; test is vacuous"
        np.testing.assert_array_equal(np.asarray(state.bricks[0]), core.bricks)


class TestEpisodeSemantics:
    def _about_to_die(self, n=1, lives=1):
        state, _ = breakout_jax.reset(jax.random.PRNGKey(0), n)
        state = jax_launched(state, x=80.0, y=200.0, vx=0.0, vy=3.0)
        return state._replace(
            lives=jnp.full(n, lives, jnp.int32),
            returns=jnp.full(n, 11.0, jnp.float32))

    def test_life_loss_surfaces_done_without_reset(self):
        state = self._about_to_die(lives=3)
        bricks_before = np.asarray(state.bricks[0]).copy()
        state, obs, r, done, ep = breakout_jax.step(
            state, jnp.asarray([breakout_sim.NOOP]), jax.random.PRNGKey(1))
        assert bool(done[0])
        assert float(ep[0]) == 0.0  # not a real game over
        assert int(state.lives[0]) == 2
        assert bool(state.ball_dead[0])
        np.testing.assert_array_equal(np.asarray(state.bricks[0]), bricks_before)

    def test_game_over_resets_and_reports_return(self):
        state = self._about_to_die(lives=1)
        state = state._replace(bricks=state.bricks.at[0, 2, 5].set(False))
        state, obs, r, done, ep = breakout_jax.step(
            state, jnp.asarray([breakout_sim.NOOP]), jax.random.PRNGKey(1))
        assert bool(done[0])
        assert float(ep[0]) == 11.0
        assert int(state.lives[0]) == 5
        assert bool(np.asarray(state.bricks).all())
        assert float(state.returns[0]) == 0.0
        # The observation is the RESET observation: newest frame live,
        # older stack slots zeroed.
        assert (np.asarray(obs[0, :, :, :3]) == 0).all()
        assert np.asarray(obs[0, :, :, 3]).any()

    def test_life_loss_replaces_reward_with_minus_one(self):
        """Reference shaping (`train_impala.py:149-154`, host parity
        `runtime/impala_runner.py`): a lost life records r=-1; a TRUE
        game over keeps the raw reward."""
        state = self._about_to_die(lives=3)
        _, _, r, done, _ = breakout_jax.step(
            state, jnp.asarray([breakout_sim.NOOP]), jax.random.PRNGKey(1))
        assert bool(done[0])
        assert float(r[0]) == -1.0
        # Last life: game over, shaping must NOT apply.
        state = self._about_to_die(lives=1)
        _, _, r, done, _ = breakout_jax.step(
            state, jnp.asarray([breakout_sim.NOOP]), jax.random.PRNGKey(1))
        assert bool(done[0])
        assert float(r[0]) == 0.0

    def test_life_loss_flag_off_mirrors_raw_done(self):
        state = self._about_to_die(lives=3)
        _, _, _, done, _ = breakout_jax.step(
            state, jnp.asarray([breakout_sim.NOOP]), jax.random.PRNGKey(1),
            life_loss=False)
        assert not bool(done[0])

    def test_fire_relaunches_after_life_loss(self):
        state = self._about_to_die(lives=3)
        state, *_ = breakout_jax.step(
            state, jnp.asarray([breakout_sim.NOOP]), jax.random.PRNGKey(1))
        assert bool(state.ball_dead[0])
        state, *_ = breakout_jax.step(
            state, jnp.asarray([breakout_sim.FIRE]), jax.random.PRNGKey(2))
        assert not bool(state.ball_dead[0])
        assert float(state.vy[0]) < 0


class TestAnakinBreakout:
    def cfg(self, **kw):
        base = dict(obs_shape=(84, 84, 4), num_actions=4, trajectory=5,
                    lstm_size=16, entropy_coef=0.01,
                    start_learning_rate=1e-3, end_learning_rate=1e-3)
        base.update(kw)
        return ImpalaConfig(**base)

    def test_train_chunk_runs_and_is_finite(self):
        anakin = AnakinImpala(ImpalaAgent(self.cfg()), num_envs=2,
                              env=breakout_jax)
        st = anakin.init(jax.random.PRNGKey(0))
        st, m = anakin.train_chunk(st, 2)
        assert int(st.train.step) == 2
        assert np.isfinite(np.asarray(m["total_loss"])).all()
        assert st.obs.dtype == jnp.uint8

    def test_aliased_18_way_head(self):
        """A reference-style 18-way head drives the 4-action env via
        `action %% 4` (train_impala.py:145 parity)."""
        anakin = AnakinImpala(ImpalaAgent(self.cfg(num_actions=18)),
                              num_envs=2, env=breakout_jax)
        st = anakin.init(jax.random.PRNGKey(0))
        st, m = anakin.train_chunk(st, 1)
        assert np.isfinite(np.asarray(m["total_loss"])).all()

    def test_obs_shape_guard(self):
        with pytest.raises(ValueError):
            AnakinImpala(ImpalaAgent(self.cfg(obs_shape=(4,), num_actions=4)),
                         2, env=breakout_jax)

    def test_mesh_matches_single_device(self):
        """Pixel-env Anakin over an 8-device data mesh computes the same
        update as the single-device program (render + preprocess +
        collect shard with the envs; XLA inserts the gradient psum)."""
        from distributed_reinforcement_learning_tpu.parallel import make_mesh

        agent = ImpalaAgent(self.cfg(trajectory=4, lstm_size=8))
        ref = AnakinImpala(agent, num_envs=8, env=breakout_jax)
        ref_st = ref.init(jax.random.PRNGKey(3))
        ref_st, ref_m = ref.train_chunk(ref_st, 2)

        sharded = AnakinImpala(agent, num_envs=8, mesh=make_mesh(8),
                               env=breakout_jax)
        st = sharded.init(jax.random.PRNGKey(3))
        st, m = sharded.train_chunk(st, 2)

        np.testing.assert_allclose(np.asarray(ref_m["total_loss"]),
                                   np.asarray(m["total_loss"]),
                                   rtol=2e-4, atol=2e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5),
            jax.device_get(ref_st.train.params), jax.device_get(st.train.params))


# -- parity with the formulation that carried the RGB raster ----------------


def _plain_push(stack, frame, over, frame0):
    """The byte form of the history, kept plain: `u8[N, 84, 84, 4]`,
    newest last, shifted by a concat; a game-over slot holds zeros with
    the reset frame newest. What `pixel_jax.push` + `observe` stand for."""
    shifted = jnp.concatenate([stack[..., 1:], frame[..., None]], axis=-1)
    fresh = jnp.zeros_like(stack).at[..., -1].set(frame0)
    return jnp.where(over[:, None, None, None], fresh, shifted)


class _RasterState(NamedTuple):
    """PR 24's state: the game, and the last RGB frame beside it. The
    twin's `game.history` is the PLAIN history: the `u8[N, 84, 84, 4]`
    stack `_plain_push` shifts, not `step`'s words."""

    game: breakout_jax.BreakoutState
    prev_raw: jax.Array  # [N, 210, 160, 3] u8


def _raster_reset(n):
    state, obs = breakout_jax.reset(jax.random.PRNGKey(0), n)
    raw = breakout_jax._render_batch(state._asdict())
    frame0 = jax.vmap(pixel_jax.preprocess)(raw)
    stack = _plain_push(jnp.zeros((n, 84, 84, 4), jnp.uint8), frame0,
                        jnp.ones(n, bool), frame0)
    return _RasterState(state._replace(history=stack), raw), stack


@functools.partial(jax.jit, static_argnames=("max_frames",))
def _raster_step(rs, actions, rng, max_frames):
    """`breakout_jax.step` as PR 24 had it, kept plain: the state carries
    the RGB raster; render the live board AND the reset board, 2-frame
    max against the carried raster, `preprocess`, and select the PICTURES
    (raster and stack) on game-over."""
    state, prev_raw = rs
    n = state.lives.shape[0]
    draws = jax.random.randint(rng, (4, n), 0, 4)
    launch_vx = jnp.asarray([-2.0, -1.0, 1.0, 2.0], jnp.float32)[draws]
    carry = (state.bricks, state.lives, state.frames, state.paddle_x,
             state.ball_dead, state.ball_x, state.ball_y, state.vx, state.vy,
             jnp.zeros((n,), jnp.float32), jnp.zeros((n,), bool))
    emulate = jax.vmap(breakout_jax._emulate_frame, in_axes=(0, 0, 0, None))
    for i in range(4):
        carry = emulate(carry, actions.astype(jnp.int32), launch_vx[i],
                        max_frames)
    *live, reward, game_over = carry
    names = ("bricks", "lives", "frames", "paddle_x", "ball_dead", "ball_x",
             "ball_y", "vx", "vy")
    live = dict(zip(names, live))

    raw = breakout_jax._render_batch(live)
    frame = pixel_jax.frame_of(raw, prev_raw)

    live["returns"] = state.returns + reward
    episode_return = jnp.where(game_over, live["returns"], 0.0)
    lost_life = live["lives"] < state.lives
    done = game_over | lost_life
    reward = jnp.where(lost_life & ~game_over, -1.0, reward)

    fresh = breakout_jax._reset_fields(n)
    raw0 = breakout_jax._render_batch(fresh)
    stack = _plain_push(state.history, frame, game_over,
                        jax.vmap(pixel_jax.preprocess)(raw0))
    pick = pixel_jax.make_pick(game_over)
    new_state = breakout_jax.BreakoutState(
        history=stack, **{k: pick(fresh[k], live[k]) for k in live})
    return (_RasterState(new_state, pick(raw0, raw)), stack, reward,
            done, episode_return)


def _assert_same_step(out, out_r, t):
    """`breakout_jax.step`'s results against `_raster_step`'s, bit for bit:
    obs, reward, done, episode return and every leaf of the state (the
    history's words unpacked, against the twin's bytes)."""
    (state, *rest), (rs, *rest_r) = out, out_r
    for name, got, want in zip(("obs", "reward", "done", "episode_return"),
                               rest, rest_r):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want), err_msg=f"{name}, step {t}")
    state = state._replace(history=pixel_jax.observe(state.history))
    for name in state._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(state, name)), np.asarray(getattr(rs.game, name)),
            err_msg=f"state.{name}, step {t}")


class TestNoRasterInState:
    """`step` selects the game state on auto-reset and renders once; the
    parent selected the pictures and carried the last RGB frame."""

    N, STEPS, MAX_FRAMES = 8, 100, 240

    def _rollout(self):
        """Envs 0-3 play at random (life losses); 4-5 enter with one life
        and a falling ball (game over by lives); 6 with one brick left
        right above a rising ball (cleared board); 7 idles on NOOP into
        `max_frames`. Yields what both formulations return each step."""
        n = self.N
        rs, obs_r = _raster_reset(n)
        state, obs = breakout_jax.reset(jax.random.PRNGKey(0), n)
        np.testing.assert_array_equal(np.asarray(obs), np.asarray(obs_r))

        def edit(s):
            i = jnp.arange(n)
            dying, clearing = (i == 4) | (i == 5), i == 6
            launched = dying | clearing
            last = jnp.zeros((6, 18), bool).at[5, 9].set(True)
            return s._replace(
                lives=jnp.where(dying, 1, s.lives),
                bricks=jnp.where(clearing[:, None, None], last, s.bricks),
                ball_dead=s.ball_dead & ~launched,
                ball_x=jnp.where(launched, 83.0, s.ball_x),
                ball_y=jnp.where(dying, 190.0, jnp.where(clearing, 100.0, s.ball_y)),
                vy=jnp.where(dying, 3.0, jnp.where(clearing, -3.0, s.vy)))

        state, rs = edit(state), rs._replace(game=edit(rs.game))
        rng = np.random.default_rng(11)
        for t in range(self.STEPS):
            a = rng.integers(0, 4, size=n)
            a[7] = breakout_sim.NOOP
            a, key = jnp.asarray(a), jax.random.PRNGKey(1000 + t)
            out = breakout_jax.step(state, a, key, max_frames=self.MAX_FRAMES)
            out_r = _raster_step(rs, a, key, max_frames=self.MAX_FRAMES)
            state, rs = out[0], out_r[0]
            yield t, out, out_r

    def test_bit_identical_to_the_raster_formulation(self):
        seen = dict(life_loss=0, game_over=0, cleared=0, timed_out=0,
                    after_reset=0)
        was_over = np.zeros(self.N, bool)
        for t, out, out_r in self._rollout():
            _assert_same_step(out, out_r, t)
            state, _, _, done, _ = out
            over = np.asarray(breakout_jax.completed_episode_mask(done, state))
            seen["life_loss"] += int((np.asarray(done) & ~over).sum())
            seen["game_over"] += int(over[4:6].sum())
            seen["cleared"] += int(over[6])
            seen["timed_out"] += int(over[7])
            seen["after_reset"] += int(was_over.sum())
            was_over = over
        assert all(seen.values()), f"rollout never covered: {seen}"

    def test_no_leaf_of_the_state_is_larger_than_the_observation(self):
        state, obs = jax.eval_shape(
            lambda: breakout_jax.reset(jax.random.PRNGKey(0), self.N))
        assert "prev_raw" not in state._fields
        for name, leaf in state._asdict().items():
            assert leaf.size * leaf.dtype.itemsize <= obs.size * obs.dtype.itemsize, name


# -- the history as 32-bit words (PR 48) --------------------------------------

_GAMES = {"breakout": breakout_jax, "invaders": invaders_jax, "pong": pong_jax}


@functools.lru_cache(maxsize=None)
def _word_rollout(game):
    """Ten steps of `game` at 2 envs with the frame cap at 24: env 1 enters
    with 12 frames played, so it is over at step 2 and env 0 at step 5.
    -> (reset obs, [(obs, history words, game-over mask) a step])."""
    env = _GAMES[game]
    state, obs0 = env.reset(jax.random.PRNGKey(0), 2)
    state = state._replace(frames=jnp.asarray([0, 12], jnp.int32))
    rng, steps = np.random.default_rng(5), []
    for t in range(10):
        before = np.asarray(state.frames)
        actions = jnp.asarray(rng.integers(0, env.NUM_ACTIONS, size=2))
        state, obs, *_ = env.step(state, actions, jax.random.PRNGKey(t),
                                  max_frames=24)
        over = np.asarray(state.frames) < before  # the auto-reset zeroes them
        steps.append((np.asarray(obs), np.asarray(state.history), over))
    return np.asarray(obs0), steps


@pytest.mark.parametrize("case", ["after_reset", "mid_run_reset", "byte_order"])
@pytest.mark.parametrize("game", sorted(_GAMES))
def test_history_words_match_the_plain_stack(game, case):
    """`pixel_jax.push` + `observe`, alone and as the three games' `step`
    uses them, against `_plain_push` on the same frames: the first four
    steps after a reset fill the stack from the newest slot down; a slot
    whose game ends mid-run shows the reset frame over three planes of
    zeros while the other slot shifts on; byte `k` of a word is the frame
    at stack index `k` (`lax.bitcast_convert_type`'s order)."""
    obs0, steps = _word_rollout(game)
    frame0 = obs0[..., 3]
    assert frame0.any() and not obs0[..., :3].any()
    plain = jnp.asarray(obs0)
    words = words0 = pixel_jax.push(jnp.zeros((2, 84, 84), jnp.uint32),
                                    jnp.asarray(frame0), jnp.ones(2, bool))
    np.testing.assert_array_equal(np.asarray(pixel_jax.observe(words)), obs0)
    overs = np.stack([over for _, _, over in steps])
    assert overs[2].tolist() == [False, True] and overs[5].tolist() == [True, False]
    window = {"after_reset": range(0, 4), "mid_run_reset": range(2, 10),
              "byte_order": range(0, 10)}[case]
    for t, (obs, history, over) in enumerate(steps):
        frame = jnp.asarray(obs[..., 3])  # the frame `step` made
        plain = _plain_push(plain, frame, jnp.asarray(over), jnp.asarray(frame0))
        words = pixel_jax.push(words, frame, jnp.asarray(over),
                               None if game == "breakout" else words0)
        if t not in window:
            continue
        if case == "byte_order":
            np.testing.assert_array_equal(
                np.asarray(jax.lax.bitcast_convert_type(jnp.asarray(history),
                                                        jnp.uint8)), obs)
            np.testing.assert_array_equal(history >> 24, obs[..., 3])
            np.testing.assert_array_equal(history & 0xFF, obs[..., 0])
            continue
        np.testing.assert_array_equal(obs, np.asarray(plain), err_msg=f"step {t}")
        np.testing.assert_array_equal(np.asarray(words), history, err_msg=f"step {t}")
        np.testing.assert_array_equal(np.asarray(pixel_jax.observe(words)), obs)
        if case == "after_reset":  # env 0: t + 1 frames since its reset
            filled = obs[0].reshape(-1, 4).any(axis=0).tolist()
            assert filled == [k >= 2 - t for k in range(4)], (t, filled)
        for e in np.flatnonzero(over):
            assert not obs[e, ..., :3].any()
            np.testing.assert_array_equal(obs[e, ..., 3], frame0[e])


# -- the luma plane `step` selects from tables (PR 35) ------------------------


def _class_colours():
    """`[3, 210, 160, 3]` u8: what a pixel of each class shows, from the
    module's constants by plain numpy broadcasting."""
    shape = (breakout_sim.H, breakout_sim.W, 3)
    return np.stack([
        breakout_jax._BASE,
        np.broadcast_to(breakout_jax._ROW_RGB_Y[:, None, :], shape),
        np.broadcast_to(breakout_jax._SPRITE, shape)])


def _parent_preprocess(rgb):
    """`pixel_jax.preprocess` as it was written before `luma` and `resize`
    were factored out of it."""
    luma = rgb.astype(jnp.float32) @ jnp.asarray(pixel_jax._LUMA)
    resized = jnp.asarray(pixel_jax._WH_CROP) @ luma @ jnp.asarray(pixel_jax._WW_T)
    return resized.astype(jnp.uint8)


class TestLumaPlane:
    @pytest.mark.parametrize("a", range(3))
    @pytest.mark.parametrize("b", range(3))
    def test_table_is_the_luma_preprocess_makes_of_that_colour_pair(self, a, b):
        colours = _class_colours()
        want = pixel_jax.luma(jnp.maximum(jnp.asarray(colours[a]),
                                          jnp.asarray(colours[b])))
        full = breakout_jax._luma_tables((0, breakout_sim.H))
        np.testing.assert_array_equal(np.asarray(full[a, b]), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(full[b, a]), np.asarray(want))
        lo, hi = pixel_jax.CROP_ROWS
        np.testing.assert_array_equal(
            np.asarray(breakout_jax._luma_tables((lo, hi))[a, b]),
            np.asarray(want[lo:hi]))

    def test_scanline_window_is_the_nonzero_columns_of_the_crop_weights(self):
        lo, hi = pixel_jax.CROP_ROWS
        read = pixel_jax._WH_CROP.any(axis=0)
        assert read[lo:hi].all() and not read[:lo].any() and not read[hi:].any()
        assert (lo, hi) == (34, 195)  # today's weights: 161 of 210 scanlines
        # Nothing the window leaves out is drawn differently from frame to
        # frame but the ball on its way out, which the crop never showed.
        assert lo < breakout_sim.BRICK_TOP and hi > breakout_sim.PADDLE_Y + breakout_sim.PADDLE_H

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_plane_is_the_luma_of_the_maxed_rasters_inside_the_window(self, seed):
        rng = np.random.default_rng(seed)
        n = 16

        def fields():
            dead = rng.random(n) < 0.25
            ball_y = rng.uniform(breakout_sim.WALL_TOP, breakout_sim.H - 2, n)
            ball_y[:6] = rng.uniform(breakout_sim.BRICK_TOP - 2,
                                     breakout_sim.BRICK_TOP + 36, 6)
            return dict(
                bricks=jnp.asarray(rng.random((n, 6, 18)) < 0.5),
                paddle_x=jnp.asarray(rng.integers(8, 137, n), jnp.float32),
                ball_dead=jnp.asarray(dead),
                ball_x=jnp.asarray(rng.uniform(0, breakout_sim.W - 2, n), jnp.float32),
                ball_y=jnp.asarray(ball_y, jnp.float32))

        new, old = fields(), fields()
        got = np.asarray(breakout_jax._luma_batch(new, old))
        want = np.asarray(pixel_jax.luma(jnp.maximum(
            breakout_jax._render_batch(new), breakout_jax._render_batch(old))))
        lo, hi = pixel_jax.CROP_ROWS
        np.testing.assert_array_equal(got[:, lo:hi], want[:, lo:hi])
        assert not got[:, :lo].any() and not got[:, hi:].any()

    @pytest.mark.parametrize("game", ["pong", "invaders"])
    def test_other_games_preprocess_is_unchanged(self, game):
        from distributed_reinforcement_learning_tpu.envs import invaders_jax, pong_jax

        env = dict(pong=pong_jax, invaders=invaders_jax)[game]
        state, obs = env.reset(jax.random.PRNGKey(0), 4)
        rng = np.random.default_rng(5)
        for t in range(6):
            prev = state.prev_raw
            a = jnp.asarray(rng.integers(0, env.NUM_ACTIONS, 4))
            state, obs, *_ = env.step(state, a, jax.random.PRNGKey(t))
            frames = jnp.maximum(state.prev_raw, prev)
            want = np.asarray(jax.vmap(_parent_preprocess)(frames))
            np.testing.assert_array_equal(
                np.asarray(jax.vmap(pixel_jax.preprocess)(frames)), want)
            if not np.asarray(state.frames == 0).any():  # no slot was reset
                np.testing.assert_array_equal(np.asarray(obs[..., -1]), want)
        assert want.any()


class TestDrawnCases:
    """A seeded rollout whose frames hold every case the masks tell apart,
    held to the raster formulation bit for bit."""

    N, STEPS, MAX_FRAMES = 8, 28, 10_000

    def _enter(self):
        """0: LEFT to the wall under a dead ball; 1: RIGHT to the other
        wall; 2: a ball at rest in a cleared cell with a live brick under
        its right column; 3: a ball over the side wall; 4: one life left
        and a falling ball; 5: three lives and a falling ball; 6: one brick
        left right above a rising ball; 7: plays at random."""
        n = self.N
        state, _ = breakout_jax.reset(jax.random.PRNGKey(0), n)
        i = jnp.arange(n)
        resting, walled, dying, clearing = i == 2, i == 3, (i == 4) | (i == 5), i == 6
        live = resting | walled | dying | clearing
        last = jnp.zeros((6, 18), bool).at[5, 9].set(True)
        holed = jnp.ones((6, 18), bool).at[3, 5].set(False)
        bricks = jnp.where(clearing[:, None, None], last, state.bricks)
        bricks = jnp.where(resting[:, None, None], holed, bricks)
        x = jnp.select([resting, walled, dying | clearing],
                       [8.0 + 5 * 8 + 7, 7.0, 83.0], state.ball_x)
        y = jnp.select([resting, walled, dying, clearing],
                       [57.0 + 3 * 6 + 2, 120.0, 190.0, 100.0], state.ball_y)
        vy = jnp.select([dying, clearing], [3.0, -3.0], state.vy)
        state = state._replace(
            bricks=bricks, ball_dead=state.ball_dead & ~live, ball_x=x, ball_y=y,
            vy=vy, lives=jnp.where(i == 4, 1, jnp.where(i == 5, 3, state.lives)))
        stack = _raster_reset(n)[0].game.history  # the edits drew no frame yet
        return state, _RasterState(state._replace(history=stack),
                                   breakout_jax._render_batch(state._asdict()))

    @staticmethod
    def _drawn(state):
        """Which cases the frame of `state` holds, per env."""
        s = {k: np.asarray(getattr(state, k)) for k in breakout_jax._DRAWN}
        bx, by = s["ball_x"].astype(int), s["ball_y"].astype(int)
        live = ~s["ball_dead"]
        over_brick = np.zeros(len(bx), bool)
        for dy in range(2):
            for dx in range(2):
                r = (by + dy - breakout_sim.BRICK_TOP) // breakout_sim.BRICK_H
                c = (bx + dx - breakout_sim.WALL_SIDE) // breakout_sim.BRICK_W
                inside = (r >= 0) & (r < 6) & (c >= 0) & (c < 18)
                over_brick |= inside & s["bricks"][
                    np.arange(len(bx)), r.clip(0, 5), c.clip(0, 17)]
        return dict(
            ball_over_brick=live & over_brick,
            ball_over_side_wall=live & ((bx < breakout_sim.WALL_SIDE) | (
                bx + 2 > breakout_sim.W - breakout_sim.WALL_SIDE)),
            paddle_at_left_wall=s["paddle_x"] == breakout_sim.WALL_SIDE,
            paddle_at_right_wall=s["paddle_x"] == (
                breakout_sim.W - breakout_sim.WALL_SIDE - breakout_sim.PADDLE_W),
            dead_ball=s["ball_dead"])

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_rollout_equals_the_raster_formulation(self, seed):
        state, rs = self._enter()
        seen = {k: int(v.sum()) for k, v in self._drawn(state).items()}
        seen.update(life_loss=0, game_over=0, cleared_board=0)
        rng = np.random.default_rng(seed)
        for t in range(self.STEPS):
            a = rng.integers(0, 4, size=self.N)
            a[0], a[1], a[2:7] = breakout_sim.LEFT, breakout_sim.RIGHT, breakout_sim.NOOP
            a, key = jnp.asarray(a), jax.random.PRNGKey(1000 * seed + t)
            out = breakout_jax.step(state, a, key, max_frames=self.MAX_FRAMES)
            out_r = _raster_step(rs, a, key, max_frames=self.MAX_FRAMES)
            _assert_same_step(out, out_r, t)
            (state, _, _, done, _), rs = out, out_r[0]
            for k, v in self._drawn(state).items():
                seen[k] += int(v.sum())
            over = np.asarray(breakout_jax.completed_episode_mask(done, state))
            seen["life_loss"] += int((np.asarray(done) & ~over).sum())
            seen["game_over"] += int(over[4])
            # A cleared board is reset in the step that clears it, so no
            # state shows one: env 6 has five lives and no frame limit, and
            # only clearing its last brick ends its game.
            seen["cleared_board"] += int(over[6])
        assert all(seen.values()), f"rollout never covered: {seen}"

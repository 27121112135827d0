"""Pong as pure-JAX functions: the second on-device (Anakin) pixel env.

Same game as `envs.pong_sim.PongCore` (the faithful ALE-spec proxy),
re-expressed as jittable batched pure functions following the
`cartpole_jax`/`breakout_jax` env contract, so Anakin IMPALA can train
both in-tree pixel games at chip rate. What Pong exercises that
Breakout cannot (see pong_sim's module docstring): the 6-action set,
SIGNED rewards, serve timers, an opponent AI, and no lives — `done`
here is always a true game end, so `completed_episode_mask` is the
identity.

Dynamics parity: constants and update order mirror `pong_sim.py` line
for line (2px/frame paddle, serve-timer auto-serve, capped-speed
tracking AI with dead zone, 2 collision substeps, hit-offset
deflection + rally speed-up, first to 21). Divergences match
`breakout_jax`'s documented set: float32 physics, `jax.random` streams
for the serve draws, and the score strip unrendered (the crop removes
scanlines < ~34; the bound strips ARE rendered — row 194 reaches the
last output row of the resize).

The observation pipeline is shared with `breakout_jax._preprocess`
(2-frame max -> luma -> INTER_AREA resize matmuls -> crop -> uint8 ->
4-stack), i.e. `envs.atari.AtariPreprocessor` stage for stage.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from distributed_reinforcement_learning_tpu.envs import pixel_jax
from distributed_reinforcement_learning_tpu.envs import pong_sim as sim
from distributed_reinforcement_learning_tpu.envs.pixel_jax import preprocess as _preprocess
from distributed_reinforcement_learning_tpu.observability import scopes

NUM_ACTIONS = sim.PongCore.num_actions  # NOOP/FIRE/RIGHT/LEFT/RIGHTFIRE/LEFTFIRE
OBS_SHAPE = (84, 84, 4)

H, W = sim.H, sim.W

# Static base frame: background + the two bound strips (the score strip
# region stays background — it is cropped out of every observation).
_BASE = np.empty((H, W, 3), np.uint8)
_BASE[:] = sim.BACKGROUND
_BASE[sim.FIELD_TOP - sim.BOUND_H:sim.FIELD_TOP, :] = sim.BOUNDS
_BASE[sim.FIELD_BOT:sim.FIELD_BOT + sim.BOUND_H, :] = sim.BOUNDS

_YS = np.arange(H)[:, None]  # [210, 1]
_XS = np.arange(W)[None, :]  # [1, 160]


class PongState(NamedTuple):
    """Batched game + observation-pipeline state (`[N, ...]` leaves)."""

    player_score: jax.Array  # [N] i32
    enemy_score: jax.Array   # [N] i32
    frames: jax.Array        # [N] i32 emulated frames this episode
    player_y: jax.Array      # [N] i32 (agent paddle, right side)
    enemy_y: jax.Array       # [N] i32 (AI paddle, left side)
    ball_dead: jax.Array     # [N] bool — between points
    serve_timer: jax.Array   # [N] i32 frames until auto-serve
    serve_dir: jax.Array     # [N] f32 (+1 toward the agent)
    rally: jax.Array         # [N] i32 hits this rally (speed-up)
    ball_x: jax.Array        # [N] f32
    ball_y: jax.Array        # [N] f32
    vx: jax.Array            # [N] f32
    vy: jax.Array            # [N] f32
    prev_raw: jax.Array      # [N, 210, 160, 3] u8
    history: jax.Array       # [N, 84, 84] u32 — last four frames, a byte each
    returns: jax.Array       # [N] f32 signed episode return


# -- rendering (single env; vmapped) ----------------------------------------


def _render(player_y, enemy_y, ball_dead, ball_x, ball_y) -> jax.Array:
    """`[210, 160, 3]` uint8 frame, `pong_sim.render` draw order."""
    f = jnp.asarray(_BASE)
    ys, xs = jnp.asarray(_YS), jnp.asarray(_XS)
    enemy = (
        (ys >= enemy_y) & (ys < enemy_y + sim.PADDLE_H)
        & (xs >= sim.ENEMY_X) & (xs < sim.ENEMY_X + sim.PADDLE_W)
    )
    f = jnp.where(enemy[:, :, None], jnp.asarray(np.asarray(sim.ENEMY, np.uint8)), f)
    player = (
        (ys >= player_y) & (ys < player_y + sim.PADDLE_H)
        & (xs >= sim.PLAYER_X) & (xs < sim.PLAYER_X + sim.PADDLE_W)
    )
    f = jnp.where(player[:, :, None], jnp.asarray(np.asarray(sim.PLAYER, np.uint8)), f)
    by = jnp.clip(ball_y, sim.FIELD_TOP, sim.FIELD_BOT - sim.BALL_H).astype(jnp.int32)
    bx = jnp.clip(ball_x, 0, W - sim.BALL_W).astype(jnp.int32)
    ball = (
        (~ball_dead)
        & (ys >= by) & (ys < by + sim.BALL_H)
        & (xs >= bx) & (xs < bx + sim.BALL_W)
    )
    return jnp.where(ball[:, :, None], jnp.asarray(np.asarray(sim.BOUNDS, np.uint8)), f)


# -- physics (single env; vmapped) ------------------------------------------


def _deflect(vy, vx, rally, ball_y, paddle_y):
    """Hit-offset steering + rally speed-up (`pong_sim._deflect`)."""
    off = (ball_y + sim.BALL_H / 2 - paddle_y - sim.PADDLE_H / 2) / (sim.PADDLE_H / 2)
    vy = jnp.clip(vy + 1.5 * off, -3.0, 3.0)
    rally = rally + 1
    speed = jnp.minimum(2.0 + 0.25 * rally.astype(jnp.float32), 3.5)
    vx = jnp.where(vx < 0, speed, -speed)  # reverse + speed-up
    return vy, vx, rally


def _collide(player_y, enemy_y, x, y, vx, vy, rally, dead,
             player_score, enemy_score, serve_timer, serve_dir, reward):
    """One `pong_sim._collide` pass; returns updated running values."""
    # Top/bottom bounds.
    vy = jnp.where(y <= sim.FIELD_TOP, jnp.abs(vy), vy)
    vy = jnp.where(y >= sim.FIELD_BOT - sim.BALL_H, -jnp.abs(vy), vy)
    y = jnp.clip(y, sim.FIELD_TOP, sim.FIELD_BOT - sim.BALL_H)
    # Agent paddle (right): only when moving toward it.
    pyf = player_y.astype(jnp.float32)
    hit_p = (
        (vx > 0) & ~dead
        & (x >= sim.PLAYER_X - sim.BALL_W) & (x <= sim.PLAYER_X + sim.PADDLE_W)
        & (y >= pyf - sim.BALL_H) & (y <= pyf + sim.PADDLE_H)
    )
    x = jnp.where(hit_p, jnp.float32(sim.PLAYER_X - sim.BALL_W), x)
    dvy, dvx, drally = _deflect(vy, vx, rally, y, pyf)
    vy = jnp.where(hit_p, dvy, vy)
    vx = jnp.where(hit_p, dvx, vx)
    rally = jnp.where(hit_p, drally, rally)
    # Enemy paddle (left).
    eyf = enemy_y.astype(jnp.float32)
    hit_e = (
        (vx < 0) & ~dead
        & (x >= sim.ENEMY_X - sim.BALL_W) & (x <= sim.ENEMY_X + sim.PADDLE_W)
        & (y >= eyf - sim.BALL_H) & (y <= eyf + sim.PADDLE_H)
    )
    x = jnp.where(hit_e, jnp.float32(sim.ENEMY_X + sim.PADDLE_W), x)
    dvy, dvx, drally = _deflect(vy, vx, rally, y, eyf)
    vy = jnp.where(hit_e, dvy, vy)
    vx = jnp.where(hit_e, dvx, vx)
    rally = jnp.where(hit_e, drally, rally)
    # Scoring: the agent owns the right side.
    scored_on = (x >= W - sim.BALL_W) & ~dead
    scored = (x <= 0) & ~dead & ~scored_on
    enemy_score = enemy_score + scored_on.astype(jnp.int32)
    player_score = player_score + scored.astype(jnp.int32)
    point = scored_on | scored
    dead = dead | point
    serve_timer = jnp.where(point, sim.SERVE_DELAY, serve_timer)
    serve_dir = jnp.where(scored_on, 1.0, jnp.where(scored, -1.0, serve_dir))
    reward = reward - scored_on.astype(jnp.float32) + scored.astype(jnp.float32)
    return (player_y, enemy_y, x, y, vx, vy, rally, dead,
            player_score, enemy_score, serve_timer, serve_dir, reward)


def _emulate_frame(carry, action, serve_y, serve_vy, max_frames):
    """One emulated frame under a held action (`_emulate_frame` parity)."""
    (player_score, enemy_score, frames, player_y, enemy_y, dead, serve_timer,
     serve_dir, rally, x, y, vx, vy, reward, halted) = carry
    live = ~halted
    frames = frames + live.astype(jnp.int32)

    up = (action == sim.RIGHT) | (action == sim.RIGHTFIRE)
    down = (action == sim.LEFT) | (action == sim.LEFTFIRE)
    fire = (action == sim.FIRE) | (action == sim.RIGHTFIRE) | (action == sim.LEFTFIRE)
    player_y = jnp.where(live & up,
                         jnp.maximum(sim.FIELD_TOP, player_y - 2), player_y)
    player_y = jnp.where(live & down,
                         jnp.minimum(sim.FIELD_BOT - sim.PADDLE_H, player_y + 2),
                         player_y)

    # Serve: FIRE serves immediately; the timer auto-serves otherwise.
    serve_timer = serve_timer - (live & dead).astype(jnp.int32)
    serving = live & dead & (fire | (serve_timer <= 0))
    x = jnp.where(serving, jnp.float32(W // 2), x)
    y = jnp.where(serving, serve_y, y)
    vx = jnp.where(serving, 2.0 * serve_dir, vx)
    vy = jnp.where(serving, serve_vy, vy)
    rally = jnp.where(serving, 0, rally)
    dead = dead & ~serving

    # Computer paddle: capped-speed ball tracking with a dead zone.
    track = live & ~dead & (vx < 0)
    target = y + sim.BALL_H / 2 - sim.PADDLE_H / 2
    diff = target - enemy_y.astype(jnp.float32)
    step_px = jnp.clip(diff, -2.0, 2.0).astype(jnp.int32)
    enemy_y = jnp.where(track & (jnp.abs(diff) > 3), enemy_y + step_px, enemy_y)
    enemy_y = jnp.clip(enemy_y, sim.FIELD_TOP, sim.FIELD_BOT - sim.PADDLE_H)

    # Two collision substeps (anti-tunnelling, `pong_sim.py:150-158`).
    for _ in range(2):
        moving = live & ~dead
        x = x + jnp.where(moving, vx / 2.0, 0.0)
        y = y + jnp.where(moving, vy / 2.0, 0.0)
        new = _collide(player_y, enemy_y, x, y, vx, vy, rally, dead,
                       player_score, enemy_score, serve_timer, serve_dir,
                       reward)
        (_, _, x2, y2, vx2, vy2, rally2, dead2,
         ps2, es2, st2, sd2, reward2) = new
        x = jnp.where(moving, x2, x)
        y = jnp.where(moving, y2, y)
        vx = jnp.where(moving, vx2, vx)
        vy = jnp.where(moving, vy2, vy)
        rally = jnp.where(moving, rally2, rally)
        dead = jnp.where(moving, dead2, dead)
        player_score = jnp.where(moving, ps2, player_score)
        enemy_score = jnp.where(moving, es2, enemy_score)
        serve_timer = jnp.where(moving, st2, serve_timer)
        serve_dir = jnp.where(moving, sd2, serve_dir)
        reward = jnp.where(moving, reward2, reward)

    game_over = ((player_score >= sim.WIN_SCORE)
                 | (enemy_score >= sim.WIN_SCORE)
                 | (frames >= max_frames))
    halted = halted | (live & game_over)
    return (player_score, enemy_score, frames, player_y, enemy_y, dead,
            serve_timer, serve_dir, rally, x, y, vx, vy, reward, halted)


# -- public API (cartpole_jax contract) -------------------------------------


def _reset_fields(n: int):
    mid = (sim.FIELD_TOP + sim.FIELD_BOT - sim.PADDLE_H) // 2
    return dict(
        player_score=jnp.zeros((n,), jnp.int32),
        enemy_score=jnp.zeros((n,), jnp.int32),
        frames=jnp.zeros((n,), jnp.int32),
        player_y=jnp.full((n,), mid, jnp.int32),
        enemy_y=jnp.full((n,), mid, jnp.int32),
        ball_dead=jnp.ones((n,), bool),
        serve_timer=jnp.full((n,), sim.SERVE_DELAY, jnp.int32),
        serve_dir=jnp.ones((n,), jnp.float32),  # toward the agent first
        rally=jnp.zeros((n,), jnp.int32),
        ball_x=jnp.zeros((n,), jnp.float32),
        ball_y=jnp.zeros((n,), jnp.float32),
        vx=jnp.zeros((n,), jnp.float32),
        vy=jnp.zeros((n,), jnp.float32),
        returns=jnp.zeros((n,), jnp.float32),
    )


def reset(rng: jax.Array, num_envs: int) -> tuple[PongState, jax.Array]:
    """-> (state, obs `[N, 84, 84, 4]` u8). Deterministic (paddles
    centered, serve pending); `rng` kept for the env contract."""
    del rng
    f = _reset_fields(num_envs)
    raw = jax.vmap(_render)(
        f["player_y"], f["enemy_y"], f["ball_dead"], f["ball_x"], f["ball_y"])
    state = PongState(prev_raw=raw, history=pixel_jax.reset_history(raw), **f)
    return state, pixel_jax.observe(state.history)


@functools.partial(jax.jit, static_argnames=("frameskip", "max_frames"))
def step(
    state: PongState,
    actions: jax.Array,
    rng: jax.Array,
    frameskip: int = 4,
    max_frames: int = 20_000,
) -> tuple[PongState, jax.Array, jax.Array, jax.Array, jax.Array]:
    """-> (state', obs', reward, done, episode_return).

    Contract matches `cartpole_jax.step`; every `done` is a true game
    end (first to 21 or the frame cap), with the fresh-game observation
    in the done slots' `obs'`.
    """
    n = state.frames.shape[0]
    k_y, k_vy = jax.random.split(rng)
    serve_y = jax.random.randint(
        k_y, (frameskip, n), sim.FIELD_TOP + 20, sim.FIELD_BOT - 20
    ).astype(jnp.float32)
    serve_vy = jnp.asarray([-1.0, -0.5, 0.5, 1.0], jnp.float32)[
        jax.random.randint(k_vy, (frameskip, n), 0, 4)]

    carry = (state.player_score, state.enemy_score, state.frames,
             state.player_y, state.enemy_y, state.ball_dead,
             state.serve_timer, state.serve_dir, state.rally,
             state.ball_x, state.ball_y, state.vx, state.vy,
             jnp.zeros((n,), jnp.float32), jnp.zeros((n,), bool))
    actions = actions.astype(jnp.int32)
    emulate = jax.vmap(_emulate_frame, in_axes=(0, 0, 0, 0, None))
    for i in range(frameskip):  # static unroll: action held, break-on-done
        carry = emulate(carry, actions, serve_y[i], serve_vy[i], max_frames)
    (player_score, enemy_score, frames, player_y, enemy_y, ball_dead,
     serve_timer, serve_dir, rally, ball_x, ball_y, vx, vy, reward,
     game_over) = carry

    with jax.named_scope(scopes.RENDER):
        raw = jax.vmap(_render)(player_y, enemy_y, ball_dead, ball_x, ball_y)
        frame = pixel_jax.frame_of(raw, state.prev_raw)

    returns = state.returns + reward
    episode_return = jnp.where(game_over, returns, 0.0)

    fresh = _reset_fields(n)
    with jax.named_scope(scopes.RENDER):
        raw0 = jax.vmap(_render)(
            fresh["player_y"], fresh["enemy_y"], fresh["ball_dead"],
            fresh["ball_x"], fresh["ball_y"])
        history = pixel_jax.push(state.history, frame, game_over,
                                 pixel_jax.reset_history(raw0))
        obs = pixel_jax.observe(history)

    pick = pixel_jax.make_pick(game_over)
    new_state = PongState(
        player_score=pick(fresh["player_score"], player_score),
        enemy_score=pick(fresh["enemy_score"], enemy_score),
        frames=pick(fresh["frames"], frames),
        player_y=pick(fresh["player_y"], player_y),
        enemy_y=pick(fresh["enemy_y"], enemy_y),
        ball_dead=pick(fresh["ball_dead"], ball_dead),
        serve_timer=pick(fresh["serve_timer"], serve_timer),
        serve_dir=pick(fresh["serve_dir"], serve_dir),
        rally=pick(fresh["rally"], rally),
        ball_x=pick(fresh["ball_x"], ball_x),
        ball_y=pick(fresh["ball_y"], ball_y),
        vx=pick(fresh["vx"], vx),
        vy=pick(fresh["vy"], vy),
        prev_raw=pick(raw0, raw),
        history=history,
        returns=pick(fresh["returns"], returns),
    )
    return new_state, obs, reward, game_over, episode_return


def completed_episode_mask(done: jax.Array, new_state: PongState) -> jax.Array:
    """Pong has no lives: every `done` is a finished game."""
    del new_state
    return done

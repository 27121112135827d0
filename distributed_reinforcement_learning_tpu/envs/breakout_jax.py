"""Breakout as pure-JAX functions: the on-device (Anakin) pixel env.

Same game as `envs.breakout_sim.BreakoutCore` (the faithful ALE-spec
proxy — see its fidelity notes), re-expressed as jittable pure functions
over a batch of N games so whole collect+learn loops run inside one
compiled TPU program (the Podracer "Anakin" pattern, arXiv:2104.06272).
This is the configuration that makes a DECISIVE Breakout score reachable
in this image: the host loop tops out at a few hundred frames/s on the
single CPU core (`benchmarks/longrun/ANALYSIS.md`), while this path
collects and learns at chip rate.

Dynamics parity: constants and update order are imported from / mirror
`breakout_sim.py` line for line (paddle ±4/frame, 2 collision substeps,
hit-position steering, row-scored bricks, 5 lives, frameskip held
action). Divergences, all deliberate and documented:

- float32 instead of Python float64 physics (TPU-native; positions are
  halves so most arithmetic is exact anyway);
- the launch velocity draw uses `jax.random` instead of
  `np.random.RandomState` — same support {-2,-1,1,2}, different stream;
- the score strip and lives indicator are NOT rendered: the reference
  crop (`wrappers.py:74`, rows 18:102 of the 110-row resize = source
  scanlines ~34..195) removes scanlines 0..34 entirely, so those pixels
  can never reach an observation;
- no fire-reset wrapper: the 4-action set includes FIRE and the policy
  learns to serve (standard for vectorized ALE training loops); a lost
  life is surfaced as `done` to the learner (the reference's life-loss
  shaping, `train_impala.py:149-154`) while the game only restarts on
  a true game-over, exactly the EpisodicLife semantics the reference's
  shaping approximates.

The observation pipeline runs on-device and matches
`envs.atari.AtariPreprocessor` stage for stage: 2-frame max over
consecutive post-frameskip raw frames -> luma -> INTER_AREA resize to
110x84 (the separable overlap weights of `atari.area_resize`, folded to
an 84x210 matrix by pre-cropping the row weights) -> [84, 84] uint8 ->
4-frame newest-last observation. The resize is two small matmuls per frame —
MXU work, which is the point of doing it on-device.

No picture is state, and `step` makes no RGB picture at all. A pixel
shows one of three things (wall or background, a brick of its row's
colour, the sprite colour), told by two masks per frame (`_classes`). The
2-frame max of a pixel is then one of 3 x 3 colour pairs, whose luma is a
constant of its position: `_luma_tables` makes those nine planes from the
colours `_render` draws, by `pixel_jax.luma`, and `step` selects among
them with the masks of the new frame and of the frame the last step drew.
That frame's masks come from five small fields (`_DRAWN`: the 6x18 board,
paddle x, ball dead / x / y; 121 bytes an env) of the state `step` was
handed, so nothing carries a frame from step to step. Only the scanlines
the crop reads (`pixel_jax.CROP_ROWS`) are made; the rest go to the resize
as the zeros its weights would have made of them. `_render` gives the RGB
frame of the same masks, for `reset` and for whoever wants a picture.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from distributed_reinforcement_learning_tpu.envs import breakout_sim as sim
from distributed_reinforcement_learning_tpu.envs import pixel_jax
from distributed_reinforcement_learning_tpu.observability import scopes

NUM_ACTIONS = sim.BreakoutCore.num_actions  # NOOP / FIRE / RIGHT / LEFT
OBS_SHAPE = (84, 84, 4)

H, W = sim.H, sim.W
_BALL = sim.BALL_SIZE

# -- static render tables ---------------------------------------------------

_YS = np.arange(H)[:, None]  # [210, 1]
_XS = np.arange(W)[None, :]  # [1, 160]

# Walls (drawn below everything else, exactly breakout_sim.render's order).
_BASE = np.zeros((H, W, 3), np.uint8)
_BASE[sim.WALL_TOP:sim.WALL_TOP + 4, :] = sim.WALL
_BASE[sim.WALL_TOP:, :sim.WALL_SIDE] = sim.WALL
_BASE[sim.WALL_TOP:, W - sim.WALL_SIDE:] = sim.WALL

# The brick field inside the screen: 6 rows x 18 columns of BRICK_H x
# BRICK_W pixels, and the colour of the brick row a scanline crosses.
_FIELD_PAD = (
    (sim.BRICK_TOP, H - sim.BRICK_TOP - 6 * sim.BRICK_H),
    (sim.WALL_SIDE, W - sim.WALL_SIDE - 18 * sim.BRICK_W),
)
_ROW_RGB_Y = np.asarray(sim.ROW_COLORS, np.uint8)[
    np.clip((np.arange(H) - sim.BRICK_TOP) // sim.BRICK_H, 0, 5)]  # [210, 3]
_SPRITE = np.asarray(sim.SPRITE, np.uint8)
_ROW_POINTS = np.asarray(sim.ROW_POINTS, np.float32)


class BreakoutState(NamedTuple):
    """Batched game + observation-pipeline state (`[N, ...]` leaves).

    The frame history is the only picture here, one 32-bit word a pixel
    (`pixel_jax`: byte 0 the oldest frame, byte 3 the newest); `step`
    returns the observation unpacked and the state does not hold it. The
    last raw frame is no state either: it always shows `_classes` of this
    state's `_DRAWN` fields (true after `reset`, kept by every `step`).
    """

    bricks: jax.Array      # [N, 6, 18] bool
    lives: jax.Array       # [N] i32
    frames: jax.Array      # [N] i32 emulated frames this episode
    paddle_x: jax.Array    # [N] f32 (integer-valued)
    ball_dead: jax.Array   # [N] bool — awaiting FIRE
    ball_x: jax.Array      # [N] f32
    ball_y: jax.Array      # [N] f32
    vx: jax.Array          # [N] f32
    vy: jax.Array          # [N] f32
    history: jax.Array     # [N, 84, 84] u32 — last four frames, a byte each
    returns: jax.Array     # [N] f32 raw (unclipped) episode return


# -- rendering (single env; vmapped) ----------------------------------------


def _classes(bricks, paddle_x, ball_dead, ball_x, ball_y, rows=(0, H)):
    """What each pixel of scanlines `rows` shows, `breakout_sim.render`
    draw order: -> (`sprite`, `brick`) bool `[rows, 160]`; `brick` is a
    live brick no sprite covers, a pixel in neither shows `_BASE`.

    The one definition of what is drawn where. Made from the 6x18 board
    by `repeat` + `pad` (no per-pixel gather) and from row and column
    comparisons, so it fuses into whatever selects by it.
    """
    ys, xs = jnp.asarray(_YS[slice(*rows)]), jnp.asarray(_XS)
    px = paddle_x.astype(jnp.int32)
    paddle = (
        (ys >= sim.PADDLE_Y) & (ys < sim.PADDLE_Y + sim.PADDLE_H)
        & (xs >= px) & (xs < px + sim.PADDLE_W)
    )
    by = ball_y.astype(jnp.int32)
    bx = ball_x.astype(jnp.int32)
    ball = (
        (~ball_dead)
        & (ys >= by) & (ys < by + _BALL)
        & (xs >= bx) & (xs < bx + _BALL)
    )
    sprite = paddle | ball
    field = jnp.repeat(jnp.repeat(bricks, sim.BRICK_H, axis=0), sim.BRICK_W, axis=1)
    brick = jnp.pad(field, _FIELD_PAD)[slice(*rows)] & ~sprite  # sprites are drawn over bricks
    return sprite, brick


def _render(bricks, paddle_x, ball_dead, ball_x, ball_y) -> jax.Array:
    """`[210, 160, 3]` uint8 frame of `_classes`: `reset`'s picture. `step`
    selects lumas by the same masks instead (`_luma_batch`)."""
    sprite, brick = _classes(bricks, paddle_x, ball_dead, ball_x, ball_y)
    f = jnp.where(brick[:, :, None], jnp.asarray(_ROW_RGB_Y)[:, None, :],
                  jnp.asarray(_BASE))
    return jnp.where(sprite[:, :, None], jnp.asarray(_SPRITE), f)


# The state fields `_classes` reads: 108 + 1 booleans and three float32.
_DRAWN = ("bricks", "paddle_x", "ball_dead", "ball_x", "ball_y")


def _render_batch(fields) -> jax.Array:
    """`[N, 210, 160, 3]` uint8 frames of a mapping of `[N, ...]` fields."""
    return jax.vmap(_render)(*(fields[k] for k in _DRAWN))


def _luma_tables(rows) -> jax.Array:
    """`[3, 3, rows, 160]` f32: the luma of `maximum(colour a, colour b)`
    at every position, for a and b in (`_BASE`, the row's brick colour,
    `_SPRITE`), `_render`'s three colours.

    Through `pixel_jax.luma`, inside the program: a table holds what
    `preprocess` makes of that colour on the device it runs on.
    """
    shown = jnp.stack([jnp.broadcast_to(jnp.asarray(c), _BASE.shape) for c in
                       (_BASE, _ROW_RGB_Y[:, None, :], _SPRITE)])[:, slice(*rows)]
    return pixel_jax.luma(jnp.maximum(shown[:, None], shown[None, :]))


def _luma_batch(fields, entered) -> jax.Array:
    """`[N, 210, 160]` f32 luma of the 2-frame max of the frames of
    `fields` and `entered` (mappings of `[N, ...]` fields), without the
    frames: a select among `_luma_tables` by both frames' `_classes`,
    made for `pixel_jax.CROP_ROWS` and zero elsewhere."""
    rows = pixel_jax.CROP_ROWS
    classes = jax.vmap(functools.partial(_classes, rows=rows))
    sprite, brick = classes(*(fields[k] for k in _DRAWN))
    sprite0, brick0 = classes(*(entered[k] for k in _DRAWN))
    base, bricked, sprited = (
        jnp.where(sprite0, t[2], jnp.where(brick0, t[1], t[0]))
        for t in _luma_tables(rows))
    plane = jnp.where(sprite, sprited, jnp.where(brick, bricked, base))
    return jnp.pad(plane, ((0, 0), (rows[0], H - rows[1]), (0, 0)))


# -- physics (single env; vmapped) ------------------------------------------


def _collide(bricks, paddle_x, lives, x, y, vx, vy, dead, reward):
    """One `breakout_sim._collide` pass; returns updated running values."""
    # Side walls.
    x = jnp.clip(x, sim.WALL_SIDE, W - sim.WALL_SIDE - _BALL)
    vx = jnp.where(x <= sim.WALL_SIDE, jnp.abs(vx), vx)
    vx = jnp.where(x >= W - sim.WALL_SIDE - _BALL, -jnp.abs(vx), vx)
    # Top wall.
    vy = jnp.where(y <= sim.WALL_TOP + 4, jnp.abs(vy), vy)
    y = jnp.maximum(y, jnp.float32(sim.WALL_TOP + 4))
    # Bricks (the moving ball can hit at most one per substep).
    row = jnp.floor((y - sim.BRICK_TOP) / sim.BRICK_H).astype(jnp.int32)
    col = jnp.floor((x - sim.WALL_SIDE) / sim.BRICK_W).astype(jnp.int32)
    rc = jnp.clip(row, 0, 5)
    cc = jnp.clip(col, 0, 17)
    hit = (
        (row >= 0) & (row < 6) & (col >= 0) & (col < 18)
        & bricks[rc, cc] & ~dead
    )
    knock = hit & (jnp.arange(6)[:, None] == rc) & (jnp.arange(18)[None, :] == cc)
    bricks = bricks & ~knock
    reward = reward + jnp.where(hit, jnp.asarray(_ROW_POINTS)[rc], 0.0)
    vy = jnp.where(hit, -vy, vy)
    # Paddle (hit position steers, exactly the sim's formula).
    on_paddle = (
        (vy > 0)
        & (y >= sim.PADDLE_Y - _BALL) & (y <= sim.PADDLE_Y + sim.PADDLE_H)
        & (x >= paddle_x - _BALL) & (x <= paddle_x + sim.PADDLE_W)
        & ~dead
    )
    off = (x + _BALL / 2 - paddle_x - sim.PADDLE_W / 2) / (sim.PADDLE_W / 2)
    steered = jnp.clip(vx + 2.0 * off, -3.0, 3.0)
    steered = jnp.where(
        jnp.abs(steered) < 0.5, jnp.where(off >= 0, 0.5, -0.5), steered)
    vx = jnp.where(on_paddle, steered, vx)
    vy = jnp.where(on_paddle, -jnp.abs(vy), vy)
    # Bottom: life lost.
    lost = (y >= H - _BALL) & ~dead
    lives = lives - lost.astype(jnp.int32)
    dead = dead | lost
    return bricks, lives, x, y, vx, vy, dead, reward


def _emulate_frame(carry, action, launch_vx, max_frames):
    """One emulated frame under a held action (`_emulate_frame` parity).

    `carry` holds the running per-env scalars plus `halted` — set once
    the episode ended mid-frameskip, freezing the remaining frames the
    way the numpy loop's `break` does.
    """
    (bricks, lives, frames, paddle_x, dead, x, y, vx, vy, reward,
     halted) = carry
    live = ~halted
    frames = frames + live.astype(jnp.int32)

    paddle_x = jnp.where(
        live & (action == sim.RIGHT),
        jnp.minimum(jnp.float32(W - sim.WALL_SIDE - sim.PADDLE_W), paddle_x + 4),
        paddle_x)
    paddle_x = jnp.where(
        live & (action == sim.LEFT),
        jnp.maximum(jnp.float32(sim.WALL_SIDE), paddle_x - 4),
        paddle_x)
    fire = live & (action == sim.FIRE) & dead & (lives > 0)
    x = jnp.where(fire, paddle_x + sim.PADDLE_W // 2, x)
    y = jnp.where(fire, jnp.float32(sim.PADDLE_Y - 8), y)
    vx = jnp.where(fire, launch_vx, vx)
    vy = jnp.where(fire, jnp.float32(-3.0), vy)
    dead = dead & ~fire

    # Two collision substeps (anti-tunnelling, `breakout_sim.py:130-140`).
    for _ in range(2):
        moving = live & ~dead
        x = x + jnp.where(moving, vx / 2.0, 0.0)
        y = y + jnp.where(moving, vy / 2.0, 0.0)
        bricks2, lives2, x2, y2, vx2, vy2, dead2, reward2 = _collide(
            bricks, paddle_x, lives, x, y, vx, vy, dead, reward)
        keep = moving  # scalar under vmap: broadcasts over every shape
        bricks = jnp.where(keep, bricks2, bricks)
        lives = jnp.where(keep, lives2, lives)
        x = jnp.where(keep, x2, x)
        y = jnp.where(keep, y2, y)
        vx = jnp.where(keep, vx2, vx)
        vy = jnp.where(keep, vy2, vy)
        dead = jnp.where(keep, dead2, dead)
        reward = jnp.where(keep, reward2, reward)

    game_over = (lives <= 0) | ~bricks.any() | (frames >= max_frames)
    halted = halted | (live & game_over)
    return (bricks, lives, frames, paddle_x, dead, x, y, vx, vy, reward,
            halted)


# -- public API (cartpole_jax contract) -------------------------------------


def _reset_fields(n: int):
    return dict(
        bricks=jnp.ones((n, 6, 18), bool),
        lives=jnp.full((n,), 5, jnp.int32),
        frames=jnp.zeros((n,), jnp.int32),
        paddle_x=jnp.full((n,), float((W - sim.PADDLE_W) // 2), jnp.float32),
        ball_dead=jnp.ones((n,), bool),
        ball_x=jnp.zeros((n,), jnp.float32),
        ball_y=jnp.zeros((n,), jnp.float32),
        vx=jnp.zeros((n,), jnp.float32),
        vy=jnp.zeros((n,), jnp.float32),
        returns=jnp.zeros((n,), jnp.float32),
    )


def reset(rng: jax.Array, num_envs: int) -> tuple[BreakoutState, jax.Array]:
    """-> (state, obs `[N, 84, 84, 4]` u8). `rng` unused (reset is
    deterministic: centered paddle, dead ball awaiting FIRE), kept for
    the cartpole_jax signature."""
    del rng
    f = _reset_fields(num_envs)
    raw = _render_batch(f)
    state = BreakoutState(history=pixel_jax.reset_history(raw), **f)
    return state, pixel_jax.observe(state.history)


@functools.partial(jax.jit, static_argnames=("frameskip", "max_frames",
                                             "life_loss"))
def step(
    state: BreakoutState,
    actions: jax.Array,
    rng: jax.Array,
    frameskip: int = 4,
    max_frames: int = 10_000,
    life_loss: bool = True,
) -> tuple[BreakoutState, jax.Array, jax.Array, jax.Array, jax.Array]:
    """-> (state', obs', reward, done, episode_return).

    Contract matches `cartpole_jax.step`: `obs'` holds the RESET
    observation for game-over slots, `episode_return` is the completed
    raw return where the game ended else 0. `done` is the TRAINING
    signal: game-over or (with `life_loss`) a lost life — the
    reference's shaping (`train_impala.py:149-154`).

    Order: emulate, auto-reset the game FIELDS of game-over slots, then
    one pass for every slot: the luma of the 2-frame max (`_luma_batch`;
    no RGB frame is made) -> resize. The max's previous frame shows the
    `_DRAWN` fields `state` came in with, because that is what the last
    step drew (the invariant on `BreakoutState`); a game-over slot takes
    its fresh fields for both frames. Then `pixel_jax.push` shifts the
    history's words down a byte and ors the frame in on top (a game-over
    slot keeps the frame alone: zeros in the older bytes, which is the
    reset observation), and `pixel_jax.observe` unpacks the words once.
    """
    n = state.lives.shape[0]
    lives_before = state.lives
    # One launch-velocity draw per emulated frame, like the sim's
    # per-launch `choice` — only consumed by a FIRE on a dead ball.
    draws = jax.random.randint(rng, (frameskip, n), 0, 4)
    launch_vx = jnp.asarray([-2.0, -1.0, 1.0, 2.0], jnp.float32)[draws]

    carry = (state.bricks, state.lives, state.frames, state.paddle_x,
             state.ball_dead, state.ball_x, state.ball_y, state.vx, state.vy,
             jnp.zeros((n,), jnp.float32), jnp.zeros((n,), bool))
    actions = actions.astype(jnp.int32)
    emulate = jax.vmap(_emulate_frame, in_axes=(0, 0, 0, None))
    for i in range(frameskip):  # static unroll: action held, break-on-done
        carry = emulate(carry, actions, launch_vx[i], max_frames)
    (bricks, lives, frames, paddle_x, ball_dead, ball_x, ball_y, vx, vy,
     reward, game_over) = carry

    returns = state.returns + reward
    episode_return = jnp.where(game_over, returns, 0.0)
    lost_life = lives < lives_before
    done = (game_over | lost_life) if life_loss else game_over
    if life_loss:
        # The reference's life-loss shaping REPLACES the step reward with
        # -1 on a lost life (`train_impala.py:149-154`). On the TERMINAL
        # life the reference still records -1 (it keys on any lives
        # change); here true game-overs keep the raw reward instead —
        # a deliberate deviation matching this repo's host path
        # (`runtime/impala_runner.py` `lost = ... & ~done`), so host and
        # on-device runners see identical shaping rather than exact
        # reference semantics on the final step. Omitting the -1 entirely
        # (pre-r4s3 versions of this env) makes ball loss nearly costless
        # to the learner — the core keep-the-rally-alive incentive
        # disappears. `returns` above is accumulated from the RAW reward,
        # so episode_return stays the true game score.
        reward = jnp.where(lost_life & ~game_over, -1.0, reward)

    # Auto-reset game-over slots: select the game state, never pictures.
    fresh = _reset_fields(n)
    pick = pixel_jax.make_pick(game_over)
    fields = {k: pick(fresh[k], v) for k, v in dict(
        bricks=bricks, lives=lives, frames=frames, paddle_x=paddle_x,
        ball_dead=ball_dead, ball_x=ball_x, ball_y=ball_y, vx=vx, vy=vy,
        returns=returns).items()}
    entered = {k: pick(fresh[k], getattr(state, k)) for k in _DRAWN}

    with jax.named_scope(scopes.RENDER):
        frame = jax.vmap(pixel_jax.resize)(_luma_batch(fields, entered))
        history = pixel_jax.push(state.history, frame, game_over)
        obs = pixel_jax.observe(history)

    new_state = BreakoutState(history=history, **fields)
    return new_state, obs, reward, done, episode_return


def completed_episode_mask(done: jax.Array, new_state: BreakoutState) -> jax.Array:
    """Which `done` slots ended a GAME (vs a life-loss boundary).

    The auto-reset restores 5 lives; a life-loss done leaves <=4. Lets
    callers count true episodes (including zero-return ones, which
    `episode_return != 0` would miss) without a second done channel.
    """
    return done & (new_state.lives == 5)

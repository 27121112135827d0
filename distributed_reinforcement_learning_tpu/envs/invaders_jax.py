"""Space Invaders as pure-JAX functions: the third on-device pixel env.

Same game as `envs.invaders_sim.InvadersCore` (see its fidelity notes),
re-expressed as jittable pure functions over a batch of N games, exactly
like `breakout_jax.py` / `pong_jax.py`. Structurally this env stresses
what the paddle pair doesn't: 36 independent entities (the alien grid),
enemy projectiles, destructible shields, combined move+fire actions,
and mid-episode lives.

Dynamics parity: constants and the per-frame update ORDER mirror
`invaders_sim._emulate_frame` statement for statement (cannon/fire ->
march -> bomb spawn -> missile flight/hits -> bombs fall -> wave
respawn -> landed/done). Divergences, deliberate and documented:

- float32 physics (all speeds are integral, so arithmetic is exact);
- the bomb-spawn draws use `jax.random` instead of
  `np.random.RandomState` — same per-frame (spawn?, column) decisions,
  different stream. `bomb_prob` is a static arg so parity tests can set
  it to 0 on both sides and compare deterministic dynamics exactly;
- the score strip / lives indicator are not rendered (the reference
  crop removes scanlines < 34, `wrappers.py:74`), same as breakout_jax.

Observation pipeline: shared `pixel_jax.observe` (2-frame max -> luma ->
resize matmuls -> crop -> 4-stack), identical to the other two games.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from distributed_reinforcement_learning_tpu.envs import invaders_sim as sim
from distributed_reinforcement_learning_tpu.envs import pixel_jax
from distributed_reinforcement_learning_tpu.envs.pixel_jax import preprocess as _preprocess
from distributed_reinforcement_learning_tpu.observability import scopes

NUM_ACTIONS = sim.InvadersCore.num_actions  # NOOP/FIRE/R/L/RFIRE/LFIRE
OBS_SHAPE = (84, 84, 4)

H, W = sim.H, sim.W
_ROWS, _COLS = sim.ROWS, sim.COLS

_YS = np.arange(H)[:, None]  # [210, 1]
_XS = np.arange(W)[None, :]  # [1, 160]

# Static base frame: ground line only (score strip never reaches an
# observation; walls don't exist in this game).
_BASE = np.zeros((H, W, 3), np.uint8)
_BASE[H - 4:H - 2, :] = sim.CANNON_RGB

_ALIEN_RGB = np.asarray(sim.ALIEN_ROW_COLORS, np.uint8)  # [6, 3]
_CANNON = np.asarray(sim.CANNON_RGB, np.uint8)
_SHIELD = np.asarray(sim.SHIELD_RGB, np.uint8)
_PROJ = np.asarray(sim.PROJ_RGB, np.uint8)
_ROW_POINTS = np.asarray(sim.ROW_POINTS, np.float32)


class InvadersState(NamedTuple):
    """Batched game + observation-pipeline state (`[N, ...]` leaves)."""

    aliens: jax.Array       # [N, 6, 6] bool
    grid_x: jax.Array       # [N] f32 grid origin
    grid_y: jax.Array       # [N] f32
    direction: jax.Array    # [N] i32 (+-1)
    march_count: jax.Array  # [N] i32
    wave: jax.Array         # [N] i32
    cannon_x: jax.Array     # [N] f32
    missile_live: jax.Array  # [N] bool
    missile_x: jax.Array    # [N] f32
    missile_y: jax.Array    # [N] f32
    bomb_live: jax.Array    # [N, 2] bool
    bomb_x: jax.Array       # [N, 2] f32
    bomb_y: jax.Array       # [N, 2] f32
    shield_hp: jax.Array    # [N, 3] i32
    lives: jax.Array        # [N] i32
    frames: jax.Array       # [N] i32
    prev_raw: jax.Array     # [N, 210, 160, 3] u8
    history: jax.Array      # [N, 84, 84] u32 — last four frames, a byte each
    returns: jax.Array      # [N] f32 raw episode return


# -- rendering (single env; vmapped) ----------------------------------------


def _render(aliens, grid_x, grid_y, cannon_x, missile_live, missile_x,
            missile_y, bomb_live, bomb_x, bomb_y, shield_hp) -> jax.Array:
    """`[210, 160, 3]` uint8 frame, `invaders_sim.render` draw order."""
    f = jnp.asarray(_BASE)
    ys, xs = jnp.asarray(_YS), jnp.asarray(_XS)

    # Aliens: pixel -> (row, col) in the marching grid.
    ry = ys - grid_y                  # [210, 1] f32
    rx = xs - grid_x                  # [1, 160] f32
    r = jnp.floor(ry / sim.PITCH_Y).astype(jnp.int32)
    c = jnp.floor(rx / sim.PITCH_X).astype(jnp.int32)
    in_r = (r >= 0) & (r < _ROWS) & (ry - r * sim.PITCH_Y < sim.ALIEN_H)
    in_c = (c >= 0) & (c < _COLS) & (rx - c * sim.PITCH_X < sim.ALIEN_W)
    rc = jnp.clip(r, 0, _ROWS - 1)
    cc = jnp.clip(c, 0, _COLS - 1)
    alive = aliens[rc[:, 0]][:, cc[0, :]]         # [210, 160]
    mask = alive & in_r & in_c
    colors = jnp.asarray(_ALIEN_RGB)[rc[:, 0]]    # [210, 3]
    f = jnp.where(mask[:, :, None], colors[:, None, :], f)

    # Shields (height erodes with hp; integer division like the sim).
    for s, sx in enumerate(sim.SHIELD_XS):
        height = sim.SHIELD_H * shield_hp[s] // sim.SHIELD_HP
        m = ((ys >= sim.SHIELD_Y) & (ys < sim.SHIELD_Y + height)
             & (xs >= sx) & (xs < sx + sim.SHIELD_W))
        f = jnp.where(m[:, :, None], jnp.asarray(_SHIELD), f)

    # Cannon.
    cx = cannon_x.astype(jnp.int32)
    m = ((ys >= sim.CANNON_Y) & (ys < sim.CANNON_Y + sim.CANNON_H)
         & (xs >= cx) & (xs < cx + sim.CANNON_W))
    f = jnp.where(m[:, :, None], jnp.asarray(_CANNON), f)

    # Player missile (clamped to the screen like the numpy slice).
    my = jnp.maximum(missile_y.astype(jnp.int32), 0)
    mx = missile_x.astype(jnp.int32)
    m = (missile_live & (ys >= my) & (ys < my + sim.PROJ_H)
         & (xs >= mx) & (xs < mx + sim.PROJ_W))
    f = jnp.where(m[:, :, None], jnp.asarray(_PROJ), f)

    # Bombs.
    for b in range(sim.MAX_BOMBS):
        by = bomb_y[b].astype(jnp.int32)
        bx = bomb_x[b].astype(jnp.int32)
        m = (bomb_live[b] & (ys >= by) & (ys < jnp.minimum(by + sim.PROJ_H, H))
             & (xs >= bx) & (xs < bx + sim.PROJ_W))
        f = jnp.where(m[:, :, None], jnp.asarray(_PROJ), f)
    return f


# -- physics helpers (single env) -------------------------------------------


def _shield_absorb(active, shield_hp, x, y):
    """Projectile tip at (x, y) vs the shield blocks -> (absorbed, hp').

    The three blocks are horizontally disjoint, so at most one can hit —
    the sim's sequential first-hit return is equivalent."""
    tip = x + sim.PROJ_W / 2
    hits = []
    for s, sx in enumerate(sim.SHIELD_XS):
        height = sim.SHIELD_H * shield_hp[s] // sim.SHIELD_HP
        hits.append(active & (shield_hp[s] > 0)
                    & (sx <= tip) & (tip <= sx + sim.SHIELD_W)
                    & (sim.SHIELD_Y <= y) & (y <= sim.SHIELD_Y + height))
    hit_vec = jnp.stack(hits)                     # [3]
    return hit_vec.any(), shield_hp - hit_vec.astype(jnp.int32)


def _missile_collide(aliens, grid_x, grid_y, shield_hp, bomb_live, bomb_x,
                     bomb_y, missile_live, x, y, reward):
    """`invaders_sim._missile_collide` order: shields -> bombs -> grid."""
    absorbed, shield_hp = _shield_absorb(missile_live, shield_hp, x, y)
    missile_live = missile_live & ~absorbed

    # Bombs: first matching bomb only (the sim's loop-and-return).
    prior = jnp.zeros((), bool)
    new_bomb_live = bomb_live
    for b in range(sim.MAX_BOMBS):
        hit_b = (missile_live & bomb_live[b] & ~prior
                 & (jnp.abs(bomb_x[b] - x) < sim.PROJ_W + 1)
                 & (jnp.abs(bomb_y[b] - y) < sim.PROJ_H))
        new_bomb_live = new_bomb_live.at[b].set(new_bomb_live[b] & ~hit_b)
        prior = prior | hit_b
    missile_live = missile_live & ~prior

    # Alien grid (one kill per frame).
    col = jnp.floor((x + sim.PROJ_W / 2 - grid_x) / sim.PITCH_X).astype(jnp.int32)
    row = jnp.floor((y - grid_y) / sim.PITCH_Y).astype(jnp.int32)
    rc = jnp.clip(row, 0, _ROWS - 1)
    cc = jnp.clip(col, 0, _COLS - 1)
    within = (x + sim.PROJ_W / 2 - (grid_x + cc * sim.PITCH_X)) < sim.ALIEN_W
    tall = (y - (grid_y + rc * sim.PITCH_Y)) < sim.ALIEN_H
    kill = (missile_live & (row >= 0) & (row < _ROWS) & (col >= 0)
            & (col < _COLS) & aliens[rc, cc] & within & tall)
    knock = (kill & (jnp.arange(_ROWS)[:, None] == rc)
             & (jnp.arange(_COLS)[None, :] == cc))
    aliens = aliens & ~knock
    reward = reward + jnp.where(kill, jnp.asarray(_ROW_POINTS)[rc], 0.0)
    missile_live = missile_live & ~kill
    return aliens, shield_hp, new_bomb_live, missile_live, reward


def _emulate_frame(carry, action, u_spawn, u_col, bomb_prob, max_frames):
    """One emulated frame under a held action (`_emulate_frame` parity)."""
    (aliens, grid_x, grid_y, direction, march_count, wave, cannon_x,
     missile_live, missile_x, missile_y, bomb_live, bomb_x, bomb_y,
     shield_hp, lives, frames, reward, halted) = carry
    live = ~halted
    frames = frames + live.astype(jnp.int32)

    # Cannon move + fire (combined actions do both).
    move_r = live & ((action == sim.RIGHT) | (action == sim.RIGHTFIRE))
    move_l = live & ((action == sim.LEFT) | (action == sim.LEFTFIRE))
    cannon_x = jnp.where(
        move_r, jnp.minimum(jnp.float32(W - 8 - sim.CANNON_W),
                            cannon_x + sim.CANNON_SPEED), cannon_x)
    cannon_x = jnp.where(
        move_l, jnp.maximum(jnp.float32(8.0), cannon_x - sim.CANNON_SPEED),
        cannon_x)
    fire = (live & ~missile_live
            & ((action == sim.FIRE) | (action == sim.RIGHTFIRE)
               | (action == sim.LEFTFIRE)))
    missile_x = jnp.where(fire, cannon_x + sim.CANNON_W / 2 - sim.PROJ_W / 2,
                          missile_x)
    missile_y = jnp.where(fire, jnp.float32(sim.CANNON_Y - sim.PROJ_H),
                          missile_y)
    missile_live = missile_live | fire

    # Grid march (uses the alien count from the frame's start, like the
    # sim's `alive` read before the missile section).
    alive_n = aliens.sum().astype(jnp.int32)
    period = 1 + (7 * alive_n) // (_ROWS * _COLS)
    march_count = march_count + live.astype(jnp.int32)
    stepping = live & (alive_n > 0) & (march_count >= period)
    nx = grid_x + direction.astype(jnp.float32) * 2.0
    bounce = (nx < sim.GRID_X_MIN) | (nx > sim.GRID_X_MAX)
    direction = jnp.where(stepping & bounce, -direction, direction)
    grid_y = jnp.where(stepping & bounce, grid_y + sim.PITCH_Y // 2, grid_y)
    grid_x = jnp.where(stepping & ~bounce, nx, grid_x)
    march_count = jnp.where(stepping, 0, march_count)

    # Alien bombs: lowest alive alien of a random column drops one into
    # the first free slot (`invaders_sim` order: spawn check, slot check).
    alive_cols = aliens.any(axis=0)               # [6]
    slot_free = ~bomb_live                        # [2]
    slot = jnp.argmax(slot_free)                  # first free (sim argmin)
    spawn = (live & (alive_n > 0) & (u_spawn < bomb_prob)
             & slot_free.any())
    count = alive_cols.sum()
    k = jnp.clip((u_col * count).astype(jnp.int32), 0, count - 1)
    col = jnp.argmax(jnp.cumsum(alive_cols.astype(jnp.int32)) > k)
    row = jnp.max(jnp.where(aliens[:, col], jnp.arange(_ROWS), -1))
    sel = (jnp.arange(sim.MAX_BOMBS) == slot) & spawn
    bomb_x = jnp.where(sel, grid_x + col * sim.PITCH_X
                       + sim.ALIEN_W / 2 - sim.PROJ_W / 2, bomb_x)
    bomb_y = jnp.where(sel, grid_y + row * sim.PITCH_Y + sim.ALIEN_H, bomb_y)
    bomb_live = bomb_live | sel

    # Player missile flight + hits.
    missile_y = jnp.where(live & missile_live, missile_y - sim.MISSILE_SPEED,
                          missile_y)
    (aliens, shield_hp, bomb_live, missile_live, reward) = _missile_collide(
        aliens, grid_x, grid_y, shield_hp, bomb_live, bomb_x, bomb_y,
        live & missile_live, missile_x, missile_y, reward)
    missile_live = missile_live & (missile_y >= sim.WALL_TOP_Y)

    # Bombs fall; erode shields; hit the cannon. Sequential like the
    # sim's loop: a cannon hit clears ALL bombs and freezes the rest of
    # the pass (its `break`).
    cannon_hit_any = jnp.zeros((), bool)
    new_live, new_y = [], []
    for b in range(sim.MAX_BOMBS):
        active = live & bomb_live[b] & ~cannon_hit_any
        y2 = jnp.where(active, bomb_y[b] + sim.BOMB_SPEED, bomb_y[b])
        absorbed, shield_hp = _shield_absorb(active, shield_hp, bomb_x[b],
                                             y2 + sim.PROJ_H)
        cannon_hit = (active & ~absorbed
                      & (y2 + sim.PROJ_H >= sim.CANNON_Y)
                      & (cannon_x - sim.PROJ_W <= bomb_x[b])
                      & (bomb_x[b] <= cannon_x + sim.CANNON_W))
        off = active & (y2 >= H)
        new_live.append(bomb_live[b] & ~(absorbed | cannon_hit | off))
        new_y.append(y2)
        cannon_hit_any = cannon_hit_any | cannon_hit
    bomb_live = jnp.stack(new_live)
    bomb_y = jnp.stack(new_y)
    lives = lives - cannon_hit_any.astype(jnp.int32)
    bomb_live = jnp.where(cannon_hit_any, jnp.zeros_like(bomb_live), bomb_live)
    cannon_x = jnp.where(cannon_hit_any,
                         jnp.float32((W - sim.CANNON_W) // 2), cannon_x)

    # Wave cleared: respawn lower and faster (sim order: before `landed`).
    cleared = live & ~aliens.any()
    wave = wave + cleared.astype(jnp.int32)
    aliens = aliens | cleared
    grid_x = jnp.where(cleared, jnp.float32(sim.GRID_X0), grid_x)
    grid_y = jnp.where(
        cleared,
        sim.GRID_Y0 + jnp.minimum(3, wave).astype(jnp.float32)
        * (sim.PITCH_Y // 2), grid_y)
    direction = jnp.where(cleared, 1, direction)
    march_count = jnp.where(cleared, 0, march_count)

    landed = (grid_y + (_ROWS - 1) * sim.PITCH_Y + sim.ALIEN_H
              >= sim.SHIELD_Y) & aliens.any()
    game_over = (lives <= 0) | landed | (frames >= max_frames)
    halted = halted | (live & game_over)
    return (aliens, grid_x, grid_y, direction, march_count, wave, cannon_x,
            missile_live, missile_x, missile_y, bomb_live, bomb_x, bomb_y,
            shield_hp, lives, frames, reward, halted)


# -- public API (cartpole_jax contract) -------------------------------------


def _reset_fields(n: int):
    return dict(
        aliens=jnp.ones((n, _ROWS, _COLS), bool),
        grid_x=jnp.full((n,), sim.GRID_X0, jnp.float32),
        grid_y=jnp.full((n,), sim.GRID_Y0, jnp.float32),
        direction=jnp.ones((n,), jnp.int32),
        march_count=jnp.zeros((n,), jnp.int32),
        wave=jnp.zeros((n,), jnp.int32),
        cannon_x=jnp.full((n,), float((W - sim.CANNON_W) // 2), jnp.float32),
        missile_live=jnp.zeros((n,), bool),
        missile_x=jnp.zeros((n,), jnp.float32),
        missile_y=jnp.zeros((n,), jnp.float32),
        bomb_live=jnp.zeros((n, sim.MAX_BOMBS), bool),
        bomb_x=jnp.zeros((n, sim.MAX_BOMBS), jnp.float32),
        bomb_y=jnp.zeros((n, sim.MAX_BOMBS), jnp.float32),
        shield_hp=jnp.full((n, len(sim.SHIELD_XS)), sim.SHIELD_HP, jnp.int32),
        lives=jnp.full((n,), 3, jnp.int32),
        frames=jnp.zeros((n,), jnp.int32),
        returns=jnp.zeros((n,), jnp.float32),
    )


def _render_state(f: dict) -> jax.Array:
    return jax.vmap(_render)(
        f["aliens"], f["grid_x"], f["grid_y"], f["cannon_x"],
        f["missile_live"], f["missile_x"], f["missile_y"],
        f["bomb_live"], f["bomb_x"], f["bomb_y"], f["shield_hp"])


def reset(rng: jax.Array, num_envs: int) -> tuple[InvadersState, jax.Array]:
    """-> (state, obs `[N, 84, 84, 4]` u8). Deterministic reset (`rng`
    kept for the cartpole_jax signature)."""
    del rng
    f = _reset_fields(num_envs)
    raw = _render_state(f)
    state = InvadersState(
        prev_raw=raw, history=pixel_jax.reset_history(raw), **f)
    return state, pixel_jax.observe(state.history)


@functools.partial(jax.jit, static_argnames=("frameskip", "max_frames",
                                             "life_loss", "bomb_prob"))
def step(
    state: InvadersState,
    actions: jax.Array,
    rng: jax.Array,
    frameskip: int = 4,
    max_frames: int = 10_000,
    life_loss: bool = True,
    bomb_prob: float = 0.04,
) -> tuple[InvadersState, jax.Array, jax.Array, jax.Array, jax.Array]:
    """-> (state', obs', reward, done, episode_return).

    Contract matches `breakout_jax.step`: auto-reset on game over with
    the reset observation in `obs'`, `done` = game over or (with
    `life_loss`) a lost life, the shaping reward -1 on non-terminal life
    loss, raw returns accumulated separately.
    """
    n = state.lives.shape[0]
    lives_before = state.lives
    k_spawn, k_col = jax.random.split(rng)
    u_spawn = jax.random.uniform(k_spawn, (frameskip, n))
    u_col = jax.random.uniform(k_col, (frameskip, n))

    carry = (state.aliens, state.grid_x, state.grid_y, state.direction,
             state.march_count, state.wave, state.cannon_x,
             state.missile_live, state.missile_x, state.missile_y,
             state.bomb_live, state.bomb_x, state.bomb_y, state.shield_hp,
             state.lives, state.frames,
             jnp.zeros((n,), jnp.float32), jnp.zeros((n,), bool))
    actions = actions.astype(jnp.int32)
    emulate = jax.vmap(_emulate_frame, in_axes=(0, 0, 0, 0, None, None))
    for i in range(frameskip):  # static unroll: action held, break-on-done
        carry = emulate(carry, actions, u_spawn[i], u_col[i], bomb_prob,
                        max_frames)
    (aliens, grid_x, grid_y, direction, march_count, wave, cannon_x,
     missile_live, missile_x, missile_y, bomb_live, bomb_x, bomb_y,
     shield_hp, lives, frames, reward, game_over) = carry

    fields = dict(
        aliens=aliens, grid_x=grid_x, grid_y=grid_y, direction=direction,
        march_count=march_count, wave=wave, cannon_x=cannon_x,
        missile_live=missile_live, missile_x=missile_x, missile_y=missile_y,
        bomb_live=bomb_live, bomb_x=bomb_x, bomb_y=bomb_y,
        shield_hp=shield_hp, lives=lives, frames=frames,
        returns=state.returns + reward)
    with jax.named_scope(scopes.RENDER):
        raw = _render_state(fields)
        frame = pixel_jax.frame_of(raw, state.prev_raw)

    episode_return = jnp.where(game_over, fields["returns"], 0.0)
    lost_life = lives < lives_before
    done = (game_over | lost_life) if life_loss else game_over
    if life_loss:
        # Same convention as breakout_jax (host-path parity): -1 replaces
        # the reward on a NON-terminal life loss; true game-overs keep
        # the raw reward.
        reward = jnp.where(lost_life & ~game_over, -1.0, reward)

    fresh = _reset_fields(n)
    with jax.named_scope(scopes.RENDER):
        raw0 = _render_state(fresh)
        history = pixel_jax.push(state.history, frame, game_over,
                                 pixel_jax.reset_history(raw0))
        obs = pixel_jax.observe(history)
    pick = pixel_jax.make_pick(game_over)
    new_fields = {k: pick(fresh[k], fields[k]) for k in fresh}
    new_state = InvadersState(
        prev_raw=pick(raw0, raw), history=history, **new_fields)
    return new_state, obs, reward, done, episode_return


def completed_episode_mask(done: jax.Array, new_state: InvadersState) -> jax.Array:
    """Which `done` slots ended a GAME (vs a life-loss boundary): the
    auto-reset restores 3 lives, a life-loss done leaves <= 2."""
    return done & (new_state.lives == 3)

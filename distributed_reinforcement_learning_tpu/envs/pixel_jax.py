"""Shared on-device Atari observation pipeline for the JAX pixel envs.

One implementation of the `envs.atari.AtariPreprocessor` stages —
2-frame max over consecutive post-frameskip raw frames, luma, INTER_AREA
resize as two matmuls (the separable overlap weights of
`atari.area_resize`, rows pre-cropped), `[84, 84]` uint8, a 4-frame
newest-last observation — used by `breakout_jax`, `pong_jax` and
`invaders_jax` so the subtle parts (crop window, history push,
reset-history semantics, auto-reset merge) cannot diverge between games.

The four-frame history is STATE as one 32-bit word a pixel,
`u32[N, 84, 84]`: byte `k` of a word is the frame at stack index `k`
(byte 0 the oldest, byte 3 the newest: the order
`lax.bitcast_convert_type(u32 -> u8[..., 4])` gives). `push` shifts the
words down a byte and ors the new frame in on top, so an old frame is
never copied, selected or concatenated; a reset slot's word is the reset
frame alone, i.e. zeros in the three older bytes (the host pipeline
clears its buffer on reset). `observe` unpacks the words to the
`u8[N, 84, 84, 4]` observation once a step, pinned batch-minor: the
layout the policy's first convolution and the rollout's stacked write
read on the chip. Without the pin a scan's carry falls to row-major there
and two transposing copies of the words appear.

`preprocess` is `resize(luma(rgb))`. Pong and Invaders hand it their RGB
frames. Breakout's `step` makes no RGB frame: it selects the luma plane
from tables that `luma` made of its constant colours, over `CROP_ROWS`
(the scanlines `_WH_CROP` gives a weight), and hands `resize` that plane
with zeros in the rows the crop never reads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from distributed_reinforcement_learning_tpu.envs.atari import _area_weights

H, W = 210, 160

# Resize rows 210 -> 110 then crop [18:102] == one 84x210 matrix
# (`atari.preprocess_frame` parity); cols 160 -> 84.
_WH_CROP = np.asarray(_area_weights(H, 110))[18:102, :]  # [84, 210]
_WW_T = np.asarray(_area_weights(W, 84)).T  # [160, 84]
_LUMA = np.array([0.299, 0.587, 0.114], np.float32)

# The scanlines the crop reads, [first, last + 1): every other column of
# `_WH_CROP` is zero, so what a frame shows there reaches no observation.
_READ = np.flatnonzero(_WH_CROP.any(axis=0))
CROP_ROWS = (int(_READ[0]), int(_READ[-1]) + 1)  # (34, 195)

# The observation's layout on the chip, N minor-most (`{0,3,2,1}` in HLO).
_BATCH_MINOR = Layout(major_to_minor=(1, 2, 3, 0))


def luma(rgb: jax.Array) -> jax.Array:
    """`[..., 3]` u8 -> `[...]` f32 luma."""
    return rgb.astype(jnp.float32) @ jnp.asarray(_LUMA)


def resize(plane: jax.Array) -> jax.Array:
    """`[210, 160]` f32 luma -> `[84, 84]` u8 (area-resize, crop)."""
    resized = jnp.asarray(_WH_CROP) @ plane @ jnp.asarray(_WW_T)  # [84, 84]
    return resized.astype(jnp.uint8)


def preprocess(rgb: jax.Array) -> jax.Array:
    """`[210, 160, 3]` u8 -> `[84, 84]` u8 (luma, area-resize, crop)."""
    return resize(luma(rgb))


def frame_of(raw: jax.Array, prev_raw: jax.Array) -> jax.Array:
    """`[N, 210, 160, 3]` u8 x2 -> `[N, 84, 84]` u8: 2-frame max with the
    previous adapter-step raw frame, preprocess."""
    return jax.vmap(preprocess)(jnp.maximum(raw, prev_raw))


def _newest(frame: jax.Array) -> jax.Array:
    """`[N, 84, 84]` u8 -> the words holding it in the newest byte alone."""
    return frame.astype(jnp.uint32) << 24


def reset_history(raw0: jax.Array) -> jax.Array:
    """History right after a reset: zeros with the reset frame in the
    newest byte (the host pipeline clears its buffer on reset)."""
    return _newest(jax.vmap(preprocess)(raw0))


def push(history: jax.Array, frame: jax.Array, reset: jax.Array,
         history0: jax.Array | None = None) -> jax.Array:
    """Next history `u32[N, 84, 84]`: drop the oldest byte, `frame` on
    top; `reset` `[N]` slots hold `history0`, the reset history (`frame`
    alone where the game renders one frame for both)."""
    new = _newest(frame)
    shifted = (history >> 8) | new
    return jnp.where(reset[:, None, None],
                     new if history0 is None else history0, shifted)


# Jitted so that an eager call (`reset`) cannot hand the pin on: a jit's
# result has the default layout. An observation that left `reset` pinned
# made the fused chunk compile again at its second call, whose `obs` is the
# first call's result.
@jax.jit
def observe(history: jax.Array) -> jax.Array:
    """`u32[N, 84, 84]` -> the observation `u8[N, 84, 84, 4]`, newest last."""
    shifts = jnp.arange(0, 32, 8, dtype=jnp.uint32)
    obs = ((history[..., None] >> shifts) & 0xFF).astype(jnp.uint8)
    return with_layout_constraint(obs, _BATCH_MINOR)


def make_pick(game_over: jax.Array):
    """-> pick(reset_val, cont_val): per-env select of the auto-reset
    value for game-over slots, broadcasting the mask over trailing dims."""
    n = game_over.shape[0]

    def pick(reset_val: jax.Array, cont_val: jax.Array) -> jax.Array:
        mask = game_over.reshape((n,) + (1,) * (cont_val.ndim - 1))
        return jnp.where(mask, reset_val, cont_val)

    return pick

"""Shared on-device Atari observation pipeline for the JAX pixel envs.

One implementation of the `envs.atari.AtariPreprocessor` stages —
2-frame max over consecutive post-frameskip raw frames, luma, INTER_AREA
resize as two matmuls (the separable overlap weights of
`atari.area_resize`, rows pre-cropped), `[84, 84]` uint8, 4-frame
newest-last stacking — used by both `breakout_jax` and `pong_jax` so the
subtle parts (crop window, stack shift, reset-stack semantics,
auto-reset merge) cannot diverge between games.

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


def observe(raw: jax.Array, prev_raw: jax.Array, stack: jax.Array) -> jax.Array:
    """Next observation stack: 2-frame max with the previous adapter-step
    raw frame, preprocess, shift the newest-last 4-stack."""
    maxed = jnp.maximum(raw, prev_raw)
    frame = jax.vmap(preprocess)(maxed)
    return jnp.concatenate([stack[..., 1:], frame[..., None]], axis=-1)


def reset_stack(raw0: jax.Array) -> jax.Array:
    """Observation stack right after a reset: zeros with the reset frame
    in the newest slot (the host pipeline clears its buffer on reset)."""
    frame0 = jax.vmap(preprocess)(raw0)
    stack = jnp.zeros(frame0.shape[:1] + (84, 84, 4), jnp.uint8)
    return stack.at[..., -1].set(frame0)


def make_pick(game_over: jax.Array):
    """-> pick(reset_val, cont_val): per-env select of the auto-reset
    value for game-over slots, broadcasting the mask over trailing dims."""
    n = game_over.shape[0]

    def pick(reset_val: jax.Array, cont_val: jax.Array) -> jax.Array:
        mask = game_over.reshape((n,) + (1,) * (cont_val.ndim - 1))
        return jnp.where(mask, reset_val, cont_val)

    return pick

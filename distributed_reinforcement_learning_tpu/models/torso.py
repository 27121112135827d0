"""Shared network torsos: Nature-DQN conv stack, action embedding, MLP.

Parity targets: conv torso `model/impala_actor_critic.py:4-10` /
`model/apex_value.py:4-10` (32/64/64, VALID, relu); action embedding
`model/impala_actor_critic.py:12-16` (one-hot -> 256 -> 256 relu); MLP
head builder `model/impala_actor_critic.py:27-30`.
"""

from __future__ import annotations

from typing import Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

_glorot = nn.initializers.xavier_uniform()


def frame_scale(frames: jax.Array) -> float | None:
    """The `input_scale` a pixel network makes its torso with for these
    frames. Integer frames come in raw and conv0's kernel carries their
    /255; float frames are the caller's and go through unscaled. The
    frames' dtype decides, not a configuration key."""
    return 1.0 / 255.0 if jnp.issubdtype(frames.dtype, jnp.integer) else None


class NatureConv(nn.Module):
    """Nature-DQN conv torso: 8x8/4 x32, 4x4/2 x64, 3x3/1 x64, flatten.

    Parameters are declared explicitly (HWIO `conv{i}_kernel` /
    `conv{i}_bias`, fp32) rather than through `nn.Conv` so the first
    kernel can carry a folded `input_scale`. Folding the frame
    normalization (1/255) into conv0's kernel — a [8, 8, C, 32]
    elementwise multiply at trace scale — lets callers feed raw uint8
    frames and skip the full-frame `x * 1/255` pass, whose HBM
    read+write (~3x the uint8 batch in the compute dtype) XLA does not
    fuse into the TPU convolution's input. conv(x * s) == conv_{k*s}(x)
    exactly, modulo one float rounding on the kernel.

    Checkpoints from before this layout (nn.Conv's `Conv_{i}/{kernel,bias}`
    nesting) restore via `upgrade_nature_conv_params`.
    """

    dtype: jnp.dtype = jnp.float32
    input_scale: float | None = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = x.astype(self.dtype)
        for i, (features, kernel, stride) in enumerate(((32, 8, 4), (64, 4, 2), (64, 3, 1))):
            k = self.param(
                f"conv{i}_kernel", _glorot, (kernel, kernel, x.shape[-1], features)
            )
            b = self.param(f"conv{i}_bias", nn.initializers.zeros_init(), (features,))
            kc = k.astype(self.dtype)
            if i == 0 and self.input_scale is not None:
                kc = kc * jnp.asarray(self.input_scale, self.dtype)
            x = jax.lax.conv_general_dilated(
                x,
                kc,
                window_strides=(stride, stride),
                padding="VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            x = nn.relu(x + b.astype(self.dtype))
        return x.reshape((x.shape[0], -1))


class ResNetTorso(nn.Module):
    """IMPALA deep torso (Espeholt et al. 2018, fig. 3): three sections of
    conv3x3 -> maxpool3x3/2 -> 2 residual blocks, then relu+flatten+Dense.

    The reference never shipped the deep model; it exists here as the
    MXU-dense IMPALA variant (VERDICT r3 item 8): `width` multiplies the
    paper's (16, 32, 32) channels, so width=4 -> (64, 128, 128) — 3x3
    contractions of 576/1152 and output channels of 64/128 that FILL the
    128-wide MXU, unlike Nature-CNN's 32/64-channel quarter-fills. SAME
    padding + pooling keep the spatial geometry analytically simple for
    a roofline model.

    conv0 carries the folded `input_scale` exactly like `NatureConv`
    (declared params, conv(x*s) == conv_{k*s}(x)).
    """

    dtype: jnp.dtype = jnp.float32
    width: int = 1
    input_scale: float | None = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = x.astype(self.dtype)
        for s, base in enumerate((16, 32, 32)):
            ch = base * self.width
            if s == 0:
                # Explicit params so the frame normalization can fold in.
                k = self.param("conv0_kernel", _glorot, (3, 3, x.shape[-1], ch))
                b = self.param("conv0_bias", nn.initializers.zeros_init(), (ch,))
                kc = k.astype(self.dtype)
                if self.input_scale is not None:
                    kc = kc * jnp.asarray(self.input_scale, self.dtype)
                x = jax.lax.conv_general_dilated(
                    x, kc, window_strides=(1, 1), padding="SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC")) + b.astype(self.dtype)
            else:
                x = nn.Conv(ch, (3, 3), padding="SAME", kernel_init=_glorot,
                            dtype=self.dtype, name=f"section{s}_conv")(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
            for r in range(2):
                skip = x
                y = nn.relu(x)
                y = nn.Conv(ch, (3, 3), padding="SAME", kernel_init=_glorot,
                            dtype=self.dtype, name=f"section{s}_res{r}_conv0")(y)
                y = nn.relu(y)
                y = nn.Conv(ch, (3, 3), padding="SAME", kernel_init=_glorot,
                            dtype=self.dtype, name=f"section{s}_res{r}_conv1")(y)
                x = skip + y
        x = nn.relu(x)
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(256, kernel_init=_glorot, dtype=self.dtype,
                             name="trunk_out")(x))
        return x


def upgrade_nature_conv_params(tree):
    """Rewrite pre-r3 NatureConv param nesting to the explicit layout.

    The r3 NatureConv declares `conv{i}_kernel` / `conv{i}_bias` directly
    (to fold the input scale) where the earlier nn.Conv-based torso
    nested `Conv_{i}: {kernel, bias}`. This maps any such nests, at any
    depth, so old serialized checkpoints restore against new templates.
    Returns a new tree; non-matching subtrees pass through unchanged.
    """
    if not isinstance(tree, dict):
        return tree
    out = {}
    for key, val in tree.items():
        if (key.startswith("Conv_") and isinstance(val, dict)
                and set(val) <= {"kernel", "bias"}):
            i = key.split("_", 1)[1]
            for pname, pval in val.items():
                out[f"conv{i}_{pname}"] = pval
        else:
            out[key] = upgrade_nature_conv_params(val)
    return out


class ActionEmbedding(nn.Module):
    """One-hot previous action -> Dense 256 relu -> Dense 256 relu."""

    num_actions: int
    width: int = 256
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, action: jax.Array) -> jax.Array:
        x = jax.nn.one_hot(action, self.num_actions, dtype=self.dtype)
        x = nn.relu(nn.Dense(self.width, kernel_init=_glorot, dtype=self.dtype)(x))
        x = nn.relu(nn.Dense(self.width, kernel_init=_glorot, dtype=self.dtype)(x))
        return x


class MLP(nn.Module):
    """relu MLP over `hidden_sizes` with a linear `output_size` head."""

    hidden_sizes: Sequence[int]
    output_size: int
    final_activation: Callable[[jax.Array], jax.Array] | None = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        for width in self.hidden_sizes:
            x = nn.relu(nn.Dense(width, kernel_init=_glorot, dtype=self.dtype)(x))
        x = nn.Dense(self.output_size, kernel_init=_glorot, dtype=self.dtype)(x)
        if self.final_activation is not None:
            x = self.final_activation(x)
        return x

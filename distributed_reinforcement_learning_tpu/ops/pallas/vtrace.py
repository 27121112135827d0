"""Fused V-trace kernel: rho-clipping + deltas + reverse scan in one pass.

The recursion (`/root/reference/optimizer/vtrace.py:71-103`):

    delta_t = min(rho_bar, rho_t) * (r_t + gamma_t * V_{t+1} - V_t)
    acc_t   = delta_t + gamma_t * min(c_bar, rho_t) * acc_{t+1}
    vs_t    = acc_t + V_t

The lax.scan baseline compiles to an XLA while-loop whose carry bounces
through HBM every step; here the whole [T, B] problem lives in VMEM and
the time loop is unrolled inside one kernel (T is a small static unroll
length — 20 for IMPALA, `config.json:40`). Outputs are consumed under
`stop_gradient` by every caller (the reference sets `back_prop=False`),
so no backward kernel is needed.

Grid: 1-D over batch tiles; each program owns all T steps of its batch
slice, so programs are independent and the grid parallelizes freely.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_reinforcement_learning_tpu.ops.pallas import batch_partitioned, pick_block

# Batch tile: multiple of the fp32 lane width; the whole [T, BLOCK_B]
# working set (6 arrays x T<=64 x 256 x 4B ~ 400 KB) sits far under VMEM.
_BLOCK_B = 256


def _vtrace_kernel(
    log_rhos_ref,  # [T, Bb]
    discounts_ref,  # [T, Bb]
    rewards_ref,  # [T, Bb]
    values_ref,  # [T, Bb]
    bootstrap_ref,  # [1, Bb]
    vs_ref,  # [T, Bb] out
    rhos_ref,  # [T, Bb] out
    *,
    clip_rho: float | None,
    clip_c: float,
):
    rhos = jnp.exp(log_rhos_ref[:])
    clipped = jnp.minimum(clip_rho, rhos) if clip_rho is not None else rhos
    cs = discounts_ref[:] * jnp.minimum(clip_c, rhos)  # fused gamma_t * c_t
    values = values_ref[:]
    next_values = jnp.concatenate([values[1:], bootstrap_ref[:]], axis=0)
    deltas = clipped * (rewards_ref[:] + discounts_ref[:] * next_values - values)

    T = values.shape[0]
    acc = jnp.zeros_like(bootstrap_ref[:])  # [1, Bb]
    rows = [None] * T
    for t in reversed(range(T)):  # static unroll: T is a compile-time constant
        acc = deltas[t : t + 1] + cs[t : t + 1] * acc
        rows[t] = acc
    vs_ref[:] = jnp.concatenate(rows, axis=0) + values
    rhos_ref[:] = clipped


@functools.partial(
    jax.jit, static_argnames=("clip_rho_threshold", "clip_c_threshold", "interpret")
)
def vtrace_pallas(
    log_rhos: jax.Array,  # [T, B] time-major, like the lax.scan core
    discounts: jax.Array,
    rewards: jax.Array,
    values: jax.Array,
    bootstrap_value: jax.Array,  # [B]
    clip_rho_threshold: float | None = 1.0,
    clip_c_threshold: float = 1.0,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """-> (vs [T, B], clipped_rhos [T, B]), both to be stop-gradiented by
    the caller (`ops.vtrace.from_importance_weights` does)."""
    kernel = functools.partial(
        _vtrace_kernel, clip_rho=clip_rho_threshold, clip_c=clip_c_threshold
    )

    def call(log_rhos, discounts, rewards, values, bootstrap):
        # Shapes are read HERE: under a mesh this runs per device, on
        # that device's [T, B / n] columns.
        T, B = log_rhos.shape
        block_b = pick_block(B, _BLOCK_B)
        seq_spec = pl.BlockSpec((T, block_b), lambda i: (0, i), memory_space=pltpu.VMEM)
        boot_spec = pl.BlockSpec((1, block_b), lambda i: (0, i), memory_space=pltpu.VMEM)
        return tuple(pl.pallas_call(
            kernel,
            grid=(B // block_b,),
            in_specs=[seq_spec, seq_spec, seq_spec, seq_spec, boot_spec],
            out_specs=[seq_spec, seq_spec],
            out_shape=[
                jax.ShapeDtypeStruct((T, B), jnp.float32),
                jax.ShapeDtypeStruct((T, B), jnp.float32),
            ],
            interpret=interpret,
        )(log_rhos, discounts, rewards, values, bootstrap))

    vs, rhos = batch_partitioned(call, (1, 1, 1, 1, 1), (1, 1))(
        log_rhos.astype(jnp.float32),
        discounts.astype(jnp.float32),
        rewards.astype(jnp.float32),
        values.astype(jnp.float32),
        bootstrap_value.astype(jnp.float32)[None, :],
    )
    return vs, rhos

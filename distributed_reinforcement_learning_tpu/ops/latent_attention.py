"""Multi-head latent attention (MLA; DeepSeek-V2/V3, arXiv:2412.19437
section 2.1.1): keys and values are rebuilt from ONE low-rank latent a
token, `c [R]` (normed), and one rotated key part `k_r [r]` that every
head shares; a head's query is `[q_n (n) | q_r (r)]`:

    [k_n_i, v_i] = W_kvb_i c            (n + v columns a head)
    s_i(t, j) = (q_n_i(t) . k_n_i(j) + q_r_i(t) . k_r(j)) / sqrt(n + r)
    o_i(t) = sum_j softmax_j(s_i(t, j)) v_i(j)       causal AND same-episode

One function, two computations:

- `expanded`: the learner's. Per-head keys `[k_n_i | k_r]` and values
  are rebuilt for the whole `[B, T]` block and go through
  `ops.attention.causal_attention` (the flash kernels on a TPU) with a
  value width of its own; q/k go UNPADDED at `n + r`, whose `** -0.5`
  is the scale it applies.
- `absorbed_step`: the decode step's. The cache holds `[c | k_r]`, `R +
  r` values a token; the query is carried into the latent's space,
  `q~_i = W^UK_i q_n_i` (`W^UK_i`: the k_n columns of `W_kvb_i`), scores
  and the weighted sum are taken on the cache itself, and the value
  up-projection `W^UV_i` is applied to the `R`-wide result. Equal to the
  expanded form in real arithmetic.

Rotary (`rope_interleave`): the pair `(2j, 2j + 1)` of the rotated part
turns by `pos x theta^(-2j / r)`; float32. Matmul operands in `dtype`
with float32 accumulation; scores, mask and softmax float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.ops.attention import (
    _MASK_VALUE, causal_attention)

F32 = jnp.float32


def rotary_interleaved(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """`x [..., r]` with neighbouring pairs `(2j, 2j + 1)` turned by `pos
    x theta^(-2j / r)`; `pos` broadcasts against `x`'s leading axes
    (`[B, T, 1]` for `[B, T, H, r]`). Float32."""
    r = x.shape[-1]
    freq = jnp.asarray(theta, F32) ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    angle = jnp.asarray(pos, F32)[..., None] * freq  # [..., r / 2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.astype(F32).reshape(*x.shape[:-1], r // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def expanded(q_n: jax.Array, q_r: jax.Array, c: jax.Array, k_r: jax.Array,
             w_kvb: jax.Array, seg: jax.Array, pos: jax.Array, theta: float,
             dtype=jnp.bfloat16, backend: str = "auto") -> jax.Array:
    """`q_n [B, T, H, n]`, `q_r [B, T, H, r]` (not yet rotated), `c [B,
    T, R]` (normed), `k_r [B, T, r]` (not yet rotated), `w_kvb [R, H x
    (n + v)]` (per head `k_n | v`), `seg, pos [B, T]` (episode ids, step
    inside the episode) -> `[B, T, H, v]` float32."""
    b, t, h, n = q_n.shape
    r = q_r.shape[-1]
    kv = jnp.dot(c.astype(dtype), w_kvb.astype(dtype),
                 preferred_element_type=F32).reshape(b, t, h, -1)
    k_n, v = kv[..., :n], kv[..., n:]
    q_r = rotary_interleaved(q_r, pos[..., None], theta)
    k_r = rotary_interleaved(k_r, pos, theta)
    q = jnp.concatenate([q_n, q_r], axis=-1).astype(dtype)
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r[:, :, None], (b, t, h, r))], -1).astype(dtype)
    return causal_attention(q, k, v.astype(dtype), q_seg=seg, k_seg=seg,
                            backend=backend).astype(F32)


def cache_entry(c: jax.Array, k_r: jax.Array, t: jax.Array, theta: float,
                dtype=jnp.bfloat16) -> jax.Array:
    """What a decode step writes at position `t` of a row of the cache:
    `[c (normed) | k_r rotated by t]`, `[N, 1, R + r]` in `dtype`."""
    return jnp.concatenate(
        [c.astype(F32), rotary_interleaved(k_r, t, theta)], -1)[:, None].astype(dtype)


def absorbed_step(q_n: jax.Array, q_r: jax.Array, cache: jax.Array,
                  w_kvb: jax.Array, t: jax.Array, span: int, theta: float,
                  dtype=jnp.bfloat16) -> jax.Array:
    """One decode step on the latent cache: `q_n [N, H, n]`, `q_r [N, H,
    r]` (not yet rotated), `cache [N, T, R + r]` (`cache_entry`'s rows,
    position `t` already written), `w_kvb [R, H x (n + v)]`; the step
    reads the static prefix `span > t` of its rows -> `[N, H, v]`
    float32."""
    rows, h, n = q_n.shape
    r, latent = q_r.shape[-1], w_kvb.shape[0]
    w = w_kvb.astype(dtype).reshape(latent, h, -1)
    w_uk, w_uv = w[..., :n], w[..., n:]
    absorbed = jnp.einsum("nhd,chd->nhc", q_n.astype(dtype), w_uk,
                          preferred_element_type=F32)
    q = jnp.concatenate([absorbed, rotary_interleaved(q_r, t, theta)],
                        axis=-1).astype(dtype)  # [N, H, R + r]
    read = cache[:, :span]
    s = jnp.einsum("nhc,nsc->nhs", q, read,
                   preferred_element_type=F32) * (n + r) ** -0.5
    seen = jnp.arange(span) <= t
    prob = jnp.where(seen, jax.nn.softmax(jnp.where(seen, s, _MASK_VALUE), -1), 0.0)
    # The weighted sum over the WHOLE row of the cache (the r rotary
    # columns ride along and are dropped): a slice of the latent's
    # columns would be a copy of the prefix every step.
    mixed = jnp.einsum("nhs,nsc->nhc", prob.astype(dtype), read,
                       preferred_element_type=F32)[..., :latent]
    return jnp.einsum("nhc,chd->nhd", mixed.astype(dtype), w_uv,
                      preferred_element_type=F32)

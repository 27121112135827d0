"""The gated delta rule (Gated DeltaNet; Qwen3-Next's linear-attention
layers) in its chunked form, which the learner runs over whole episodes,
and as the one-token update that acting runs.

Per value head, S in R^{K x V}, zero before an episode's first step:

    S_t = a_t S_{t-1} + b_t k_t (v_t - (a_t S_{t-1})^T k_t)^T,   o_t = S_t^T q_t

with a_t = exp(g_t) in (0, 1] the decay and b_t in (0, 1) the write
strength: the state forgets (a), and what it writes at key k_t is the
DIFFERENCE between v_t and what it already returns for k_t (the `k k^T`
correction that `ops/ssd.py`'s recurrence does not have). With
u_t = b_t (v_t - (a_t S_{t-1})^T k_t) the update is S_t = a_t S_{t-1} +
k_t u_t^T, and inside a chunk of C steps, c_i the running sum of g:

    L_ij = b_i exp(c_i - c_j) (k_i . k_j)  for j < i      (strictly lower)
    (I + L) U~ = b V          (I + L) W = b exp(c) K      (two solves, one matrix)
    U   = U~ - W S_0
    o_i = exp(c_i) S_0^T q_i + sum_{j<=i} exp(c_i - c_j) (k_j . q_i) u_j
    S_C = exp(c_C) S_0 + sum_j exp(c_C - c_j) k_j u_j^T

so T steps are T / C steps of a scan whose body is matrix products and
one triangular solve, where the step-by-step form (`models/moe_lm.py`
decode, the plain reference) is T rank-one updates. Every exponent is
<= 0. An episode boundary inside a chunk cuts every sum at it, as in
`ops/ssd.py`: a pair (i, j) counts only if both steps are of one episode
(`seg`), and S_0 reaches only the steps of the episode the previous
chunk ended in.

Plain `jax.numpy`, no kernel (ISSUE 36: the chunked rule as a Pallas
kernel is `perf_opt` work this cell will judge). g, b, the cumulative
sums, L, the solve and the state are float32; the matrix products take
operands in `dtype` with float32 accumulation. The body is
rematerialised: the backward, which is autodiff's (through the solve
too), keeps one state `[B, H, K, V]` a chunk.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def l2_normalize(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """`x / sqrt(sum(x^2) + eps)` over the last axis, float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def gated_delta_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                        beta: jax.Array, seg: jax.Array, chunk: int = 64,
                        dtype=jnp.bfloat16, carry_dtype=F32):
    """`q, k [B, T, H, K]` (as the rule reads them: normalised and scaled
    by the caller), `v [B, T, H, V]`, `g, beta [B, T, H]` (g <= 0), `seg
    [B, T]` episode ids (not negative, not decreasing) -> (`o [B, T, H,
    V]` float32, the state after the last step `[B, H, K, V]` float32).
    T need not be whole chunks: the tail is padded with steps that write
    nothing (b = 0, g = 0) and dropped from `o`. `carry_dtype` is the
    dtype the state crosses a chunk boundary in (float32; a test plants
    another)."""
    b, t, h, dk = q.shape
    c = min(chunk, t)
    pad = -t % c
    if pad:
        tail = lambda x, mode="constant": jnp.pad(
            x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2), mode=mode)
        q, k, v, g, beta = (tail(x) for x in (q, k, v, g, beta))
        seg = tail(seg, "edge")
    n = (t + pad) // c
    mm = lambda spec, x, y: jnp.einsum(spec, x.astype(dtype), y.astype(dtype),
                                       preferred_element_type=F32)
    steps = jnp.arange(c)
    lower = steps[:, None] > steps[None, :]
    causal = steps[:, None] >= steps[None, :]

    @jax.checkpoint
    def one_chunk(carry, xs):
        state, seg_before = carry  # S_0 and the episode it belongs to
        q_c, k_c, v_c, g_c, b_c, seg_c = xs
        cs = jnp.moveaxis(jnp.cumsum(g_c.astype(F32), axis=1), 2, 1)  # [B, H, C]
        b_h = jnp.moveaxis(b_c.astype(F32), 2, 1)  # [B, H, C]
        same = (seg_c[:, :, None] == seg_c[:, None, :])[:, None]  # [B, 1, C, C]
        decay = lambda pairs: jnp.exp(jnp.where(
            pairs, cs[..., :, None] - cs[..., None, :], -jnp.inf))
        live = (seg_c == seg_before[:, None])[:, None]  # [B, 1, C]: S_0 reaches them
        from_past = jnp.where(live, jnp.exp(cs), 0.0)  # [B, H, C]
        # (I + L) [U~ | W] = b [V | exp(c) K]: one unit-lower-triangular
        # solve of K + V right-hand sides a head, float32.
        kk = mm("bihd,bjhd->bhij", k_c, k_c)
        lmat = b_h[..., None] * decay(lower & same) * kk
        k_h, v_h = (jnp.moveaxis(x.astype(F32), 2, 1) for x in (k_c, v_c))
        rhs = jnp.concatenate([b_h[..., None] * v_h,
                               (b_h * from_past)[..., None] * k_h], axis=-1)
        solved = jax.scipy.linalg.solve_triangular(
            lmat + jnp.eye(c, dtype=F32), rhs, lower=True, unit_diagonal=True)
        u, w = solved[..., :v_h.shape[-1]], solved[..., v_h.shape[-1]:]
        u = u - mm("bhik,bhkv->bhiv", w, state)
        # read-out: what the past hands on, and the chunk's own writes
        qk = mm("bihd,bjhd->bhij", q_c, k_c) * decay(causal & same)
        o = (mm("bihk,bhkv->bhiv", q_c, state) * from_past[..., None]
             + mm("bhij,bhjv->bhiv", qk, u))
        # the state the chunk leaves: its last episode's writes, and S_0
        # if that episode is the one the chunk began in
        ends = seg_c[:, -1]
        to_end = jnp.where((seg_c == ends[:, None])[:, None],
                           jnp.exp(cs[..., -1:] - cs), 0.0)  # [B, H, C]
        kept = jnp.where((ends == seg_before)[:, None], jnp.exp(cs[..., -1]), 0.0)
        state = (kept[..., None, None] * state.astype(F32)
                 + mm("bhjk,bhjv->bhkv", k_h * to_end[..., None], u))
        return (state.astype(carry_dtype), ends), jnp.moveaxis(o, 1, 2)

    chunks = lambda x: jnp.moveaxis(x.reshape(b, n, c, *x.shape[2:]), 1, 0)
    carry = (jnp.zeros((b, h, dk, v.shape[-1]), carry_dtype),
             jnp.full((b,), -1, seg.dtype))  # no episode is -1: S before step 0 is 0
    (state, _), o = jax.lax.scan(
        one_chunk, carry, tuple(chunks(x) for x in (q, k, v, g, beta, seg)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t + pad, h, -1)[:, :t]
    return o, state.astype(F32)


def gated_delta_step(state: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array,
                     g: jax.Array, beta: jax.Array):
    """One step of the rule at batch N: `state [N, H, K, V]`, `q, k [N,
    H, K]`, `v [N, H, V]`, `g, beta [N, H]` -> (`o [N, H, V]`, the new
    state), float32. The state is read once for both of its products
    (S^T k and S^T q) and written once:
    o = S_t^T q = a S^T q + (k . q) u."""
    s = state.astype(F32)
    alpha = jnp.exp(g.astype(F32))[..., None]
    read_k = jnp.sum(s * k[..., None], axis=-2)  # S^T k  [N, H, V]
    read_q = jnp.sum(s * q[..., None], axis=-2)
    u = beta[..., None] * (v - alpha * read_k)
    o = alpha * read_q + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, alpha[..., None] * s + k[..., None] * u[..., None, :]

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

so T steps are T / C steps of a scan where the step-by-step form
(`models/moe_lm.py` decode, the plain reference) is T rank-one updates.
Every exponent is <= 0. An episode boundary inside a chunk cuts every
sum at it, as in `ops/ssd.py`: a pair (i, j) counts only if both steps
are of one episode (`seg`), and S_0 reaches only the steps of the
episode the previous chunk ended in.

Only S_0 depends on the chunk before, so `gated_delta_chunked` runs in
three phases (PR 37), n = T / C chunks, N = n B H matrices:

1. All chunks at once, `[n, B, H, C, ...]` (chunk-major so that the scan
   slices rows, head-major by the one transposition of q, k, v): the
   running sums, the masked decays, which episode each chunk's S_0
   belongs to (-1 before the first, then the chunk before's last `seg`),
   `k k^T`, L, the right-hand side `b [V | exp(c) K]`, ONE call of
   `jax.scipy.linalg.solve_triangular` for U~ and W, `q k^T`, the keys
   scaled to the chunk's end.
2. A `lax.scan` over the chunks that keeps what touches S and nothing
   else: `u = U~ - W S`, the past's part of the read-out `(q S)
   exp(c)`, `S' = exp(c_C) S + (k exp(c_C - c))^T u`. Three products;
   it emits `u` and the past's read-out.
3. All chunks at once: `o = past + (q k^T . decay) u`, back to `[B, T,
   H, V]`.

The solve is called with its batch FLAT, `[N, C, C]` and `[N, C, V +
K]`: the TPU's triangular inversion (`InvertDiagBlocksLowerTriangular`,
what XLA lowers the call to, then one product with the right-hand side)
lays the LAST batch dimension along the 128 lanes, so at `[n, B, H] =
[16, 4, 32]` three lanes in four are empty and every array in that
layout is padded fourfold: 5.46 ms a call against 1.38 flat, and with
it the whole rule 9.5 + 17.5 ms against 4.7 + 11.2 (forward; forward
and backward under `trunk`'s checkpoint; a row block on a v5e, PR 37).
Two things `perfbench/tests/test_qwen3_next_faults.py`
holds the call to: it is reached as the attribute
`jax.scipy.linalg.solve_triangular(matrix, rhs, ...)` at trace time (a
planted fault swaps that function), and the right-hand side is `[V | K]`
on the last axis, V first (the fault slices `rhs[..., :V]`).

Plain `jax.numpy`, no kernel. g, b, the cumulative sums, L, the solve
and the state are float32; the matrix products take operands in `dtype`
with float32 accumulation; the state crosses a chunk boundary in
`carry_dtype`. What phase 1 makes lives only inside one call, which
`models/moe_lm.trunk` makes a row block at a time: at the published
sizes (4 rows x 1,024 steps x 32 heads of 128 x 128, N = 2,048) k and v
head-major 67 MB each and q 34 (bfloat16), `k k^T`, L, `q k^T` and each
masked decay 34 MB, the right-hand side and the solve's result 134 MB
each (U~ 67 of it, W kept as 34 in bfloat16), the scaled keys 34, `u`,
the past's read-out and `o` 67 each: 338 MB of temporaries forward and
982 MB forward and backward by the compiler's count. The scan's BODY is
rematerialised and nothing else: the backward keeps one state `[B, H,
K, V]` a chunk (16 x 8.4 MB) and recomputes three products, which reads
0.4 ms and 100 MB under keeping the body's own residuals; phases 1 and 3
are differentiated as they stand, so under `trunk`'s per-row-block
`jax.checkpoint` they run twice an update (forward, recomputed forward),
not three times.
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
    dv = v.shape[-1]
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

    # -- 1. all chunks at once: everything the state does not reach ------
    # chunk-major, head-major: [n, B, H, C, ...], so the scan slices rows
    heads = lambda x: jnp.transpose(x.reshape(b, n, c, h, -1), (1, 0, 3, 2, 4))
    q_h, k_h, v_h = heads(q.astype(dtype)), heads(k.astype(F32)), heads(v.astype(F32))
    cs = jnp.cumsum(heads(g.astype(F32))[..., 0], axis=-1)  # [n, B, H, C]
    b_h = heads(beta.astype(F32))  # [n, B, H, C, 1]
    seg_c = jnp.moveaxis(seg.reshape(b, n, c), 1, 0)  # [n, B, C]
    ends = seg_c[..., -1]
    # the episode S_0 belongs to; no episode is -1: S before step 0 is 0
    seg_before = jnp.concatenate([jnp.full((1, b), -1, seg.dtype), ends[:-1]])
    same = (seg_c[..., :, None] == seg_c[..., None, :])[:, :, None]  # [n, B, 1, C, C]
    decay = lambda pairs: jnp.exp(jnp.where(
        pairs, cs[..., :, None] - cs[..., None, :], -jnp.inf))
    live = (seg_c == seg_before[..., None])[:, :, None]  # [n, B, 1, C]: S_0 reaches them
    from_past = jnp.where(live, jnp.exp(cs), 0.0)[..., None]  # [n, B, H, C, 1]
    # (I + L) [U~ | W] = b [V | exp(c) K]: one unit-lower-triangular
    # solve of V + K right-hand sides a head and chunk, float32.
    lmat = b_h * decay(lower & same) * mm("nbhid,nbhjd->nbhij", k_h, k_h)
    rhs = jnp.concatenate([b_h * v_h, b_h * from_past * k_h], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        (lmat + jnp.eye(c, dtype=F32)).reshape(-1, c, c),
        rhs.reshape(-1, c, rhs.shape[-1]), lower=True,
        unit_diagonal=True).reshape(rhs.shape)
    u_alone, w = solved[..., :dv], solved[..., dv:].astype(dtype)
    qk = mm("nbhid,nbhjd->nbhij", q_h, k_h) * decay(causal & same)
    # what a chunk leaves: its last episode's writes, and S_0 if that
    # episode is the one the chunk began in
    to_end = jnp.where((seg_c == ends[..., None])[:, :, None],
                       jnp.exp(cs[..., -1:] - cs), 0.0)  # [n, B, H, C]
    k_end = (k_h * to_end[..., None]).astype(dtype)
    kept = jnp.where((ends == seg_before)[..., None], jnp.exp(cs[..., -1]), 0.0)

    # -- 2. chunk after chunk: the three products that touch S -----------
    @jax.checkpoint
    def one_chunk(state, xs):
        u_c, w_c, q_c, past_c, k_c, kept_c = xs
        u = u_c - mm("bhik,bhkv->bhiv", w_c, state)
        read = mm("bhik,bhkv->bhiv", q_c, state) * past_c
        state = (kept_c[..., None, None] * state.astype(F32)
                 + mm("bhjk,bhjv->bhkv", k_c, u))
        return state.astype(carry_dtype), (u, read)

    state, (u, read) = jax.lax.scan(
        one_chunk, jnp.zeros((b, h, dk, dv), carry_dtype),
        (u_alone, w, q_h, from_past, k_end, kept))

    # -- 3. all chunks at once: the chunk's own writes, read out ----------
    o = read + mm("nbhij,nbhjv->nbhiv", qk, u)
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(b, t + pad, h, dv)[:, :t]
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

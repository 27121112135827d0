"""The selective state-space recurrence of Mamba-2 in its chunked
("state-space dual") form: what the learner runs over whole episodes.

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,   y_t = S_t C_t

per head (S: `[P, N]`; A < 0 a scalar a head; B_t, C_t `[N]` a GROUP:
the H heads are G groups of H / G, head h reads B and C of group
h // (H / G); granite-4.0-h has one group, Nemotron-H eight), S = 0
before an episode's first step. With a_t = dt_t A and c = cumsum(a)
inside a chunk of Q steps:

    inside the chunk   Y_in[i]  = sum_{j<=i} exp(c_i - c_j) (C_i . B_j) dt_j x_j
    the chunk's state  S_c      = sum_j exp(c_Q - c_j) dt_j x_j (x) B_j
    across chunks      H_c      = exp(c_Q) H_{c-1} + S_c
    from the past      Y_out[i] = exp(c_i) H_{c-1} C_i

so the T steps are T / Q steps of a scan whose body is four matrix
products (`C . B` once a GROUP, its `[Q, Q]` scores shared by the group's
heads under each head's own decay; the chunk's state and the read of the
past by group too), where the step-by-step form (`models/hybrid_lm.py` decode, the
plain reference) is T steps of an outer product. An episode boundary
inside a chunk cuts every sum at it: a pair (i, j) counts only if both
steps are of one episode (`seg`), and H_{c-1} reaches the steps of the
episode that the previous chunk ended in.

Plain `jax.numpy`: no kernel (ISSUE 32; the scan as a Pallas kernel is a
later PR's). Decays, cumulative sums and states are float32, matmul
operands `dtype` with float32 accumulation. The body is rematerialised,
so the backward, which is autodiff's, keeps one state `[B, H, P, N]` a
chunk and never a `[B, chunks, H, Q, Q]` decay matrix: each chunk's
`[B, H, Q, Q]` lives for its own forward or backward alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def chunk_length(steps: int, chunk: int) -> int:
    """The chunk the scan uses for an episode of `steps`: `chunk`, or the
    whole of a shorter episode."""
    chunk = min(chunk, steps)
    if steps % chunk:
        raise ValueError(f"{steps} steps are not whole chunks of {chunk}")
    return chunk


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, bmat: jax.Array,
                cmat: jax.Array, seg: jax.Array, chunk: int,
                dtype=jnp.bfloat16, carry_dtype=F32):
    """`x [B, T, H, P]`, `dt [B, T, H]` (after its softplus), `a [H]`
    (negative), `bmat, cmat [B, T, N]` (one group) or `[B, T, G, N]`
    (head h reads group h // (H / G)), `seg [B, T]` episode ids (not
    negative, not decreasing) -> (`y [B, T, H, P]` float32, the state
    after the last step `[B, H, P, N]`). `carry_dtype` is the dtype the
    state crosses a chunk boundary in (float32; a test plants another)."""
    b, t, h, p = x.shape
    groups = bmat.shape[2] if bmat.ndim == 4 else None
    if groups is not None and h % groups:
        raise ValueError(f"{h} heads are not whole groups of {groups}")
    q = chunk_length(t, chunk)
    mm = lambda spec, u, v: jnp.einsum(spec, u.astype(dtype), v.astype(dtype),
                                       preferred_element_type=F32)
    causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]

    @jax.checkpoint
    def one_chunk(carry, xs):
        state, seg_before = carry  # H_{c-1} and the episode it belongs to
        x_c, dt_c, b_c, c_c, seg_c = xs
        dt_c = dt_c.astype(F32)
        cs = jnp.cumsum(dt_c * a.astype(F32), axis=1)  # [B, Q, H]
        cs_h = jnp.moveaxis(cs, 2, 1)  # [B, H, Q]
        pair = causal & (seg_c[:, :, None] == seg_c[:, None, :])  # [B, Q, Q]
        decay = jnp.exp(jnp.where(
            pair[:, None], cs_h[..., :, None] - cs_h[..., None, :], -jnp.inf))
        xdt = x_c.astype(F32) * dt_c[..., None]  # [B, Q, H, P]
        by_group = lambda v, at: v.reshape(  # the head axis `at` as (G, H / G)
            *v.shape[:at], groups, h // groups, *v.shape[at + 1:])
        if groups is None:
            scores = mm("bin,bjn->bij", c_c, b_c)[:, None] * decay  # [B, H, Q, Q]
        else:  # `[B, G, Q, Q]` scores a group, under each of its heads' decay
            scores = (mm("bign,bjgn->bgij", c_c, b_c)[:, :, None]
                      * by_group(decay, 1)).reshape(decay.shape)
        y = mm("bhij,bjhp->bihp", scores, xdt)
        # what the past hands to the steps of the episode it ended in
        live = seg_c == seg_before[:, None]  # [B, Q]
        past = (mm("bin,bhpn->bihp", c_c, state) if groups is None else
                mm("bign,bgrpn->bigrp", c_c, by_group(state, 1)).reshape(x_c.shape))
        y = y + past * jnp.where(live[..., None], jnp.exp(cs), 0.0)[..., None]
        # the chunk's own state, and the whole chunk's decay of the past
        ends = seg_c[:, -1]
        to_end = jnp.where((seg_c == ends[:, None])[..., None],
                           jnp.exp(cs[:, -1:] - cs), 0.0)  # [B, Q, H]
        if groups is None:
            own = mm("bjhp,bjn->bhpn", xdt * to_end[..., None], b_c)
        else:
            own = mm("bjgrp,bjgn->bgrpn", by_group(xdt * to_end[..., None], 2),
                     b_c).reshape(state.shape)
        kept = jnp.where((ends == seg_before)[:, None], jnp.exp(cs[:, -1]), 0.0)
        state = kept[..., None, None] * state.astype(F32) + own
        return (state.astype(carry_dtype), ends), y

    chunks = lambda v: jnp.moveaxis(
        v.reshape(b, t // q, q, *v.shape[2:]), 1, 0)
    carry = (jnp.zeros((b, h, p, bmat.shape[-1]), carry_dtype),
             jnp.full((b,), -1, seg.dtype))  # no episode is -1: H_{-1} = 0
    (state, _), y = jax.lax.scan(
        one_chunk, carry, tuple(chunks(v) for v in (x, dt, bmat, cmat, seg)))
    return jnp.moveaxis(y, 0, 1).reshape(b, t, h, p), state.astype(F32)

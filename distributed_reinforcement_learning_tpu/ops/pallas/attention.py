"""Fused flash-attention TPU kernels: causal, segment-masked MHA.

The transformer family's hot op (models/transformer_net.py). The XLA
paths in ops/attention.py materialize [Tq, Tkv] probability blocks in
HBM between the softmax and the PV matmul; these kernels keep the whole
online-softmax recurrence in VMEM per query block — one launch per
(batch*head, q-block) instead of a scan of fused-but-HBM-roundtripping
block steps.

Layout: inputs are flattened to `[BH, T, D]` (batch*heads leading); the
grid is (BH, q-blocks, kv-blocks) with ONLY one block of each operand
VMEM-resident per step (online-softmax / gradient accumulators live in
scratch across the innermost kv/q walk), so T is bounded by HBM, not by
VMEM: a whole-K/V-resident design capped out at T~8k.
Per-query-row vectors (segment ids, logsumexp, delta) travel as
`[BH, T, 1]`, the keys' segment ids as `[BH, 1, T]`: a block of either
satisfies the TPU (8, 128)-tiling rule on the last two dims, and the
`[bq, 1] == [1, bkv]` compare needs no relayout in the kernel (turning
a `[bkv, 1]` column into a row cost a step more than its products).
Segment ids confine attention within episodes exactly like the
XLA paths; "no segments" is the all-zeros id vector (same segment
everywhere), so one kernel serves both cases.

The tile follows the input (`flash_blocks`): a side is the largest power
of two up to 512 that divides T, so a row of 2,048 is a grid of 4 x 4
steps of `[512, D] x [D, 512]` products where a fixed 128 x 128 tile made
16 x 16 steps of one 128-cube each (6.5 % of the matrix unit's peak in
the cell that runs it, 34 % since: PERF.md section 6, PR 41); a
row of 128 keeps its single 128 x 128 tile by the same rule. The rule
reckons the working set of the largest kernel (double-buffered blocks at
their lane-padded widths, the `[bq, bkv]` float32 scores / p / dp / ds,
the accumulators) and halves the longer side while it overflows the
scoped VMEM a kernel gets (`_SCOPED_VMEM_BYTES`, the compiler's default
16 MB: it holds 512 x 512 at the widths the cells run, not at every
width). Every product takes its operands in the dtype the caller sent
and accumulates in float32: p and ds are computed in float32 (scores,
max, exp, sums, `dp - delta`) and cast AT the product, as every other
matmul operand of a bfloat16 model is; float32 callers lose nothing.

Backward follows the standard flash decomposition: the forward saves
only (out, logsumexp); dq and (dk, dv) are two kernels that recompute
the probabilities from q/k/lse, using the precomputed per-row
`delta = rowsum(dout * out)` (a cheap XLA reduction outside).

Numerics are validated against `ops/attention.dense_attention` (values
and grads) in interpret mode on CPU and on TPU by tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_reinforcement_learning_tpu.ops.attention import _MASK_VALUE as _NEG
from distributed_reinforcement_learning_tpu.ops.pallas import batch_partitioned

# The largest tile a side (the sweep of PERF.md section 6, PR 41) and the
# scoped VMEM a kernel may use: the compiler's default, which the calls do
# not raise (asking for 32 MB moved ops nobody touched in the same program,
# decode up and the update's tail down: PERF.md section 6, PR 41).
_MAX_BLOCK = 512
_SCOPED_VMEM_BYTES = 16 << 20


def _pos(start, rows, cols, axis):
    """2-D position grid [rows, cols] counting along `axis` from `start`."""
    return start + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), axis)


def _block_mask(iq_start, jk_start, bq, bkv, qs, ks_row, window=None):
    """[bq, bkv] causal & same-segment mask, inside `window` if there is one
    (`q_pos - k_pos < window`).

    qs: [bq, 1] query segment ids; ks_row: [1, bkv] key segment ids.
    """
    q_pos, k_pos = _pos(iq_start, bq, bkv, 0), _pos(jk_start, bq, bkv, 1)
    causal = q_pos >= k_pos
    if window is not None:
        causal &= q_pos - k_pos < window
    return causal & (qs == ks_row)


def _kv_walk(iq, bq: int, bkv: int, window):
    """(first, last) kv block that q block `iq` sees: `last` holds its
    last row's own position; `first` (0 without a window) the oldest key
    its FIRST row's window reaches. None: no lower bound."""
    last = ((iq + 1) * bq - 1) // bkv
    if window is None:
        return None, last
    return jnp.maximum(iq * bq - (window - 1), 0) // bkv, last


def _in_walk(j, first, last):
    """Whether block `j` lies in a walk `first..last` (None: unbounded)."""
    inside = j <= last if last is not None else True
    return inside if first is None else inside & (j >= first)


def _q_walk(jk, bq: int, bkv: int, n_q: int, window):
    """(first, last) q block that sees kv block `jk`: the first is the one
    that holds its first key's position (causal), the last (None without
    a window) the one whose first row still reaches its last key."""
    first = (jk * bkv) // bq
    if window is None:
        return first, None
    return first, jnp.minimum(((jk + 1) * bkv - 1 + window - 1) // bq, n_q - 1)


def _fwd_kernel(q_ref, k_ref, v_ref, qs_ref, ks_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, window=None):
    """Grid (BH, nq, nkv): kv is a GRID axis (one k/v block VMEM-resident
    at a time — a full [T, D] K/V residency caps T at ~8k), with the
    online-softmax state in scratch across the inner kv walk; o/lse
    blocks revisit and flush on the last contributing step."""
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    bq = q_ref.shape[1]
    bkv = k_ref.shape[1]
    scale = q_ref.shape[2] ** -0.5

    @pl.when(jk == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    first, last = _kv_walk(iq, bq, bkv, window)  # the kv blocks this q block attends

    @pl.when(_in_walk(jk, first, last))
    def _():
        q = q_ref[0]
        qs = qs_ref[0]
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        ks_row = ks_ref[0]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        msk = _block_mask(iq * bq, jk * bkv, bq, bkv, qs, ks_row, window)
        s = jnp.where(msk, s, _NEG)
        m = m_scr[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(msk, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jk == pl.num_programs(2) - 1)
    def _():
        l_safe = jnp.maximum(l_scr[:], jnp.finfo(jnp.float32).tiny)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l_safe)


def _dq_kernel(q_ref, k_ref, v_ref, qs_ref, ks_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, *, window=None):
    """Grid (BH, nq, nkv), kv walked by the grid; dq accumulates in
    scratch and flushes on the last step (same shape as _fwd_kernel)."""
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    bq = q_ref.shape[1]
    bkv = k_ref.shape[1]
    scale = q_ref.shape[2] ** -0.5

    @pl.when(jk == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    first, last = _kv_walk(iq, bq, bkv, window)

    @pl.when(_in_walk(jk, first, last))
    def _():
        q = q_ref[0]
        qs = qs_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        ks_row = ks_ref[0]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        msk = _block_mask(iq * bq, jk * bkv, bq, bkv, qs, ks_row, window)
        p = jnp.where(msk, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jk == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, qs_ref, ks_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, block_q: int, window=None):
    """Grid (BH, nk, nq): the q axis is a GRID dimension, not an
    in-kernel loop, so only one q/do block is VMEM-resident at a time
    (a full [T, D] q + do residency overflowed scoped VMEM at T=8192).
    dk/dv accumulate in scratch across the inner q walk — the (b, jk)
    output blocks revisit — and flush on the last q step."""
    jk = pl.program_id(1)
    iq = pl.program_id(2)
    n_q = pl.num_programs(2)
    bkv = k_ref.shape[1]
    scale = k_ref.shape[2] ** -0.5

    @pl.when(iq == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # Causal: q blocks strictly before this kv block are fully masked; so
    # are those whose first row's window ends before it.
    seen = iq * block_q + block_q > jk * bkv
    if window is not None:
        seen &= iq <= _q_walk(jk, block_q, bkv, n_q, window)[1]

    @pl.when(seen)
    def _():
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        ks_row = ks_ref[0]
        q_i = q_ref[0]
        do_i = do_ref[0]
        lse_i = lse_ref[0]
        delta_i = delta_ref[0]
        qs_i = qs_ref[0]
        s = jax.lax.dot_general(
            q_i, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        msk = _block_mask(iq * block_q, jk * bkv, block_q, bkv, qs_i, ks_row, window)
        p = jnp.where(msk, jnp.exp(s - lse_i), 0.0)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do_i.dtype), do_i, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_i, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_i) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q_i.dtype), q_i, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == n_q - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _qkv_specs(d: int, bq: int, bkv: int, dv: int, window=None):
    """3-D-grid (b, i_q, j_kv) block specs: q-indexed, kv-indexed rows
    at q/k's width `d`, then the output (and its cotangent) and the
    value rows at the value's width `dv`.

    The kv index is CLAMPED to the last causally-visible block for the
    current q block: past it the index map repeats the same block, which
    Pallas recognizes as a revisit and does not re-DMA — the ~half of
    the rectangular grid that is fully future-masked (compute skipped by
    pl.when in the kernels) costs no HBM traffic either. Under a `window`
    it is clamped from below too, to the first block the window reaches:
    the steps before it fetch the block the first computed step needs.
    """

    def jcap(i, j):
        first, last = _kv_walk(i, bq, bkv, window)
        return jnp.minimum(j, last) if first is None else jnp.clip(j, first, last)

    q3 = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM)
    qrow3 = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM)
    kv3 = pl.BlockSpec(
        (1, bkv, d), lambda b, i, j: (b, jcap(i, j), 0), memory_space=pltpu.VMEM)
    krow3 = pl.BlockSpec(
        (1, 1, bkv), lambda b, i, j: (b, 0, jcap(i, j)), memory_space=pltpu.VMEM)
    o3 = pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM)
    v3 = pl.BlockSpec(
        (1, bkv, dv), lambda b, i, j: (b, jcap(i, j), 0), memory_space=pltpu.VMEM)
    return q3, qrow3, kv3, krow3, o3, v3


def _fwd_call(q, k, v, qs, ks, bq, bkv, interpret, window=None):
    bh, t, d = q.shape
    dv = v.shape[2]
    q3, qrow3, kv3, krow3, o3, v3 = _qkv_specs(d, bq, bkv, dv, window)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, window=window),
        grid=(bh, t // bq, t // bkv),
        in_specs=[q3, kv3, v3, qrow3, krow3],
        out_specs=[o3, qrow3],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, qs, ks)


def _bwd_call(q, k, v, qs, ks, do, lse, delta, bq, bkv, interpret, window=None):
    bh, t, d = q.shape
    dv = v.shape[2]
    q3, qrow3, kv3, krow3, o3, v3 = _qkv_specs(d, bq, bkv, dv, window)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, window=window),
        grid=(bh, t // bq, t // bkv),
        in_specs=[q3, kv3, v3, qrow3, krow3, o3, qrow3, qrow3],
        out_specs=[q3],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, qs, ks, do, lse, delta)[0]
    # 3-D grid: kv blocks indexed by j (middle), q/do blocks by the
    # innermost iq axis; dk/dv blocks revisit across iq. The q index is
    # clamped to the first causally-contributing block for this kv block
    # (skipped early steps revisit it — no re-DMA, compute pl.when'd off),
    # and under a window to the last q block that still reaches it.
    def icap(j, i):
        first, last = _q_walk(j, bq, bkv, t // bq, window)
        return jnp.maximum(i, first) if last is None else jnp.clip(i, first, last)

    kv3 = pl.BlockSpec((1, bkv, d), lambda b, j, i: (b, j, 0), memory_space=pltpu.VMEM)
    v3 = pl.BlockSpec((1, bkv, dv), lambda b, j, i: (b, j, 0), memory_space=pltpu.VMEM)
    krow3 = pl.BlockSpec((1, 1, bkv), lambda b, j, i: (b, 0, j), memory_space=pltpu.VMEM)
    q3 = pl.BlockSpec(
        (1, bq, d), lambda b, j, i: (b, icap(j, i), 0), memory_space=pltpu.VMEM)
    o3 = pl.BlockSpec(
        (1, bq, dv), lambda b, j, i: (b, icap(j, i), 0), memory_space=pltpu.VMEM)
    qrow3 = pl.BlockSpec(
        (1, bq, 1), lambda b, j, i: (b, icap(j, i), 0), memory_space=pltpu.VMEM)
    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=bq, window=window),
        grid=(bh, t // bkv, t // bq),
        in_specs=[q3, kv3, v3, qrow3, krow3, o3, qrow3, qrow3],
        out_specs=[kv3, v3],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv, d), jnp.float32),
            pltpu.VMEM((bkv, dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, qs, ks, do, lse, delta)
    return dq, dk, dv_


@functools.cache
def _make_flash(bq: int, bkv: int, interpret: bool, window: int | None = None):
    # Every kernel is independent along the batch*heads dim: under a
    # mesh each device runs it on its own rows (batch_partitioned reads
    # the context mesh at TRACE time, hence inside these functions).
    def fwd_call(q, k, v, qs, ks):
        return batch_partitioned(
            lambda *a: tuple(_fwd_call(*a, bq, bkv, interpret, window)),
            (0,) * 5, (0, 0))(q, k, v, qs, ks)

    def bwd_call(q, k, v, qs, ks, do, lse, delta):
        return batch_partitioned(
            lambda *a: _bwd_call(*a, bq, bkv, interpret, window),
            (0,) * 8, (0, 0, 0))(q, k, v, qs, ks, do, lse, delta)

    @jax.custom_vjp
    def f(q, k, v, qs, ks):
        out, _ = fwd_call(q, k, v, qs, ks)
        return out

    def f_fwd(q, k, v, qs, ks):
        out, lse = fwd_call(q, k, v, qs, ks)
        return out, (q, k, v, qs, ks, out, lse)

    def f_bwd(res, do):
        q, k, v, qs, ks, out, lse = res
        delta = jnp.sum(
            do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True)
        dq, dk, dv = bwd_call(q, k, v, qs, ks, do, lse, delta)
        return dq, dk, dv, None, None

    f.defvjp(f_fwd, f_bwd)
    return f


def flash_attention_bhtd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_seg: jax.Array,
    k_seg: jax.Array,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Causal flash attention on `[BH, T, D]` with `[BH, T]` segment ids.

    The tile is `flash_blocks`' for these shapes unless a test names its
    own (T must divide by both sides); differentiable via the fused
    dq/dkv kernels. `v` may have a width of its own (`[BH, T, Dv]`: the
    output's); the scores are scaled by q/k's `D ** -0.5`. Every product
    takes its operands in the dtype they arrive in and accumulates in
    float32; the softmax is float32 throughout. `window` (a static int;
    None: full causal): key j is visible to query t iff `t - j < window`
    too; kv blocks wholly outside a q block's window are neither computed
    nor fetched, and a row whose window holds no same-episode key gives
    zeros.
    """
    bh, t, d = q.shape
    if block_q is None or block_kv is None:
        block_q, block_kv = flash_blocks(t, d, v.shape[2], q.dtype.itemsize)
    if not block_q or t % block_q or t % block_kv:
        raise ValueError(f"T={t} not divisible by blocks ({block_q}, {block_kv})")
    if window is not None and window < 1:
        raise ValueError(f"window {window}: a query sees itself at least")
    f = _make_flash(block_q, block_kv, interpret, window)
    return f(q, k, v,
             q_seg.astype(jnp.int32).reshape(bh, t, 1),
             k_seg.astype(jnp.int32).reshape(bh, 1, t))


def _lanes(width: int) -> int:
    return -(-width // 128) * 128


def flash_working_set_bytes(bq: int, bkv: int, d: int, dv: int, itemsize: int) -> int:
    """VMEM a `(bq, bkv)` tile needs in the largest of the three kernels
    (dkv): every operand and result block twice (the pipeline's double
    buffer) at its lane-padded width, a `[bq, 1]` row vector as one
    128-lane word a row and the `[1, bkv]` ids as eight sublanes, the
    float32 accumulators, and the `[bq, bkv]` float32 scores, p, dp and ds
    with the two operand-dtype copies the products take. An upper
    reckoning: the compiler reuses what this counts apart."""
    width = _lanes(d) + _lanes(dv)  # a row of q + do, of k + v, of dk + dv
    blocks = 2 * ((bq + 2 * bkv) * width * itemsize
                  + 3 * bq * 128 * 4 + 8 * _lanes(bkv) * 4)
    accumulators = bkv * width * 4
    scores = bq * bkv * (4 * 4 + 2 * itemsize)
    return blocks + accumulators + scores


def flash_blocks(t: int, d: int, dv: int, itemsize: int) -> tuple[int, int]:
    """`(block_q, block_kv)` for a `[BH, t, d]` x `[BH, t, dv]` call.

    A side is the largest power of two (>= 8) up to the sweep's best that
    divides t; the kv side, which lies along the lanes of the scores and of
    the keys' segment ids, is a multiple of 128 or the whole row. The longer
    side is halved while the working set overflows a kernel's scoped VMEM.
    `(0, 0)` if no such tile exists (the caller keeps to XLA)."""

    def side(cap, least):
        while cap >= least and t % cap:
            cap //= 2
        return cap if cap >= least else 0

    bq, bkv = side(_MAX_BLOCK, 8), side(_MAX_BLOCK, 128) or t
    if not bq:
        return 0, 0
    while flash_working_set_bytes(bq, bkv, d, dv, itemsize) > _SCOPED_VMEM_BYTES:
        if bkv >= bq and bkv % 256 == 0:
            bkv //= 2
        elif bq > 8:
            bq //= 2
        else:
            return 0, 0
    return bq, bkv

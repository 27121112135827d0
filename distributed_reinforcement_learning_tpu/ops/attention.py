"""Multi-head attention ops: dense reference + blockwise online-softmax.

The reference has no attention at all — its long-context strategy is
short LSTM unrolls with stored state and burn-in (SURVEY §5.7,
`/root/reference/model/r2d2_lstm.py:65-112`). This module is the
TPU-native long-context generalization: a causal multi-head attention
primitive whose blockwise form (online-softmax accumulation over KV
blocks, the flash-attention recurrence) is exactly the per-device step
of ring attention (`parallel/sequence.py`), so the sequence-parallel
path and the single-device path share one numerics core.

Conventions: `q/k/v` are `[B, T, H, D]` (batch, time, heads, head_dim);
positions are absolute so sequence-sharded callers can pass global
offsets for causal masking.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Finite stand-in for -inf in masked logits: big enough that exp(x - m)
# underflows against any real logit, small enough that subtracting two of
# them is exact (no nan from inf - inf in the online-softmax rescale).
_MASK_VALUE = -0.5 * float(jnp.finfo(jnp.float32).max)


def _causal_mask(q_pos: jax.Array, k_pos: jax.Array) -> jax.Array:
    """[Tq, Tk] bool: query at global position i may attend keys <= i."""
    return q_pos[:, None] >= k_pos[None, :]


def _combined_mask(causal, q_pos, k_pos, q_seg, k_seg, window=None):
    """[B|1, 1, Tq, Tk] bool mask, or None when nothing constrains.
    `window` (a static int): a query sees the keys at most `window - 1`
    positions behind it, itself included (`q_pos - k_pos < window`).

    Segment ids (per batch row, e.g. episode indices from cumsum(done))
    confine attention within an episode: RL sequences cross episode
    boundaries mid-unroll, and a transformer must not attend across a
    reset the way the recurrent nets zero their (h, c) carries.
    """
    mask = None
    if causal:
        mask = _causal_mask(q_pos, k_pos)[None, None]
    if window is not None:
        near = (q_pos[:, None] - k_pos[None, :] < window)[None, None]
        mask = near if mask is None else (mask & near)
    if q_seg is not None:
        seg = (q_seg[:, None, :, None] == k_seg[:, None, None, :])
        mask = seg if mask is None else (mask & seg)
    return mask


def dense_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: int | jax.Array = 0,
    kv_offset: int | jax.Array = 0,
    q_seg: jax.Array | None = None,
    k_seg: jax.Array | None = None,
    window: int | None = None,
) -> jax.Array:
    """Plain softmax(QKᵀ/√d)V — the golden reference the blockwise and
    ring paths are tested against, and the fast path for short sequences
    where one fused XLA softmax beats any blocking. `v` may have a
    width of its own. `window`: a sliding window (`_combined_mask`)."""
    dim = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (dim**-0.5)
    q_pos = q_offset + jnp.arange(q.shape[1])
    k_pos = kv_offset + jnp.arange(k.shape[1])
    mask = _combined_mask(causal, q_pos, k_pos, q_seg, k_seg, window)
    if mask is not None:
        logits = jnp.where(mask, logits, _MASK_VALUE)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if mask is not None:
        # A fully-masked row (no same-segment key) must output zeros, not
        # a uniform average of _MASK_VALUE logits.
        probs = jnp.where(mask, probs, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def attention_block_init(q: jax.Array, v_dim: int | None = None):
    """(m, l, o) accumulator for online-softmax over KV blocks.

    m: running row max of logits `[B, H, Tq]` (f32); l: running softmax
    denominator `[B, H, Tq]` (f32); o: unnormalized numerator
    `[B, Tq, H, D]` (f32 — accumulating in the compute dtype loses the
    small-probability tail in bf16).
    """
    b, t, h, _ = q.shape
    m = jnp.full((b, h, t), _MASK_VALUE, jnp.float32)
    l = jnp.zeros((b, h, t), jnp.float32)
    o = jnp.zeros(q.shape if v_dim is None else (*q.shape[:-1], v_dim), jnp.float32)
    return m, l, o


def attention_block_step(
    acc,
    q: jax.Array,
    k_block: jax.Array,
    v_block: jax.Array,
    *,
    causal: bool,
    q_pos: jax.Array,
    k_pos: jax.Array,
    q_seg: jax.Array | None = None,
    k_seg: jax.Array | None = None,
    window: int | None = None,
):
    """Fold one KV block into the accumulator (flash-attention recurrence).

    `q_pos`/`k_pos` are global positions (`[Tq]`, `[Tk]`), so a
    sequence-sharded caller gets correct causal masking across shards;
    `q_seg`/`k_seg` (`[B, Tq]`, `[B, Tk]`) optionally confine attention
    within episode segments. Masked probabilities are zeroed explicitly
    (not just pushed to `_MASK_VALUE`) so a fully-masked block
    contributes exactly nothing.
    """
    m, l, o = acc
    dim = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_block).astype(jnp.float32) * (dim**-0.5)
    mask = _combined_mask(causal, q_pos, k_pos, q_seg, k_seg, window)
    if mask is not None:
        s = jnp.where(mask, s, _MASK_VALUE)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    scale = jnp.exp(m - m_new)
    l_new = l * scale + jnp.sum(p, axis=-1)
    o_new = o * scale.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v_block.astype(jnp.float32)
    )
    return m_new, l_new, o_new


def attention_block_finish(acc, dtype) -> jax.Array:
    """Normalize the accumulator into the attention output `[B, T, H, D]`."""
    _, l, o = acc
    denom = jnp.maximum(l, jnp.finfo(jnp.float32).tiny)
    return (o / denom.transpose(0, 2, 1)[..., None]).astype(dtype)


def causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_seg: jax.Array | None = None,
    k_seg: jax.Array | None = None,
    backend: str = "auto",
    window: int | None = None,
) -> jax.Array:
    """Causal (optionally segment-masked) MHA with backend dispatch.

    `auto` resolves to the fused Pallas flash kernels on TPU
    (`ops/pallas/attention.py`) when T divides by a >=8 power-of-two
    block — VMEM holds only per-block operands, so T is HBM-bound, and
    the kernels take their tile from T, the widths and the dtype
    (`flash_blocks`) — else to plain dense softmax for short sequences
    or the blockwise online-softmax path for long ones. All paths share the same
    numerics contract (validated against dense in tests). `v` may have
    a width of its own (`[B, T, H, Dv]`, the output's), on every path.
    `window` (a static int, None: full causal): key j is visible to query
    t iff `t - j < window` as well; the kernels skip and do not fetch the
    blocks that lie wholly outside it.
    """
    from distributed_reinforcement_learning_tpu.ops.pallas import resolve_backend
    from distributed_reinforcement_learning_tpu.ops.pallas.attention import flash_blocks

    if (q_seg is None) != (k_seg is None):
        raise ValueError("q_seg and k_seg must be provided together")
    b, t, h, d = q.shape
    resolved = resolve_backend(backend)
    if (resolved in ("pallas", "pallas_interpret")
            and flash_blocks(t, d, v.shape[-1], q.dtype.itemsize)[0]):
        from distributed_reinforcement_learning_tpu.ops.pallas.attention import (
            flash_attention_bhtd)

        zeros = jnp.zeros((b, t), jnp.int32)
        qs = zeros if q_seg is None else q_seg.astype(jnp.int32)
        ks = zeros if k_seg is None else k_seg.astype(jnp.int32)
        flat = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, x.shape[-1])
        seg_flat = lambda s: jnp.repeat(s, h, axis=0)
        out = flash_attention_bhtd(
            flat(q), flat(k), flat(v), seg_flat(qs), seg_flat(ks),
            interpret=(resolved == "pallas_interpret"), window=window,
        )
        return out.reshape(b, h, t, v.shape[-1]).transpose(0, 2, 1, 3)
    if t <= 1024:
        return dense_attention(q, k, v, causal=True, q_seg=q_seg, k_seg=k_seg,
                               window=window)
    return blockwise_attention(
        q, k, v, causal=True, block_size=512,
        segment_ids=q_seg, kv_segment_ids=k_seg, window=window,
    )


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_size: int = 512,
    segment_ids: jax.Array | None = None,
    kv_segment_ids: jax.Array | None = None,
    window: int | None = None,
) -> jax.Array:
    """Single-device attention computed block-by-block over keys.

    Memory is O(T·block) instead of O(T²) — the long-context path when a
    full logits matrix would blow HBM. Same numerics core as ring
    attention; used as its single-device functional test double.
    `segment_ids` `[B, Tq]` optionally confines attention within
    episodes (`kv_segment_ids` defaults to it for self-attention).
    """
    t_kv = k.shape[1]
    block_size = min(block_size, t_kv)
    if t_kv % block_size != 0:
        raise ValueError(f"kv length {t_kv} not divisible by block {block_size}")
    n_blocks = t_kv // block_size
    q_pos = jnp.arange(q.shape[1])
    kb = k.reshape(k.shape[0], n_blocks, block_size, *k.shape[2:])
    vb = v.reshape(v.shape[0], n_blocks, block_size, *v.shape[2:])
    kv_seg = segment_ids if kv_segment_ids is None else kv_segment_ids
    segb = (
        None
        if kv_seg is None
        else kv_seg.reshape(kv_seg.shape[0], n_blocks, block_size)
    )

    def step(acc, blk):
        k_blk, v_blk, seg_blk, i = blk
        k_pos = i * block_size + jnp.arange(block_size)
        return (
            attention_block_step(
                acc, q, k_blk, v_blk, causal=causal, q_pos=q_pos, k_pos=k_pos,
                q_seg=segment_ids, k_seg=seg_blk, window=window,
            ),
            None,
        )

    xs = (
        kb.swapaxes(0, 1),
        vb.swapaxes(0, 1),
        None if segb is None else segb.swapaxes(0, 1),
        jnp.arange(n_blocks),
    )
    acc, _ = jax.lax.scan(step, attention_block_init(q, v.shape[-1]), xs)
    return attention_block_finish(acc, q.dtype)

"""Transformer Q-network: attention-based long-context alternative to LSTM.

The reference's only sequence model is a Python-loop LSTM with stored
state (`/root/reference/model/r2d2_lstm.py:65-112`), which caps usable
context at the unroll length. This model family removes the recurrence:
a causal pre-LN transformer over the sequence whose attention is
confined within episodes by segment ids derived from `done` — the exact
transformer counterpart of the reference's done-masked (h, c) zeroing
(`model/r2d2_lstm.py:78-80`). Context length is then a config knob, and
for long sequences the attention routes through the sequence-parallel
ring / all-to-all paths in `parallel/sequence.py` via `attention_fn`.

Torso/head conventions follow the in-tree R2D2 net (`models/r2d2_net.py`):
two 256-wide MLP layers on the observation, a prev-action embedding, and
the reference's nonstandard dueling head `value - learned_mean`
(`/root/reference/model/r2d2_lstm.py:45-47`).
"""

from __future__ import annotations

from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.models.torso import ActionEmbedding
from distributed_reinforcement_learning_tpu.ops.attention import causal_attention

_glorot = nn.initializers.xavier_uniform()

# attention_fn contract: (q, k, v, segment_ids) -> out, all [B, T, H, D]
# (segment_ids [B, T]); must implement causal masking internally.
AttentionFn = Callable[[jax.Array, jax.Array, jax.Array, jax.Array], jax.Array]


def episode_segments(done_seq: jax.Array) -> jax.Array:
    """[B, T] episode ids from done flags.

    done[t] marks transition t as terminal: step t still belongs to the
    ending episode, t+1 starts the next — matching where the recurrent
    nets zero their carries (*after* the step at which done is set).
    """
    d = done_seq.astype(jnp.int32)
    return jnp.concatenate([jnp.zeros_like(d[:, :1]), jnp.cumsum(d, axis=1)[:, :-1]], axis=1)


def rope(x: jax.Array, positions: jax.Array | None = None, base: float = 10_000.0) -> jax.Array:
    """Rotary position embedding over the time axis of `[B, T, H, D]`.

    RELATIVE positions are the load-bearing choice, not a style one: the
    TD loss supervises window positions burn_in..T-2 while the actor
    always queries the final position of its rolling window. A learned
    absolute embedding leaves that acting position untrained (it only
    ever feeds the stop-gradded double-Q argmax), which measurably
    prevented CartPole-POMDP learning; with RoPE, "current step
    attending k back" is the same computation wherever the window sits.

    `positions` overrides the default arange when the stream is held in
    a permuted layout (zigzag sequence parallelism); `[B, T]` positions
    give every row its own (the position inside its episode:
    `models/looped_lm.py`, which also sets `base` from its section's
    `rope_theta`).
    """
    d2 = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    if positions is None:
        positions = jnp.arange(x.shape[1])
    angles = positions.astype(jnp.float32)[..., None] * freqs
    if angles.ndim == 2:  # [T, d2]: the same positions for every row
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


class SelfAttentionBlock(nn.Module):
    """Pre-LN attention + MLP block; `num_experts > 0` swaps the dense
    MLP for a mixture-of-experts layer (`ops/moe.py`), with the router's
    load-balancing aux loss sown into the `losses` collection (a no-op
    on act paths that don't mark it mutable)."""

    d_model: int
    num_heads: int
    dtype: jnp.dtype
    attention_fn: AttentionFn | None
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    moe_mesh: object = None

    @nn.compact
    def __call__(self, x: jax.Array, segs: jax.Array, positions: jax.Array | None = None) -> jax.Array:
        b, t, _ = x.shape
        head_dim = self.d_model // self.num_heads
        y = nn.LayerNorm(dtype=self.dtype)(x)
        qkv = nn.Dense(3 * self.d_model, kernel_init=_glorot, dtype=self.dtype)(y)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        split = lambda z: z.reshape(b, t, self.num_heads, head_dim)
        q, k, v = rope(split(q), positions), rope(split(k), positions), split(v)
        if self.attention_fn is not None:
            out = self.attention_fn(q, k, v, segs)
        else:
            # Backend-dispatched: Pallas flash kernels on TPU when the
            # shape qualifies, dense/blockwise XLA otherwise.
            out = causal_attention(q, k, v, q_seg=segs, k_seg=segs)
        out = out.reshape(b, t, self.d_model).astype(self.dtype)
        x = x + nn.Dense(self.d_model, kernel_init=_glorot, dtype=self.dtype)(out)

        y = nn.LayerNorm(dtype=self.dtype)(x)
        if self.num_experts:
            from distributed_reinforcement_learning_tpu.ops import moe as moe_ops

            # One pytree param via ops/moe.py's own init: shapes and
            # initializers live in one place; the nested moe_* keys are
            # what learner.py's expert-sharding path rule matches.
            p = self.param(
                "moe",
                lambda rng: moe_ops.init_moe_params(
                    rng, self.d_model, 4 * self.d_model, self.num_experts
                ),
            )
            y, aux = moe_ops.moe_mlp(
                y,
                p,
                top_k=self.moe_top_k,
                capacity_factor=self.moe_capacity_factor,
                mesh=self.moe_mesh,
            )
            self.sow("losses", "moe_aux", aux)
            return x + y.astype(self.dtype)
        y = nn.Dense(4 * self.d_model, kernel_init=_glorot, dtype=self.dtype)(y)
        y = nn.relu(y)
        return x + nn.Dense(self.d_model, kernel_init=_glorot, dtype=self.dtype)(y)


def _stacked_block_init(rng: jax.Array, num_layers: int, d_model: int) -> dict:
    """[L, ...]-stacked parameters for `_stacked_block_apply`.

    One pytree whose leaves carry a leading layer dimension — the layout
    `lax.scan`-over-layers and the pipeline schedule both want (and the
    layout `parallel/learner.py` shards over the `pipe` axis). Stacked
    with `parallel.pipeline.stack_stage_params` so the init follows the
    same per-stage rng convention as every other pipelined stack.
    """
    d, h = d_model, 4 * d_model
    glorot = jax.nn.initializers.glorot_uniform()

    def one(rng):
        ks = jax.random.split(rng, 4)
        return {
            "ln1_scale": jnp.ones((d,)),
            "ln1_bias": jnp.zeros((d,)),
            "qkv_kernel": glorot(ks[0], (d, 3 * d)),
            "qkv_bias": jnp.zeros((3 * d,)),
            "proj_kernel": glorot(ks[1], (d, d)),
            "proj_bias": jnp.zeros((d,)),
            "ln2_scale": jnp.ones((d,)),
            "ln2_bias": jnp.zeros((d,)),
            "mlp1_kernel": glorot(ks[2], (d, h)),
            "mlp1_bias": jnp.zeros((h,)),
            "mlp2_kernel": glorot(ks[3], (h, d)),
            "mlp2_bias": jnp.zeros((d,)),
        }

    from distributed_reinforcement_learning_tpu.parallel.pipeline import stack_stage_params

    return stack_stage_params(one, rng, num_layers)


def _layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array) -> jax.Array:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + 1e-6) * scale + bias


def _stacked_block_apply(
    p: dict, x: jax.Array, segs: jax.Array, *, num_heads: int, dtype
) -> jax.Array:
    """One pre-LN transformer block as a pure function of one stage's
    params — the same math as `SelfAttentionBlock`'s dense path, but
    with explicit parameters so the pipeline schedule can hold exactly
    one layer's weights per device."""
    b, t, d = x.shape
    head_dim = d // num_heads
    cast = lambda a: a.astype(dtype)
    y = _layer_norm(x, cast(p["ln1_scale"]), cast(p["ln1_bias"]))
    qkv = y @ cast(p["qkv_kernel"]) + cast(p["qkv_bias"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    split = lambda z: z.reshape(b, t, num_heads, head_dim)
    q, k, v = rope(split(q)), rope(split(k)), split(v)
    out = causal_attention(q, k, v, q_seg=segs, k_seg=segs)
    out = out.reshape(b, t, d).astype(dtype)
    x = x + out @ cast(p["proj_kernel"]) + cast(p["proj_bias"])
    y = _layer_norm(x, cast(p["ln2_scale"]), cast(p["ln2_bias"]))
    y = nn.relu(y @ cast(p["mlp1_kernel"]) + cast(p["mlp1_bias"]))
    return x + y @ cast(p["mlp2_kernel"]) + cast(p["mlp2_bias"])


class TransformerQNet(nn.Module):
    """MLP torso + action embed -> causal transformer -> output head.

    One signature: `(obs_seq [B,T,...], prev_action_seq [B,T],
    done_seq [B,T])`. Two heads over the same trunk (every body feature
    — ring/zigzag/ulysses attention, MoE, stacked layers, pipeline,
    remat — serves both):

    - `head="dueling_q"` (default): `q [B,T,A]` via the reference's
      nonstandard dueling `value - learned-mean` form — the
      Transformer-R2D2 family.
    - `head="actor_critic"`: `(policy [B,T,A] softmax, value [B,T])` —
      the Transformer-IMPALA family (V-trace consumes softmax policies,
      `ops/vtrace.py`).

    Acting uses the same forward over a rolling window (the actor's
    "recurrent state" is the window itself); training unrolls the stored
    sequence exactly like the recurrent nets, so the loss-side logic is
    model-agnostic.
    """

    num_actions: int
    d_model: int = 256
    num_heads: int = 4
    num_layers: int = 2
    max_len: int = 512
    dtype: jnp.dtype = jnp.float32
    attention_fn: AttentionFn | None = None
    # (perm, inverse) int tuples from `parallel.sequence.zigzag_permutation`:
    # the residual stream is reordered ONCE here (and the output back)
    # instead of inside every attention call — per-layer permutes of a
    # sequence-sharded stream would each cost a resharding collective.
    # RoPE and segment masking use the true global positions throughout;
    # the zigzag attention body computes its block positions from the
    # same layout, so `attention_fn` must be a pre_permuted zigzag ring.
    sequence_perm: tuple | None = None
    # Mixture-of-experts MLPs (ops/moe.py) in every block when > 0;
    # `moe_mesh` with an `expert` axis > 1 runs them expert-parallel.
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    moe_mesh: object = None
    # Pipeline parallelism: `stack_layers` stores the blocks as one
    # [num_layers, ...]-stacked param pytree ("blocks_stacked" — a
    # different checkpoint layout, like any scan-over-layers model) and
    # applies them with lax.scan; `pipeline_mesh` with a `pipe` axis
    # that divides num_layers runs them as GPipe stages instead
    # (parallel/pipeline.py), each stage scanning its contiguous
    # num_layers/pipe layer group locally (virtual stages).
    stack_layers: bool = False
    pipeline_mesh: object = None
    pipeline_microbatches: int = 2
    # Rematerialize each block in the backward pass (jax.checkpoint):
    # activation memory stops growing with num_layers x seq_len at the
    # cost of one extra forward — the standard long-context lever.
    remat: bool = False
    # "dueling_q" | "actor_critic" — see the class docstring.
    head: str = "dueling_q"

    @nn.compact
    def __call__(self, obs_seq: jax.Array, prev_action_seq: jax.Array, done_seq: jax.Array):
        b, t = prev_action_seq.shape
        if t > self.max_len:
            raise ValueError(f"sequence length {t} exceeds max_len {self.max_len}")
        x = obs_seq.astype(self.dtype).reshape(b, t, -1)
        x = nn.relu(nn.Dense(256, kernel_init=_glorot, dtype=self.dtype)(x))
        x = nn.relu(nn.Dense(256, kernel_init=_glorot, dtype=self.dtype)(x))
        a = ActionEmbedding(self.num_actions, dtype=self.dtype)(prev_action_seq)
        z = jnp.concatenate([x, a], axis=-1)
        z = nn.Dense(self.d_model, kernel_init=_glorot, dtype=self.dtype)(z)
        # No absolute position embedding: order information enters via
        # RoPE on (q, k) inside each block — see `rope` for why relative
        # positions are required here.

        segs = episode_segments(done_seq)  # chronological, before any reorder
        positions = None
        if self.sequence_perm is not None:
            if self.attention_fn is None:
                raise ValueError(
                    "sequence_perm without a layout-aware attention_fn would "
                    "causally mask in the wrong order")
            perm, _ = self.sequence_perm
            if len(perm) != t:
                raise ValueError(f"sequence_perm is for T={len(perm)}, got T={t}")
            positions = jnp.asarray(perm)
            z = jnp.take(z, positions, axis=1)
            segs = jnp.take(segs, positions, axis=1)
        if self.stack_layers:
            if self.attention_fn is not None or self.num_experts:
                raise ValueError(
                    "stack_layers uses the dense-attention pure-function block; "
                    "sequence-parallel attention_fn / MoE need the module body "
                    "(nesting their shard_maps inside a pipeline stage is "
                    "unsupported)")
            blocks = self.param(
                "blocks_stacked",
                lambda rng: _stacked_block_init(rng, self.num_layers, self.d_model),
            )
            def block(p, zz, ss):
                return _stacked_block_apply(
                    p, zz, ss, num_heads=self.num_heads, dtype=self.dtype)

            if self.remat:
                # prevent_cse=False: this block only ever runs under
                # lax.scan (layer scan / pipeline stage scan), whose loop
                # structure already provides the guarantee prevent_cse's
                # optimization barriers exist for — keeping them would
                # just block XLA fusion inside the remat body.
                block = jax.checkpoint(block, prevent_cse=False)
            apply = lambda p, zz: block(p, zz, segs)
            if self.pipeline_mesh is not None:
                from distributed_reinforcement_learning_tpu.parallel import pipeline as pp
                from distributed_reinforcement_learning_tpu.parallel.mesh import (
                    DATA_AXIS, PIPE_AXIS)

                mesh = self.pipeline_mesh
                stages = mesh.shape.get(PIPE_AXIS, 1)
                if stages < 2 or self.num_layers % stages != 0:
                    raise ValueError(
                        f"pipeline mesh pipe axis {stages} must be >= 2 and "
                        f"divide num_layers {self.num_layers}")
                per_stage = self.num_layers // stages
                # Virtual stages: each device owns a contiguous group of
                # `per_stage` layers, scanned locally within its tick.
                staged = jax.tree.map(
                    lambda a: a.reshape(stages, per_stage, *a.shape[1:]), blocks)
                batch_axis = DATA_AXIS if mesh.shape.get(DATA_AXIS, 1) > 1 else None

                # Segment ids ride through the activation pytree so each
                # microbatch attends with ITS rows' episode boundaries.
                def stage(p, act):
                    zz, ss = act
                    zz = jax.lax.scan(
                        lambda c, pl: (block(pl, c, ss), None), zz, p
                    )[0]
                    return zz, ss

                z, _ = pp.pipeline_apply(
                    mesh,
                    stage,
                    staged,
                    (z, segs),
                    num_microbatches=self.pipeline_microbatches,
                    batch_axis=batch_axis,
                )
            else:
                z = jax.lax.scan(lambda zz, p: (apply(p, zz), None), z, blocks)[0]
        else:
            block_cls = nn.remat(SelfAttentionBlock) if self.remat else SelfAttentionBlock
            for i in range(self.num_layers):
                z = block_cls(
                    self.d_model,
                    self.num_heads,
                    self.dtype,
                    self.attention_fn,
                    num_experts=self.num_experts,
                    moe_top_k=self.moe_top_k,
                    moe_capacity_factor=self.moe_capacity_factor,
                    moe_mesh=self.moe_mesh,
                    # Explicit name: nn.remat changes the class name and
                    # with it the auto-name, and the param tree must stay
                    # identical with remat on/off (checkpoints, actor
                    # twins).
                    name=f"SelfAttentionBlock_{i}",
                )(z, segs, positions)
        z = nn.LayerNorm(dtype=self.dtype)(z)
        h = nn.relu(nn.Dense(128, kernel_init=_glorot, dtype=self.dtype)(z))
        unperm = (
            (lambda x: x)
            if self.sequence_perm is None
            else (lambda x: jnp.take(x, jnp.asarray(self.sequence_perm[1]), axis=1))
        )
        if self.head == "actor_critic":
            logits = nn.Dense(
                self.num_actions, kernel_init=_glorot, dtype=self.dtype
            )(h).astype(jnp.float32)
            value = nn.Dense(1, kernel_init=_glorot, dtype=self.dtype)(h)
            policy = jax.nn.softmax(unperm(logits), axis=-1)
            return policy, unperm(value.astype(jnp.float32)[..., 0])
        if self.head != "dueling_q":
            raise ValueError(f"unknown head {self.head!r}")
        q = nn.Dense(self.num_actions, kernel_init=_glorot, dtype=self.dtype)(h)
        mean = nn.Dense(1, kernel_init=_glorot, dtype=self.dtype)(h)
        return unperm((q - mean).astype(jnp.float32))

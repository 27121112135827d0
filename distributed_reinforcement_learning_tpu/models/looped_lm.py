"""Looped decoder language model (Ouro: ByteDance Seed, "Scaling Latent
Reasoning via Looped Language Models", 2025-10): ONE stack of decoder
layers run `loop_passes` times on its own output with the same weights,
a learned exit gate after every pass, an untied vocabulary head, and
(RL's addition) a value head.

    h^(0) = E[x];   h^(r) = Layer_L(... Layer_1(h^(r-1))),  r = 1..R
    Layer: u = h + N2(Attn(N1(h)));  h' = u + N4(W_d(silu(W_g N3 u) * W_u N3 u))
    z^(r) = RMSNorm(h^(r); g_f);  logits^(r) = z^(r) W_out
    lambda^(r) = sigmoid(z^(r) . w_e + b_e);   v^(r) = z^(r) . w_v + b_v

Parameters are ONE `[L, ...]`-stacked pytree: the stack is a `lax.scan`
over layers inside a `lax.scan` over passes, so the compiled program
holds one layer body whatever L and R are, and the gradient of every
weight is the sum over its R uses (the scan's transpose accumulates it
in the parameters' float32).

Two entries:

- `LoopedLM.trunk` + `token_stats`: the learner's `[B, T]` forward
  under the causal AND same-episode mask (`ops.attention.causal_attention`,
  the flash kernel on a TPU), every layer rematerialised;
- `LoopedLM.decode`: one token a row through a key/value cache `[R, L,
  N, T, H, d]` x 2, ONE PER LOOP PASS: pass r's keys are projections of
  h^(r-1), so the passes cannot share a cache, and a looped decoder
  carries R times the cache of a plain one of the same depth. Step t
  writes position t and attends over positions <= t, reading a static
  prefix that covers them (`span`; `decode_spans` cuts an episode into
  the segments that share one): the read is bound by the bytes of the
  row, and past t the row holds only `init_cache`'s zeros.

Precision (`dtype`, bfloat16 as the configuration states it): matmul
operands and activations in `dtype` with float32 accumulation; norm
statistics, RoPE, softmax, the gate, the value and everything after the
logits in float32; parameters float32.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.models.transformer_net import (
    episode_segments, rope)
from distributed_reinforcement_learning_tpu.observability import scopes
from distributed_reinforcement_learning_tpu.ops.attention import (
    _MASK_VALUE, causal_attention)

F32 = jnp.float32
# The stacked matrices of the layer stack: what acting casts to the
# compute dtype once an update (`for_acting` below).
STACK_MATRICES = ("wqkv", "wo", "wgu", "wd")


class KVCache(NamedTuple):
    """Keys and values of every pass and layer, `[R, L, N, T, H, d]`."""

    k: jax.Array
    v: jax.Array


def decode_spans(length: int, segments: int | None = None) -> tuple[int, ...]:
    """The static cache prefixes an episode of `length` decode steps
    reads: segment i is the steps `[spans[i-1], spans[i])` (from 0) and
    every step of it reads the first `spans[i]` positions of its cache
    row. `segments` follows from the shape: 8 from 128 steps on (a mean
    read of 9/16 of the row; 1/2 is the least any scheme reads), fewer
    for short episodes (segments of at least 16 steps), one under 32."""
    if segments is None:
        segments = min(8, max(1, length // 16))
    if not 1 <= segments <= length:
        raise ValueError(f"{segments} segments of {length} decode steps")
    step = -(-length // segments)
    return tuple(range(step, length, step)) + (length,)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """`scale * x / sqrt(mean(x^2) + eps)`, statistics and result float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * scale.astype(F32)


def episode_positions(done: jax.Array) -> jax.Array:
    """`[B, T]` position of every step inside its episode: `done[t]`
    ends an episode AT t, so t + 1 is position 0 of the next."""
    t = done.shape[1]
    steps = jnp.arange(t)[None]
    start = jnp.concatenate(
        [jnp.ones_like(done[:, :1]), done[:, :-1]], axis=1).astype(bool)
    return steps - jax.lax.cummax(jnp.where(start, steps, 0), axis=1)


def exit_distribution(gate: jax.Array) -> jax.Array:
    """`gate [R, ...]` -> the probability of leaving after pass r,
    p(r) = lambda^(r) prod_{j<r}(1 - lambda^(j)), what is left for r = R."""
    stay = jnp.cumprod(1.0 - gate[:-1], axis=0)  # prod_{j<=r}
    before = jnp.concatenate([jnp.ones_like(gate[:1]), stay[:-1]], axis=0)
    # what is left after the last pass; all of it where there is one pass
    return jnp.concatenate([gate[:-1] * before, (stay if len(stay) else before)[-1:]], axis=0)


class LoopedLM(nn.Module):
    vocab: int
    d_model: int
    num_heads: int
    head_dim: int
    d_ff: int
    num_layers: int
    loop_passes: int
    rms_eps: float = 1e-6
    rope_theta: float = 1e6
    dtype: Any = jnp.bfloat16
    init_std: float = 0.02
    attention_backend: str = "auto"  # `ops.attention.causal_attention`'s

    def setup(self):
        init, ones = nn.initializers.normal(self.init_std), nn.initializers.ones
        n, d, f, a = (self.num_layers, self.d_model, self.d_ff,
                      self.num_heads * self.head_dim)
        self.embed = self.param("embed", init, (self.vocab, d))
        self.wqkv = self.param("wqkv", init, (n, d, 3 * a))
        self.wo = self.param("wo", init, (n, a, d))
        self.wgu = self.param("wgu", init, (n, d, 2 * f))
        self.wd = self.param("wd", init, (n, f, d))
        self.norms = self.param("norms", ones, (n, 4, d))  # N1..N4 of a layer
        self.final_norm = self.param("final_norm", ones, (d,))
        self.w_out = self.param("w_out", init, (d, self.vocab))
        self.w_exit = self.param("w_exit", init, (d,))
        self.b_exit = self.param("b_exit", nn.initializers.zeros, ())
        self.w_value = self.param("w_value", init, (d,))
        self.b_value = self.param("b_value", nn.initializers.zeros, ())

    def __call__(self, tokens: jax.Array, done: jax.Array) -> jax.Array:
        return self.trunk(tokens, done)

    # -- shared pieces ----------------------------------------------------
    def _mm(self, x: jax.Array, w: jax.Array) -> jax.Array:
        """`x @ w`, operands in `dtype`, float32 accumulation."""
        return jnp.dot(x.astype(self.dtype), w.astype(self.dtype),
                       preferred_element_type=F32)

    def _stack(self):
        return {"wqkv": self.wqkv, "wo": self.wo, "wgu": self.wgu,
                "wd": self.wd, "norms": self.norms}

    def _mlp(self, u: jax.Array, lp: dict) -> jax.Array:
        y = rms_norm(u, lp["norms"][2], self.rms_eps)
        gate, up = jnp.split(self._mm(y, lp["wgu"]), 2, axis=-1)
        mlp = self._mm(jax.nn.silu(gate) * up, lp["wd"])
        return (u.astype(F32) + rms_norm(mlp, lp["norms"][3], self.rms_eps)
                ).astype(self.dtype)

    # -- the learner's forward --------------------------------------------
    def _layer(self, h, lp, segs, pos):
        b, t, _ = h.shape
        y = rms_norm(h, lp["norms"][0], self.rms_eps)
        split = lambda x: x.reshape(b, t, self.num_heads, self.head_dim)
        q, k, v = (split(x) for x in jnp.split(self._mm(y, lp["wqkv"]), 3, -1))
        q = rope(q, pos, self.rope_theta).astype(self.dtype)
        k = rope(k, pos, self.rope_theta).astype(self.dtype)
        att = causal_attention(q, k, v.astype(self.dtype), q_seg=segs,
                               k_seg=segs, backend=self.attention_backend)
        att = self._mm(att.reshape(b, t, -1), lp["wo"])
        u = (h.astype(F32) + rms_norm(att, lp["norms"][1], self.rms_eps)
             ).astype(self.dtype)
        return self._mlp(u, lp)

    def trunk(self, tokens: jax.Array, done: jax.Array) -> jax.Array:
        """`tokens, done [B, T]` -> h^(r) of every pass `[R, B, T, D]`
        (before the final norm). Every layer is rematerialised: what the
        backward pass keeps is R x L layer inputs."""
        segs, pos = episode_segments(done), episode_positions(done)
        stack = self._stack()
        layer = jax.checkpoint(
            lambda h, lp: (self._layer(h, lp, segs, pos), None))

        def one_pass(h, _):
            h, _ = jax.lax.scan(layer, h, stack)
            return h, h

        with jax.named_scope(scopes.LOOP):
            h0 = self.embed[tokens].astype(self.dtype)
            _, hs = jax.lax.scan(one_pass, h0, None, length=self.loop_passes)
        return hs

    def token_stats(self, h: jax.Array, actions: jax.Array) -> dict:
        """One pass's heads on a block of positions: `h [..., D]`,
        `actions [...]` -> float32 `logp` of the taken action, `entropy`
        of the policy, `gate`, `value`; the `[..., V]` logits live only
        inside (the caller rematerialises this per pass and block)."""
        logits, gate, value = self.logits(h)
        lse = jax.nn.logsumexp(logits, axis=-1)
        taken = jnp.take_along_axis(logits, actions[..., None], axis=-1)[..., 0]
        p = jnp.exp(logits - lse[..., None])
        return {"logp": taken - lse,
                "entropy": lse - jnp.sum(p * logits, axis=-1),
                "gate": gate, "value": value}

    def logits(self, h: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
        """(logits, gate, value) of one pass, float32: the acting head
        and what the comparison with the plain reference reads."""
        z = rms_norm(h, self.final_norm, self.rms_eps)
        return (self._mm(z, self.w_out),
                jax.nn.sigmoid(z @ self.w_exit.astype(F32) + self.b_exit),
                z @ self.w_value.astype(F32) + self.b_value)

    # -- acting as decode --------------------------------------------------
    def cache_shape(self, num_rows: int, length: int) -> tuple[int, ...]:
        return (self.loop_passes, self.num_layers, num_rows, length,
                self.num_heads, self.head_dim)

    def _decode_layer(self, carry, xs, slot, t, span=None):
        """One layer of decode pass `slot`, which reads and writes cache
        `slot`: its own, as far as `span` (the whole row by default)."""
        h, cache = carry
        span = cache.k.shape[3] if span is None else span
        lp, layer_index = xs
        n = h.shape[0]
        y = rms_norm(h, lp["norms"][0], self.rms_eps)
        split = lambda x: x.reshape(n, 1, self.num_heads, self.head_dim)
        q, k, v = (split(x) for x in jnp.split(self._mm(y, lp["wqkv"]), 3, -1))
        at = jnp.full((1,), t)
        q = rope(q, at, self.rope_theta).astype(self.dtype)
        k = rope(k, at, self.rope_theta).astype(self.dtype)
        with jax.named_scope(scopes.ACT_CACHE):
            where = (slot, layer_index, 0, t, 0, 0)
            cache = KVCache(
                jax.lax.dynamic_update_slice(cache.k, k[None, None], where),
                jax.lax.dynamic_update_slice(cache.v, v.astype(self.dtype)[None, None],
                                             where))
            row = (slot, layer_index, 0, 0, 0, 0)
            size = (1, 1, cache.k.shape[2], span, *cache.k.shape[4:])
            keys = jax.lax.dynamic_slice(cache.k, row, size)[0, 0]
            values = jax.lax.dynamic_slice(cache.v, row, size)[0, 0]
        s = jnp.einsum("nqhd,nkhd->nhqk", q, keys,
                       preferred_element_type=F32) * self.head_dim ** -0.5
        seen = (jnp.arange(span) <= t)[None, None, None, :]
        p = jnp.where(seen, jax.nn.softmax(jnp.where(seen, s, _MASK_VALUE), -1), 0.0)
        att = jnp.einsum("nhqk,nkhd->nqhd", p.astype(self.dtype), values,
                         preferred_element_type=F32)
        att = self._mm(att.reshape(n, -1), lp["wo"])
        u = (h.astype(F32) + rms_norm(att, lp["norms"][1], self.rms_eps)
             ).astype(self.dtype)
        return (self._mlp(u, lp), cache), None

    def decode(self, tokens: jax.Array, t: jax.Array, cache: KVCache,
               span: int | None = None):
        """One decode step at batch N: `tokens [N]` shown at step `t` of
        the episode (the same for every row: an episode is one unroll,
        `ximpala`'s rule, so every cached position <= t is of this
        episode). All `loop_passes` passes x L layers, each writing its
        key and value at position t of ITS cache and attending over
        positions <= t, reading a static prefix that covers them: the
        first `span` positions of the row (a Python int, the whole row by
        default). `t` is traced, so that `t < span` is the CALLER's to
        hold; past `span` the step would read nothing it wrote.
        -> (h^(R) `[N, D]`, cache)."""
        length = cache.k.shape[3]
        span = length if span is None else span
        if not 0 < span <= length:
            raise ValueError(f"span {span} of a cache of {length} positions")
        stack = self._stack()
        index = jnp.arange(self.num_layers)

        # The whole row is the call as it was before there were spans: a
        # subclass that overrides `_decode_layer(carry, xs, slot, t)` (the
        # benchmark plants a shared cache that way) keeps working.
        prefix = () if span == length else (span,)

        def one_pass(carry, loop_pass):
            step = lambda c, xs: self._decode_layer(c, xs, loop_pass, t, *prefix)
            return jax.lax.scan(step, carry, (stack, index))[0], None

        with jax.named_scope(scopes.ACT_LOOP):
            h0 = self.embed[tokens].astype(self.dtype)
            (h, cache), _ = jax.lax.scan(
                one_pass, (h0, cache), jnp.arange(self.loop_passes))
        return h, cache


def for_acting(params, dtype):
    """The parameters as the decode steps of one update read them: the
    layer stack's matrices and the vocabulary head cast to the compute
    dtype ONCE, outside the scan over env steps (a decode step is bound
    by the bytes of the weights it reads, R times a step)."""
    p = dict(params["params"])
    for key in (*STACK_MATRICES, "w_out"):
        p[key] = p[key].astype(dtype)
    return {"params": p}

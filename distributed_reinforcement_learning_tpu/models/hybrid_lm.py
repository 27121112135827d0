"""Hybrid state-space / attention language model (IBM granite-4.0-h:
`granitemoehybrid`, 2025-10): a stack whose layers are of TWO kinds in a
published order, nine Mamba-2 state-space mixers to one grouped-query
attention mixer, every layer followed by the same SwiGLU MLP, four
scalar multipliers, a tied vocabulary head, and (RL's addition) a value
head. D wide, tokens x_1..x_T:

    h_0 = m_e E[x];  per layer:  u = h + m_r Mix(N1(h))
                                 h' = u + m_r W_d(silu(a) * b), [a, b] = W_gu N2(u)
    z = RMSNorm(h_L; g_f);  logits = z E^T / s_l;  v = z . w_v + b_v
    attention:  32 query / 8 key-value heads of 64, NO position term,
                softmax(q k^T m_a), causal AND same-episode
    Mamba-2:    [z, xBC, dt] = W_in y;  xBC = silu(conv4(xBC) + b_c);
                [x, B, C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
                S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
                out = W_out RMSNorm(y * silu(z); g_n)
    `mamba_groups` G (granite: 1; `models/ssm_moe_lm.py`, which borrows
    this mixer, 8): B and C are `[G, N]`, head h reads those of group
    h // (H / G), and the gated norm's mean square is over each group's
    d_inner / G channels (the gate BEFORE the norm, as here); at G = 1
    every trace is what it was without it.

Parameters are one `[n, ...]`-stacked dict PER RUN of equal layers
(`run0`: 5 state-space layers, `run1`: the attention layer, `run2`: 4
more, for the published order's first period), each run one `lax.scan`:
`looped_lm.LoopedLM`'s single stacked pytree cannot hold two kinds, and
slices of one 9-deep stack would be copies of it. Not a flax module:
`init` and `apply(params, *args, method=...)` are all that
`agents/looplm.LoopLMAgent` asks of its model.

Two entries:

- `trunk` + `token_stats`: the learner's `[B, T]` forward. The
  state-space recurrence runs in its chunked form (`ops/ssd.py`), the
  attention through `ops.attention.causal_attention` (the flash kernels
  on a TPU). A layer is applied to `row_block` rows at a time and each
  such application is rematerialised: the backward keeps the layers'
  inputs and one block's activations, and a layer's weight gradients
  add up over the blocks in float32 inside the scan.
- `decode`: one token a row through THREE kinds of state side by side
  (`HybridState`): per state-space layer the recurrent state `[N, H, P,
  S]` float32, READ AND WRITTEN WHOLE every step (O(1) in t), and its
  convolution window `[N, 3, C]`; for the attention layer a key/value
  cache `[N, T, 8, 64]` x 2 written at t and read as far as `span`. The
  layers of a decode step are a Python loop over `per_layer` parameters,
  not a scan: each layer's state is then its own buffer.

Precision (`dtype`, bfloat16 as the configuration states it): matmul
operands and the residual stream in `dtype` with float32 accumulation;
norm statistics, the convolution, softplus, decays, cumulative sums,
recurrent states, softmax and everything after the logits in float32;
parameters float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.models.looped_lm import (
    episode_positions, rms_norm)
from distributed_reinforcement_learning_tpu.models.transformer_net import (
    episode_segments)
from distributed_reinforcement_learning_tpu.observability import scopes
from distributed_reinforcement_learning_tpu.ops import ssd
from distributed_reinforcement_learning_tpu.ops.attention import (
    _MASK_VALUE, causal_attention)

F32 = jnp.float32
LAYER_KINDS = ("mamba", "attention")
# What acting casts to the compute dtype once an update (`for_acting`).
RUN_MATRICES = ("in_proj", "out_proj", "wq", "wkv", "wo", "wgu", "wd")


class HybridState(NamedTuple):
    """The act-time state, one entry PER LAYER in the published order
    (None where the layer's kind has no such state). Every layer's state
    is a leaf of its own: a decode step then updates it elementwise, in
    place in the scan's carry. As slices of one `[n, ...]` array per run,
    read and written at a traced layer index inside a scan over layers,
    the compiler copied the whole array twice a layer a step (seen in
    the chunk compiled for a described v5e, PR 32)."""

    ssm: tuple  # [N, H, P, S] float32 a state-space layer
    conv: tuple  # [N, K - 1, C] float32 a state-space layer
    k: tuple  # [N, T, KV, d] an attention layer
    v: tuple


def layer_runs(layer_types, kinds=LAYER_KINDS) -> tuple:
    """`("mamba",) * 5 + ("attention",) + ...` -> `(("mamba", 5),
    ("attention", 1), ...)`; a kind this file does not compute is refused."""
    runs: list = []
    for kind in layer_types:
        if kind not in kinds:
            raise ValueError(f"unknown layer type {kind!r}: {kinds}")
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return tuple((k, n) for k, n in runs)


@dataclasses.dataclass(frozen=True)
class HybridLM:
    vocab: int
    d_model: int
    layer_types: tuple
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    mamba_heads: int
    mamba_head_dim: int
    mamba_state: int
    mamba_conv: int = 4
    mamba_chunk: int = 256
    mamba_groups: int = 1  # G: head h reads B and C of group h // (heads / G)
    rms_eps: float = 1e-5
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    dtype: Any = jnp.bfloat16
    init_std: float = 0.02
    attention_backend: str = "auto"  # `ops.attention.causal_attention`'s
    row_block: int = 4  # rows a layer is applied to at a time (no section key)
    state_dtype: Any = F32  # the recurrent state at act time and across chunks

    @property
    def runs(self) -> tuple:
        return layer_runs(self.layer_types)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.mamba_groups * self.mamba_state  # x, B, C

    # -- parameters ---------------------------------------------------------
    def init(self, rng: jax.Array, *_) -> dict:
        """Normal(`init_std`) matrices and embedding, ones for norm scales
        and D, zeros for biases, and Mamba-2's own defaults for the rest:
        the convolution uniform(+-1 / sqrt(K)), A = uniform(1, 16), dt_bias
        the inverse softplus of log-uniform(1e-3, 0.1)."""
        keys = iter(jax.random.split(rng, 16 * (len(self.runs) + 1)))
        normal = lambda *shape: self.init_std * jax.random.normal(
            next(keys), shape, F32)
        uniform = lambda lo, hi, *shape: jax.random.uniform(
            next(keys), shape, F32, lo, hi)
        d, f, h = self.d_model, self.d_ff, self.mamba_heads
        a = self.num_heads * self.head_dim
        p = {"embed": normal(self.vocab, d), "final_norm": jnp.ones((d,), F32),
             "w_value": normal(d), "b_value": jnp.zeros((), F32)}
        for i, (kind, n) in enumerate(self.runs):
            run = {"norms": jnp.ones((n, 2, d), F32), "wgu": normal(n, d, 2 * f),
                   "wd": normal(n, f, d)}
            if kind == "mamba":
                dt = jnp.exp(uniform(math.log(1e-3), math.log(0.1), n, h))
                bound = self.mamba_conv ** -0.5
                run.update(
                    in_proj=normal(n, d, self.d_inner + self.conv_channels + h),
                    conv_w=uniform(-bound, bound, n, self.conv_channels,
                                   self.mamba_conv),
                    conv_b=jnp.zeros((n, self.conv_channels), F32),
                    dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                    A_log=jnp.log(uniform(1.0, 16.0, n, h)),
                    D=jnp.ones((n, h), F32),
                    gate_norm=jnp.ones((n, self.d_inner), F32),
                    out_proj=normal(n, self.d_inner, d))
            else:
                run.update(wq=normal(n, d, a), wo=normal(n, a, d),
                           wkv=normal(n, d, 2 * self.num_kv_heads * self.head_dim))
            p[f"run{i}"] = run
        return {"params": p}

    def apply(self, params, *args, method):
        return method(params["params"], *args)

    # -- shared pieces ----------------------------------------------------
    def _mm(self, x: jax.Array, w: jax.Array) -> jax.Array:
        """`x @ w`, operands in `dtype`, float32 accumulation."""
        return jnp.dot(x.astype(self.dtype), w.astype(self.dtype),
                       preferred_element_type=F32)

    def _residual(self, h: jax.Array, branch: jax.Array) -> jax.Array:
        return (h.astype(F32) + self.residual_multiplier * branch
                ).astype(self.dtype)

    def _mlp(self, u: jax.Array, lp: dict) -> jax.Array:
        y = rms_norm(u, lp["norms"][1], self.rms_eps)
        gate, up = jnp.split(self._mm(y, lp["wgu"]), 2, axis=-1)
        return self._residual(u, self._mm(jax.nn.silu(gate) * up, lp["wd"]))

    def _split_in(self, zxbcdt: jax.Array):
        return jnp.split(zxbcdt, [self.d_inner,
                                  self.d_inner + self.conv_channels], axis=-1)

    def _split_conv(self, xbc: jax.Array):
        """-> x `[..., H, P]`, B, C `[..., N]` (one group) or `[..., G, N]`."""
        x, b, c = jnp.split(xbc, [self.d_inner, (
            self.d_inner + self.conv_channels) // 2], axis=-1)
        by_group = lambda m: m if self.mamba_groups == 1 else m.reshape(
            *m.shape[:-1], self.mamba_groups, self.mamba_state)
        return (x.reshape(*x.shape[:-1], self.mamba_heads, self.mamba_head_dim),
                by_group(b), by_group(c))

    def _step_size(self, dt: jax.Array, lp: dict) -> jax.Array:
        return jax.nn.softplus(dt + lp["dt_bias"])

    def _rate(self, lp: dict) -> jax.Array:
        """A, a negative scalar a head: exp(dt A) is a decay."""
        return -jnp.exp(lp["A_log"].astype(F32))

    def _gated_out(self, y: jax.Array, x: jax.Array, z: jax.Array, lp: dict):
        """The skip, the gate and THEN the norm, its mean square over each
        GROUP's `d_inner / G` channels (one group: the whole inner width),
        and the output projection."""
        g = (y + lp["D"][:, None] * x).reshape(*z.shape) * jax.nn.silu(z)
        if self.mamba_groups > 1:
            groups = lambda v: v.reshape(*v.shape[:-1], self.mamba_groups, -1)
            return self._mm(rms_norm(groups(g), groups(lp["gate_norm"]),
                                     self.rms_eps).reshape(g.shape), lp["out_proj"])
        return self._mm(rms_norm(g, lp["gate_norm"], self.rms_eps),
                        lp["out_proj"])

    def _grouped(self, q: jax.Array) -> jax.Array:
        """`[..., heads, d]` -> `[..., KV, heads / KV, d]`: query head i
        reads key/value head i // (heads / KV)."""
        return q.reshape(*q.shape[:-2], self.num_kv_heads,
                         self.num_heads // self.num_kv_heads, self.head_dim)

    # -- the learner's forward --------------------------------------------
    def _mamba(self, y, lp, seg, pos):
        z, xbc, dt = self._split_in(self._mm(y, lp["in_proj"]))
        x, bmat, cmat = self._split_conv(jax.nn.silu(
            causal_conv(xbc, lp["conv_w"], lp["conv_b"], pos)))
        dt, rate = self._step_size(dt, lp), self._rate(lp)
        with jax.named_scope(scopes.SSD):
            ssm, _ = ssd.ssd_chunked(x, dt, rate, bmat, cmat, seg,
                                     self.mamba_chunk, self.dtype,
                                     self.state_dtype)
        stats = jax.lax.stop_gradient(
            {"dt_sum": jnp.sum(dt), "decay_min": jnp.min(jnp.exp(dt * rate))})
        return self._gated_out(ssm, x, z, lp), stats

    def _attention(self, y, lp, seg):
        b, t, _ = y.shape
        with jax.named_scope(scopes.ATTENTION):
            # `causal_attention` and its kernels take as many key/value
            # heads as query heads and scale by d ** -0.5 (ISSUE 32, 3): the
            # key/value heads are repeated, and q carries the rest of the
            # published scale (m_a sqrt(d): 1/64 x 8 = 1/8, exact).
            q = self._mm(y, lp["wq"]).reshape(b, t, self.num_heads, self.head_dim)
            q = q * (self.attention_multiplier * self.head_dim ** 0.5)
            k, v = jnp.split(self._mm(y, lp["wkv"]).reshape(
                b, t, 2 * self.num_kv_heads, self.head_dim), 2, axis=2)
            groups = self.num_heads // self.num_kv_heads
            att = causal_attention(
                q.astype(self.dtype), jnp.repeat(k, groups, 2).astype(self.dtype),
                jnp.repeat(v, groups, 2).astype(self.dtype), q_seg=seg, k_seg=seg,
                backend=self.attention_backend)
        return self._mm(att.reshape(b, t, -1), lp["wo"])

    def _layer(self, kind, h, seg, pos, lp):
        """One layer on a block of rows -> (h', the layer's counters)."""
        y = rms_norm(h, lp["norms"][0], self.rms_eps)
        if kind == "mamba":
            mix, stats = self._mamba(y, lp, seg, pos)
        else:
            mix, stats = self._attention(y, lp, seg), {}
        return self._mlp(self._residual(h, mix), lp), stats

    def trunk(self, p: dict, tokens: jax.Array, done: jax.Array):
        """`tokens, done [B, T]` -> (h_L `[1, B, T, D]` before the final
        norm: one pass, the leading axis `LoopLMAgent` reads as R; the
        counters `dt_mean`, `decay_min` of the state-space layers)."""
        b, t = tokens.shape
        rows = math.gcd(b, self.row_block)
        blocks = lambda x: x.reshape(b // rows, rows, *x.shape[1:])
        seg, pos = blocks(episode_segments(done)), blocks(episode_positions(done))
        stats = []
        with jax.named_scope(scopes.LAYERS):
            h = (self.embedding_multiplier * p["embed"][tokens]).astype(self.dtype)
            for i, (kind, _) in enumerate(self.runs):
                block = jax.checkpoint(functools.partial(self._layer, kind))

                def layer(h, lp):
                    out, stat = jax.lax.map(
                        lambda xs: block(*xs, lp), (blocks(h), seg, pos))
                    return out.reshape(h.shape), stat

                h, stat = jax.lax.scan(layer, h, p[f"run{i}"])
                if kind == "mamba":
                    stats.append(stat)
        steps = sum(s["dt_sum"].shape[0] for s in stats) * b * t * self.mamba_heads
        counters = {
            "dt_mean": sum(jnp.sum(s["dt_sum"]) for s in stats) / max(1, steps),
            "decay_min": jnp.min(jnp.stack([jnp.min(s["decay_min"]) for s in stats]))
            if stats else jnp.ones(())}
        return h[None], counters

    def token_stats(self, p: dict, h: jax.Array, actions: jax.Array) -> dict:
        """The heads on a block of positions: `h [..., D]`, `actions
        [...]` -> float32 `logp` of the taken action, `entropy` of the
        policy, `gate` (1: there is one pass and it is never left early),
        `value`; the `[..., V]` logits live only inside."""
        logits, gate, value = self.logits(p, h)
        lse = jax.nn.logsumexp(logits, axis=-1)
        taken = jnp.take_along_axis(logits, actions[..., None], axis=-1)[..., 0]
        prob = jnp.exp(logits - lse[..., None])
        return {"logp": taken - lse,
                "entropy": lse - jnp.sum(prob * logits, axis=-1),
                "gate": gate, "value": value}

    def logits(self, p: dict, h: jax.Array):
        """(logits, gate, value), float32: the acting head and what the
        comparison with the plain reference reads. The vocabulary head is
        the embedding, transposed (`embed_head`: acting's copy of it in
        the compute dtype)."""
        z = rms_norm(h, p["final_norm"], self.rms_eps)
        head = p.get("embed_head", p["embed"])
        logits = jnp.einsum("...d,vd->...v", z.astype(self.dtype),
                            head.astype(self.dtype), preferred_element_type=F32)
        value = z @ p["w_value"].astype(F32) + p["b_value"]
        return logits / self.logits_scaling, jnp.ones_like(value), value

    # -- acting as decode --------------------------------------------------
    def init_state(self, num_rows: int, length: int) -> HybridState:
        """Zeros: every episode starts from no past."""
        ssm, conv, k, v = [], [], [], []
        for kind in self.layer_types:
            mamba = kind == "mamba"
            ssm.append(jnp.zeros((num_rows, self.mamba_heads, self.mamba_head_dim,
                                  self.mamba_state), self.state_dtype)
                       if mamba else None)
            conv.append(jnp.zeros((num_rows, self.mamba_conv - 1,
                                   self.conv_channels), F32) if mamba else None)
            cache = (None if mamba else jnp.zeros(
                (num_rows, length, self.num_kv_heads, self.head_dim), self.dtype))
            k.append(cache)
            v.append(cache)
        return HybridState(tuple(ssm), tuple(conv), tuple(k), tuple(v))

    def _per_head(self, m: jax.Array) -> jax.Array:
        """A decode step's B or C, `[N, S]` or `[N, G, S]`, as every head
        reads it: `[N, 1 or H, 1, S]`."""
        if self.mamba_groups == 1:
            return m[:, None, None, :]
        return jnp.repeat(m, self.mamba_heads // self.mamba_groups, 1)[:, :, None]

    def _decode_ssm(self, y, lp, state, window):
        """The state-space mixer of a decode step on the normed rows `y`:
        the window shifted by one, the state updated and read out, the
        gated norm and the output projection -> (mix, state, the K taps
        `[N, K, C]`: the last K - 1 are the next step's window)."""
        z, xbc, dt = self._split_in(self._mm(y, lp["in_proj"]))
        with jax.named_scope(scopes.ACT_SSM):
            taps = jnp.concatenate([window, xbc[:, None]], axis=1)  # [N, K, C]
            x, bmat, cmat = self._split_conv(jax.nn.silu(
                lp["conv_b"] + jnp.einsum("nkc,ck->nc", taps, lp["conv_w"])))
            dt = self._step_size(dt, lp)
            decay = jnp.exp(dt * self._rate(lp))  # [N, H]
            state = (decay[..., None, None] * state.astype(F32)
                     + (dt[..., None] * x)[..., None] * self._per_head(bmat)
                     ).astype(self.state_dtype)
            read = jnp.sum(state.astype(F32) * self._per_head(cmat), axis=-1)
        return self._gated_out(read, x, z, lp), state, taps

    def _decode_mamba(self, h, lp, state, window):
        """One state-space layer of a decode step -> (h', state, window)."""
        mix, state, taps = self._decode_ssm(
            rms_norm(h, lp["norms"][0], self.rms_eps), lp, state, window)
        return self._mlp(self._residual(h, mix), lp), state, taps[:, 1:]

    def _decode_attention(self, h, lp, keys, values, t, span):
        """The attention layer of a decode step: one key and one value
        written at t, the first `span` positions of the row read ->
        (h', keys, values)."""
        n, length = h.shape[0], keys.shape[1]
        span = length if span is None else span
        if not 0 < span <= length:
            raise ValueError(f"span {span} of a cache of {length} positions")
        y = rms_norm(h, lp["norms"][0], self.rms_eps)
        q = self._grouped(self._mm(y, lp["wq"]).reshape(
            n, self.num_heads, self.head_dim)).astype(self.dtype)
        k, v = jnp.split(self._mm(y, lp["wkv"]).reshape(
            n, 1, 2 * self.num_kv_heads, self.head_dim).astype(self.dtype), 2, 2)
        with jax.named_scope(scopes.ACT_CACHE):
            keys = jax.lax.dynamic_update_slice(keys, k, (0, t, 0, 0))
            values = jax.lax.dynamic_update_slice(values, v, (0, t, 0, 0))
            k_read, v_read = keys[:, :span], values[:, :span]
        s = jnp.einsum("nkgd,nskd->nkgs", q, k_read,
                       preferred_element_type=F32) * self.attention_multiplier
        seen = jnp.arange(span) <= t
        prob = jnp.where(seen, jax.nn.softmax(jnp.where(seen, s, _MASK_VALUE), -1), 0.0)
        att = jnp.einsum("nkgs,nskd->nkgd", prob.astype(self.dtype), v_read,
                         preferred_element_type=F32)
        mix = self._mm(att.reshape(n, -1), lp["wo"])
        return self._mlp(self._residual(h, mix), lp), keys, values

    def decode(self, p: dict, tokens: jax.Array, t: jax.Array,
               state: HybridState, span: int | None = None):
        """One decode step at batch N: `tokens [N]` shown at step `t` of
        the episode (the same for every row: an episode is one unroll).
        Every state-space layer shifts its window and updates its state,
        whatever t; the attention layer writes position t of its cache and
        reads the static prefix `span` (a Python int, the whole row by
        default; `t < span` is the CALLER's to hold, as in
        `looped_lm.LoopedLM.decode`). `p`: `for_acting`'s parameters, or
        the learner's. -> (h_L `[N, D]`, state)."""
        ssm, conv, keys, values = (list(x) for x in state)
        layers = p["layers"] if "layers" in p else per_layer(p)
        with jax.named_scope(scopes.ACT_LAYERS):
            h = (self.embedding_multiplier * p["embed"][tokens]).astype(self.dtype)
            for i, (kind, lp) in enumerate(zip(self.layer_types, layers)):
                if kind == "mamba":
                    h, ssm[i], conv[i] = self._decode_mamba(h, lp, ssm[i], conv[i])
                else:
                    h, keys[i], values[i] = self._decode_attention(
                        h, lp, keys[i], values[i], t, span)
        return h, HybridState(tuple(ssm), tuple(conv), tuple(keys), tuple(values))


def causal_conv(xbc: jax.Array, w: jax.Array, bias: jax.Array,
                pos: jax.Array) -> jax.Array:
    """Depthwise causal convolution over time, `xbc [B, T, C]`, `w [C, K]`:
    out_t = bias + sum_j w[:, j] xbc_{t-(K-1)+j}; a tap that lies before
    the episode's first step (`pos [B, T]`, the step inside its episode)
    reads zero."""
    width, t = w.shape[1], xbc.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.broadcast_to(bias, xbc.shape)
    for j in range(width):
        tap = jnp.where((pos >= width - 1 - j)[..., None], padded[:, j:j + t], 0.0)
        out = out + w[:, j] * tap
    return out


def per_layer(p: dict, dtype=None, run_matrices=RUN_MATRICES) -> list:
    """The runs' `[n, ...]`-stacked parameters as one dict per layer, in
    the published order; with `dtype`, the matrices cast to it."""
    layers = []
    for name in sorted((k for k in p if k.startswith("run")),
                       key=lambda k: int(k[3:])):
        for i in range(p[name]["norms"].shape[0]):
            layers.append({k: v[i].astype(dtype)
                           if dtype is not None and k in run_matrices else v[i]
                           for k, v in p[name].items()})
    return layers


def for_acting(params, dtype):
    """The parameters as the decode steps of one update read them: every
    layer's matrices cast to the compute dtype ONCE, outside the scan over
    env steps, each layer a dict of its own (`per_layer`), and a copy of
    the embedding in that dtype for the vocabulary head (the lookup reads
    the float32 one, as the learner's does: 32 rows a step)."""
    p = {k: v for k, v in params["params"].items() if not k.startswith("run")}
    p["layers"] = per_layer(params["params"], dtype)
    p["embed_head"] = p["embed"].astype(dtype)
    return {"params": p}

"""Sparse-expert hybrid language model (Qwen3-Next: `qwen3_next`,
2025-09): a stack whose mixers are of TWO kinds in a published order,
three gated-delta-rule (linear attention) layers to one gated softmax
attention layer, and in EVERY layer a sparse expert MLP: a router over
all `num_experts` experts of the layer, of which this chip holds
`experts_held` from `first_expert` on, beside a gated shared expert; a
zero-centred RMSNorm, an untied vocabulary head and (RL's addition) a
value head. D wide, tokens x_1..x_T:

    N(x; g) = x rsqrt(mean(x^2) + eps) (1 + g)
    h_0 = E[x];  per layer:  u = h + Mix(N1(h)),  h' = u + MoE(N2(u))
    logits = N(h_L; g_f) W_head^T;  v = N(h_L; g_f) . w_v + b_v
    gated delta rule (`linear_attention`), y = N1(h):
        [q, k, v, z] = W_qkvz y (columns: q 16 x 128 | k 16 x 128 | v 32 x 128 | z 32 x 128,
        heads contiguous);  [b, a] = W_ba y (32 | 32)
        [q, k, v] <- silu(conv4([q, k, v])), depthwise, causal, no bias
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
        q <- q / |q| / sqrt(128),  k <- k / |k|  (key head j serves value heads 2j, 2j + 1)
        S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - (exp(g_t) S_{t-1})^T k_t)^T;  o_t = S_t^T q_t
        Mix = W_o [ o rsqrt(mean_128(o^2) + eps) g_n * silu(z) ]   (g_n a PLAIN scale)
    gated attention (`full_attention`):
        [q | gate] = W_q y per head (256 | 256);  [k | v] = W_kv y (2 x 256 | 2 x 256)
        q <- N(q; g_q), k <- N(k; g_k) per head;  rotary (rotate-half) on the first
        `rotary_dim` of each head, position = step in the episode
        Mix = W_o [ softmax(q k^T / sqrt(256), causal AND same-episode) v * sigmoid(gate) ]
    experts, x = N2(u) (`ops/expert_share.py`):
        MoE(x) = sum_{i in top-k, held here} w_i E_i(x) + sigmoid(w_s . x) E_shared(x)

Parameters, the two entries (`trunk` + `token_stats` for the learner's
`[B, T]` forward, `decode` for acting), the row-block rematerialisation
and the one-leaf-a-layer act-time state are `models/hybrid_lm.py`'s
design (one `[n, ...]`-stacked dict and one `lax.scan` PER RUN of equal
layers; its `layer_runs`, `causal_conv` and `per_layer` are imported);
both mixers and the MLP are this file's: `HybridLM` computes neither.
The learner runs the delta rule in its chunked form
(`ops/gated_delta.py`), attention through
`ops.attention.causal_attention` (the flash kernels on a TPU; the two
key/value heads repeated eight times, as that file wants).

`decode`: one token a row through THREE kinds of state side by side
(`MoEState`): per linear-attention layer its matrix-valued state `[N,
32, 128, 128]` float32, read and written whole every step, and its
convolution window `[N, 3, 8192]`; for the attention layer a key/value
cache `[N, T, 2, 256]` x 2 written at t and read as far as `span`.
Beside them `routes [N, T, layers, top_k]` int16: the experts each step
CHOSE, a record and no state (nothing reads it back): routing is
discontinuous, and a reader that replays the episode (the benchmark's
plain reference) is given the choices the program made.

Precision (`dtype`, bfloat16 as the configuration states it): matmul
operands and the residual stream in `dtype` with float32 accumulation;
router logits (a `highest` product), softmax, top-k weights, norm
statistics, the convolution, g, beta, the l2 norms, the solve, the
recurrent state and window, softmax and everything after the logits in
float32; parameters float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.models.hybrid_lm import (
    HybridLM, causal_conv, layer_runs, per_layer)
from distributed_reinforcement_learning_tpu.models.looped_lm import (
    episode_positions)
from distributed_reinforcement_learning_tpu.models.transformer_net import (
    episode_segments, rope)
from distributed_reinforcement_learning_tpu.observability import scopes
from distributed_reinforcement_learning_tpu.ops import expert_share, gated_delta
from distributed_reinforcement_learning_tpu.ops.attention import (
    _MASK_VALUE, causal_attention)

F32 = jnp.float32
LAYER_KINDS = ("linear_attention", "full_attention")
# What acting casts to the compute dtype once an update (`for_acting`);
# the router stays float32.
RUN_MATRICES = ("in_proj", "in_ba", "out_proj", "wq", "wkv", "wo",
                "expert_wgu", "expert_wd", "shared_wgu", "shared_wd")


class MoEState(NamedTuple):
    """The act-time state, one entry PER LAYER in the published order
    (None where the layer's kind has no such state), every layer's a
    leaf of its own (`hybrid_lm.HybridState`'s rule), and the record of
    the experts chosen."""

    gdn: tuple  # [N, H, K, V] float32 a linear-attention layer
    conv: tuple  # [N, 3, C] float32 a linear-attention layer
    k: tuple  # [N, T, KV, d] an attention layer
    v: tuple
    routes: jax.Array  # [N, T, layers, top_k] int16: a record


def zero_centred_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """`x rsqrt(mean(x^2) + eps) (1 + scale)`, statistics and result float32."""
    x = x.astype(F32)
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                              + eps) * (1.0 + scale.astype(F32)))


@dataclasses.dataclass(frozen=True)
class MoELM:
    vocab: int
    d_model: int
    layer_types: tuple
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    gdn_key_heads: int
    gdn_value_heads: int
    gdn_key_dim: int
    gdn_value_dim: int
    num_experts: int  # the router's width: every expert of a layer
    experts_held: int  # those this chip holds, from `first_expert` on
    first_expert: int
    top_k: int
    expert_width: int
    shared_width: int
    gdn_conv: int = 4
    gdn_chunk: int = 64
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    init_std: float = 0.02
    attention_backend: str = "auto"  # `ops.attention.causal_attention`'s
    row_block: int = 4  # rows a layer is applied to at a time (no section key)
    state_dtype: Any = F32  # the recurrent state at act time and across chunks

    @property
    def runs(self) -> tuple:
        return layer_runs(self.layer_types, LAYER_KINDS)

    @property
    def key_width(self) -> int:
        return self.gdn_key_heads * self.gdn_key_dim

    @property
    def value_width(self) -> int:
        return self.gdn_value_heads * self.gdn_value_dim

    @property
    def conv_channels(self) -> int:
        return 2 * self.key_width + self.value_width  # q, k, v

    # -- parameters ---------------------------------------------------------
    def init(self, rng: jax.Array, *_) -> dict:
        """Normal(`init_std`) matrices, embedding and head; zeros for the
        zero-centred norm scales and the bias, ones for the delta rule's
        plain output scale and `dt_bias` (the source's); the convolution
        uniform(+-1 / sqrt(K)) and A = uniform(1/64, 1/4) (NOT the
        source's uniform(0, 16), under which exp(g) is e^-10 for most
        heads and no comparison could see a wrong recurrence: `assumed`
        in the configuration's file)."""
        keys = iter(jax.random.split(rng, 24 * (len(self.runs) + 1)))
        normal = lambda *shape: self.init_std * jax.random.normal(
            next(keys), shape, F32)
        uniform = lambda lo, hi, *shape: jax.random.uniform(
            next(keys), shape, F32, lo, hi)
        d, e, f, s = self.d_model, self.experts_held, self.expert_width, self.shared_width
        a, kv = self.num_heads * self.head_dim, self.num_kv_heads * self.head_dim
        hv = self.gdn_value_heads
        p = {"embed": normal(self.vocab, d), "head": normal(self.vocab, d),
             "final_norm": jnp.zeros((d,), F32), "w_value": normal(d),
             "b_value": jnp.zeros((), F32)}
        for i, (kind, n) in enumerate(self.runs):
            run = {"norms": jnp.zeros((n, 2, d), F32),
                   "router": normal(n, d, self.num_experts),
                   "expert_wgu": normal(n, e, d, 2 * f),
                   "expert_wd": normal(n, e, f, d),
                   "shared_wgu": normal(n, d, 2 * s), "shared_wd": normal(n, s, d),
                   "shared_gate": normal(n, d)}
            if kind == "linear_attention":
                bound = self.gdn_conv ** -0.5
                run.update(
                    in_proj=normal(n, d, self.conv_channels + self.value_width),
                    in_ba=normal(n, d, 2 * hv),
                    conv_w=uniform(-bound, bound, n, self.conv_channels,
                                   self.gdn_conv),
                    dt_bias=jnp.ones((n, hv), F32),
                    A_log=jnp.log(uniform(1 / 64, 1 / 4, n, hv)),
                    gate_norm=jnp.ones((n, self.gdn_value_dim), F32),
                    out_proj=normal(n, self.value_width, d))
            else:
                run.update(wq=normal(n, d, 2 * a), wkv=normal(n, d, 2 * kv),
                           q_norm=jnp.zeros((n, self.head_dim), F32),
                           k_norm=jnp.zeros((n, self.head_dim), F32),
                           wo=normal(n, a, d))
            p[f"run{i}"] = run
        return {"params": p}

    def apply(self, params, *args, method):
        return method(params["params"], *args)

    # -- shared pieces ----------------------------------------------------
    def _mm(self, x: jax.Array, w: jax.Array) -> jax.Array:
        """`x @ w`, operands in `dtype`, float32 accumulation."""
        return jnp.dot(x.astype(self.dtype), w.astype(self.dtype),
                       preferred_element_type=F32)

    def _norm(self, x: jax.Array, scale: jax.Array) -> jax.Array:
        return zero_centred_norm(x, scale, self.rms_eps)

    def _residual(self, h: jax.Array, branch: jax.Array) -> jax.Array:
        return (h.astype(F32) + branch).astype(self.dtype)

    def _moe(self, u: jax.Array, lp: dict, scope: dict):
        """The expert MLP on rows `u [N, D]` -> (u + MoE(N2(u)), the
        experts chosen `[N, top_k]` and their probabilities, counters).
        `scope`: the names of the three parts, act time's or the learner's."""
        x = self._norm(u, lp["norms"][1])
        with jax.named_scope(scope["route"]):
            probs, chosen, weight = expert_share.route(x, lp["router"], self.top_k)
        with jax.named_scope(scope["experts"]):
            routed, counters = expert_share.held_experts(
                x, chosen, weight, lp["expert_wgu"], lp["expert_wd"],
                self.first_expert, self.num_experts, self.dtype)
        with jax.named_scope(scope["shared"]):
            gate, up = jnp.split(self._mm(x, lp["shared_wgu"]), 2, axis=-1)
            share = jax.nn.sigmoid(x @ lp["shared_gate"].astype(F32))
            shared = share[..., None] * self._mm(jax.nn.silu(gate) * up,
                                                 lp["shared_wd"])
        stats = jax.lax.stop_gradient({
            **{k: counters[k] for k in ("held_pairs", "expert_pairs", "pair_slabs",
                                        "dropped_pairs")},
            "shared_gate_sum": jnp.sum(share),
            "router_entropy_sum": -jnp.sum(jnp.where(
                probs > 0, probs * jnp.log(jnp.where(probs > 0, probs, 1.0)), 0.0))})
        picked = jax.lax.stop_gradient(jnp.take_along_axis(probs, chosen, axis=-1))
        return self._residual(u, routed + shared), (chosen, picked), stats

    def _split_in(self, qkvz: jax.Array):
        """`W_qkvz y` -> (`qkv [..., C]` for the convolution, `z [..., Hv, V]`)."""
        qkv, z = jnp.split(qkvz, [self.conv_channels], axis=-1)
        return qkv, z.reshape(*z.shape[:-1], self.gdn_value_heads, self.gdn_value_dim)

    def _split_conv(self, qkv: jax.Array):
        """The convolved channels as the rule reads them: q and k
        l2-normalised per head (q scaled by K ** -0.5 besides) and
        repeated to the value heads, v per value head; float32."""
        q, k, v = jnp.split(qkv, [self.key_width, 2 * self.key_width], axis=-1)
        heads = lambda x, n, d: x.reshape(*x.shape[:-1], n, d)
        groups = self.gdn_value_heads // self.gdn_key_heads
        q = gated_delta.l2_normalize(
            heads(q, self.gdn_key_heads, self.gdn_key_dim)) * self.gdn_key_dim ** -0.5
        k = gated_delta.l2_normalize(heads(k, self.gdn_key_heads, self.gdn_key_dim))
        return (jnp.repeat(q, groups, axis=-2), jnp.repeat(k, groups, axis=-2),
                heads(v, self.gdn_value_heads, self.gdn_value_dim).astype(F32))

    def _gates(self, ba: jax.Array, lp: dict):
        """`W_ba y` -> (g <= 0, beta in (0, 1)), float32, a value head."""
        b, a = jnp.split(ba.astype(F32), 2, axis=-1)
        return (-jnp.exp(lp["A_log"].astype(F32))
                * jax.nn.softplus(a + lp["dt_bias"]), jax.nn.sigmoid(b))

    def _gated_out(self, o: jax.Array, z: jax.Array, lp: dict) -> jax.Array:
        """The per-head norm with its plain scale, the gate, the output
        projection."""
        normed = (o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                                    + self.rms_eps) * lp["gate_norm"])
        gated = normed * jax.nn.silu(z)
        return self._mm(gated.reshape(*gated.shape[:-2], -1), lp["out_proj"])

    def _rotary(self, x: jax.Array, pos: jax.Array) -> jax.Array:
        """Rotate-half on the first `rotary_dim` of each head."""
        r = self.rotary_dim
        return jnp.concatenate(
            [rope(x[..., :r], pos, self.rope_theta), x[..., r:]], axis=-1)

    def _qkv(self, y: jax.Array, lp: dict, pos: jax.Array):
        """`y [B, T, D]` -> q `[B, T, heads, d]` (normed, rotated), its
        gate, k (normed, rotated) and v `[B, T, KV, d]`; float32."""
        b, t, _ = y.shape
        q, gate = jnp.split(self._mm(y, lp["wq"]).reshape(
            b, t, self.num_heads, 2 * self.head_dim), 2, axis=-1)
        k, v = jnp.split(self._mm(y, lp["wkv"]).reshape(
            b, t, 2 * self.num_kv_heads, self.head_dim), 2, axis=2)
        q = self._rotary(self._norm(q, lp["q_norm"]), pos)
        k = self._rotary(self._norm(k, lp["k_norm"]), pos)
        return q, gate, k, v

    # -- the learner's forward --------------------------------------------
    def _delta_rule(self, y, lp, seg, pos):
        qkv, z = self._split_in(self._mm(y, lp["in_proj"]))
        g, beta = self._gates(self._mm(y, lp["in_ba"]), lp)
        q, k, v = self._split_conv(jax.nn.silu(
            causal_conv(qkv, lp["conv_w"], 0.0, pos)))
        with jax.named_scope(scopes.GDN):
            o, _ = gated_delta.gated_delta_chunked(
                q, k, v, g, beta, seg, self.gdn_chunk, self.dtype,
                self.state_dtype)
        stats = jax.lax.stop_gradient(
            {"beta_sum": jnp.sum(beta), "decay_min": jnp.min(jnp.exp(g))})
        return self._gated_out(o, z, lp), stats

    def _attention(self, y, lp, seg, pos):
        b, t, _ = y.shape
        with jax.named_scope(scopes.ATTENTION):
            q, gate, k, v = self._qkv(y, lp, pos)
            # `causal_attention` takes as many key/value heads as query
            # heads and scales by d ** -0.5, which is the published scale.
            groups = self.num_heads // self.num_kv_heads
            att = causal_attention(
                q.astype(self.dtype), jnp.repeat(k, groups, 2).astype(self.dtype),
                jnp.repeat(v, groups, 2).astype(self.dtype), q_seg=seg, k_seg=seg,
                backend=self.attention_backend)
            att = att.astype(F32) * jax.nn.sigmoid(gate)
        return self._mm(att.reshape(b, t, -1), lp["wo"])

    def _layer(self, kind, h, seg, pos, lp):
        """One layer on a block of rows -> (h', the experts chosen `[rows
        x T, top_k]` int16 and their probabilities, the layer's counters)."""
        y = self._norm(h, lp["norms"][0])
        if kind == "linear_attention":
            mix, stats = self._delta_rule(y, lp, seg, pos)
        else:
            mix, stats = self._attention(y, lp, seg, pos), {}
        u = self._residual(h, mix)
        out, (chosen, picked), moe = self._moe(u.reshape(-1, u.shape[-1]), lp,
                                               scopes.MOE_LEARN)
        return out.reshape(u.shape), (chosen.astype(jnp.int16), picked), {**stats, **moe}

    def trunk(self, p: dict, tokens: jax.Array, done: jax.Array):
        """`tokens, done [B, T]` -> (h_L `[1, B, T, D]` before the final
        norm: one pass, the leading axis `LoopLMAgent` reads as R; the
        counters of the module's docstring, `routes [layers, B, T,
        top_k]`, the experts every position chose, and `route_probs`,
        their probabilities before the renormalisation)."""
        b, t = tokens.shape
        rows = math.gcd(b, self.row_block)
        blocks = lambda x: x.reshape(b // rows, rows, *x.shape[1:])
        seg, pos = blocks(episode_segments(done)), blocks(episode_positions(done))
        stats, routes, route_probs = [], [], []
        with jax.named_scope(scopes.LAYERS):
            h = p["embed"][tokens].astype(self.dtype)
            for i, (kind, _) in enumerate(self.runs):
                block = jax.checkpoint(functools.partial(self._layer, kind))

                def layer(h, lp):
                    out, chosen, stat = jax.lax.map(
                        lambda xs: block(*xs, lp), (blocks(h), seg, pos))
                    return out.reshape(h.shape), (chosen, stat)

                h, ((chosen, picked), stat) = jax.lax.scan(layer, h, p[f"run{i}"])
                routes.append(chosen.reshape(-1, b, t, self.top_k))
                route_probs.append(picked.reshape(-1, b, t, self.top_k))
                stats.append(stat)  # every leaf [layers of the run, blocks, ...]
        return h[None], {**self._counters(stats, b * t),
                         "routes": jnp.concatenate(routes),
                         "route_probs": jnp.concatenate(route_probs)}

    def _counters(self, stats: list, tokens: int) -> dict:
        """The counters of one forward from every layer's sums."""
        every = lambda key: jnp.concatenate(
            [s[key] for s in stats if key in s])  # [layers, blocks, ...]
        layers = len(self.layer_types)
        pairs = jnp.sum(every("expert_pairs"), axis=1).astype(F32)  # [layers, held]
        delta = [s for s in stats if "beta_sum" in s]
        steps = sum(s["beta_sum"].shape[0] for s in delta) * tokens * self.gdn_value_heads
        return {
            "held_pair_share": jnp.sum(pairs) / (layers * tokens * self.top_k),
            "expert_load_max_over_mean": jnp.max(
                jnp.max(pairs, -1) / jnp.maximum(jnp.mean(pairs, -1), 1e-9)),
            "experts_untouched": jnp.sum(pairs == 0).astype(F32),
            "dropped_pairs": jnp.sum(every("dropped_pairs")).astype(F32),
            "pair_slabs_mean": jnp.mean(every("pair_slabs").astype(F32)),
            "pair_slabs_max": jnp.max(every("pair_slabs")).astype(F32),
            "router_entropy": jnp.sum(every("router_entropy_sum")) / (layers * tokens),
            "shared_gate_mean": jnp.sum(every("shared_gate_sum")) / (layers * tokens),
            "beta_mean": sum(jnp.sum(s["beta_sum"]) for s in delta) / max(1, steps),
            "decay_min": jnp.min(jnp.stack([jnp.min(s["decay_min"]) for s in delta]))
            if delta else jnp.ones(())}

    def pair_slab_rows(self, b: int, t: int) -> int:
        """Rows of a slab of the learner's sorted pairs (`expert_share.
        slab_rows`) where a layer is applied to `[B, T]` a row block at a time."""
        return expert_share.slab_rows(math.gcd(b, self.row_block) * t * self.top_k,
                                      self.experts_held, self.num_experts)

    # The heads on a block of positions -> float32 `logp` of the taken
    # action, `entropy`, `gate` (1: one pass, never left early), `value`:
    # the hybrid model's, which reads nothing but `self.logits`.
    token_stats = HybridLM.token_stats

    def logits(self, p: dict, h: jax.Array):
        """(logits, gate, value), float32; the vocabulary head is untied
        from the embedding (`head`: acting's copy is in the compute dtype)."""
        z = self._norm(h, p["final_norm"])
        logits = jnp.einsum("...d,vd->...v", z.astype(self.dtype),
                            p["head"].astype(self.dtype), preferred_element_type=F32)
        value = z @ p["w_value"].astype(F32) + p["b_value"]
        return logits, jnp.ones_like(value), value

    # -- acting as decode --------------------------------------------------
    def init_state(self, num_rows: int, length: int) -> MoEState:
        """Zeros: every episode starts from no past."""
        gdn, conv, k, v = [], [], [], []
        for kind in self.layer_types:
            linear = kind == "linear_attention"
            gdn.append(jnp.zeros((num_rows, self.gdn_value_heads, self.gdn_key_dim,
                                  self.gdn_value_dim), self.state_dtype)
                       if linear else None)
            conv.append(jnp.zeros((num_rows, self.gdn_conv - 1, self.conv_channels),
                                  F32) if linear else None)
            cache = (None if linear else jnp.zeros(
                (num_rows, length, self.num_kv_heads, self.head_dim), self.dtype))
            k.append(cache)
            v.append(cache)
        routes = jnp.zeros((num_rows, length, len(self.layer_types), self.top_k),
                           jnp.int16)
        return MoEState(tuple(gdn), tuple(conv), tuple(k), tuple(v), routes)

    def _decode_delta_rule(self, y, lp, state, window):
        """One linear-attention mixer of a decode step: the window shifted
        by one, the state updated and read out -> (mix, state, window)."""
        qkv, z = self._split_in(self._mm(y, lp["in_proj"]))
        g, beta = self._gates(self._mm(y, lp["in_ba"]), lp)
        with jax.named_scope(scopes.ACT_GDN):
            taps = jnp.concatenate([window, qkv[:, None]], axis=1)  # [N, K, C]
            q, k, v = self._split_conv(jax.nn.silu(
                jnp.einsum("nkc,ck->nc", taps, lp["conv_w"])))
            o, state = gated_delta.gated_delta_step(state, q, k, v, g, beta)
        return self._gated_out(o, z, lp), state.astype(self.state_dtype), taps[:, 1:]

    def _decode_attention(self, y, lp, keys, values, t, span):
        """The attention mixer of a decode step: one key and one value
        written at t, the first `span` positions of the row read ->
        (mix, keys, values)."""
        n, length = y.shape[0], keys.shape[1]
        span = length if span is None else span
        if not 0 < span <= length:
            raise ValueError(f"span {span} of a cache of {length} positions")
        q, gate, k, v = self._qkv(y[:, None], lp, jnp.full((1,), t))
        q = q.reshape(n, self.num_kv_heads, self.num_heads // self.num_kv_heads,
                      self.head_dim).astype(self.dtype)
        with jax.named_scope(scopes.ACT_CACHE):
            keys = jax.lax.dynamic_update_slice(keys, k.astype(self.dtype), (0, t, 0, 0))
            values = jax.lax.dynamic_update_slice(values, v.astype(self.dtype),
                                                  (0, t, 0, 0))
            k_read, v_read = keys[:, :span], values[:, :span]
        s = jnp.einsum("nkgd,nskd->nkgs", q, k_read,
                       preferred_element_type=F32) * self.head_dim ** -0.5
        seen = jnp.arange(span) <= t
        prob = jnp.where(seen, jax.nn.softmax(jnp.where(seen, s, _MASK_VALUE), -1), 0.0)
        att = jnp.einsum("nkgs,nskd->nkgd", prob.astype(self.dtype), v_read,
                         preferred_element_type=F32)
        att = att.reshape(n, self.num_heads, self.head_dim) * jax.nn.sigmoid(gate[:, 0])
        return self._mm(att.reshape(n, -1), lp["wo"]), keys, values

    def decode(self, p: dict, tokens: jax.Array, t: jax.Array,
               state: MoEState, span: int | None = None):
        """One decode step at batch N: `tokens [N]` shown at step `t` of
        the episode (the same for every row). Every linear-attention layer
        shifts its window and updates its state, whatever t; the attention
        layer writes position t of its cache and reads the static prefix
        `span` (`t < span` is the CALLER's to hold, as in
        `looped_lm.LoopedLM.decode`); every layer's expert MLP runs on the
        N rows. `p`: `for_acting`'s parameters, or the learner's.
        -> (h_L `[N, D]`, state)."""
        gdn, conv, keys, values = (list(x) for x in state[:4])
        layers = p["layers"] if "layers" in p else per_layer(p)
        routes = []
        with jax.named_scope(scopes.ACT_LAYERS):
            h = p["embed"][tokens].astype(self.dtype)
            for i, (kind, lp) in enumerate(zip(self.layer_types, layers)):
                y = self._norm(h, lp["norms"][0])
                if kind == "linear_attention":
                    mix, gdn[i], conv[i] = self._decode_delta_rule(
                        y, lp, gdn[i], conv[i])
                else:
                    mix, keys[i], values[i] = self._decode_attention(
                        y, lp, keys[i], values[i], t, span)
                with jax.named_scope(scopes.ACT_MOE):
                    h, (chosen, _), _ = self._moe(self._residual(h, mix), lp,
                                                  scopes.MOE_ACT)
                routes.append(chosen.astype(jnp.int16))
        with jax.named_scope(scopes.ACT_MOE_ROUTE):
            record = jax.lax.dynamic_update_slice(
                state.routes, jnp.stack(routes, axis=1)[:, None], (0, t, 0, 0))
        return h, MoEState(tuple(gdn), tuple(conv), tuple(keys), tuple(values),
                           record)


def for_acting(params, dtype):
    """The parameters as the decode steps of one update read them
    (`hybrid_lm.for_acting`'s rule): every layer's matrices cast to the
    compute dtype ONCE, each layer a dict of its own, and the vocabulary
    head in that dtype; the router and the embedding stay float32."""
    p = {k: v for k, v in params["params"].items() if not k.startswith("run")}
    p["layers"] = per_layer(params["params"], dtype, RUN_MATRICES)
    p["head"] = p["head"].astype(dtype)
    return {"params": p}

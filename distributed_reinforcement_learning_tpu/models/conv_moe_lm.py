"""Gated-short-convolution sparse-expert language model (LiquidAI
LFM2-24B-A2B: `lfm2_moe`): a stack whose mixers are of TWO kinds in a
published order, three double-gated short convolutions to one
grouped-query softmax attention, and whose MLPs are of two kinds that do
NOT line up with them: a dense SwiGLU in the leading layers, then a
sparse expert MLP (a sigmoid-scored router over all `num_experts` experts
of the layer with a selection bias that no gradient trains, of which this
chip holds `experts_held` from `first_expert` on, and NO shared expert);
a plain RMSNorm, a tied vocabulary head and (RL's addition) a value head.
D wide, tokens x_1..x_T:

    N(x; g) = g x rsqrt(mean(x^2) + eps)
    h_0 = E[x];  per layer:  u = h + Mix(N(h; g_op)),  h' = u + F(N(u; g_ffn))
    logits = N(h_L; g_f) E^T;  v = N(h_L; g_f) . w_v + b_v
    short convolution (`conv`), y = N(h; g_op):
        [B | C | X] = y W_in (3 D columns, in that order);  u = B * X
        c_t = sum_{j=0..K-1} w[:, j] u_{t-(K-1)+j}   (depthwise, causal, no bias, K = 3;
        a tap before the episode's first step reads zero)
        Mix = (C * c) W_out          NO activation anywhere in it
    attention (`full_attention`):
        q = y W_q (32 x 64);  [k | v] = y W_kv (8 x 64 each);  q <- N(q; g_q), k <- N(k; g_k)
        per head;  rotary (rotate-half) over the WHOLE head, position = step in the episode
        Mix = W_o [ softmax(q k^T / sqrt(64), causal AND same-episode) v ]   no gate, no bias
    F of a leading layer: W_d (silu(W_g x) * W_u x)
    F of the others, x = N(u; g_ffn) (`ops/expert_share.py`, `scoring="sigmoid"`):
        s = sigmoid(W_r x);  I = top-k of s + b;  w_i = c s_i / (sum_{j in I} s_j + 1e-6)
        MoE(x) = sum_{i in I, held here} w_i E_i(x)        no shared expert

A layer's kind is the PAIR (mixer, MLP): the runs of equal layers
(`hybrid_lm.layer_runs`) are keyed by it, so the published order's first
period behind one dense layer is three runs (conv + dense; attention +
experts; three conv + experts). Parameters, the two entries (`trunk` +
`token_stats` for the learner's `[B, T]` forward, `decode` for acting),
the row-block rematerialisation and the one-leaf-a-layer act-time state
are `models/hybrid_lm.py`'s design (its `layer_runs`, `causal_conv` and
`per_layer` are imported); the expert layer's counters are
`models/latent_moe_lm.py`'s.

`decode`: one token a row through TWO kinds of state side by side
(`ConvState`): per convolution layer the last K - 1 = 2 gated inputs u
(`[N, 2, D]`: a window, no matrix and nothing that grows), for the
attention layer a key/value cache `[N, T, 8, 64]` x 2 written at t and
read as far as `span`. Beside them `routes [N, T, expert layers, top_k]`
int16, the record of the experts each step chose (`models/moe_lm.py`'s,
for the same reason).

Precision (`dtype`, bfloat16 as the configuration states it): matmul
operands, the gated input u (so the window), the cache and the residual
stream in `dtype` with float32 accumulation; the gates' products, the
taps' sum, router logits (a `highest` product), sigmoid, selection and
weights, norm statistics, rotary, softmax and everything after the logits
in float32; parameters float32.
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
from distributed_reinforcement_learning_tpu.models.latent_moe_lm import (
    LatentMoELM, merged)
from distributed_reinforcement_learning_tpu.models.looped_lm import (
    episode_positions, rms_norm)
from distributed_reinforcement_learning_tpu.models.transformer_net import (
    episode_segments, rope)
from distributed_reinforcement_learning_tpu.observability import scopes
from distributed_reinforcement_learning_tpu.ops import expert_share
from distributed_reinforcement_learning_tpu.ops.attention import (
    _MASK_VALUE, causal_attention)

F32 = jnp.float32
MIXERS = ("conv", "full_attention")
MLPS = ("dense", "moe")
LAYER_KINDS = tuple((mixer, mlp) for mixer in MIXERS for mlp in MLPS)
# What acting casts to the compute dtype once an update (`for_acting`);
# the taps, the router and its bias stay float32.
RUN_MATRICES = ("in_proj", "out_proj", "wq", "wkv", "wo", "wgu", "wd",
                "expert_wgu", "expert_wd")
WEIGHT_EPS = 1e-6  # in the denominator of the routing weights (the source family's code)


class ConvState(NamedTuple):
    """The act-time state, one entry PER LAYER in the published order
    (None where the layer's mixer has no such state), every layer's a
    leaf of its own (`hybrid_lm.HybridState`'s rule), and the record of
    the experts chosen."""

    window: tuple  # [N, K - 1, D] a convolution layer: u_{t-2}, u_{t-1}
    k: tuple  # [N, T, KV, d] an attention layer
    v: tuple
    routes: jax.Array  # [N, T, expert layers, top_k] int16: a record


@dataclasses.dataclass(frozen=True)
class ConvMoELM:
    vocab: int
    d_model: int
    layer_types: tuple  # every layer's mixer, in the published order
    num_dense_layers: int  # the leading layers whose MLP is dense
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    d_ff: int  # the dense layers' SwiGLU
    num_experts: int  # the router's width: every expert of a layer
    experts_held: int  # those this chip holds, from `first_expert` on
    first_expert: int
    top_k: int
    expert_width: int
    route_scale: float = 1.0
    conv_width: int = 3  # K, the source's `conv_L_cache`
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    init_std: float = 0.02
    attention_backend: str = "auto"  # `ops.attention.causal_attention`'s
    row_block: int = 4  # rows a layer is applied to at a time (no section key)

    @property
    def kinds(self) -> tuple:
        """Every layer's (mixer, MLP)."""
        return tuple((mixer, "dense" if i < self.num_dense_layers else "moe")
                     for i, mixer in enumerate(self.layer_types))

    @property
    def runs(self) -> tuple:
        return layer_runs(self.kinds, LAYER_KINDS)

    @property
    def expert_layers(self) -> int:
        return len(self.layer_types) - self.num_dense_layers

    @property
    def bias_holders(self) -> tuple:
        """The key path of every run that holds a selection bias, in the
        order of `router_load`'s rows (`expert_share.rebias`)."""
        return tuple((f"run{i}",) for i, ((_, mlp), _) in enumerate(self.runs)
                     if mlp == "moe")

    # -- parameters ---------------------------------------------------------
    def init(self, rng: jax.Array, *_) -> dict:
        """Normal(`init_std`) matrices and embedding; ones for the norm
        scales; zeros for the value bias and the router's selection bias;
        the taps uniform(+-1 / sqrt(K)), the default of the source's
        depthwise `Conv1d` (as `hybrid_lm`'s and `moe_lm`'s windows)."""
        keys = iter(jax.random.split(rng, 16 * (len(self.runs) + 1)))
        normal = lambda *shape: self.init_std * jax.random.normal(
            next(keys), shape, F32)
        d, a = self.d_model, self.num_heads * self.head_dim
        p = {"embed": normal(self.vocab, d), "final_norm": jnp.ones((d,), F32),
             "w_value": normal(d), "b_value": jnp.zeros((), F32)}
        for i, ((mixer, mlp), n) in enumerate(self.runs):
            run = {"norms": jnp.ones((n, 2, d), F32)}
            if mixer == "conv":
                bound = self.conv_width ** -0.5
                run.update(in_proj=normal(n, d, 3 * d), out_proj=normal(n, d, d),
                           conv_w=jax.random.uniform(
                               next(keys), (n, d, self.conv_width), F32, -bound, bound))
            else:
                run.update(wq=normal(n, d, a), wo=normal(n, a, d),
                           wkv=normal(n, d, 2 * self.num_kv_heads * self.head_dim),
                           q_norm=jnp.ones((n, self.head_dim), F32),
                           k_norm=jnp.ones((n, self.head_dim), F32))
            if mlp == "dense":
                run.update(wgu=normal(n, d, 2 * self.d_ff), wd=normal(n, self.d_ff, d))
            else:
                e, f = self.experts_held, self.expert_width
                run.update(router=normal(n, d, self.num_experts),
                           router_bias=jnp.zeros((n, self.num_experts), F32),
                           expert_wgu=normal(n, e, d, 2 * f),
                           expert_wd=normal(n, e, f, d))
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
        return rms_norm(x, scale, self.rms_eps)

    def _residual(self, h: jax.Array, branch: jax.Array) -> jax.Array:
        return (h.astype(F32) + branch).astype(self.dtype)

    def _gates(self, bcx: jax.Array):
        """`y W_in` -> (u = B * X in `dtype`: what the taps read and the
        window keeps; the output gate C, float32; sum |B| + sum |C|)."""
        b, c, x = jnp.split(bcx, 3, axis=-1)
        return ((b * x).astype(self.dtype), c,
                jax.lax.stop_gradient(jnp.sum(jnp.abs(b)) + jnp.sum(jnp.abs(c))))

    def _qkv(self, y: jax.Array, lp: dict, pos: jax.Array):
        """`y [B, T, D]` -> q `[B, T, heads, d]`, k, v `[B, T, KV, d]`:
        q and k normed per head and rotated over the whole head; float32."""
        b, t, _ = y.shape
        q = self._mm(y, lp["wq"]).reshape(b, t, self.num_heads, self.head_dim)
        k, v = jnp.split(self._mm(y, lp["wkv"]).reshape(
            b, t, 2 * self.num_kv_heads, self.head_dim), 2, axis=2)
        return (rope(self._norm(q, lp["q_norm"]), pos, self.rope_theta),
                rope(self._norm(k, lp["k_norm"]), pos, self.rope_theta), v)

    def _ffn(self, mlp: str, u: jax.Array, lp: dict, scope: dict):
        """The layer's MLP on rows `u [N, D]` -> (u + F(N(u; g_ffn)), the
        experts chosen `[N, top_k]` int16 and their scores, counters); a
        dense layer chooses none. There is no shared expert: an expert
        layer's branch is the held experts' part alone."""
        x = self._norm(u, lp["norms"][1])
        if mlp == "dense":
            with jax.named_scope(scope["dense"]):
                gate, up = jnp.split(self._mm(x, lp["wgu"]), 2, axis=-1)
                return (self._residual(u, self._mm(jax.nn.silu(gate) * up, lp["wd"])),
                        None, {})
        with jax.named_scope(scope["route"]):
            scores, chosen, weight, load = expert_share.route(
                x, lp["router"], self.top_k, "sigmoid", lp["router_bias"],
                self.route_scale, WEIGHT_EPS)
        with jax.named_scope(scope["experts"]):
            routed, counters = expert_share.held_experts(
                x, chosen, weight, lp["expert_wgu"], lp["expert_wd"],
                self.first_expert, self.num_experts, self.dtype)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        stats = jax.lax.stop_gradient({
            **{k: counters[k] for k in ("expert_pairs", "dropped_pairs", "pair_slabs")},
            "pair_slabs_max": counters["pair_slabs"], "router_load": load,
            "score_sum": jnp.sum(scores)})
        return (self._residual(u, routed),
                (chosen.astype(jnp.int16), jax.lax.stop_gradient(picked)), stats)

    # -- the learner's forward --------------------------------------------
    def _conv(self, y, lp, pos):
        with jax.named_scope(scopes.CONV):
            u, c, gate_abs = self._gates(self._mm(y, lp["in_proj"]))
            taps = causal_conv(u.astype(F32), lp["conv_w"], 0.0, pos)
            return self._mm(c * taps, lp["out_proj"]), {"gate_abs_sum": gate_abs}

    def _attention(self, y, lp, seg, pos):
        b, t, _ = y.shape
        with jax.named_scope(scopes.ATTENTION):
            q, k, v = self._qkv(y, lp, pos)
            # `causal_attention` takes as many key/value heads as query
            # heads and scales by d ** -0.5, which is the published scale.
            groups = self.num_heads // self.num_kv_heads
            att = causal_attention(
                q.astype(self.dtype), jnp.repeat(k, groups, 2).astype(self.dtype),
                jnp.repeat(v, groups, 2).astype(self.dtype), q_seg=seg, k_seg=seg,
                backend=self.attention_backend)
            return self._mm(att.reshape(b, t, -1), lp["wo"])

    def _layer(self, kind, h, seg, pos, lp):
        """One layer on a block of rows -> (h', the experts chosen `[rows
        x T, top_k]` int16 and their scores (None: a dense layer), the
        layer's counters)."""
        mixer, mlp = kind
        y = self._norm(h, lp["norms"][0])
        if mixer == "conv":
            mix, stats = self._conv(y, lp, pos)
        else:
            mix, stats = self._attention(y, lp, seg, pos), {}
        u = self._residual(h, mix)
        out, chosen, moe = self._ffn(mlp, u.reshape(-1, u.shape[-1]), lp,
                                     scopes.CONV_LEARN)
        return out.reshape(u.shape), chosen, {**stats, **moe}

    def trunk(self, p: dict, tokens: jax.Array, done: jax.Array):
        """`tokens, done [B, T]` -> (h_L `[1, B, T, D]` before the final
        norm: one pass, the leading axis `LoopLMAgent` reads as R; the
        layers' facts: of the expert layers, every leaf with a leading
        layer axis, `routes`, `route_scores [layers, B, T, top_k]` (the
        experts every position chose and their unbiased scores),
        `router_load [layers, E]`, `expert_pairs [layers, held]`,
        `dropped_pairs`, `pair_slabs`, `pair_slabs_max`, `score_sum
        [layers]`; of the convolution layers `gate_abs_sum [layers]`:
        `counters` reduces them)."""
        b, t = tokens.shape
        rows = math.gcd(b, self.row_block)
        blocks = lambda x: x.reshape(b // rows, rows, *x.shape[1:])
        seg, pos = blocks(episode_segments(done)), blocks(episode_positions(done))
        facts, gates = [], []
        with jax.named_scope(scopes.LAYERS):
            h = p["embed"][tokens].astype(self.dtype)
            for i, (kind, n) in enumerate(self.runs):
                block = jax.checkpoint(functools.partial(self._layer, kind))

                def layer(h, lp):
                    out, chosen, stat = jax.lax.map(
                        lambda xs: block(*xs, lp), (blocks(h), seg, pos))
                    return out.reshape(h.shape), (chosen, stat)

                h, (chosen, stat) = jax.lax.scan(layer, h, p[f"run{i}"])
                stat = {k: (jnp.max if k.endswith("_max") else jnp.sum)(v, axis=1)
                        for k, v in stat.items()}  # over the blocks
                if "gate_abs_sum" in stat:
                    gates.append(stat.pop("gate_abs_sum"))
                if chosen is not None:
                    facts.append({
                        "routes": chosen[0].reshape(n, b, t, self.top_k),
                        "route_scores": chosen[1].reshape(n, b, t, self.top_k),
                        **stat})
        return h[None], {**merged(facts), "gate_abs_sum": jnp.concatenate(
            gates or [jnp.zeros((0,), F32)])}

    def counters(self, facts: dict, tokens: int) -> dict:
        """The counters of one forward from `trunk`'s facts over `tokens`
        positions: the expert share's (`LatentMoELM.counters`) and
        `conv_gate_abs_mean`, the mean |B| and |C| of the convolution
        layers' two gates."""
        gates = facts["gate_abs_sum"]
        return {**LatentMoELM.counters(self, facts, tokens),
                "conv_gate_abs_mean": jnp.sum(gates)
                / (max(1, gates.shape[0]) * tokens * 2 * self.d_model)}

    # Rows of a slab of the learner's sorted pairs where a layer is applied
    # to `[B, T]` a row block at a time, and the heads on a block of
    # positions (float32 `logp` of the taken action, `entropy`, `gate`,
    # `value`): the latent model's and the hybrid model's, which read
    # nothing of `self` that this model lacks.
    pair_slab_rows = LatentMoELM.pair_slab_rows
    token_stats = HybridLM.token_stats

    def logits(self, p: dict, h: jax.Array):
        """(logits, gate, value), float32. The vocabulary head is the
        embedding, transposed (`embed_head`: acting's copy of it in the
        compute dtype)."""
        z = self._norm(h, p["final_norm"])
        head = p.get("embed_head", p["embed"])
        logits = jnp.einsum("...d,vd->...v", z.astype(self.dtype),
                            head.astype(self.dtype), preferred_element_type=F32)
        value = z @ p["w_value"].astype(F32) + p["b_value"]
        return logits, jnp.ones_like(value), value

    def rebias(self, before: dict, after: dict, load: jax.Array, gamma: float):
        """`after` with every router's selection bias set to `before`'s
        moved by `gamma sign(mean_j(n_j) - n_i)` (`expert_share.rebias`),
        `load [expert layers, E]` in the layers' order."""
        return {"params": expert_share.rebias(
            before["params"], after["params"], load, gamma, self.bias_holders)}

    # -- acting as decode --------------------------------------------------
    def init_state(self, num_rows: int, length: int) -> ConvState:
        """Zeros: every episode starts from no past."""
        window, k, v = [], [], []
        for mixer in self.layer_types:
            conv = mixer == "conv"
            window.append(jnp.zeros((num_rows, self.conv_width - 1, self.d_model),
                                    self.dtype) if conv else None)
            cache = (None if conv else jnp.zeros(
                (num_rows, length, self.num_kv_heads, self.head_dim), self.dtype))
            k.append(cache)
            v.append(cache)
        routes = jnp.zeros((num_rows, length, self.expert_layers, self.top_k),
                           jnp.int16)
        return ConvState(tuple(window), tuple(k), tuple(v), routes)

    def _decode_conv(self, y, lp, window):
        """One convolution mixer of a decode step: the gated input joins
        the window, the taps read all K, the window drops its oldest
        -> (mix, window)."""
        bcx = self._mm(y, lp["in_proj"])
        with jax.named_scope(scopes.ACT_CONV):
            u, c, _ = self._gates(bcx)
            taps = jnp.concatenate([window, u[:, None]], axis=1)  # [N, K, D]
            gated = c * jnp.einsum("nkc,ck->nc", taps.astype(F32), lp["conv_w"])
            window = taps[:, 1:]
        return self._mm(gated, lp["out_proj"]), window

    def _decode_attention(self, y, lp, keys, values, t, span):
        """The attention mixer of a decode step: one key and one value
        written at t, the first `span` positions of the row read ->
        (mix, keys, values)."""
        n, length = y.shape[0], keys.shape[1]
        span = length if span is None else span
        if not 0 < span <= length:
            raise ValueError(f"span {span} of a cache of {length} positions")
        q, k, v = self._qkv(y[:, None], lp, jnp.full((1,), t))
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
        return self._mm(att.reshape(n, -1), lp["wo"]), keys, values

    def decode(self, p: dict, tokens: jax.Array, t: jax.Array,
               state: ConvState, span: int | None = None):
        """One decode step at batch N: `tokens [N]` shown at step `t` of
        the episode (the same for every row). Every convolution layer
        shifts its window, whatever t; the attention layer writes position
        t of its cache and reads the static prefix `span` (`t < span` is
        the CALLER's to hold, as in `looped_lm.LoopedLM.decode`); every
        layer's MLP runs on the N rows. `p`: `for_acting`'s parameters, or
        the learner's. -> (h_L `[N, D]`, state)."""
        window, keys, values = (list(x) for x in state[:3])
        layers = p["layers"] if "layers" in p else per_layer(p)
        routes = []
        with jax.named_scope(scopes.ACT_LAYERS):
            h = p["embed"][tokens].astype(self.dtype)
            for i, ((mixer, mlp), lp) in enumerate(zip(self.kinds, layers)):
                y = self._norm(h, lp["norms"][0])
                if mixer == "conv":
                    mix, window[i] = self._decode_conv(y, lp, window[i])
                else:
                    mix, keys[i], values[i] = self._decode_attention(
                        y, lp, keys[i], values[i], t, span)
                with jax.named_scope(scopes.ACT_MOE if mlp == "moe"
                                     else scopes.ACT_LAYERS):
                    h, chosen, _ = self._ffn(mlp, self._residual(h, mix), lp,
                                             scopes.CONV_ACT)
                if chosen is not None:
                    routes.append(chosen[0])
        with jax.named_scope(scopes.ACT_MOE_ROUTE):
            record = jax.lax.dynamic_update_slice(
                state.routes, jnp.stack(routes, axis=1)[:, None], (0, t, 0, 0))
        return h, ConvState(tuple(window), tuple(keys), tuple(values), record)


def for_acting(params, dtype):
    """The parameters as the decode steps of one update read them
    (`hybrid_lm.for_acting`'s rule): every layer's matrices cast to the
    compute dtype ONCE, each layer a dict of its own, and a copy of the
    embedding in that dtype for the vocabulary head; the taps, the router,
    its bias (which acting takes with the weights) and the embedding the
    lookup reads stay float32."""
    p = {k: v for k, v in params["params"].items() if not k.startswith("run")}
    p["layers"] = per_layer(params["params"], dtype, RUN_MATRICES)
    p["embed_head"] = p["embed"].astype(dtype)
    return {"params": p}

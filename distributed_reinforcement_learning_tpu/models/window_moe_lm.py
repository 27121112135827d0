"""Sliding-window / global-attention sparse-expert language model
(PowerInfer SmallThinker-21BA3B-Instruct: `smallthinker_moe`): a stack
whose attention layers are of TWO kinds in a published order, one GLOBAL
layer with NO positional encoding (NoPE) to three SLIDING-WINDOW layers
with rotary positions, every layer followed by a sparse expert MLP whose
router reads the layer's INPUT, before attention and before any norm (a
softmax router over all `num_experts` experts of the layer, of which this
chip holds `experts_held` from `first_expert` on; ReGLU experts; no shared
expert, no selection bias); a plain RMSNorm, an UNTIED vocabulary head
and (RL's addition) a value head. D wide, tokens x_1..x_T:

    N(x; s) = s x rsqrt(mean(x^2) + eps)
    h_0 = E[x];  per layer, input h:
        r = h W_r                      the router's logits, from the layer's INPUT
        y = N(h; s_att);  q = y W_q (heads x d);  [k | v] = y W_kv (KV x d each)
        window layer: q, k rotated (rotate-half over the whole head, position =
            step in the episode); key j visible to query t iff j <= t, t - j < W
            and same episode
        global layer: NO rotation; key j visible iff j <= t and same episode
        a = softmax(q k^T / sqrt(d) over the visible keys) v;  query head i reads
            key/value head i // (heads / KV);  u = h + a W_o
        x = N(u; s_ffn);  I = the top_k largest of r;  w = softmax(r_I)
        h' = u + sum_{i in I, held here} w_i W_d,i (relu(W_g,i x) * W_u,i x)
    logits = N(h_L; s_f) W_head;  v = N(h_L; s_f) . w_v + b_v

A layer's kind is its attention's (`global` | `window`): the runs of equal
layers (`hybrid_lm.layer_runs`) are keyed by it, so one period is two
runs ([global] + [window x 3]). Parameters, the two entries (`trunk` +
`token_stats` for the learner's `[B, T]` forward, `decode` for acting),
the row-block rematerialisation and the one-leaf-a-layer act-time state
are `models/hybrid_lm.py`'s design; the expert layer's counters are
`models/latent_moe_lm.py`'s.

`decode`: one token a row through TWO kinds of cache side by side
(`WindowState`): the global layer's `[N, T, KV, d]` x 2 written at t and
read as far as `span`; a window layer's RING `[N, W, KV, d]` x 2 written
at `t mod W`, its key ALREADY rotated by its true position t (so the
ring's order does not matter to the scores), read as far as `min(span,
W)`, slot s valid iff it has been written in this episode (`s <= t`: every
slot once the ring is full). Beside them `routes [N, T, layers, top_k]`
int16, the record of the experts each step chose.

Precision (`dtype`, bfloat16 as the configuration states it): matmul
operands, the caches and the residual stream in `dtype` with float32
accumulation; router logits (a `highest` product of float32 operands),
softmax, selection and weights, norm statistics, rotary, the attention
softmax and everything after the logits in float32; parameters float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.models.hybrid_lm import (
    HybridLM, layer_runs, per_layer)
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
LAYER_KINDS = ("global", "window")
# What acting casts to the compute dtype once an update (`for_acting`);
# the router stays float32.
RUN_MATRICES = ("wq", "wkv", "wo", "expert_wgu", "expert_wd")
LEARN_ATTENTION = {"global": scopes.GLOBAL_ATTENTION, "window": scopes.WINDOW_ATTENTION}
ACT_KV = {"global": scopes.ACT_CACHE, "window": scopes.ACT_RING}


class WindowState(NamedTuple):
    """The act-time state, one entry PER LAYER in the published order,
    every layer's a leaf of its own (`hybrid_lm.HybridState`'s rule), and
    the record of the experts chosen."""

    k: tuple  # [N, T, KV, d] a global layer; [N, min(W, T), KV, d] a window layer's ring
    v: tuple
    routes: jax.Array  # [N, T, layers, top_k] int16: a record


@dataclasses.dataclass(frozen=True)
class WindowMoELM:
    vocab: int
    d_model: int
    layer_types: tuple  # every layer's attention kind, in the published order
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int  # W: a window layer's query sees keys t - W + 1 .. t
    rope_theta: float
    num_experts: int  # the router's width: every expert of a layer
    experts_held: int  # those this chip holds, from `first_expert` on
    first_expert: int
    top_k: int
    expert_width: int
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    init_std: float = 0.02
    embed_init_std: float = 1.0  # the embedding's own range (`init` says why)
    attention_backend: str = "auto"  # `ops.attention.causal_attention`'s
    row_block: int = 1  # rows a layer is applied to at a time (no section key)

    @property
    def runs(self) -> tuple:
        return layer_runs(self.layer_types, LAYER_KINDS)

    # -- parameters ---------------------------------------------------------
    def init(self, rng: jax.Array, *_) -> dict:
        """Normal(`init_std`) matrices and head (the section's
        `initializer_range`), a normal(`embed_init_std`) embedding (its
        `embedding_initializer_range`, 1.0 in both sections: a key of its
        own, so that no leaf ignores the other in silence); ones for the
        norm scales; zero for the value bias. Why the embedding has a range
        of its own: the router reads the residual stream UN-NORMED; under
        an embedding of 0.02 a row of it is a fiftieth of what attention
        adds at random weights (the running mean of the row's past values,
        the same for every token of the row), so whole rows go to the same
        experts (`expert_load_max_over_mean` 3.0-3.7 over 65,536 tokens)
        and the share of the pairs held here, and with it an update's time,
        swings with the seed (0.238-0.274; my chip runs, PR 49). No public
        source states the family's embedding range: this is a DEPARTURE
        (`perfbench/configs/smallthinker_moe.json` `departures`), made so
        that random weights route as evenly as a trained router does."""
        keys = iter(jax.random.split(rng, 8 * (len(self.runs) + 1)))
        normal = lambda *shape, std=self.init_std: std * jax.random.normal(
            next(keys), shape, F32)
        d, a = self.d_model, self.num_heads * self.head_dim
        e, f = self.experts_held, self.expert_width
        p = {"embed": normal(self.vocab, d, std=self.embed_init_std),
             "head": normal(self.vocab, d),
             "final_norm": jnp.ones((d,), F32),
             "w_value": normal(d), "b_value": jnp.zeros((), F32)}
        for i, (_, n) in enumerate(self.runs):
            p[f"run{i}"] = {
                "norms": jnp.ones((n, 2, d), F32), "wq": normal(n, d, a),
                "wkv": normal(n, d, 2 * self.num_kv_heads * self.head_dim),
                "wo": normal(n, a, d), "router": normal(n, d, self.num_experts),
                "expert_wgu": normal(n, e, d, 2 * f), "expert_wd": normal(n, e, f, d)}
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

    def _qkv(self, kind: str, y: jax.Array, lp: dict, pos: jax.Array):
        """`y [B, T, D]` -> q `[B, T, heads, d]`, k, v `[B, T, KV, d]`,
        float32; q and k of a window layer rotated over the whole head by
        the step inside the episode, a global layer's left as they are
        (NoPE); no per-head norm, no bias."""
        b, t, _ = y.shape
        q = self._mm(y, lp["wq"]).reshape(b, t, self.num_heads, self.head_dim)
        k, v = jnp.split(self._mm(y, lp["wkv"]).reshape(
            b, t, 2 * self.num_kv_heads, self.head_dim), 2, axis=2)
        if kind == "window":
            q, k = rope(q, pos, self.rope_theta), rope(k, pos, self.rope_theta)
        return q, k, v

    def _route(self, h: jax.Array, lp: dict, scope: str):
        """The router on the layer's INPUT rows `h [N, D]` (the residual
        stream as it arrives, un-normed) -> (probs, chosen, weight, load
        `[E]` int32: the rows that chose each expert)."""
        with jax.named_scope(scope):
            probs, chosen, weight = expert_share.route(h, lp["router"], self.top_k)
            load = jnp.sum(chosen[..., None] == jnp.arange(self.num_experts),
                           axis=(0, 1), dtype=jnp.int32)
        return probs, chosen, weight, load

    def _experts(self, u: jax.Array, routed_by, lp: dict, scope: str):
        """The expert MLP on rows `u [N, D]` with the sets `_route` chose
        ahead of attention -> (u + the held ReGLU experts' part of
        N(u; s_ffn), the experts chosen `[N, top_k]` int16 and their
        probabilities, counters)."""
        probs, chosen, weight, load = routed_by
        x = self._norm(u, lp["norms"][1])
        with jax.named_scope(scope):
            routed, counters = expert_share.held_experts(
                x, chosen, weight, lp["expert_wgu"], lp["expert_wd"],
                self.first_expert, self.num_experts, self.dtype, "relu")
        picked = jnp.take_along_axis(probs, chosen, axis=-1)
        stats = jax.lax.stop_gradient({
            **{k: counters[k] for k in ("expert_pairs", "dropped_pairs",
                                        "pair_slabs", "gate_zeroed")},
            "pair_slabs_max": counters["pair_slabs"], "router_load": load,
            "score_sum": jnp.sum(probs)})
        return (self._residual(u, routed),
                (chosen.astype(jnp.int16), jax.lax.stop_gradient(picked)), stats)

    # -- the learner's forward --------------------------------------------
    def _attention(self, kind, y, lp, seg, pos):
        b, t, _ = y.shape
        with jax.named_scope(LEARN_ATTENTION[kind]):
            q, k, v = self._qkv(kind, y, lp, pos)
            # `causal_attention` takes as many key/value heads as query
            # heads and scales by d ** -0.5, which is the published scale.
            groups = self.num_heads // self.num_kv_heads
            att = causal_attention(
                q.astype(self.dtype), jnp.repeat(k, groups, 2).astype(self.dtype),
                jnp.repeat(v, groups, 2).astype(self.dtype), q_seg=seg, k_seg=seg,
                backend=self.attention_backend,
                window=self.window if kind == "window" else None)
            return self._mm(att.reshape(b, t, -1), lp["wo"])

    def _layer(self, kind, h, seg, pos, lp):
        """One layer on a block of rows -> (h', the experts chosen `[rows
        x T, top_k]` int16 and their probabilities, the layer's counters)."""
        routed_by = self._route(h.reshape(-1, h.shape[-1]), lp, scopes.MOE_ROUTE)
        y = self._norm(h, lp["norms"][0])
        u = self._residual(h, self._attention(kind, y, lp, seg, pos))
        out, chosen, stats = self._experts(u.reshape(-1, u.shape[-1]), routed_by, lp,
                                           scopes.MOE_EXPERTS)
        return out.reshape(u.shape), chosen, stats

    def trunk(self, p: dict, tokens: jax.Array, done: jax.Array):
        """`tokens, done [B, T]` -> (h_L `[1, B, T, D]` before the final
        norm: one pass, the leading axis `LoopLMAgent` reads as R; the
        layers' facts, every leaf with a leading layer axis: `routes`,
        `route_scores [layers, B, T, top_k]` (the experts every position
        chose and their probabilities), `router_load [layers, E]`,
        `expert_pairs [layers, held]`, `dropped_pairs`, `pair_slabs`,
        `pair_slabs_max`, `gate_zeroed`, `score_sum [layers]`; and
        `window_pairs`, `causal_pairs`: the (query, key) pairs a window
        layer and a global layer let through, a head: `counters` reduces
        them)."""
        b, t = tokens.shape
        rows = math.gcd(b, self.row_block)
        blocks = lambda x: x.reshape(b // rows, rows, *x.shape[1:])
        position = episode_positions(done)
        seg, pos = blocks(episode_segments(done)), blocks(position)
        facts = []
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
                facts.append({"routes": chosen[0].reshape(n, b, t, self.top_k),
                              "route_scores": chosen[1].reshape(n, b, t, self.top_k),
                              **stat})
        seen = (position + 1).astype(F32)
        return h[None], {**merged(facts),
                         "window_pairs": jnp.sum(jnp.minimum(seen, self.window)),
                         "causal_pairs": jnp.sum(seen)}

    def counters(self, facts: dict, tokens: int) -> dict:
        """The counters of one forward from `trunk`'s facts over `tokens`
        positions: the expert share's (`LatentMoELM.counters`; a softmax
        router's mean score is 1 / E and is left out),
        `relu_gate_zero_share` (the share of the held pairs' gate values,
        `expert_width` a pair, that ReLU zeroed) and `window_pair_share`
        (the pairs a window layer lets through over a global layer's)."""
        out = LatentMoELM.counters(self, facts, tokens)
        del out["router_score_mean"]
        pairs = jnp.sum(facts["expert_pairs"]).astype(F32)
        return {**out,
                "relu_gate_zero_share": jnp.sum(facts["gate_zeroed"].astype(F32))
                / jnp.maximum(pairs * self.expert_width, 1.0),
                "window_pair_share": facts["window_pairs"] / facts["causal_pairs"]}

    # Rows of a slab of the learner's sorted pairs where a layer is applied
    # to `[B, T]` a row block at a time, and the heads on a block of
    # positions (float32 `logp` of the taken action, `entropy`, `gate`,
    # `value`): the latent model's and the hybrid model's, which read
    # nothing of `self` that this model lacks.
    pair_slab_rows = LatentMoELM.pair_slab_rows
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
    def init_state(self, num_rows: int, length: int) -> WindowState:
        """Zeros: every episode starts from no past. A ring is never longer
        than the episode."""
        cache = lambda positions: jnp.zeros(
            (num_rows, positions, self.num_kv_heads, self.head_dim), self.dtype)
        k = tuple(cache(length if kind == "global" else min(self.window, length))
                  for kind in self.layer_types)
        routes = jnp.zeros((num_rows, length, len(self.layer_types), self.top_k),
                           jnp.int16)
        return WindowState(k, k, routes)

    def _decode_attention(self, kind, y, lp, keys, values, t, span):
        """The attention of a decode step: one key (a window layer's
        rotated by t) and one value written at t (a ring: at `t mod W`),
        the first `span` positions of the row read (a ring: `min(span, W)`
        slots, whatever their order) -> (attention, keys, values)."""
        n, length = y.shape[0], keys.shape[1]
        if span is None:
            span = length
        if kind == "global" and not 0 < span <= length:
            raise ValueError(f"span {span} of a cache of {length} positions")
        read = min(span, length)
        q, k, v = self._qkv(kind, y[:, None], lp, jnp.full((1,), t))
        q = q.reshape(n, self.num_kv_heads, self.num_heads // self.num_kv_heads,
                      self.head_dim).astype(self.dtype)
        # the write, and the two products that READ the cache (the compiler
        # fuses the prefix's slice into them), under the cache's own name
        with jax.named_scope(ACT_KV[kind]):
            slot = t % length if kind == "window" else t
            keys = jax.lax.dynamic_update_slice(keys, k.astype(self.dtype),
                                                (0, slot, 0, 0))
            values = jax.lax.dynamic_update_slice(values, v.astype(self.dtype),
                                                  (0, slot, 0, 0))
            k_read, v_read = keys[:, :read], values[:, :read]
            s = jnp.einsum("nkgd,nskd->nkgs", q, k_read,
                           preferred_element_type=F32) * self.head_dim ** -0.5
            # written in this episode: every slot of a ring once it is full
            seen = jnp.arange(read) <= t
            prob = jnp.where(seen, jax.nn.softmax(
                jnp.where(seen, s, _MASK_VALUE), -1), 0.0)
            att = jnp.einsum("nkgs,nskd->nkgd", prob.astype(self.dtype), v_read,
                             preferred_element_type=F32)
        return self._mm(att.reshape(n, -1), lp["wo"]), keys, values

    def decode(self, p: dict, tokens: jax.Array, t: jax.Array,
               state: WindowState, span: int | None = None):
        """One decode step at batch N: `tokens [N]` shown at step `t` of
        the episode (the same for every row). The global layer writes
        position t of its cache and reads the static prefix `span` (`t <
        span` is the CALLER's to hold, as in `looped_lm.LoopedLM.decode`);
        a window layer writes slot `t mod W` of its ring and reads `min(span,
        W)` slots; the router reads each layer's input, the experts run on
        the N rows. `p`: `for_acting`'s parameters, or the learner's.
        -> (h_L `[N, D]`, state)."""
        keys, values = list(state.k), list(state.v)
        layers = p["layers"] if "layers" in p else per_layer(p)
        routes = []
        with jax.named_scope(scopes.ACT_LAYERS):
            h = p["embed"][tokens].astype(self.dtype)
            for i, (kind, lp) in enumerate(zip(self.layer_types, layers)):
                routed_by = self._route(h, lp, scopes.ACT_MOE_ROUTE)
                y = self._norm(h, lp["norms"][0])
                mix, keys[i], values[i] = self._decode_attention(
                    kind, y, lp, keys[i], values[i], t, span)
                h, chosen, _ = self._experts(self._residual(h, mix), routed_by, lp,
                                             scopes.ACT_MOE_EXPERTS)
                routes.append(chosen[0])
        with jax.named_scope(scopes.ACT_MOE_ROUTE):
            record = jax.lax.dynamic_update_slice(
                state.routes, jnp.stack(routes, axis=1)[:, None], (0, t, 0, 0))
        return h, WindowState(tuple(keys), tuple(values), record)


def for_acting(params, dtype):
    """The parameters as the decode steps of one update read them
    (`hybrid_lm.for_acting`'s rule): every layer's matrices and the
    vocabulary HEAD cast to the compute dtype ONCE, each layer a dict of
    its own; the routers and the embedding the lookup reads stay float32."""
    p = {k: v for k, v in params["params"].items() if not k.startswith("run")}
    p["layers"] = per_layer(params["params"], dtype, RUN_MATRICES)
    p["head"] = p["head"].astype(dtype)
    return {"params": p}

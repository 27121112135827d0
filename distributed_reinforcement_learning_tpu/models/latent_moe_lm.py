"""Latent-attention sparse-expert language model (JoyAI-LLM-Flash:
`joyai_llm_flash`, 2026-04; its keys are DeepSeek-V3's, arXiv:2412.19437
sections 2.1-2.2): multi-head latent attention in EVERY layer, a dense
SwiGLU in the leading layer and a sparse expert MLP in the others (a
sigmoid-scored router over all `num_experts` experts of the layer with a
selection bias that no gradient trains, of which this chip holds
`experts_held` from `first_expert` on, beside an ungated shared expert),
a multi-token-prediction module beside the stack (learner only), a plain
RMSNorm, an untied vocabulary head and (RL's addition) a value head. D
wide, tokens x_1..x_T:

    N(x; g) = x rsqrt(mean(x^2) + eps) g
    h_0 = E[x];  per layer:  u = h + MLA(N1(h)),  h' = u + F(N2(u))
    logits = N(h_L; g_f) W_head^T;  v = N(h_L; g_f) . w_v + b_v
    MLA, y = N1(h) (`ops/latent_attention.py`):
        c_q = N(W_qa y; g_q);  [q_n | q_r] = W_qb c_q per head (128 | 64)
        [c | k_r] = W_kva y (512 | 64);  c <- N(c; g_kv);  [k_n | v] = W_kvb c per head
        q_r, k_r rotated (neighbouring pairs) at the step in the episode; one k_r for all heads
        MLA = W_o [ softmax((q_n . k_n + q_r . k_r) / sqrt(192), causal AND same-episode) v ]
    F of layer 0: W_d (silu(W_g x) * W_u x)
    F of the others, x = N2(u) (`ops/expert_share.py`, `scoring="sigmoid"`):
        s = sigmoid(W_r x);  I = top-k of s + b;  w_i = c s_i / sum_{j in I} s_j
        MoE(x) = sum_{i in I, held here} w_i E_i(x) + E_shared(x)
    multi-token prediction (`mtp`), x_{t+1} the NEXT token on show:
        h' = W_p [N(h_L; g_h) ; N(E[x_{t+1}]; g_e)];  h'' = Layer_mtp(h')
        logits' = N(h''; g_m) W_head^T      (the trunk's embedding and head)

Layout of the fused matrices (any fixed layout is the same model): `wqb`
columns per head `q_n (128) | q_r (64)`, heads contiguous; `wkva`
columns `c (512) | k_r (64)`; `wkvb` columns per head `k_n (128) | v
(128)`; `wgu`, `expert_wgu`, `shared_wgu` gate | up; rotary pairs are
the neighbours `(2j, 2j + 1)` of the 64.

Parameters, the two entries (`trunk` + `token_stats` for the learner's
`[B, T]` forward, `decode` for acting), the row-block rematerialisation
and the one-leaf-a-layer act-time state are `models/hybrid_lm.py`'s
design (one `[n, ...]`-stacked dict and one `lax.scan` PER RUN of equal
layers: `run0` the dense layer, `run1` the expert layers; its
`layer_runs` and `per_layer` are imported); the prediction module's one
layer is a run of its own under `mtp`. The learner runs the attention
EXPANDED through `ops.attention.causal_attention` (the flash kernels on
a TPU, q/k 192 wide and v 128), the decode step ABSORBED on a cache of
`[N, T, 576]` a layer (`LatentState`), the normed latent and the ONE
rotated key part: 1,152 B a token a layer in bfloat16 where per-head
keys and values would be 20,480 B. Beside the cache `routes [N, T,
expert layers, top_k]` int16, the record of the experts each step chose
(`models/moe_lm.py`'s, for the same reason).

Precision (`dtype`, bfloat16 as the configuration states it): matmul
operands, the cache and the residual stream in `dtype` with float32
accumulation; router logits (a `highest` product), sigmoid, selection
and weights, norm statistics, rotary, softmax and everything after the
logits in float32; parameters float32.
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
from distributed_reinforcement_learning_tpu.models.looped_lm import (
    episode_positions, rms_norm)
from distributed_reinforcement_learning_tpu.models.transformer_net import (
    episode_segments)
from distributed_reinforcement_learning_tpu.observability import scopes
from distributed_reinforcement_learning_tpu.ops import (
    expert_share, latent_attention)

F32 = jnp.float32
LAYER_KINDS = ("dense", "moe")
# What acting casts to the compute dtype once an update (`for_acting`);
# the router and its bias stay float32.
RUN_MATRICES = ("wqa", "wqb", "wkva", "wkvb", "wo", "wgu", "wd",
                "expert_wgu", "expert_wd", "shared_wgu", "shared_wd")


class LatentState(NamedTuple):
    """The act-time state: every layer's latent cache a leaf of its own
    (`hybrid_lm.HybridState`'s rule), and the record of the experts
    chosen."""

    cache: tuple  # [N, T, kv_rank + rope_dim] a layer: c (normed) | k_r (rotated)
    routes: jax.Array  # [N, T, expert layers, top_k] int16: a record


@dataclasses.dataclass(frozen=True)
class LatentMoELM:
    vocab: int
    d_model: int
    layer_types: tuple  # ("dense",) * first_k_dense_replace + ("moe",) * the rest
    num_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float
    d_ff: int  # the dense layers' SwiGLU
    num_experts: int  # the router's width: every expert of a layer
    experts_held: int  # those this chip holds, from `first_expert` on
    first_expert: int
    top_k: int
    expert_width: int
    shared_width: int
    route_scale: float
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    init_std: float = 0.02
    attention_backend: str = "auto"  # `ops.attention.causal_attention`'s
    row_block: int = 4  # rows a layer is applied to at a time (no section key)

    @property
    def runs(self) -> tuple:
        return layer_runs(self.layer_types, LAYER_KINDS)

    @property
    def expert_layers(self) -> int:
        return sum(kind == "moe" for kind in self.layer_types)

    @property
    def cache_width(self) -> int:
        return self.kv_rank + self.rope_dim

    # -- parameters ---------------------------------------------------------
    def _run_init(self, kind: str, n: int, normal) -> dict:
        d, h = self.d_model, self.num_heads
        run = {"norms": jnp.ones((n, 2, d), F32),
               "wqa": normal(n, d, self.q_rank),
               "q_norm": jnp.ones((n, self.q_rank), F32),
               "wqb": normal(n, self.q_rank, h * (self.nope_dim + self.rope_dim)),
               "wkva": normal(n, d, self.cache_width),
               "kv_norm": jnp.ones((n, self.kv_rank), F32),
               "wkvb": normal(n, self.kv_rank, h * (self.nope_dim + self.v_dim)),
               "wo": normal(n, h * self.v_dim, d)}
        if kind == "dense":
            run.update(wgu=normal(n, d, 2 * self.d_ff), wd=normal(n, self.d_ff, d))
        else:
            e, f, s = self.experts_held, self.expert_width, self.shared_width
            run.update(router=normal(n, d, self.num_experts),
                       router_bias=jnp.zeros((n, self.num_experts), F32),
                       expert_wgu=normal(n, e, d, 2 * f), expert_wd=normal(n, e, f, d),
                       shared_wgu=normal(n, d, 2 * s), shared_wd=normal(n, s, d))
        return run

    def init(self, rng: jax.Array, *_) -> dict:
        """Normal(`init_std`) matrices, embedding and head; ones for the
        norm scales; zeros for the value bias and the router's selection
        bias (the source family's start)."""
        keys = iter(jax.random.split(rng, 16 * (len(self.runs) + 2)))
        normal = lambda *shape: self.init_std * jax.random.normal(
            next(keys), shape, F32)
        d = self.d_model
        p = {"embed": normal(self.vocab, d), "head": normal(self.vocab, d),
             "final_norm": jnp.ones((d,), F32), "w_value": normal(d),
             "b_value": jnp.zeros((), F32),
             "mtp": {"norm_h": jnp.ones((d,), F32), "norm_e": jnp.ones((d,), F32),
                     "norm_out": jnp.ones((d,), F32), "proj": normal(2 * d, d),
                     "layer": self._run_init("moe", 1, normal)}}
        for i, (kind, n) in enumerate(self.runs):
            p[f"run{i}"] = self._run_init(kind, n, normal)
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

    def _swiglu(self, x: jax.Array, wgu: jax.Array, wd: jax.Array) -> jax.Array:
        gate, up = jnp.split(self._mm(x, wgu), 2, axis=-1)
        return self._mm(jax.nn.silu(gate) * up, wd)

    def _latents(self, y: jax.Array, lp: dict):
        """`y [..., D]` -> q_n `[..., H, 128]`, q_r `[..., H, 64]`, the
        normed latent `c [..., 512]` and `k_r [..., 64]`; q_r and k_r not
        yet rotated; float32."""
        c_q = self._norm(self._mm(y, lp["wqa"]), lp["q_norm"])
        q = self._mm(c_q, lp["wqb"]).reshape(
            *y.shape[:-1], self.num_heads, self.nope_dim + self.rope_dim)
        c, k_r = jnp.split(self._mm(y, lp["wkva"]), [self.kv_rank], axis=-1)
        return (q[..., :self.nope_dim], q[..., self.nope_dim:],
                self._norm(c, lp["kv_norm"]), k_r)

    def _ffn(self, kind: str, u: jax.Array, lp: dict, scope: dict):
        """The layer's MLP on rows `u [N, D]` -> (u + F(N2(u)), the
        experts chosen `[N, top_k]` and their scores, counters); a dense
        layer chooses none."""
        x = self._norm(u, lp["norms"][1])
        if kind == "dense":
            with jax.named_scope(scope["dense"]):
                return self._residual(u, self._swiglu(x, lp["wgu"], lp["wd"])), None, {}
        with jax.named_scope(scope["route"]):
            scores, chosen, weight, load = expert_share.route(
                x, lp["router"], self.top_k, "sigmoid", lp["router_bias"],
                self.route_scale)
        with jax.named_scope(scope["experts"]):
            routed, counters = expert_share.held_experts(
                x, chosen, weight, lp["expert_wgu"], lp["expert_wd"],
                self.first_expert, self.num_experts, self.dtype)
        with jax.named_scope(scope["shared"]):
            shared = self._swiglu(x, lp["shared_wgu"], lp["shared_wd"])
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        stats = jax.lax.stop_gradient({
            **{k: counters[k] for k in ("expert_pairs", "dropped_pairs", "pair_slabs")},
            "pair_slabs_max": counters["pair_slabs"], "router_load": load,
            "score_sum": jnp.sum(scores)})
        return (self._residual(u, routed + shared),
                (chosen.astype(jnp.int16), jax.lax.stop_gradient(picked)), stats)

    # -- the learner's forward --------------------------------------------
    def _layer(self, kind, scope, h, seg, pos, lp):
        """One layer on a block of rows -> (h', the experts chosen `[rows
        x T, top_k]` int16 and their scores, the layer's counters)."""
        b, t, _ = h.shape
        y = self._norm(h, lp["norms"][0])
        with jax.named_scope(scope["project"]):
            q_n, q_r, c, k_r = self._latents(y, lp)
        with jax.named_scope(scope["attend"]):
            att = latent_attention.expanded(
                q_n, q_r, c, k_r, lp["wkvb"], seg, pos, self.rope_theta,
                self.dtype, self.attention_backend)
        with jax.named_scope(scope["project"]):
            mix = self._mm(att.reshape(b, t, -1), lp["wo"])
        u = self._residual(h, mix)
        out, chosen, stats = self._ffn(kind, u.reshape(-1, u.shape[-1]), lp, scope)
        return out.reshape(u.shape), chosen, stats

    def _run(self, kind, scope, h, done, run):
        """A run of equal layers on `h [B, T, D]`, each applied to
        `row_block` rows at a time and rematerialised -> (h', routes and
        scores `[n, B, T, top_k]` (None for a dense run), counters with a
        leading layer axis)."""
        b, t, _ = h.shape
        rows = math.gcd(b, self.row_block)
        blocks = lambda x: x.reshape(b // rows, rows, *x.shape[1:])
        seg, pos = blocks(episode_segments(done)), blocks(episode_positions(done))
        block = jax.checkpoint(functools.partial(self._layer, kind, scope))

        def layer(h, lp):
            out, chosen, stat = jax.lax.map(
                lambda xs: block(*xs, lp), (blocks(h), seg, pos))
            return out.reshape(h.shape), (chosen, stat)

        h, (chosen, stat) = jax.lax.scan(layer, h, run)
        if chosen is None:
            return h, None, {}
        n = stat["score_sum"].shape[0]
        return (h, tuple(x.reshape(n, b, t, self.top_k) for x in chosen),
                {k: (jnp.max if k.endswith("_max") else jnp.sum)(v, axis=1)
                 for k, v in stat.items()})  # over the blocks

    def trunk(self, p: dict, tokens: jax.Array, done: jax.Array):
        """`tokens, done [B, T]` -> (h_L `[1, B, T, D]` before the final
        norm: one pass, the leading axis `LoopLMAgent` reads as R; the
        expert layers' facts, every leaf with a leading layer axis:
        `routes`, `route_scores [layers, B, T, top_k]` (the experts every
        position chose and their unbiased scores), `router_load [layers,
        E]`, `expert_pairs [layers, held]`, `dropped_pairs`, `pair_slabs`,
        `pair_slabs_max`, `score_sum [layers]`: `counters` reduces them)."""
        facts = []
        with jax.named_scope(scopes.LAYERS):
            h = p["embed"][tokens].astype(self.dtype)
            for i, (kind, _) in enumerate(self.runs):
                h, chosen, stat = self._run(kind, scopes.MLA_LEARN, h, done,
                                            p[f"run{i}"])
                if chosen is not None:
                    facts.append({"routes": chosen[0], "route_scores": chosen[1],
                                  **stat})
        return h[None], merged(facts)

    def mtp(self, p: dict, h: jax.Array, tokens: jax.Array, done: jax.Array):
        """The multi-token-prediction module: `h [B, T, D]` (the trunk's
        last state before the final norm), `tokens, done [B, T]` -> (h''
        `[B, T, D]` before the module's last norm, the facts of its one
        expert layer as `trunk`'s). Position t is given the token shown
        at t + 1; where t + 1 is not in t's episode (and at the last
        step, which wraps) the input is another episode's and the CALLER
        leaves the position out of its loss: no position that counts
        attends to one."""
        m = p["mtp"]
        nxt = jnp.roll(tokens, -1, axis=1)
        with jax.named_scope(scopes.MTP):
            joined = jnp.concatenate(
                [self._norm(h, m["norm_h"]),
                 self._norm(p["embed"][nxt], m["norm_e"])], axis=-1)
            h2 = self._mm(joined, m["proj"]).astype(self.dtype)
            h2, chosen, stat = self._run("moe", scopes.MLA_MTP, h2, done, m["layer"])
        return h2, {"routes": chosen[0], "route_scores": chosen[1], **stat}

    def counters(self, facts: dict, tokens: int) -> dict:
        """The counters of one forward from `trunk`'s (and `mtp`'s,
        `merged`) facts over `tokens` positions."""
        pairs = facts["expert_pairs"].astype(F32)  # [layers, held]
        load = facts["router_load"].astype(F32)  # [layers, E]
        layers, b = facts["routes"].shape[:2]
        calls = layers * (b // math.gcd(b, self.row_block))  # of `held_experts`
        over_mean = lambda x: jnp.max(
            jnp.max(x, -1) / jnp.maximum(jnp.mean(x, -1), 1e-9))
        return {
            "held_pair_share": jnp.sum(pairs) / (layers * tokens * self.top_k),
            "expert_load_max_over_mean": over_mean(pairs),
            "router_load_max_over_mean": over_mean(load),
            "experts_untouched": jnp.sum(pairs == 0).astype(F32),
            "dropped_pairs": jnp.sum(facts["dropped_pairs"]).astype(F32),
            "pair_slabs_mean": jnp.sum(facts["pair_slabs"]) / calls,
            "pair_slabs_max": jnp.max(facts["pair_slabs_max"]).astype(F32),
            "router_score_mean": jnp.sum(facts["score_sum"])
            / (layers * tokens * self.num_experts)}

    def pair_slab_rows(self, b: int, t: int) -> int:
        """Rows of a slab of the learner's sorted pairs (`expert_share.
        slab_rows`) where a layer is applied to `[B, T]` a row block at a time."""
        return expert_share.slab_rows(math.gcd(b, self.row_block) * t * self.top_k,
                                      self.experts_held, self.num_experts)

    def token_stats(self, p: dict, h: jax.Array, actions: jax.Array) -> dict:
        """`HybridLM.token_stats` (float32 `logp` of the taken action,
        `entropy`, `gate`, `value`) and `greedy`, the head's argmax."""
        logits, _, _ = self.logits(p, h)
        return {**HybridLM.token_stats(self, p, h, actions),
                "greedy": jnp.argmax(logits, axis=-1).astype(jnp.int32)}

    def mtp_stats(self, p: dict, h2: jax.Array, targets: jax.Array) -> dict:
        """The module's head on a block of positions: `h2 [..., D]`,
        `targets [...]` -> float32 `logp` of the target under
        softmax(N(h''; g_m) W_head^T) and `greedy`, its argmax."""
        z = self._norm(h2, p["mtp"]["norm_out"])
        logits = jnp.einsum("...d,vd->...v", z.astype(self.dtype),
                            p["head"].astype(self.dtype), preferred_element_type=F32)
        taken = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return {"logp": taken - jax.nn.logsumexp(logits, axis=-1),
                "greedy": jnp.argmax(logits, axis=-1).astype(jnp.int32)}

    def logits(self, p: dict, h: jax.Array):
        """(logits, gate, value), float32; the vocabulary head is untied
        from the embedding (`head`: acting's copy is in the compute dtype)."""
        z = self._norm(h, p["final_norm"])
        logits = jnp.einsum("...d,vd->...v", z.astype(self.dtype),
                            p["head"].astype(self.dtype), preferred_element_type=F32)
        value = z @ p["w_value"].astype(F32) + p["b_value"]
        return logits, jnp.ones_like(value), value

    @property
    def bias_holders(self) -> tuple:
        """The key path of every dict of the parameters that holds a
        selection bias, in the order of `router_load`'s rows: the trunk's
        expert runs, then the prediction module's layer
        (`expert_share.rebias`)."""
        return (*((f"run{i}",) for i, (kind, _) in enumerate(self.runs)
                  if kind == "moe"), ("mtp", "layer"))

    def rebias(self, before: dict, after: dict, load: jax.Array, gamma: float):
        """`after` with every router's selection bias set to `before`'s
        moved by `gamma sign(mean_j(n_j) - n_i)` (`expert_share.rebias`),
        `load [expert layers + 1, E]`: the trunk's layers in order, then
        the prediction module's."""
        return {"params": expert_share.rebias(
            before["params"], after["params"], load, gamma, self.bias_holders)}

    # -- acting as decode --------------------------------------------------
    def init_state(self, num_rows: int, length: int) -> LatentState:
        """Zeros: every episode starts from no past."""
        cache = tuple(jnp.zeros((num_rows, length, self.cache_width), self.dtype)
                      for _ in self.layer_types)
        return LatentState(cache, jnp.zeros(
            (num_rows, length, self.expert_layers, self.top_k), jnp.int16))

    def _decode_mla(self, y, lp, cache, t, span):
        """The mixer of a decode step: `[c | k_r]` written at t, the first
        `span` positions of the row read, absorbed -> (mix, cache)."""
        n, length = y.shape[0], cache.shape[1]
        span = length if span is None else span
        if not 0 < span <= length:
            raise ValueError(f"span {span} of a cache of {length} positions")
        with jax.named_scope(scopes.ACT_MLA_PROJECT):
            q_n, q_r, c, k_r = self._latents(y, lp)
        with jax.named_scope(scopes.ACT_CACHE):
            cache = jax.lax.dynamic_update_slice(
                cache, latent_attention.cache_entry(c, k_r, t, self.rope_theta,
                                                    self.dtype), (0, t, 0))
        with jax.named_scope(scopes.ACT_MLA_ATTEND):
            att = latent_attention.absorbed_step(
                q_n, q_r, cache, lp["wkvb"], t, span, self.rope_theta, self.dtype)
        with jax.named_scope(scopes.ACT_MLA_PROJECT):
            return self._mm(att.reshape(n, -1), lp["wo"]), cache

    def decode(self, p: dict, tokens: jax.Array, t: jax.Array,
               state: LatentState, span: int | None = None):
        """One decode step at batch N: `tokens [N]` shown at step `t` of
        the episode (the same for every row). Every layer writes position
        t of its latent cache and reads the static prefix `span` (`t <
        span` is the CALLER's to hold, as in `looped_lm.LoopedLM.decode`);
        every layer's MLP runs on the N rows; the prediction module is
        left out. `p`: `for_acting`'s parameters, or the learner's.
        -> (h_L `[N, D]`, state)."""
        cache = list(state.cache)
        layers = p["layers"] if "layers" in p else per_layer(p)
        routes = []
        with jax.named_scope(scopes.ACT_LAYERS):
            h = p["embed"][tokens].astype(self.dtype)
            for i, (kind, lp) in enumerate(zip(self.layer_types, layers)):
                y = self._norm(h, lp["norms"][0])
                mix, cache[i] = self._decode_mla(y, lp, cache[i], t, span)
                with jax.named_scope(scopes.ACT_MOE if kind == "moe"
                                     else scopes.ACT_LAYERS):
                    h, chosen, _ = self._ffn(kind, self._residual(h, mix), lp,
                                             scopes.MLA_ACT)
                if chosen is not None:
                    routes.append(chosen[0])
        with jax.named_scope(scopes.ACT_MOE_ROUTE):
            record = jax.lax.dynamic_update_slice(
                state.routes, jnp.stack(routes, axis=1)[:, None], (0, t, 0, 0))
        return h, LatentState(tuple(cache), record)


def merged(facts: list) -> dict:
    """Layers' facts (`trunk`'s, `mtp`'s) joined along their layer axis."""
    return {k: jnp.concatenate([f[k] for f in facts]) for k in facts[0]}


def for_acting(params, dtype):
    """The parameters as the decode steps of one update read them
    (`hybrid_lm.for_acting`'s rule): every layer's matrices cast to the
    compute dtype ONCE, each layer a dict of its own, and the vocabulary
    head in that dtype; the router, its bias (which acting takes with the
    weights) and the embedding stay float32. The prediction module does
    not act and is left out."""
    p = {k: v for k, v in params["params"].items()
         if not k.startswith("run") and k != "mtp"}
    p["layers"] = per_layer(params["params"], dtype, RUN_MATRICES)
    p["head"] = p["head"].astype(dtype)
    return {"params": p}

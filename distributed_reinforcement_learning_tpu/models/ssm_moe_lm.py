"""State-space / sparse-expert / attention language model whose layers
are ONE sublayer each (NVIDIA Nemotron-3-Nano-30B-A3B, `nemotron_h`; the
family is Nemotron-H, arXiv:2504.03624): a stack of THREE kinds of layer
in a published order (`hybrid_override_pattern`, one character a layer:
`M` a Mamba-2 mixer, `E` a sparse expert layer, `*` an attention layer),
each under ONE norm and with its own residual; no MLP follows a mixer and
no mixer precedes an expert layer. An untied vocabulary head and (RL's
addition) a value head. D wide, eps 1e-5, no bias but the convolution's:

    h_0 = E[x];   h_{l+1} = h_l + Mix_k(N(h_l; g_l)),  k the l-th character;
    z = N(h_L; g_f);   logits = z W_head;   v = z . w_v + b_v
    N(x; g) = g x rsqrt(mean(x^2) + eps)

    M, Mamba-2 (H heads of P, d_inner = H P, G groups, state N, C = d_inner + 2 G N):
        [z | xBC | dt] = W_in y  (D -> d_inner + C + H)
        xBC = silu(conv_K(xBC) + b_c)   depthwise, causal, cut at an episode's start
        [x | B | C] = xBC, x [H, P], B and C [G, N];   dt = softplus(dt + dt_bias)  (no clamp)
        A = -exp(A_log) a head;   g(h) = h // (H / G)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t[g(h)];   y_t = S_t C_t[g(h)] + D_h x_t
        Mix = W_out GroupRMSNorm(y * silu(z); g_n)   the mean square over each group's
              d_inner / G channels, the gate BEFORE the norm
    *, attention: `num_heads` query and `num_kv_heads` key/value heads of d, query
        head i reads key/value head i // (heads / KV), softmax(q k^T / sqrt(d)),
        causal AND same-episode, NO position term (NoPE)
    E, sparse experts on y (`ops/expert_share.py`, `scoring="sigmoid"`):
        s = sigmoid(W_r y) over all `num_experts`, float32;  I = the top_k of s + b
        (b: the selection bias, no gradient reaches it);
        w_i = c s_i / (sum_{j in I} s_j + 1e-20);   E_i(y) = W_d,i relu(W_u,i y)^2  (UNGATED)
        Mix = sum_{i in I, held here} w_i E_i(y) + E_s(y)   E_s: one shared expert of the
        same form at its own width, under no gate

The Mamba-2 mixer is `models/hybrid_lm.py`'s with `mamba_groups` = G (its
splits, step size, rate, gated norm and decode update are borrowed, not
copied), the attention `models/window_moe_lm.py`'s global layer, the
routing counters `models/latent_moe_lm.py`'s, the bias's move
`models/conv_moe_lm.py`'s. This file's own: the layer of one sublayer,
the ungated experts with their shared expert, the initialisation, and
the act-time state.

A layer's kind is its one sublayer's; the runs of equal layers
(`hybrid_lm.layer_runs`) are keyed by it (`mamba` | `moe` | `attention`).
The published order never repeats a kind, so every run is one layer.

`decode`: one token a row through THREE kinds of state side by side
(`SSMoEState`) and a record: per `M` layer the recurrent state `[N, H, P,
S]` float32, READ AND WRITTEN WHOLE every step, and its convolution
window `[N, K - 1, C]`; per `*` layer a key/value cache `[N, T, KV, d]` x
2 written at t and read as far as `span`; and `routes [N, T, E layers,
top_k]` int16, the experts every step chose.

Precision (`dtype`, bfloat16 as the configuration states it): matmul
operands, the cache and the residual stream in `dtype` with float32
accumulation; router logits (a `highest` product of float32 operands),
sigmoid, selection and weights, norm statistics, the convolution,
softplus, decays, cumulative sums, recurrent states and windows, the
attention softmax and everything after the logits in float32; parameters
float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.models.conv_moe_lm import ConvMoELM
from distributed_reinforcement_learning_tpu.models.hybrid_lm import (
    HybridLM, causal_conv, layer_runs, per_layer)
from distributed_reinforcement_learning_tpu.models.latent_moe_lm import (
    LatentMoELM, merged)
from distributed_reinforcement_learning_tpu.models.looped_lm import (
    episode_positions, rms_norm)
from distributed_reinforcement_learning_tpu.models.transformer_net import (
    episode_segments)
from distributed_reinforcement_learning_tpu.models.window_moe_lm import WindowMoELM
from distributed_reinforcement_learning_tpu.observability import scopes
from distributed_reinforcement_learning_tpu.ops import expert_share, ssd

F32 = jnp.float32
PATTERN = {"M": "mamba", "E": "moe", "*": "attention"}  # `hybrid_override_pattern`
LAYER_KINDS = tuple(PATTERN.values())
# What acting casts to the compute dtype once an update (`for_acting`);
# the taps, the router and its bias stay float32.
RUN_MATRICES = ("in_proj", "out_proj", "wq", "wkv", "wo", "expert_wu", "expert_wd",
                "shared_wu", "shared_wd")


def layer_kinds(pattern: str) -> tuple:
    """`"MEM*"` -> `("mamba", "moe", "mamba", "attention")`; a character
    this file does not compute (`-`: the family's dense MLP) is refused."""
    unknown = sorted(set(pattern) - set(PATTERN))
    if unknown:
        raise ValueError(f"hybrid_override_pattern {pattern!r}: {unknown} are not "
                         f"computed, only {sorted(PATTERN)}")
    return tuple(PATTERN[c] for c in pattern)


class SSMoEState(NamedTuple):
    """The act-time state, one entry PER LAYER in the published order
    (None where the layer's kind has no such state), every layer's a leaf
    of its own (`hybrid_lm.HybridState`'s rule), and the record of the
    experts chosen."""

    ssm: tuple  # [N, H, P, S] float32 a state-space layer
    conv: tuple  # [N, K - 1, C] float32 a state-space layer
    k: tuple  # [N, T, KV, d] an attention layer
    v: tuple
    routes: jax.Array  # [N, T, expert layers, top_k] int16: a record


@dataclasses.dataclass(frozen=True)
class SSMoELM:
    vocab: int
    d_model: int
    layer_types: tuple  # every layer's kind, in the published order
    num_heads: int
    num_kv_heads: int
    head_dim: int
    mamba_heads: int
    mamba_head_dim: int
    mamba_groups: int
    mamba_state: int
    num_experts: int  # the router's width: every expert of a layer
    experts_held: int  # those this chip holds, from `first_expert` on
    first_expert: int
    top_k: int
    expert_width: int
    shared_width: int
    route_scale: float = 1.0
    mamba_conv: int = 4
    mamba_chunk: int = 128
    dt_range: tuple = (1e-3, 0.1, 1e-4)  # dt's initial min, max and floor
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    init_std: float = 0.02
    attention_backend: str = "auto"  # `ops.attention.causal_attention`'s
    row_block: int = 4  # rows a layer is applied to at a time (no section key)
    state_dtype: Any = F32  # the recurrent state at act time and across chunks

    @property
    def runs(self) -> tuple:
        return layer_runs(self.layer_types, LAYER_KINDS)

    @property
    def expert_layers(self) -> int:
        return self.layer_types.count("moe")

    @property
    def bias_holders(self) -> tuple:
        """The key path of every run that holds a selection bias, in the
        order of `router_load`'s rows (`expert_share.rebias`)."""
        return tuple((f"run{i}",) for i, (kind, _) in enumerate(self.runs)
                     if kind == "moe")

    # The Mamba-2 mixer's pieces with `mamba_groups` groups, and the
    # NoPE grouped-query attention (the `global` layer): those models',
    # which read nothing of `self` that this one lacks.
    d_inner = HybridLM.d_inner
    conv_channels = HybridLM.conv_channels
    _mm = HybridLM._mm
    _split_in = HybridLM._split_in
    _split_conv = HybridLM._split_conv
    _step_size = HybridLM._step_size
    _rate = HybridLM._rate
    _gated_out = HybridLM._gated_out
    _per_head = HybridLM._per_head
    _decode_ssm = HybridLM._decode_ssm
    _norm = WindowMoELM._norm
    _residual = WindowMoELM._residual
    _qkv = WindowMoELM._qkv
    _attend = WindowMoELM._attention
    _decode_attend = WindowMoELM._decode_attention

    # -- parameters ---------------------------------------------------------
    def init(self, rng: jax.Array, *_) -> dict:
        """Normal(`init_std`) matrices, embedding and head; ones for the
        norm scales and D; zeros for the convolution's and the value's
        bias and for the router's selection bias; Mamba-2's own defaults
        for the rest: the taps uniform(+-1 / sqrt(K)), A = uniform(1, 16),
        dt_bias the inverse softplus of a log-uniform draw over
        `dt_range`'s (min, max), not under its floor. The Mamba-2
        out-projection is divided by sqrt(layers)
        (`rescale_prenorm_residual`: the family's code rescales the
        parameters NAMED `out_proj.weight`, which are these alone)."""
        keys = iter(jax.random.split(rng, 16 * (len(self.runs) + 1)))
        normal = lambda *shape: self.init_std * jax.random.normal(
            next(keys), shape, F32)
        uniform = lambda lo, hi, *shape: jax.random.uniform(
            next(keys), shape, F32, lo, hi)
        d, h, a = self.d_model, self.mamba_heads, self.num_heads * self.head_dim
        p = {"embed": normal(self.vocab, d), "head": normal(self.vocab, d),
             "final_norm": jnp.ones((d,), F32),
             "w_value": normal(d), "b_value": jnp.zeros((), F32)}
        for i, (kind, n) in enumerate(self.runs):
            run = {"norms": jnp.ones((n, 1, d), F32)}
            if kind == "mamba":
                lo, hi, floor = self.dt_range
                dt = jnp.maximum(jnp.exp(uniform(math.log(lo), math.log(hi), n, h)),
                                 floor)
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
                    out_proj=normal(n, self.d_inner, d) / len(self.layer_types) ** 0.5)
            elif kind == "attention":
                run.update(wq=normal(n, d, a), wo=normal(n, a, d),
                           wkv=normal(n, d, 2 * self.num_kv_heads * self.head_dim))
            else:
                e, f, fs = self.experts_held, self.expert_width, self.shared_width
                run.update(router=normal(n, d, self.num_experts),
                           router_bias=jnp.zeros((n, self.num_experts), F32),
                           expert_wu=normal(n, e, d, f), expert_wd=normal(n, e, f, d),
                           shared_wu=normal(n, d, fs), shared_wd=normal(n, fs, d))
            p[f"run{i}"] = run
        return {"params": p}

    def apply(self, params, *args, method):
        return method(params["params"], *args)

    # -- the expert layer (learner and decode step alike) --------------------
    def _experts(self, y: jax.Array, lp: dict, scope: dict):
        """The expert layer on normed rows `y [N, D]` -> (the held experts'
        weighted part + the shared expert, the experts chosen `[N, top_k]`
        int16 and their unbiased scores, counters)."""
        with jax.named_scope(scope["route"]):
            scores, chosen, weight, load = expert_share.route(
                y, lp["router"], self.top_k, "sigmoid", lp["router_bias"],
                self.route_scale)
        with jax.named_scope(scope["experts"]):
            routed, counters = expert_share.held_experts(
                y, chosen, weight, lp["expert_wu"], lp["expert_wd"],
                self.first_expert, self.num_experts, self.dtype, "relu2")
        with jax.named_scope(scope["shared"]):
            shared = self._mm(jnp.square(jax.nn.relu(self._mm(y, lp["shared_wu"]))),
                              lp["shared_wd"])
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        stats = jax.lax.stop_gradient({
            **{k: counters[k] for k in ("expert_pairs", "dropped_pairs",
                                        "pair_slabs", "gate_zeroed")},
            "pair_slabs_max": counters["pair_slabs"], "router_load": load,
            "score_sum": jnp.sum(scores)})
        return (routed + shared,
                (chosen.astype(jnp.int16), jax.lax.stop_gradient(picked)), stats)

    # -- the learner's forward --------------------------------------------
    def _mamba(self, y, lp, seg, pos):
        z, xbc, dt = self._split_in(self._mm(y, lp["in_proj"]))
        with jax.named_scope(scopes.CONV):
            xbc = jax.nn.silu(causal_conv(xbc, lp["conv_w"], lp["conv_b"], pos))
        x, bmat, cmat = self._split_conv(xbc)
        dt, rate = self._step_size(dt, lp), self._rate(lp)
        with jax.named_scope(scopes.SSD):
            ssm, _ = ssd.ssd_chunked(x, dt, rate, bmat, cmat, seg,
                                     self.mamba_chunk, self.dtype,
                                     self.state_dtype)
        return self._gated_out(ssm, x, z, lp), jax.lax.stop_gradient(
            {"dt_sum": jnp.sum(dt)})

    def _layer(self, kind, h, seg, pos, lp):
        """One layer (ONE sublayer under one norm) on a block of rows ->
        (h', the experts chosen `[rows x T, top_k]` int16 and their scores
        (None: no expert layer), the layer's counters)."""
        y = self._norm(h, lp["norms"][0])
        chosen = None
        if kind == "mamba":
            mix, stats = self._mamba(y, lp, seg, pos)
        elif kind == "attention":
            mix, stats = self._attend("global", y, lp, seg, pos), {}
        else:
            mix, chosen, stats = self._experts(y.reshape(-1, y.shape[-1]), lp,
                                               scopes.MOE_LEARN)
            mix = mix.reshape(h.shape)
        return self._residual(h, mix), chosen, stats

    def trunk(self, p: dict, tokens: jax.Array, done: jax.Array):
        """`tokens, done [B, T]` -> (h_L `[1, B, T, D]` before the final
        norm: one pass, the leading axis `LoopLMAgent` reads as R; the
        layers' facts: of the expert layers, every leaf with a leading
        layer axis, `routes`, `route_scores [layers, B, T, top_k]`,
        `router_load [layers, E]`, `expert_pairs [layers, held]`,
        `dropped_pairs`, `pair_slabs`, `pair_slabs_max`, `gate_zeroed`,
        `score_sum [layers]`; of the state-space layers `dt_sum
        [layers]`: `counters` reduces them)."""
        b, t = tokens.shape
        rows = math.gcd(b, self.row_block)
        blocks = lambda x: x.reshape(b // rows, rows, *x.shape[1:])
        seg, pos = blocks(episode_segments(done)), blocks(episode_positions(done))
        facts, steps = [], []
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
                if kind == "mamba":
                    steps.append(stat["dt_sum"])
                elif kind == "moe":
                    facts.append({
                        "routes": chosen[0].reshape(n, b, t, self.top_k),
                        "route_scores": chosen[1].reshape(n, b, t, self.top_k),
                        **stat})
        return h[None], {**merged(facts), "dt_sum": jnp.concatenate(
            steps or [jnp.zeros((0,), F32)])}

    def counters(self, facts: dict, tokens: int) -> dict:
        """The counters of one forward from `trunk`'s facts over `tokens`
        positions: the expert share's (`LatentMoELM.counters`: of the held
        experts, and `router_load_max_over_mean` over all the router's),
        `router_experts_untouched` (of ALL the router's experts, those no
        token chose in some layer), `relu2_zero_share` (the share of the
        held pairs' up-projections, `expert_width` a pair, that ReLU
        zeroed) and `dt_mean` (the state-space layers' mean step size)."""
        pairs = jnp.sum(facts["expert_pairs"]).astype(F32)
        steps = facts["dt_sum"]
        return {**LatentMoELM.counters(self, facts, tokens),
                "router_experts_untouched": jnp.sum(
                    facts["router_load"] == 0).astype(F32),
                "relu2_zero_share": jnp.sum(facts["gate_zeroed"].astype(F32))
                / jnp.maximum(pairs * self.expert_width, 1.0),
                "dt_mean": jnp.sum(steps)
                / (max(1, steps.shape[0]) * tokens * self.mamba_heads)}

    # Rows of a slab of the learner's sorted pairs, the heads on a block of
    # positions, the untied head and the bias's move: the latent, hybrid,
    # window and convolution models', which read nothing of `self` that
    # this model lacks.
    pair_slab_rows = LatentMoELM.pair_slab_rows
    token_stats = HybridLM.token_stats
    logits = WindowMoELM.logits
    rebias = ConvMoELM.rebias

    # -- acting as decode --------------------------------------------------
    def init_state(self, num_rows: int, length: int) -> SSMoEState:
        """Zeros: every episode starts from no past."""
        ssm, conv, k, v = [], [], [], []
        for kind in self.layer_types:
            mamba, attention = kind == "mamba", kind == "attention"
            ssm.append(jnp.zeros((num_rows, self.mamba_heads, self.mamba_head_dim,
                                  self.mamba_state), self.state_dtype)
                       if mamba else None)
            conv.append(jnp.zeros((num_rows, self.mamba_conv - 1,
                                   self.conv_channels), F32) if mamba else None)
            cache = (jnp.zeros((num_rows, length, self.num_kv_heads, self.head_dim),
                               self.dtype) if attention else None)
            k.append(cache)
            v.append(cache)
        routes = jnp.zeros((num_rows, length, self.expert_layers, self.top_k),
                           jnp.int16)
        return SSMoEState(tuple(ssm), tuple(conv), tuple(k), tuple(v), routes)

    def decode(self, p: dict, tokens: jax.Array, t: jax.Array,
               state: SSMoEState, span: int | None = None):
        """One decode step at batch N: `tokens [N]` shown at step `t` of
        the episode (the same for every row). Every state-space layer
        shifts its window and updates its state, whatever t; an attention
        layer writes position t of its cache and reads the static prefix
        `span` (`t < span` is the CALLER's to hold, as in
        `looped_lm.LoopedLM.decode`); an expert layer routes the N rows
        and records the sets. `p`: `for_acting`'s parameters, or the
        learner's. -> (h_L `[N, D]`, state)."""
        ssm, conv, keys, values = (list(x) for x in state[:4])
        layers = p["layers"] if "layers" in p else per_layer(p)
        routes = []
        with jax.named_scope(scopes.ACT_LAYERS):
            h = p["embed"][tokens].astype(self.dtype)
            for i, (kind, lp) in enumerate(zip(self.layer_types, layers)):
                y = self._norm(h, lp["norms"][0])
                if kind == "mamba":
                    mix, ssm[i], taps = self._decode_ssm(y, lp, ssm[i], conv[i])
                    conv[i] = taps[:, 1:]
                elif kind == "attention":
                    with jax.named_scope(scopes.ACT_ATTEND):
                        mix, keys[i], values[i] = self._decode_attend(
                            "global", y, lp, keys[i], values[i], t, span)
                else:
                    mix, chosen, _ = self._experts(y, lp, scopes.MOE_ACT)
                    routes.append(chosen[0])
                h = self._residual(h, mix)
        with jax.named_scope(scopes.ACT_MOE_ROUTE):
            record = jax.lax.dynamic_update_slice(
                state.routes, jnp.stack(routes, axis=1)[:, None], (0, t, 0, 0))
        return h, SSMoEState(tuple(ssm), tuple(conv), tuple(keys), tuple(values),
                             record)


def for_acting(params, dtype):
    """The parameters as the decode steps of one update read them
    (`hybrid_lm.for_acting`'s rule): every layer's matrices and the
    vocabulary HEAD cast to the compute dtype ONCE, each layer a dict of
    its own; the taps, the routers, their biases (which acting takes with
    the weights) and the embedding the lookup reads stay float32."""
    p = {k: v for k, v in params["params"].items() if not k.startswith("run")}
    p["layers"] = per_layer(params["params"], dtype, RUN_MATRICES)
    p["head"] = p["head"].astype(dtype)
    return {"params": p}

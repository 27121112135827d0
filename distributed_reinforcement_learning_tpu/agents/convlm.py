"""Token-level IMPALA on a gated-short-convolution sparse-expert language
model (`models/conv_moe_lm.py`: LFM2-24B-A2B's three double-gated short
convolutions to one grouped-query attention, a dense leading layer, then
a sigmoid-scored bias-corrected router over all of a layer's experts and
this chip's share of them, with no shared expert). The actor-critic is
`agents/looplm.py`'s at one pass, as for `agents/moelm.py`: its V-trace
loss, its vocabulary head in blocks of positions, its optimizer. This
file's own:

- the router's selection bias (`_learn`): a parameter leaf that acting
  takes with the weights and NO gradient trains; after each optimizer
  step it moves by `bias_update_speed sign(mean load - load)` from the
  tokens each of ALL the router's experts was chosen by in the step's
  forward, whatever the optimizer did to it (`expert_share.rebias`, the
  rule `agents/mlalm.py` shares);
- the act-time state (`conv_moe_lm.ConvState`): a window of the last two
  gated inputs a convolution layer (no matrix, nothing that grows), ONE
  key/value cache for a period's one attention layer, and the record of
  the experts every decode step chose.
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.agents.hybridlm import check_layer_types
from distributed_reinforcement_learning_tpu.agents.looplm import (
    LoopLMAgent, LoopLMBatch, TokenLMConfig, fixed)
from distributed_reinforcement_learning_tpu.models import conv_moe_lm
from distributed_reinforcement_learning_tpu.observability import scopes

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class ConvLMConfig(TokenLMConfig):
    """The model's published keys under their published names (section
    `lfm2_moe` of `config.json`). `num_experts` is what this chip HOLDS
    of a layer's `router_width` experts, from `first_expert` on;
    `rope_theta` is the published `rope_parameters.rope_theta`, which a
    section may carry whole beside it (`check_section` holds them equal)."""

    vocab_size: int = 16_384
    hidden_size: int = 2048
    layer_types: tuple = ("conv", "full_attention", "conv", "conv", "conv")
    num_dense_layers: int = 1
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rope_theta: float = 1e6
    conv_L_cache: int = 3
    intermediate_size: int = 11_776
    num_experts: int = 16
    router_width: int = 64
    first_expert: int = 0
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5  # the source's name for `rms_norm_eps`, which is not read
    bias_update_speed: float = 1e-3  # gamma (no key of the source's config.json)
    row_block: int = fixed(4)  # rows a layer is applied to at a time

    MUST: ClassVar[tuple] = (
        "vocab_size", "hidden_size", "layer_types", "num_hidden_layers",
        "num_dense_layers", "num_attention_heads", "num_key_value_heads",
        "rope_theta", "conv_L_cache", "intermediate_size", "num_experts",
        "router_width", "first_expert", "num_experts_per_tok",
        "moe_intermediate_size", "routed_scaling_factor", "norm_eps")
    ONLY: ClassVar[dict] = {
        "conv_bias": False, "use_expert_bias": True, "norm_topk_prob": True,
        "tie_word_embeddings": True}

    @classmethod
    def check_section(cls, d: dict) -> None:
        check_layer_types(d, conv_moe_lm.MIXERS)
        rope = d.get("rope_parameters", {"rope_theta": d["rope_theta"],
                                         "rope_type": "default"})
        if rope != {"rope_theta": d["rope_theta"], "rope_type": "default"}:
            raise ValueError(f"rope_parameters {rope}: only rope_type 'default' at "
                             f"the section's rope_theta {d['rope_theta']} is computed")
        if "rms_norm_eps" in d:
            raise ValueError("rms_norm_eps: this model's key is norm_eps")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


class ConvLMAgent(LoopLMAgent):
    def __init__(self, cfg: ConvLMConfig):
        if cfg.total_ut_steps != 1:
            raise ValueError("the stack is run once: total_ut_steps is 1")
        if not 0 <= cfg.first_expert <= cfg.router_width - cfg.num_experts:
            raise ValueError(
                f"experts {cfg.first_expert}..{cfg.first_expert + cfg.num_experts - 1}"
                f" of a router {cfg.router_width} wide")
        if not 0 <= cfg.num_dense_layers < len(cfg.layer_types):
            raise ValueError(f"{cfg.num_dense_layers} dense layers of "
                             f"{len(cfg.layer_types)}: no expert layer is left")
        self.cfg = cfg
        self.model = conv_moe_lm.ConvMoELM(
            vocab=cfg.vocab_size, d_model=cfg.hidden_size,
            layer_types=tuple(cfg.layer_types),
            num_dense_layers=cfg.num_dense_layers,
            num_heads=cfg.num_attention_heads,
            num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta, d_ff=cfg.intermediate_size,
            num_experts=cfg.router_width, experts_held=cfg.num_experts,
            first_expert=cfg.first_expert, top_k=cfg.num_experts_per_tok,
            expert_width=cfg.moe_intermediate_size,
            route_scale=cfg.routed_scaling_factor, conv_width=cfg.conv_L_cache,
            rms_eps=cfg.norm_eps, dtype=cfg.dtype, init_std=cfg.init_std,
            attention_backend=cfg.attention_backend, row_block=cfg.row_block)
        self._schedule = common.polynomial_lr(
            cfg.start_learning_rate, cfg.end_learning_rate, cfg.learning_frame)
        self.tx = common.rmsprop_with_clip(self._schedule, cfg.gradient_clip_norm)
        self.learn = jax.jit(self._learn, donate_argnums=(0,))

    def init_cache(self, num_rows: int) -> conv_moe_lm.ConvState:
        return self.model.init_state(num_rows, self.cfg.trajectory)

    def for_acting(self, params):
        return conv_moe_lm.for_acting(params, self.cfg.dtype)

    # -- learn -----------------------------------------------------------
    def _stats(self, params, batch: LoopLMBatch) -> dict:
        """`LoopLMAgent._stats` at one pass, its counters the expert
        share's and the convolution's (`ConvMoELM.counters`) with the sets
        the learner chose, their scores and the counts that move the bias."""
        stats = super()._stats(params, batch)
        facts = stats["counters"]
        return {**stats, "counters": {
            **self.model.counters(facts, batch.tokens.size),
            **{k: facts[k] for k in ("routes", "route_scores", "router_load")}}}

    def _learn(self, state: common.TrainState, batch: LoopLMBatch):
        new, metrics = super()._learn(state, batch)
        with jax.named_scope(scopes.OPTIMIZER):
            params = self.model.rebias(state.params, new.params,
                                       metrics["router_load"],
                                       self.cfg.bias_update_speed)
        metrics["bias_abs_max"] = jnp.max(jnp.abs(jnp.concatenate(
            [jnp.ravel(x) for x in self.router_biases(params)])))
        return new.replace(params=params), metrics

    def router_biases(self, params) -> list:
        """Every router's selection bias `[n, E]`, the expert runs in order."""
        return [params["params"][run]["router_bias"]
                for (run,) in self.model.bias_holders]

    # -- the act-time state ------------------------------------------------
    def state_facts(self, num_rows: int) -> dict:
        """Bytes of the act-time state of `num_rows` rows, by kind, the
        order of the layers that hold it (`mixer+mlp` a layer), this
        chip's share of the experts, the rows of a slab of the learner's
        sorted pairs, and `act_weight_bytes`: the bytes, in the compute
        dtype, of every matrix a decode step reads whole (every layer's
        `conv_moe_lm.RUN_MATRICES`, the router in float32, the head)."""
        cfg = self.cfg
        state = jax.eval_shape(lambda: self.init_cache(num_rows))
        params = jax.eval_shape(lambda: self.for_acting(
            self.model.init(jax.random.PRNGKey(0))))["params"]
        size = lambda part: sum(x.size * x.dtype.itemsize
                                for x in jax.tree.leaves(part))
        read = [params["embed_head"]] + [
            lp[k] for lp in params["layers"] for k in (*conv_moe_lm.RUN_MATRICES,
                                                       "router") if k in lp]
        return {"conv_state_bytes": size(state.window),
                "kv_cache_bytes": size((state.k, state.v)),
                "layer_order": tuple("+".join(kind) for kind in self.model.kinds),
                "experts_held": cfg.num_experts, "router_width": cfg.router_width,
                "first_expert": cfg.first_expert,
                "pair_slab_rows": self.model.pair_slab_rows(num_rows, cfg.trajectory),
                "act_weight_bytes": size(read)}

    def state_counters(self, cache: conv_moe_lm.ConvState) -> dict:
        """`conv_state_abs_max`: the largest gated input the windows held
        when the episode ended; `act_routes`: the experts every decode
        step chose, which a reader replaying the update holds against its
        own."""
        return {"conv_state_abs_max": jnp.max(jnp.stack(
                    [jnp.max(jnp.abs(w.astype(F32)))
                     for w in cache.window if w is not None])),
                "act_routes": cache.routes}

"""Token-level IMPALA on a state-space / sparse-expert / attention
language model whose layers are one sublayer each (`models/ssm_moe_lm.py`:
Nemotron-3-Nano-30B-A3B's Mamba-2 mixers with eight B/C groups, its
sigmoid-scored bias-corrected router over all of a layer's UNGATED relu^2
experts and this chip's share of them beside one shared expert, its NoPE
grouped-query attention, in the published order). The actor-critic is
`agents/looplm.py`'s at one pass; the router's selection bias is moved as
`agents/convlm.py` moves it (its `_stats`, `_learn` and `router_biases`
are inherited: a parameter leaf that acting takes with the weights and NO
gradient trains, moved by `bias_update_speed sign(mean load - load)`
after each optimizer step). This file's own:

- the configuration's published keys (`hybrid_override_pattern`: one
  character a layer);
- the act-time state (`ssm_moe_lm.SSMoEState`): a recurrent state and a
  convolution window per state-space layer, a key/value cache per
  attention layer, and the record of the experts every decode step
  chose: no other family carries a recurrent state AND a route record;
- the counters read from it when the episode ends.
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.agents.convlm import ConvLMAgent
from distributed_reinforcement_learning_tpu.agents.hybridlm import HybridLMAgent
from distributed_reinforcement_learning_tpu.agents.looplm import TokenLMConfig, fixed
from distributed_reinforcement_learning_tpu.models import ssm_moe_lm

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class SSMoELMConfig(TokenLMConfig):
    """The model's published keys under their published names (section
    `nemotron_h_moe` of `config.json`). `n_routed_experts` is what this
    chip HOLDS of a layer's `router_width` experts, from `first_expert`
    on; `hybrid_override_pattern` is the kind of every layer (`M`, `E`,
    `*`), as many characters as `num_hidden_layers`; d_inner is
    `mamba_num_heads x mamba_head_dim` (the family's code; `expand` is
    not read)."""

    vocab_size: int = 16_384
    hidden_size: int = 2688
    hybrid_override_pattern: str = "MEMEM*EME"
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 1e-3  # the range dt_bias is DRAWN from, no clamp
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    n_routed_experts: int = 8
    router_width: int = 128
    first_expert: int = 0
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    layer_norm_epsilon: float = 1e-5  # the source's name for `rms_norm_eps`, which is not read
    bias_update_speed: float = 1e-3  # gamma (no key of the source's config.json)
    trajectory: int = 2048
    row_block: int = fixed(4)  # rows a layer is applied to at a time

    MUST: ClassVar[tuple] = (
        "vocab_size", "hidden_size", "hybrid_override_pattern", "num_hidden_layers",
        "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
        "conv_kernel", "chunk_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "n_routed_experts", "router_width", "first_expert",
        "num_experts_per_tok", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "routed_scaling_factor",
        "layer_norm_epsilon")
    ONLY: ClassVar[dict] = {
        "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu", "use_conv_bias": True,
        "mamba_proj_bias": False, "attention_bias": False, "mlp_bias": False,
        "use_bias": False, "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
        "n_shared_experts": 1, "tie_word_embeddings": False,
        "residual_in_fp32": False, "sliding_window": None}

    @classmethod
    def check_section(cls, d: dict) -> None:
        pattern = d["hybrid_override_pattern"]
        ssm_moe_lm.layer_kinds(pattern)
        if len(pattern) != d["num_hidden_layers"]:
            raise ValueError(f"{len(pattern)} characters of hybrid_override_pattern "
                             f"for num_hidden_layers {d['num_hidden_layers']}")
        if d.get("norm_eps", d["layer_norm_epsilon"]) != d["layer_norm_epsilon"]:
            raise ValueError(f"norm_eps {d['norm_eps']} != layer_norm_epsilon "
                             f"{d['layer_norm_epsilon']}")
        if "rms_norm_eps" in d:
            raise ValueError("rms_norm_eps: this model's key is layer_norm_epsilon")

    @property
    def layer_types(self) -> tuple:
        return ssm_moe_lm.layer_kinds(self.hybrid_override_pattern)


class SSMoELMAgent(ConvLMAgent):
    def __init__(self, cfg: SSMoELMConfig):
        if cfg.total_ut_steps != 1:
            raise ValueError("the stack is run once: total_ut_steps is 1")
        if not 0 <= cfg.first_expert <= cfg.router_width - cfg.n_routed_experts:
            raise ValueError(
                f"experts {cfg.first_expert}.."
                f"{cfg.first_expert + cfg.n_routed_experts - 1}"
                f" of a router {cfg.router_width} wide")
        for heads, groups in ((cfg.num_attention_heads, cfg.num_key_value_heads),
                              (cfg.mamba_num_heads, cfg.n_groups)):
            if heads % groups:
                raise ValueError(f"{heads} heads over {groups} groups")
        self.cfg = cfg
        self.model = ssm_moe_lm.SSMoELM(
            vocab=cfg.vocab_size, d_model=cfg.hidden_size,
            layer_types=cfg.layer_types, num_heads=cfg.num_attention_heads,
            num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            mamba_heads=cfg.mamba_num_heads, mamba_head_dim=cfg.mamba_head_dim,
            mamba_groups=cfg.n_groups, mamba_state=cfg.ssm_state_size,
            num_experts=cfg.router_width, experts_held=cfg.n_routed_experts,
            first_expert=cfg.first_expert, top_k=cfg.num_experts_per_tok,
            expert_width=cfg.moe_intermediate_size,
            shared_width=cfg.moe_shared_expert_intermediate_size,
            route_scale=cfg.routed_scaling_factor, mamba_conv=cfg.conv_kernel,
            mamba_chunk=cfg.chunk_size,
            dt_range=(cfg.time_step_min, cfg.time_step_max, cfg.time_step_floor),
            rms_eps=cfg.layer_norm_epsilon, dtype=cfg.dtype, init_std=cfg.init_std,
            attention_backend=cfg.attention_backend, row_block=cfg.row_block)
        self._schedule = common.polynomial_lr(
            cfg.start_learning_rate, cfg.end_learning_rate, cfg.learning_frame)
        self.tx = common.rmsprop_with_clip(self._schedule, cfg.gradient_clip_norm)
        self.learn = jax.jit(self._learn, donate_argnums=(0,))

    def init_cache(self, num_rows: int) -> ssm_moe_lm.SSMoEState:
        return self.model.init_state(num_rows, self.cfg.trajectory)

    def for_acting(self, params):
        return ssm_moe_lm.for_acting(params, self.cfg.dtype)

    # -- the act-time state ------------------------------------------------
    def state_facts(self, num_rows: int) -> dict:
        """Bytes of the act-time state of `num_rows` rows, by kind, the
        order of the layers that hold it (the published string), this
        chip's share of the experts, the rows of a slab of the learner's
        sorted pairs, and `act_weight_bytes`: the bytes of every matrix a
        decode step could read whole (every layer's
        `ssm_moe_lm.RUN_MATRICES` and the head in the compute dtype, the
        routers in float32)."""
        cfg = self.cfg
        state = jax.eval_shape(lambda: self.init_cache(num_rows))
        params = jax.eval_shape(lambda: self.for_acting(
            self.model.init(jax.random.PRNGKey(0))))["params"]
        size = lambda part: sum(x.size * x.dtype.itemsize
                                for x in jax.tree.leaves(part))
        read = [params["head"]] + [
            lp[k] for lp in params["layers"]
            for k in (*ssm_moe_lm.RUN_MATRICES, "router") if k in lp]
        return {"ssm_state_bytes": size(state.ssm),
                "conv_state_bytes": size(state.conv),
                "kv_cache_bytes": size((state.k, state.v)),
                "route_record_bytes": size(state.routes),
                "layer_order": cfg.hybrid_override_pattern,
                "experts_held": cfg.n_routed_experts,
                "router_width": cfg.router_width, "first_expert": cfg.first_expert,
                "pair_slab_rows": self.model.pair_slab_rows(num_rows, cfg.trajectory),
                "act_weight_bytes": size(read)}

    def state_counters(self, cache: ssm_moe_lm.SSMoEState) -> dict:
        """`state_norm_mean` and `state_sample` of the recurrent states the
        episode ended with (`HybridLMAgent.state_counters`, which reads
        nothing of `cache` but its `ssm`: the mean norm of a head's state,
        and a strided sample that a reader replaying the update holds
        against its own); `held_experts_touched_mean`: the
        held experts that some row chose, a mean over the episode's decode
        steps and the expert layers (what the sorted one-slab form reads
        of a layer's held experts); `act_routes`: the experts every decode
        step chose."""
        cfg = self.cfg
        held = cfg.first_expert + jnp.arange(cfg.n_routed_experts, dtype=jnp.int16)
        touched = jnp.any(cache.routes[..., None] == held, axis=(0, 3))  # [T, L, held]
        return {**HybridLMAgent.state_counters(self, cache),
                "held_experts_touched_mean": jnp.mean(
                    jnp.sum(touched, axis=-1, dtype=F32)),
                "act_routes": cache.routes}

"""Token-level IMPALA on a sparse-expert hybrid language model
(`models/moe_lm.py`: Qwen3-Next's three gated-delta-rule layers to one
gated attention layer, a router over all of a layer's experts and this
chip's share of them in every layer). The actor-critic is
`agents/looplm.py`'s, unchanged, as for `agents/hybridlm.py`: its loss at
R = 1 with no gate IS the plain per-position V-trace loss, its vocabulary
head in blocks of positions, its optimizer. What differs is the model it
is given and the act-time state (`moe_lm.MoEState`): a matrix-valued
recurrent state and a convolution window per linear-attention layer, a
key/value cache for the attention layer, and the record of the experts
every decode step chose.
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.agents.hybridlm import check_layer_types
from distributed_reinforcement_learning_tpu.agents.looplm import (
    LoopLMAgent, TokenLMConfig, fixed)
from distributed_reinforcement_learning_tpu.models import moe_lm

F32 = jnp.float32
STATE_SAMPLE = 16384  # elements of the final recurrent state a chunk logs


@dataclasses.dataclass(frozen=True)
class MoELMConfig(TokenLMConfig):
    """The model's published keys under their published names (section
    `qwen3_next` of `config.json`). `num_experts` is what this chip HOLDS
    of a layer's `router_width` experts, from `first_expert` on."""

    vocab_size: int = 18_992
    hidden_size: int = 2048
    layer_types: tuple = ("linear_attention",) * 3 + ("full_attention",)
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 32
    router_width: int = 512
    first_expert: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    row_block: int = fixed(4)  # rows a layer is applied to at a time
    gdn_chunk: int = fixed(64)  # steps of the delta rule a chunk

    MUST: ClassVar[tuple] = (
        "vocab_size", "hidden_size", "layer_types", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
        "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
        "linear_conv_kernel_dim", "num_experts", "router_width", "first_expert",
        "num_experts_per_tok", "moe_intermediate_size",
        "shared_expert_intermediate_size")
    ONLY: ClassVar[dict] = {
        "decoder_sparse_step": 1, "mlp_only_layers": [], "norm_topk_prob": True,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "rope_scaling": None, "attention_bias": False, "hidden_act": "silu"}

    @classmethod
    def check_section(cls, d: dict) -> None:
        check_layer_types(d, moe_lm.LAYER_KINDS)


class MoELMAgent(LoopLMAgent):
    def __init__(self, cfg: MoELMConfig):
        if cfg.total_ut_steps != 1:
            raise ValueError("a hybrid stack is run once: total_ut_steps is 1")
        if not 0 <= cfg.first_expert <= cfg.router_width - cfg.num_experts:
            raise ValueError(
                f"experts {cfg.first_expert}..{cfg.first_expert + cfg.num_experts - 1}"
                f" of a router {cfg.router_width} wide")
        self.cfg = cfg
        self.model = moe_lm.MoELM(
            vocab=cfg.vocab_size, d_model=cfg.hidden_size,
            layer_types=tuple(cfg.layer_types),
            num_heads=cfg.num_attention_heads,
            num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            rotary_dim=int(cfg.head_dim * cfg.partial_rotary_factor),
            rope_theta=cfg.rope_theta, gdn_key_heads=cfg.linear_num_key_heads,
            gdn_value_heads=cfg.linear_num_value_heads,
            gdn_key_dim=cfg.linear_key_head_dim,
            gdn_value_dim=cfg.linear_value_head_dim,
            num_experts=cfg.router_width, experts_held=cfg.num_experts,
            first_expert=cfg.first_expert, top_k=cfg.num_experts_per_tok,
            expert_width=cfg.moe_intermediate_size,
            shared_width=cfg.shared_expert_intermediate_size,
            gdn_conv=cfg.linear_conv_kernel_dim, gdn_chunk=cfg.gdn_chunk,
            rms_eps=cfg.rms_norm_eps, dtype=cfg.dtype, init_std=cfg.init_std,
            attention_backend=cfg.attention_backend, row_block=cfg.row_block)
        self._schedule = common.polynomial_lr(
            cfg.start_learning_rate, cfg.end_learning_rate, cfg.learning_frame)
        self.tx = common.rmsprop_with_clip(self._schedule, cfg.gradient_clip_norm)
        self.learn = jax.jit(self._learn, donate_argnums=(0,))

    def init_cache(self, num_rows: int) -> moe_lm.MoEState:
        return self.model.init_state(num_rows, self.cfg.trajectory)

    def for_acting(self, params):
        return moe_lm.for_acting(params, self.cfg.dtype)

    def state_facts(self, num_rows: int) -> dict:
        """Bytes of the act-time state of `num_rows` rows, by kind, the
        order of the layers that hold it, this chip's share of the
        experts, and the rows of a slab of the learner's sorted pairs."""
        state = jax.eval_shape(lambda: self.init_cache(num_rows))
        size = lambda part: sum(x.size * x.dtype.itemsize
                                for x in jax.tree.leaves(part))
        cfg = self.cfg
        return {"kv_cache_bytes": size((state.k, state.v)),
                "gdn_state_bytes": size(state.gdn),
                "conv_state_bytes": size(state.conv),
                "layer_order": tuple(cfg.layer_types),
                "experts_held": cfg.num_experts, "router_width": cfg.router_width,
                "first_expert": cfg.first_expert,
                "pair_slab_rows": self.model.pair_slab_rows(num_rows, cfg.trajectory)}

    def state_counters(self, cache: moe_lm.MoEState) -> dict:
        """`state_norm_mean`: the mean over rows, layers and heads of the
        norm of a head's recurrent state `[K, V]` at the episode's end;
        `state_sample`: a strided sample of that state, and `act_routes`:
        the experts every decode step chose, both of which a reader
        replaying the update holds against its own."""
        gdn = [s for s in cache.gdn if s is not None]  # [N, H, K, V] a layer
        norms = [jnp.sqrt(jnp.sum(jnp.square(s.astype(F32)), axis=(-2, -1)))
                 for s in gdn]
        every = max(1, sum(s.size for s in gdn) // STATE_SAMPLE)
        return {"state_norm_mean": jnp.mean(jnp.concatenate(
                    [n.reshape(-1) for n in norms])),
                "state_sample": jnp.concatenate(
                    [s.reshape(-1)[::every].astype(F32) for s in gdn]),
                "act_routes": cache.routes}

"""Token-level IMPALA on a hybrid state-space / attention language
model (`models/hybrid_lm.py`: granite-4.0-h's nine Mamba-2 layers to one
grouped-query attention layer). The actor-critic is `agents/looplm.py`'s,
unchanged: its loss at R = 1 with no gate IS the plain per-position
V-trace loss (p(1) = 1, the exit entropy 0), its vocabulary head in
blocks of positions, its optimizer. What differs is the model it is
given and the act-time state, which is of three kinds side by side
(`hybrid_lm.HybridState`): a recurrent state and a convolution window
per state-space layer, a key/value cache for the attention layer.
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.agents.looplm import (
    LoopLMAgent, TokenLMConfig, fixed)
from distributed_reinforcement_learning_tpu.models import hybrid_lm

F32 = jnp.float32
STATE_SAMPLE = 16384  # elements of the final recurrent state a chunk logs


@dataclasses.dataclass(frozen=True)
class HybridLMConfig(TokenLMConfig):
    """The model's published keys under their published names (section
    `granite_hybrid` of `config.json`)."""

    vocab_size: int = 12_544
    hidden_size: int = 2048
    layer_types: tuple = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    shared_intermediate_size: int = 8192
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    row_block: int = fixed(4)  # rows a layer is applied to at a time

    MUST: ClassVar[tuple] = (
        "vocab_size", "hidden_size", "layer_types", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "shared_intermediate_size",
        "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv",
        "mamba_chunk_size", "embedding_multiplier", "residual_multiplier",
        "attention_multiplier", "logits_scaling")
    ONLY: ClassVar[dict] = {
        "mamba_n_groups": 1, "num_local_experts": 0,
        "position_embedding_type": "nope", "tie_word_embeddings": True,
        "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
        "attention_bias": False}

    @classmethod
    def check_section(cls, d: dict) -> None:
        check_layer_types(d, hybrid_lm.LAYER_KINDS)
        if d["mamba_n_heads"] * d["mamba_d_head"] != 2 * d["hidden_size"]:
            raise ValueError("mamba_n_heads x mamba_d_head is not twice hidden_size")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def check_layer_types(d: dict, kinds) -> None:
    """A section's `layer_types`: every one of `kinds` (an unknown layer
    type raises in `layer_runs`), as many as `num_hidden_layers`."""
    hybrid_lm.layer_runs(tuple(d["layer_types"]), kinds)
    if len(d["layer_types"]) != d["num_hidden_layers"]:
        raise ValueError(f"{len(d['layer_types'])} layer_types for "
                         f"num_hidden_layers {d['num_hidden_layers']}")


class HybridLMAgent(LoopLMAgent):
    def __init__(self, cfg: HybridLMConfig):
        if cfg.total_ut_steps != 1:
            raise ValueError("a hybrid stack is run once: total_ut_steps is 1")
        self.cfg = cfg
        self.model = hybrid_lm.HybridLM(
            vocab=cfg.vocab_size, d_model=cfg.hidden_size,
            layer_types=tuple(cfg.layer_types),
            num_heads=cfg.num_attention_heads,
            num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            d_ff=cfg.shared_intermediate_size, mamba_heads=cfg.mamba_n_heads,
            mamba_head_dim=cfg.mamba_d_head, mamba_state=cfg.mamba_d_state,
            mamba_conv=cfg.mamba_d_conv, mamba_chunk=cfg.mamba_chunk_size,
            rms_eps=cfg.rms_norm_eps,
            embedding_multiplier=cfg.embedding_multiplier,
            residual_multiplier=cfg.residual_multiplier,
            attention_multiplier=cfg.attention_multiplier,
            logits_scaling=cfg.logits_scaling, dtype=cfg.dtype,
            init_std=cfg.init_std, attention_backend=cfg.attention_backend,
            row_block=cfg.row_block)
        self._schedule = common.polynomial_lr(
            cfg.start_learning_rate, cfg.end_learning_rate, cfg.learning_frame)
        self.tx = common.rmsprop_with_clip(self._schedule, cfg.gradient_clip_norm)
        self.learn = jax.jit(self._learn, donate_argnums=(0,))

    def init_cache(self, num_rows: int) -> hybrid_lm.HybridState:
        return self.model.init_state(num_rows, self.cfg.trajectory)

    def for_acting(self, params):
        return hybrid_lm.for_acting(params, self.cfg.dtype)

    def state_facts(self, num_rows: int) -> dict:
        """Bytes of the act-time state of `num_rows` rows, by kind, and
        the order of the layers that hold it."""
        state = jax.eval_shape(lambda: self.init_cache(num_rows))
        size = lambda part: sum(x.size * x.dtype.itemsize
                                for x in jax.tree.leaves(part))
        return {"kv_cache_bytes": size((state.k, state.v)),
                "ssm_state_bytes": size(state.ssm),
                "conv_state_bytes": size(state.conv),
                "layer_order": tuple(self.cfg.layer_types)}

    def state_counters(self, cache: hybrid_lm.HybridState) -> dict:
        """`state_norm_mean`: the mean over rows, layers and heads of the
        norm of a head's recurrent state `[P, S]` at the episode's end;
        `state_sample`: a strided sample of that state, which a reader
        replaying the update holds against its own."""
        ssm = [s for s in cache.ssm if s is not None]  # [N, H, P, S] a layer
        norms = [jnp.sqrt(jnp.sum(jnp.square(s.astype(F32)), axis=(-2, -1)))
                 for s in ssm]
        every = max(1, sum(s.size for s in ssm) // STATE_SAMPLE)
        return {"state_norm_mean": jnp.mean(jnp.concatenate(
                    [n.reshape(-1) for n in norms])),
                "state_sample": jnp.concatenate(
                    [s.reshape(-1)[::every].astype(F32) for s in ssm])}

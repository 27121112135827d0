"""Token-level IMPALA on a sliding-window / global-attention sparse-expert
language model (`models/window_moe_lm.py`: SmallThinker-21BA3B's one
global NoPE attention layer to three rotary sliding-window layers, in
every layer a softmax router that reads the layer's INPUT and this chip's
share of the ReGLU experts, an untied head). The actor-critic is
`agents/looplm.py`'s at one pass, as for `agents/convlm.py`: its V-trace
loss, its vocabulary head in blocks of positions, its optimizer. This
file's own:

- the act-time state (`window_moe_lm.WindowState`): a full key/value
  cache for the global layer, a RING of `sliding_window_size` positions
  for each window layer, and the record of the experts every decode step
  chose;
- the counters read from it when the episode ends: the held experts a
  decode step touched, and the share of a ring a step read.
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.agents.looplm import (
    LoopLMAgent, LoopLMBatch, TokenLMConfig, fixed)
from distributed_reinforcement_learning_tpu.models import looped_lm, window_moe_lm

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class SwaLMConfig(TokenLMConfig):
    """The model's published keys under their published names (section
    `smallthinker_moe` of `config.json`). `moe_num_primary_experts` is
    what this chip HOLDS of a layer's `router_width` experts, from
    `first_expert` on; `sliding_window_layout` says of every layer
    whether it attends inside the window (1) or globally (0), and
    `rope_layout`, which has to equal it, whether it has rotary positions."""

    vocab_size: int = 37_984
    hidden_size: int = 2560
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window_layout: tuple = (0, 1, 1, 1)
    sliding_window_size: int = 4096
    rope_theta: float = 1.5e6
    moe_num_primary_experts: int = 16
    router_width: int = 64
    first_expert: int = 0
    moe_num_active_primary_experts: int = 6
    moe_ffn_hidden_size: int = 768
    # The embedding's range, beside `initializer_range` for every matrix:
    # the router reads the stream un-normed (`WindowMoELM.init` says why).
    embedding_initializer_range: float = 1.0
    trajectory: int = 8192
    row_block: int = fixed(1)  # rows a layer is applied to at a time

    MUST: ClassVar[tuple] = (
        "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "sliding_window_layout", "rope_layout",
        "sliding_window_size", "rope_theta", "moe_num_primary_experts",
        "router_width", "first_expert", "moe_num_active_primary_experts",
        "moe_ffn_hidden_size", "rms_norm_eps")
    ONLY: ClassVar[dict] = {
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "tie_word_embeddings": False, "rope_scaling": None}

    @classmethod
    def check_section(cls, d: dict) -> None:
        layout = list(d["sliding_window_layout"])
        if not set(layout) <= {0, 1}:
            raise ValueError(f"sliding_window_layout {layout}: 0 (global) or 1 (window)")
        if len(layout) != d["num_hidden_layers"]:
            raise ValueError(f"{len(layout)} entries of sliding_window_layout for "
                             f"num_hidden_layers {d['num_hidden_layers']}")
        if list(d["rope_layout"]) != layout:
            raise ValueError(
                f"rope_layout {list(d['rope_layout'])} != sliding_window_layout "
                f"{layout}: a global layer without positions and a window layer "
                f"with rotary ones is what is computed")

    @property
    def layer_types(self) -> tuple:
        return tuple(window_moe_lm.LAYER_KINDS[int(w)]
                     for w in self.sliding_window_layout)


class SwaLMAgent(LoopLMAgent):
    def __init__(self, cfg: SwaLMConfig):
        if cfg.total_ut_steps != 1:
            raise ValueError("the stack is run once: total_ut_steps is 1")
        if not 0 <= cfg.first_expert <= cfg.router_width - cfg.moe_num_primary_experts:
            raise ValueError(
                f"experts {cfg.first_expert}.."
                f"{cfg.first_expert + cfg.moe_num_primary_experts - 1}"
                f" of a router {cfg.router_width} wide")
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError(f"{cfg.num_attention_heads} query heads over "
                             f"{cfg.num_key_value_heads} key/value heads")
        self.cfg = cfg
        self.model = window_moe_lm.WindowMoELM(
            vocab=cfg.vocab_size, d_model=cfg.hidden_size,
            layer_types=cfg.layer_types, num_heads=cfg.num_attention_heads,
            num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            window=cfg.sliding_window_size, rope_theta=cfg.rope_theta,
            num_experts=cfg.router_width, experts_held=cfg.moe_num_primary_experts,
            first_expert=cfg.first_expert, top_k=cfg.moe_num_active_primary_experts,
            expert_width=cfg.moe_ffn_hidden_size, rms_eps=cfg.rms_norm_eps,
            dtype=cfg.dtype, init_std=cfg.init_std,
            embed_init_std=cfg.embedding_initializer_range,
            attention_backend=cfg.attention_backend, row_block=cfg.row_block)
        self._schedule = common.polynomial_lr(
            cfg.start_learning_rate, cfg.end_learning_rate, cfg.learning_frame)
        self.tx = common.rmsprop_with_clip(self._schedule, cfg.gradient_clip_norm)
        self.learn = jax.jit(self._learn, donate_argnums=(0,))

    def init_cache(self, num_rows: int) -> window_moe_lm.WindowState:
        return self.model.init_state(num_rows, self.cfg.trajectory)

    def for_acting(self, params):
        return window_moe_lm.for_acting(params, self.cfg.dtype)

    # -- learn -----------------------------------------------------------
    def _stats(self, params, batch: LoopLMBatch) -> dict:
        """`LoopLMAgent._stats` at one pass, its counters the expert
        share's and the window's (`WindowMoELM.counters`) with the sets the
        learner chose, their probabilities and the router's counts."""
        stats = super()._stats(params, batch)
        facts = stats["counters"]
        return {**stats, "counters": {
            **self.model.counters(facts, batch.tokens.size),
            **{k: facts[k] for k in ("routes", "route_scores", "router_load")}}}

    # -- the act-time state ------------------------------------------------
    def state_facts(self, num_rows: int) -> dict:
        """Bytes of the act-time state of `num_rows` rows, by kind (the
        global layers' caches; the window layers' rings), the order of the
        layers, the ring's length, this chip's share of the experts, the
        rows of a slab of the learner's sorted pairs, and
        `act_weight_bytes`: the bytes of every matrix a decode step could
        read whole (every layer's `window_moe_lm.RUN_MATRICES` and the
        head in the compute dtype, the routers in float32)."""
        cfg = self.cfg
        state = jax.eval_shape(lambda: self.init_cache(num_rows))
        params = jax.eval_shape(lambda: self.for_acting(
            self.model.init(jax.random.PRNGKey(0))))["params"]
        size = lambda part: sum(x.size * x.dtype.itemsize
                                for x in jax.tree.leaves(part))
        of = lambda kind: [(k, v) for k, v, layer in zip(
            state.k, state.v, cfg.layer_types) if layer == kind]
        read = [params["head"]] + [
            lp[k] for lp in params["layers"]
            for k in (*window_moe_lm.RUN_MATRICES, "router")]
        return {"kv_cache_bytes": size(of("global")),
                "ring_bytes": size(of("window")),
                "ring_positions": min(cfg.sliding_window_size, cfg.trajectory),
                "layer_order": cfg.layer_types,
                "experts_held": cfg.moe_num_primary_experts,
                "router_width": cfg.router_width, "first_expert": cfg.first_expert,
                "pair_slab_rows": self.model.pair_slab_rows(num_rows, cfg.trajectory),
                "act_weight_bytes": size(read)}

    def state_counters(self, cache: window_moe_lm.WindowState) -> dict:
        """`held_experts_touched_mean`: the held experts that some row
        chose, a mean over the episode's decode steps and the layers (what
        the sorted one-slab form reads of a layer's held experts);
        `ring_read_share`: the mean share of a ring that a decode step
        read (`min(span, W) / W` over `looped_lm.decode_spans`' scans: a
        constant of the shapes); `act_routes`: the experts every decode
        step chose, which a reader replaying the update holds against its
        own."""
        cfg = self.cfg
        held = cfg.first_expert + jnp.arange(cfg.moe_num_primary_experts,
                                             dtype=jnp.int16)
        touched = jnp.any(cache.routes[..., None] == held, axis=(0, 3))  # [T, L, held]
        ring = min(cfg.sliding_window_size, cfg.trajectory)
        spans = looped_lm.decode_spans(cfg.trajectory)
        read = sum((hi - lo) * min(hi, ring) for lo, hi in zip((0, *spans), spans))
        return {"held_experts_touched_mean": jnp.mean(
                    jnp.sum(touched, axis=-1, dtype=F32)),
                "ring_read_share": jnp.asarray(read / (cfg.trajectory * ring), F32),
                "act_routes": cache.routes}

"""Token-level IMPALA on a latent-attention sparse-expert language model
(`models/latent_moe_lm.py`: JoyAI-LLM-Flash's multi-head latent attention
in every layer, a dense leading layer, then a sigmoid-scored
bias-corrected router over all of a layer's experts and this chip's share
of them, and a multi-token-prediction module). The actor-critic is
`agents/looplm.py`'s at one pass, as for `agents/moelm.py`: its V-trace
loss, its vocabulary head in blocks of positions, its optimizer. This
file's own:

- the prediction module's loss beside it (`_stats`, `_loss`): position t
  predicts the action taken at t + 1 from the trunk's state at t and the
  token shown at t + 1,
      L_mtp = mean over t with t + 1 in t's episode of -log softmax(logits'_t)[a_{t+1}]
      total = L_impala + mtp_loss_coef n L_mtp,    n the positions that count
  with gradients into the trunk, the embedding and the head. The module's
  loss is SUMMED over positions because the V-trace loss beside it is (the
  published objective takes the same reduction for both): as a mean
  beside that sum its clipped step on every leaf of the module is under
  1e-6 of float32's spacing (my chip run, PR 40) and the module never
  trains. `mtp_loss` in the metrics is the mean;
- the router's selection bias (`_learn`): a parameter leaf that acting
  takes with the weights and NO gradient trains; after each optimizer
  step it moves by `bias_update_speed sign(mean load - load)` from the
  tokens each of ALL the router's experts was chosen by in the step's
  forward, whatever the optimizer did to it;
- the act-time state (`latent_moe_lm.LatentState`): a latent cache of
  576 values a token a layer, and the record of the experts every decode
  step chose.
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.agents.looplm import (
    LoopLMAgent, LoopLMBatch, TokenLMConfig, fixed)
from distributed_reinforcement_learning_tpu.models import latent_moe_lm
from distributed_reinforcement_learning_tpu.observability import scopes

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class MLALMConfig(TokenLMConfig):
    """The model's published keys under their published names (section
    `joyai_flash` of `config.json`). `n_routed_experts` is what this chip
    HOLDS of a layer's `router_width` experts, from `first_expert` on."""

    vocab_size: int = 16_160
    hidden_size: int = 2048
    num_hidden_layers: int = 5
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 3.2e7
    intermediate_size: int = 7168
    n_routed_experts: int = 16
    router_width: int = 256
    first_expert: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    bias_update_speed: float = 1e-3  # gamma (no key of the source's config.json)
    mtp_loss_coef: float = 0.3  # lambda, on the SUMMED module loss (no key of the source's config.json)
    trajectory: int = 2048
    row_block: int = fixed(2)  # rows a layer is applied to at a time

    MUST: ClassVar[tuple] = (
        "vocab_size", "hidden_size", "num_hidden_layers", "first_k_dense_replace",
        "num_attention_heads", "num_key_value_heads", "q_lora_rank",
        "kv_lora_rank", "qk_head_dim", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "rope_theta", "intermediate_size", "n_routed_experts",
        "router_width", "first_expert", "num_experts_per_tok",
        "moe_intermediate_size", "n_shared_experts", "routed_scaling_factor")
    ONLY: ClassVar[dict] = {
        "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
        "topk_group": 1, "moe_layer_freq": 1, "norm_topk_prob": True,
        "rope_interleave": True, "rope_scaling": None, "attention_bias": False,
        "tie_word_embeddings": False, "hidden_act": "silu",
        "num_nextn_predict_layers": 1, "ep_size": 1}

    @classmethod
    def check_section(cls, d: dict) -> None:
        if d["qk_head_dim"] != d["qk_nope_head_dim"] + d["qk_rope_head_dim"]:
            raise ValueError("qk_head_dim is not qk_nope_head_dim + qk_rope_head_dim")
        if d["num_key_value_heads"] != d["num_attention_heads"]:
            raise ValueError("latent attention rebuilds a key and a value for "
                             "every query head: num_key_value_heads differs")

    @property
    def layer_types(self) -> tuple:
        dense = self.first_k_dense_replace
        return ("dense",) * dense + ("moe",) * (self.num_hidden_layers - dense)


class MLALMAgent(LoopLMAgent):
    def __init__(self, cfg: MLALMConfig):
        if cfg.total_ut_steps != 1:
            raise ValueError("the stack is run once: total_ut_steps is 1")
        if not 0 <= cfg.first_expert <= cfg.router_width - cfg.n_routed_experts:
            raise ValueError(
                f"experts {cfg.first_expert}.."
                f"{cfg.first_expert + cfg.n_routed_experts - 1}"
                f" of a router {cfg.router_width} wide")
        if not 0 <= cfg.first_k_dense_replace < cfg.num_hidden_layers:
            raise ValueError(f"{cfg.first_k_dense_replace} dense layers of "
                             f"{cfg.num_hidden_layers}: no expert layer is left")
        self.cfg = cfg
        self.model = latent_moe_lm.LatentMoELM(
            vocab=cfg.vocab_size, d_model=cfg.hidden_size,
            layer_types=cfg.layer_types, num_heads=cfg.num_attention_heads,
            q_rank=cfg.q_lora_rank, kv_rank=cfg.kv_lora_rank,
            nope_dim=cfg.qk_nope_head_dim, rope_dim=cfg.qk_rope_head_dim,
            v_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta,
            d_ff=cfg.intermediate_size, num_experts=cfg.router_width,
            experts_held=cfg.n_routed_experts, first_expert=cfg.first_expert,
            top_k=cfg.num_experts_per_tok, expert_width=cfg.moe_intermediate_size,
            shared_width=cfg.moe_intermediate_size * cfg.n_shared_experts,
            route_scale=cfg.routed_scaling_factor, rms_eps=cfg.rms_norm_eps,
            dtype=cfg.dtype, init_std=cfg.init_std,
            attention_backend=cfg.attention_backend, row_block=cfg.row_block)
        self._schedule = common.polynomial_lr(
            cfg.start_learning_rate, cfg.end_learning_rate, cfg.learning_frame)
        self.tx = common.rmsprop_with_clip(self._schedule, cfg.gradient_clip_norm)
        self.learn = jax.jit(self._learn, donate_argnums=(0,))

    def init_cache(self, num_rows: int) -> latent_moe_lm.LatentState:
        return self.model.init_state(num_rows, self.cfg.trajectory)

    def for_acting(self, params):
        return latent_moe_lm.for_acting(params, self.cfg.dtype)

    # -- learn -----------------------------------------------------------
    def _stats(self, params, batch: LoopLMBatch) -> dict:
        """`LoopLMAgent._stats` at one pass (float32 `[1, B, T]` `logp`,
        `entropy`, `gate`, `value`), and among its counters the
        prediction module's `mtp_loss` (differentiable: `_loss` adds it)
        and `mtp_agreement`, the share of the positions that count where
        the module's argmax is the main head's one step later."""
        model, cfg = self.model, self.cfg
        hs, facts = model.apply(params, batch.tokens, batch.done, method=model.trunk)
        _, b, t, d = hs.shape
        block = min(cfg.head_block, b * t)
        if (b * t) % block:
            raise ValueError(f"head_block {block} does not divide {b} x {t}")
        blocked = lambda x: x.reshape(b * t // block, block, *x.shape[2:])
        heads = jax.checkpoint(lambda h, a: model.apply(
            params, h, a, method=model.token_stats))
        with jax.named_scope(scopes.HEADS):
            out = jax.lax.map(lambda xs: heads(*xs),
                              (blocked(hs[0]), blocked(batch.action)))
        with jax.named_scope(scopes.MTP):
            h2, mtp_facts = model.apply(params, hs[0], batch.tokens, batch.done,
                                        method=model.mtp)
            mtp_heads = jax.checkpoint(lambda h, a: model.apply(
                params, h, a, method=model.mtp_stats))
            ahead = lambda x: jnp.roll(x, -1, axis=1)  # x_{t+1} at t
            mtp = jax.lax.map(lambda xs: mtp_heads(*xs),
                              (blocked(h2), blocked(ahead(batch.action))))
            counts = ~batch.done & (jnp.arange(t) < t - 1)  # t + 1 in t's episode
            n = jnp.maximum(jnp.sum(counts), 1)
            agree = mtp["greedy"].reshape(b, t) == ahead(out["greedy"].reshape(b, t))
            mtp_loss = -jnp.sum(jnp.where(counts, mtp["logp"].reshape(b, t), 0.0)) / n
        facts = latent_moe_lm.merged([facts, mtp_facts])
        counters = {**model.counters(facts, b * t), "mtp_loss": mtp_loss,
                    "mtp_positions": n.astype(F32),
                    "mtp_agreement": jnp.sum(counts & agree) / n,
                    **{k: facts[k] for k in ("routes", "route_scores", "router_load")}}
        return {"counters": counters,
                **{k: out[k].reshape(1, b, t)
                   for k in ("logp", "entropy", "gate", "value")}}

    def _loss(self, params, batch: LoopLMBatch):
        total, metrics = super()._loss(params, batch)
        total = total + (self.cfg.mtp_loss_coef * metrics["mtp_positions"]
                         * metrics["mtp_loss"])  # summed, as the loss beside it
        return total, {**metrics, "total_loss": total}

    def _learn(self, state: common.TrainState, batch: LoopLMBatch):
        new, metrics = super()._learn(state, batch)
        with jax.named_scope(scopes.OPTIMIZER):
            params = self.model.rebias(state.params, new.params,
                                       metrics["router_load"],
                                       self.cfg.bias_update_speed)
        metrics["bias_abs_max"] = jnp.max(jnp.abs(jnp.concatenate(
            [jnp.ravel(x) for x in self.router_biases(params)])))
        return new.replace(params=params), metrics

    @staticmethod
    def router_biases(params) -> list:
        """Every router's selection bias `[n, E]`: the trunk's runs in
        order, then the prediction module's."""
        p = params["params"]
        runs = sorted((k for k in p if k.startswith("run")), key=lambda k: int(k[3:]))
        return [p[k]["router_bias"] for k in runs if "router_bias" in p[k]] + [
            p["mtp"]["layer"]["router_bias"]]

    # -- the act-time state ------------------------------------------------
    def state_facts(self, num_rows: int) -> dict:
        """Bytes of the latent cache of `num_rows` rows and a token, the
        order of the layers that hold it, this chip's share of the
        experts, and the rows of a slab of the learner's sorted pairs."""
        state = jax.eval_shape(lambda: self.init_cache(num_rows))
        cfg = self.cfg
        size = sum(x.size * x.dtype.itemsize for x in state.cache)
        heads = cfg.num_attention_heads
        return {"latent_cache_bytes": size,
                "cache_bytes_per_token": size // (num_rows * cfg.trajectory),
                "expanded_cache_bytes_per_token": len(cfg.layer_types) * heads * (
                    cfg.qk_nope_head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim)
                * jnp.dtype(cfg.dtype).itemsize,
                "layer_order": tuple(cfg.layer_types),
                "experts_held": cfg.n_routed_experts,
                "router_width": cfg.router_width,
                "first_expert": cfg.first_expert,
                "pair_slab_rows": self.model.pair_slab_rows(num_rows, cfg.trajectory)}

    def state_counters(self, cache: latent_moe_lm.LatentState) -> dict:
        """`act_routes`: the experts every decode step chose, which a
        reader replaying the update holds against its own."""
        return {"act_routes": cache.routes}

"""Token-level IMPALA on a looped language model: V-trace actor-critic
whose observation is a token, whose action is a token out of the whole
vocabulary, and whose policy is the output head of `models/looped_lm.py`
(Ouro's looped decoder) after its last pass.

The loss is Ouro's stage-I objective with V-trace actor-critic as the
loss of a pass (ISSUE 30, Tentpole 2). For every pass r, with the
behaviour log-probability log mu(a_t) recorded at act time (ONE float a
step, not a `[V]` distribution: `XImpalaBatch.behavior_policy` at V =
49,152 would be 197 KB a step):

    log rho = log pi^(r)(a_t) - log mu(a_t)
    vs^(r), rho = V-trace(log rho, 0.99 x not-done, r, v^(r))     rho-bar = c-bar = 1
    l^(r)_t = -A^(r)_t log pi^(r)(a_t) + c_v 0.5 (vs^(r)_t - v^(r)_t)^2 - c_H H(pi^(r)_t)
    total = sum_t sum_r p_t(r) l^(r)_t - beta sum_t H(p_t)

with IMPALA's double evaluation over the first / middle views of the
unroll (`agents/ximpala.py:_loss`), sum-reduced, and p_t the exit
distribution of the gates, through which the gate learns. What it shares
with the other families: `ops/vtrace.from_importance_weights` (the
Pallas kernel on a TPU; the R passes ride in its batch dimension, so a
learn step holds two calls whatever R is), `common.rmsprop_with_clip`,
`common.polynomial_lr`, `common.TrainState`.

The vocabulary head never holds R `[B*T, V]` float32 arrays at once:
`token_stats` runs per pass and per block of `head_block` positions
under `jax.checkpoint`, inside one `lax.scan`, and hands back four
floats a position.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, NamedTuple

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.models import looped_lm
from distributed_reinforcement_learning_tpu.observability import scopes
from distributed_reinforcement_learning_tpu.ops import vtrace

F32 = jnp.float32


def fixed(default):
    """A field of a token family's config that no section of `config.json`
    sets: `utils.config.load_config` leaves it at this default."""
    return dataclasses.field(default=default, metadata={"section_key": False})


@dataclasses.dataclass(frozen=True)
class TokenLMConfig:
    """What every token family's config says of the loop, the loss and the
    optimizer they share (`LoopLMAgent`, `runtime/anakin_tokens.py`):
    IMPALA's keys under `ImpalaConfig`'s names. A family's class adds its
    model's published keys under their published names, and says beside
    them what `utils.config.load_config` holds a section to:

    - `MUST`: the published keys a section must carry, `KeyError` naming
      the one it lacks (a width is never guessed);
    - `ONLY`: what the family's config can say and this program does not
      compute, as key -> the one value that is: `ValueError` naming the key;
    - `check_section(d)`: the checks across keys, `ValueError`.
    """

    rms_norm_eps: float = 1e-6
    trajectory: int = 1024  # unroll == episode == cache length
    recall_distance: int = 8  # envs/token_recall_jax.py
    discount_factor: float = 0.99
    baseline_loss_coef: float = 1.0
    entropy_coef: float = 0.05
    gradient_clip_norm: float = 40.0
    reward_clipping: str = "abs_one"
    start_learning_rate: float = 1e-5
    end_learning_rate: float = 0.0
    learning_frame: int = 1_000_000_000
    dtype: Any = jnp.bfloat16  # matmul operands, act-time state and activations
    init_std: float = 0.02  # the section's `initializer_range`
    head_block: int = fixed(1024)  # positions whose `[*, V]` logits live at once
    attention_backend: str = fixed("auto")
    # One pass of the stack and no exit gate: what `LoopLMAgent` and the
    # token loop read of a looped model, said for every other.
    total_ut_steps: int = fixed(1)
    exit_entropy_coef: float = fixed(0.0)

    MUST: ClassVar[tuple] = ()
    ONLY: ClassVar[dict] = {}

    @classmethod
    def check_section(cls, d: dict) -> None:
        pass

    @property
    def num_actions(self) -> int:  # what `utils.config.check_config` reads
        return self.vocab_size


@dataclasses.dataclass(frozen=True)
class LoopLMConfig(TokenLMConfig):
    """The model's published keys under their published names (section
    `ouro_looplm` of `config.json`)."""

    vocab_size: int = 49_152
    hidden_size: int = 2048
    num_attention_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    num_hidden_layers: int = 8
    total_ut_steps: int = 4  # R: passes of the whole stack
    early_exit_threshold: float = 1.0  # 1 = every position runs every pass
    rope_theta: float = 1e6
    trajectory: int = 128
    exit_entropy_coef: float = 0.05  # beta

    MUST: ClassVar[tuple] = (
        "vocab_size", "hidden_size", "num_attention_heads", "head_dim",
        "intermediate_size", "num_hidden_layers", "total_ut_steps")


class LoopLMBatch(NamedTuple):
    """One learner batch of `[B, T]` token episodes."""

    tokens: jax.Array  # [B, T] i32 the token shown at step t
    action: jax.Array  # [B, T] i32 the token answered
    behaviour_logp: jax.Array  # [B, T] f32 log mu(a_t) at act time
    reward: jax.Array  # [B, T] f32
    done: jax.Array  # [B, T] bool


class LoopLMAgent:
    def __init__(self, cfg: LoopLMConfig):
        if cfg.early_exit_threshold < 1.0:
            # Nobody publishes another value; a data-dependent exit is
            # not built for one (ISSUE 30).
            raise ValueError(
                f"early_exit_threshold {cfg.early_exit_threshold} < 1: acting "
                f"runs every pass; an early exit is not implemented")
        self.cfg = cfg
        self.model = looped_lm.LoopedLM(
            vocab=cfg.vocab_size, d_model=cfg.hidden_size,
            num_heads=cfg.num_attention_heads, head_dim=cfg.head_dim,
            d_ff=cfg.intermediate_size, num_layers=cfg.num_hidden_layers,
            loop_passes=cfg.total_ut_steps, rms_eps=cfg.rms_norm_eps,
            rope_theta=cfg.rope_theta, dtype=cfg.dtype, init_std=cfg.init_std,
            attention_backend=cfg.attention_backend)
        self._schedule = common.polynomial_lr(
            cfg.start_learning_rate, cfg.end_learning_rate, cfg.learning_frame)
        self.tx = common.rmsprop_with_clip(self._schedule, cfg.gradient_clip_norm)
        self.learn = jax.jit(self._learn, donate_argnums=(0,))

    # -- init ------------------------------------------------------------
    def init_state(self, rng: jax.Array) -> common.TrainState:
        tokens = jnp.zeros((1, 2), jnp.int32)
        params = jax.jit(self.model.init)(rng, tokens, jnp.zeros((1, 2), bool))
        return common.TrainState.create(params, self.tx)

    def init_cache(self, num_rows: int) -> looped_lm.KVCache:
        shape = self.model.cache_shape(num_rows, self.cfg.trajectory)
        return looped_lm.KVCache(jnp.zeros(shape, self.cfg.dtype),
                                 jnp.zeros(shape, self.cfg.dtype))

    @property
    def kv_cache_bytes(self) -> int:
        """Bytes of the cache a row of the batch, keys and values."""
        c = self.cfg
        return (2 * c.total_ut_steps * c.num_hidden_layers * c.trajectory
                * c.num_attention_heads * c.head_dim * jnp.dtype(c.dtype).itemsize)

    # -- act: one decode step ---------------------------------------------
    def for_acting(self, params):
        return looped_lm.for_acting(params, self.cfg.dtype)

    def _act(self, act_params, tokens, t, cache, rng, span=None):
        """-> (action `[N]`, log mu(action) `[N]`, cache): sample from
        softmax(logits^(R)) (threshold 1: every pass is run). `span`:
        the static prefix of the cache that covers t (`LoopedLM.decode`)."""
        model = self.model
        h, cache = model.apply(act_params, tokens, t, cache, span,
                               method=model.decode)
        with jax.named_scope(scopes.ACT_HEAD):
            logits, _, _ = model.apply(act_params, h, method=model.logits)
            action = jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)
            logp = (jnp.take_along_axis(logits, action[:, None], axis=-1)[:, 0]
                    - jax.nn.logsumexp(logits, axis=-1))
        return action, logp, cache

    # -- learn -----------------------------------------------------------
    def _stats(self, params, batch: LoopLMBatch) -> dict:
        """Float32 `[R, B, T]` `logp` (taken action), `entropy`, `gate`,
        `value` of every pass."""
        model, cfg = self.model, self.cfg
        hs = model.apply(params, batch.tokens, batch.done, method=model.trunk)
        hs, counters = hs if isinstance(hs, tuple) else (hs, {})  # a trunk's own
        r, b, t, d = hs.shape
        block = min(cfg.head_block, b * t)
        if (b * t) % block:
            raise ValueError(f"head_block {block} does not divide {b} x {t}")
        blocks = b * t // block
        heads = jax.checkpoint(lambda h, a: model.apply(
            params, h, a, method=model.token_stats))
        actions = jnp.broadcast_to(batch.action.reshape(1, blocks, block),
                                   (r, blocks, block)).reshape(r * blocks, block)
        with jax.named_scope(scopes.HEADS):
            out = jax.lax.map(lambda xs: heads(*xs), (hs.reshape(-1, block, d), actions))
        return {"counters": counters, **{k: v.reshape(r, b, t) for k, v in out.items()}}

    def _loss(self, params, batch: LoopLMBatch):
        cfg = self.cfg
        with jax.named_scope(scopes.LOSS):
            stats = self._stats(params, batch)
            logp, value = stats["logp"], stats["value"]
            r, b, _ = logp.shape
            reward = common.clip_rewards(batch.reward, cfg.reward_clipping)
            disc = (~batch.done).astype(F32) * cfg.discount_factor
            first = lambda x: x[..., :-2]
            middle = lambda x: x[..., 1:-1]
            last = lambda x: x[..., 2:]
            # [R, B, T'] -> [T', R*B]: the passes ride in the batch of ONE
            # V-trace call a view (two Mosaic kernels a learn step).
            tm = lambda x: jnp.moveaxis(
                jnp.broadcast_to(x, (r, *x.shape[-2:])), -1, 0).reshape(
                    x.shape[-1], r * b)
            back = lambda x: jnp.moveaxis(x.reshape(-1, r, b), 0, -1)
            log_rho = logp - batch.behaviour_logp
            with jax.named_scope(scopes.LOSS_VTRACE):
                vs, rho = vtrace.from_importance_weights(
                    tm(first(log_rho)), tm(first(disc)), tm(first(reward)),
                    tm(first(value)), middle(value)[..., -1].reshape(r * b))
                vs1, _ = vtrace.from_importance_weights(
                    tm(middle(log_rho)), tm(middle(disc)), tm(middle(reward)),
                    tm(middle(value)), last(value)[..., -1].reshape(r * b))
            vs, rho, vs1 = back(vs), back(rho), back(vs1)
            adv = jax.lax.stop_gradient(
                rho * (first(reward) + first(disc) * vs1 - first(value)))
            pi = -adv * first(logp)
            vl = 0.5 * jnp.square(jax.lax.stop_gradient(vs) - first(value))
            ent = first(stats["entropy"])
            per_pass = pi + cfg.baseline_loss_coef * vl - cfg.entropy_coef * ent
            p_exit = first(looped_lm.exit_distribution(stats["gate"]))
            exit_entropy = -jnp.sum(jnp.where(p_exit > 0, p_exit * jnp.log(
                jnp.where(p_exit > 0, p_exit, 1.0)), 0.0), axis=0)
            total = (jnp.sum(p_exit * per_pass)
                     - cfg.exit_entropy_coef * jnp.sum(exit_entropy))
        cdf = jnp.mean(jnp.cumsum(p_exit, axis=0), axis=(1, 2))
        metrics = {
            "total_loss": total,
            "pi_loss": jnp.sum(p_exit * pi),
            "baseline_loss": jnp.sum(p_exit * vl),
            "entropy": jnp.sum(p_exit * ent),
            "exit_entropy": jnp.mean(exit_entropy),
            **{f"exit_cdf_pass{i + 1}": cdf[i] for i in range(r - 1)},
            # positions of the LAST pass whose raw rho was cut to rho-bar = 1
            "rho_clipped_share": jnp.mean((first(log_rho)[-1] > 0).astype(F32)),
            "behaviour_logp_mean": jnp.mean(batch.behaviour_logp), **stats["counters"],
        }
        return total, metrics

    def _learn(self, state: common.TrainState, batch: LoopLMBatch):
        with jax.named_scope(scopes.LEARN):
            grads, metrics = jax.grad(self._loss, has_aux=True)(state.params, batch)
            with jax.named_scope(scopes.OPTIMIZER):
                updates, opt_state = self.tx.update(
                    grads, state.opt_state, state.params)
                params = jax.tree.map(lambda p, u: p + u, state.params, updates)
                metrics["grad_norm"] = common.global_norm(grads)
        metrics["learning_rate"] = self._schedule(state.step)
        return state.replace(params=params, opt_state=opt_state,
                             step=state.step + 1), metrics

    # -- what a subclass with another model replaces (agents/hybridlm.py) --
    def state_facts(self, num_rows: int) -> dict:
        """Bytes of the act-time state of `num_rows` rows, by kind."""
        return {"kv_cache_bytes": self.kv_cache_bytes * num_rows}

    def state_counters(self, cache) -> dict:
        """Counters read from the act-time state an episode ended with."""
        return {}

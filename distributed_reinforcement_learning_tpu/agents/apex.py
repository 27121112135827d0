"""Ape-X DQN agent: double-DQN on a dueling network with prioritized replay.

Re-design of `/root/reference/agent/apex.py` as jitted pure functions:

- `act`: epsilon-greedy over main-net Q (`agent/apex.py:92-107`); epsilon
  enters as data so one compiled function serves the whole schedule.
- `td_error`: priority scoring forward pass (`agent/apex.py:119-134`).
- `learn`: weighted double-DQN step (`agent/apex.py:136-153`), Adam +
  polynomial LR + global-norm clip, returning fresh |TD| for priority
  updates.
- `sync_target`: main -> target copy (`agent/apex.py:78,82`).

The main net is applied to s and s' in one stacked batch (single conv
pass over 2B frames) instead of the reference's two scoped graph copies
(`model/apex_value.py:42-58`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.models.apex_net import DuelingQNetwork, SimpleQNetwork
from distributed_reinforcement_learning_tpu.ops import dqn


@dataclasses.dataclass(frozen=True)
class ApexConfig:
    """Hyperparameters, mirroring the `apex` block of `config.json:68-106`."""

    obs_shape: tuple[int, ...] = (84, 84, 4)
    num_actions: int = 4
    discount_factor: float = 0.99
    reward_clipping: str = "abs_one"
    gradient_clip_norm: float = 40.0
    start_learning_rate: float = 1e-4
    end_learning_rate: float = 0.0
    learning_frame: int = 100_000_000_000_000
    dtype: Any = jnp.float32


class ApexBatch(NamedTuple):
    """Flat transition batch (the per-transition replay of `train_apex.py:114-122`)."""

    state: jax.Array  # [B, *obs]
    next_state: jax.Array  # [B, *obs]
    previous_action: jax.Array  # [B] i32 (embedding input for s)
    action: jax.Array  # [B] i32 (taken at s; embedding input for s')
    reward: jax.Array  # [B] f32
    done: jax.Array  # [B] bool


class ApexAgent:
    def __init__(self, cfg: ApexConfig):
        self.cfg = cfg
        if len(cfg.obs_shape) == 1:
            self.model = SimpleQNetwork(num_actions=cfg.num_actions, dtype=cfg.dtype)
        else:
            self.model = DuelingQNetwork(num_actions=cfg.num_actions, dtype=cfg.dtype)
        self._schedule = common.polynomial_lr(
            cfg.start_learning_rate, cfg.end_learning_rate, cfg.learning_frame
        )
        self.tx = common.adam_with_clip(self._schedule, cfg.gradient_clip_norm)
        self.act = jax.jit(self._act)
        self.td_error = jax.jit(self._td_error)
        self.learn = jax.jit(self._learn, donate_argnums=(0,))
        # Split learn step for the sharded learner tier
        # (runtime/learner_tier.py): grads computes, the host collective
        # merges, apply_grads commits. apply_grads does NOT donate state:
        # the tier may retry a round against the same state after a
        # membership change aborts the first attempt.
        self.grads = jax.jit(self._grads)
        self.apply_grads = jax.jit(self._apply_grads)
        # K prioritized steps per dispatch; priorities come back stacked
        # [K, B] and land K-1 steps stale (common.scan_learn_weighted).
        self.learn_many = jax.jit(
            common.scan_learn_weighted(self._learn), donate_argnums=(0,)
        )
        self.sync_target = jax.jit(lambda s: s.sync_target())

    def init_state(self, rng: jax.Array) -> common.TargetTrainState:
        obs = jnp.zeros((1, *self.cfg.obs_shape), jnp.float32)
        pa = jnp.zeros((1,), jnp.int32)
        params = self.model.init(rng, obs, pa)
        return common.TargetTrainState.create(params, self.tx)

    def _prep_obs(self, obs):
        """Integer frames go to the model raw (conv0 owns their /255)."""
        return common.prep_obs(obs, self.cfg.obs_shape, self.cfg.dtype)

    # -- act -------------------------------------------------------------
    def _act(self, params, obs, prev_action, epsilon, rng):
        """Batched epsilon-greedy: argmax Q with probability 1-eps."""
        q = self.model.apply(params, self._prep_obs(obs), prev_action)
        action = common.epsilon_greedy(q, epsilon, self.cfg.num_actions, rng)
        return action, q

    # -- shared target math ----------------------------------------------
    def _targets(self, params, target_params, batch: ApexBatch):
        cfg = self.cfg
        obs = self._prep_obs(batch.state)
        next_obs = self._prep_obs(batch.next_state)
        # One conv pass over [s; s'] for the main net.
        stacked = jnp.concatenate([obs, next_obs], axis=0)
        stacked_pa = jnp.concatenate([batch.previous_action, batch.action], axis=0)
        q_all = self.model.apply(params, stacked, stacked_pa)
        B = batch.state.shape[0]
        main_q, next_main_q = q_all[:B], q_all[B:]
        target_q = self.model.apply(target_params, next_obs, batch.action)

        clipped_r = common.clip_rewards(batch.reward, cfg.reward_clipping)
        discounts = (~batch.done).astype(jnp.float32) * cfg.discount_factor
        target_value = dqn.double_q_target(next_main_q, target_q, clipped_r, discounts)
        state_action_value = dqn.take_state_action_value(main_q, batch.action)
        return target_value, state_action_value

    def _td_error(self, state: common.TargetTrainState, batch: ApexBatch):
        tv, sav = self._targets(state.params, state.target_params, batch)
        return dqn.td_error(tv, sav)

    # -- learn -----------------------------------------------------------
    def _loss(self, params, target_params, batch: ApexBatch, is_weight):
        tv, sav = self._targets(params, target_params, batch)
        td_sq = jnp.square(tv - sav)
        loss = jnp.mean(td_sq * is_weight)
        return loss, dqn.td_error(tv, sav)

    def _grads(self, state: common.TargetTrainState, batch: ApexBatch, is_weight):
        """Gradient half of the learn step: (grads, td, loss) with NO
        update applied. The learner-tier allreduce (parallel/
        collective.py) runs between this and `_apply_grads`, so a seat's
        local-batch gradients can be mean-merged across the tier before
        the (identical-everywhere) Adam update — the host-side analogue
        of `_learn`'s in-graph pmean."""
        (loss, td), grads = jax.value_and_grad(self._loss, has_aux=True)(
            state.params, state.target_params, batch, is_weight
        )
        return grads, td, loss

    def _apply_grads(self, state: common.TargetTrainState, grads, loss):
        """Update half of the learn step: optimizer + param apply on
        (possibly tier-merged) gradients; metrics match `_learn`'s."""
        updates, opt_state = self.tx.update(grads, state.opt_state, state.params)
        params = jax.tree.map(lambda p, u: p + u, state.params, updates)
        new_state = state.replace(params=params, opt_state=opt_state, step=state.step + 1)
        metrics = {
            "loss": loss,
            "grad_norm": common.global_norm(grads),
            "learning_rate": self._schedule(state.step),
        }
        return new_state, metrics

    def _learn(self, state: common.TargetTrainState, batch: ApexBatch, is_weight,
               axis_name: str | None = None):
        grads, td, loss = self._grads(state, batch, is_weight)
        if axis_name is not None:
            # shard_map data-parallel callers (runtime/anakin_apex.py mesh
            # mode): each device grads its local prioritized batch; the
            # pmean makes the applied update the global-batch gradient and
            # keeps the replicated params bit-identical across devices.
            grads = jax.lax.pmean(grads, axis_name)
            loss = jax.lax.pmean(loss, axis_name)
        new_state, metrics = self._apply_grads(state, grads, loss)
        return new_state, td, metrics

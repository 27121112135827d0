"""IMPALA agent: V-trace actor-critic as pure init/act/learn functions.

Re-design of `/root/reference/agent/impala.py`. The reference's `Agent`
class builds a TF1 graph with a 1-step inference head plus 3*(T-2)
replicated training copies; here the same math is two jit-compiled pure
functions over one flax model:

- `act`: single-step policy/value + LSTM state advance (the actor hot
  path, `agent/impala.py:118-130`).
- `learn`: stored-state batched forward over `[B, T]`, double V-trace over
  the first/middle time views, sum-reduced losses, RMSProp + polynomial
  LR + global-norm clip (`agent/impala.py:63-100`).

The loss is written once, time-major (`_vtrace_loss`), and has two
entries that differ only in how their data lies. `_loss` / `_learn` /
`learn` take an `ImpalaBatch`, `[B, T, ...]`, as a queue delivers it;
`_loss_time_major` / `_learn_time_major` take an `ImpalaRollout`,
`[T, B, ...]`, as the fused loop's scan writes it. Each flattens the
frames as they lie (the network has no recurrence across its N rows),
so neither transposes pixels; the batch-major entry swaps the network's
`[B, T]` outputs and the batch's `[B, T]` scalars before the loss.

Loss math parity (`agent/impala.py:63-93`):
    vs, rho     = vtrace(first view; next_values = middle values)
    vs_plus_1   = vtrace(middle view; next_values = last values)
    pg_adv      = rho * (r_first + gamma_first * vs_plus_1 - V_first)
    total = pi_loss + c_v * baseline_loss + c_e * entropy_loss
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.models.impala_net import ImpalaActorCritic, apply_stored_state
from distributed_reinforcement_learning_tpu.observability import scopes
from distributed_reinforcement_learning_tpu.ops import vtrace


@dataclasses.dataclass(frozen=True)
class ImpalaConfig:
    """Hyperparameters, mirroring the `impala` block of `config.json:25-67`."""

    obs_shape: tuple[int, ...] = (84, 84, 4)
    num_actions: int = 18
    trajectory: int = 20
    lstm_size: int = 256
    discount_factor: float = 0.99
    baseline_loss_coef: float = 1.0
    entropy_coef: float = 0.05
    gradient_clip_norm: float = 40.0
    reward_clipping: str = "abs_one"
    start_learning_rate: float = 6e-4
    end_learning_rate: float = 0.0
    learning_frame: int = 1_000_000_000
    dtype: Any = jnp.float32
    # Rematerialize the [B*T] stored-state forward in the backward pass
    # (jax.checkpoint): trades ~1 extra forward of FLOPs for not holding
    # conv/LSTM activations of B*T frames in HBM — the knob that lets
    # batch size keep scaling once activations, not params, bound memory.
    remat: bool = False
    # "nature" (reference parity, model/impala_actor_critic.py:4-10) or
    # "resnet" — the IMPALA paper's deep torso, `torso_width`-multiplied
    # channels (models/torso.py ResNetTorso, the MXU-dense variant).
    torso: str = "nature"
    torso_width: int = 1


class ImpalaBatch(NamedTuple):
    """One learner batch, batch-major: `[B, T, ...]` unrolls (queue
    payload, SURVEY §2 row 7). What `_loss` / `_learn` / `learn` take."""

    state: jax.Array  # [B, T, *obs] uint8 (or float for vector envs)
    reward: jax.Array  # [B, T] f32 raw rewards
    action: jax.Array  # [B, T] i32
    done: jax.Array  # [B, T] bool
    behavior_policy: jax.Array  # [B, T, A] f32 softmax at act time
    previous_action: jax.Array  # [B, T] i32
    initial_h: jax.Array  # [B, T, H] actor-recorded per-step LSTM h
    initial_c: jax.Array  # [B, T, H]


class ImpalaRollout(NamedTuple):
    """`ImpalaBatch`'s fields time-major, `[T, B, ...]`: a rollout as
    `lax.scan` stacks it. What `_loss_time_major` / `_learn_time_major` take."""

    state: jax.Array  # [T, B, *obs]
    reward: jax.Array  # [T, B]
    action: jax.Array  # [T, B]
    done: jax.Array  # [T, B]
    behavior_policy: jax.Array  # [T, B, A]
    previous_action: jax.Array  # [T, B]
    initial_h: jax.Array  # [T, B, H]
    initial_c: jax.Array  # [T, B, H]


class ActOutput(NamedTuple):
    action: jax.Array
    policy: jax.Array
    h: jax.Array
    c: jax.Array


class ImpalaAgent:
    """Thin wrapper binding config + model to jitted pure functions."""

    def __init__(self, cfg: ImpalaConfig):
        self.cfg = cfg
        self.model = ImpalaActorCritic(
            num_actions=cfg.num_actions, lstm_size=cfg.lstm_size, dtype=cfg.dtype,
            torso=cfg.torso, torso_width=cfg.torso_width,
        )
        self._schedule = common.polynomial_lr(
            cfg.start_learning_rate, cfg.end_learning_rate, cfg.learning_frame
        )
        self.tx = common.rmsprop_with_clip(self._schedule, cfg.gradient_clip_norm)
        self.act = jax.jit(self._act)
        self.learn = jax.jit(scopes.tagged(self._learn), donate_argnums=(0,))
        # K optimizer steps per dispatch (lax.scan over stacked batches):
        # strips the per-step host->device dispatch gap (not measured on
        # the attached chip).
        self.learn_many = jax.jit(scopes.tagged(common.scan_learn(self._learn)),
                                  donate_argnums=(0,))

    # -- init ------------------------------------------------------------
    def init_state(self, rng: jax.Array) -> common.TrainState:
        obs = jnp.zeros((1, *self.cfg.obs_shape), jnp.float32)
        pa = jnp.zeros((1,), jnp.int32)
        h = c = jnp.zeros((1, self.cfg.lstm_size), jnp.float32)
        params = self.model.init(rng, obs, pa, h, c)
        return common.TrainState.create(params, self.tx)

    def initial_lstm_state(self, batch_size: int) -> tuple[jax.Array, jax.Array]:
        z = jnp.zeros((batch_size, self.cfg.lstm_size), jnp.float32)
        return z, z

    def _prep_obs(self, obs: jax.Array) -> jax.Array:
        """Integer frames go to the model raw (conv0 owns their /255)."""
        return common.prep_obs(obs, self.cfg.obs_shape, self.cfg.dtype)

    # -- act -------------------------------------------------------------
    def _act(self, params, obs, prev_action, h, c, rng) -> ActOutput:
        """Batched single-step act: sample from the softmax policy.

        Parity with `agent/impala.py:118-130` (np.random.choice(p=policy) ->
        jax.random.categorical over log-probabilities), batched over the
        actor's parallel envs instead of one `sess.run` per env.
        """
        out = self.model.apply(params, self._prep_obs(obs), prev_action, h, c)
        action = jax.random.categorical(rng, jnp.log(out.policy + 1e-20), axis=-1)
        return ActOutput(action, out.policy, out.h, out.c)

    # -- learn -----------------------------------------------------------
    def _forward(self, params, data: ImpalaBatch | ImpalaRollout):
        """Policy and value for every step of `data`, its two leading
        axes kept as they are."""
        forward = functools.partial(apply_stored_state, self.model)
        if self.cfg.remat:
            forward = jax.checkpoint(forward)
        return forward(
            params,
            self._prep_obs(data.state),
            data.previous_action,
            data.initial_h,
            data.initial_c,
        )

    def _vtrace_loss(self, policy, value, action, reward, done, behavior_policy):
        """The V-trace actor-critic loss, time-major: `[T, B, A]`
        policies, `[T, B]` everything else."""
        cfg = self.cfg
        clipped_r = common.clip_rewards(reward, cfg.reward_clipping)
        discounts = (~done).astype(jnp.float32) * cfg.discount_factor

        first_p, middle_p, _ = vtrace.split_time_major(policy)
        first_v, middle_v, last_v = vtrace.split_time_major(value)
        first_a, middle_a, _ = vtrace.split_time_major(action)
        first_r, middle_r, _ = vtrace.split_time_major(clipped_r)
        first_d, middle_d, _ = vtrace.split_time_major(discounts)
        first_b, middle_b, _ = vtrace.split_time_major(behavior_policy)

        vs, rho = vtrace.from_softmax_time_major(
            behavior_policy=first_b, target_policy=first_p, actions=first_a,
            discounts=first_d, rewards=first_r, values=first_v, next_values=middle_v)
        vs_plus_1, _ = vtrace.from_softmax_time_major(
            behavior_policy=middle_b, target_policy=middle_p, actions=middle_a,
            discounts=middle_d, rewards=middle_r, values=middle_v, next_values=last_v)

        pg_adv = jax.lax.stop_gradient(rho * (first_r + first_d * vs_plus_1 - first_v))

        pi_loss = vtrace.policy_gradient_loss(first_p, first_a, pg_adv)
        v_loss = vtrace.baseline_loss(vs, first_v)
        ent_loss = vtrace.entropy_loss(first_p)
        total = pi_loss + cfg.baseline_loss_coef * v_loss + cfg.entropy_coef * ent_loss
        metrics = {
            "pi_loss": pi_loss,
            "baseline_loss": v_loss,
            "entropy": ent_loss,
            "total_loss": total,
        }
        return total, metrics

    @jax.named_scope(scopes.LOSS)
    def _loss(self, params, batch: ImpalaBatch):
        policy, value = self._forward(params, batch)
        tm = lambda x: jnp.swapaxes(x, 0, 1)
        return self._vtrace_loss(tm(policy), tm(value), tm(batch.action),
                                 tm(batch.reward), tm(batch.done),
                                 tm(batch.behavior_policy))

    @jax.named_scope(scopes.LOSS)
    def _loss_time_major(self, params, rollout: ImpalaRollout):
        policy, value = self._forward(params, rollout)
        return self._vtrace_loss(policy, value, rollout.action, rollout.reward,
                                 rollout.done, rollout.behavior_policy)

    def _learn_with(self, loss, state: common.TrainState, data):
        grads, metrics = jax.grad(loss, has_aux=True)(state.params, data)
        with jax.named_scope(scopes.OPTIMIZER):
            updates, opt_state = self.tx.update(grads, state.opt_state, state.params)
            params = jax.tree.map(lambda p, u: p + u, state.params, updates)
        metrics["grad_norm"] = common.global_norm(grads)
        metrics["learning_rate"] = self._schedule(state.step)
        new_state = state.replace(params=params, opt_state=opt_state, step=state.step + 1)
        return new_state, metrics

    @jax.named_scope(scopes.LEARN)
    def _learn(self, state: common.TrainState, batch: ImpalaBatch):
        return self._learn_with(self._loss, state, batch)

    @jax.named_scope(scopes.LEARN)
    def _learn_time_major(self, state: common.TrainState, rollout: ImpalaRollout):
        return self._learn_with(self._loss_time_major, state, rollout)

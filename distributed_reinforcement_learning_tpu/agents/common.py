"""Shared agent machinery: train states, optimizers, preprocessing.

Replaces the reference's TF1 graph plumbing (`tf.train.get_or_create_global_step`,
`tf.train.polynomial_decay`, `clip_by_global_norm` + optimizer at
`agent/impala.py:95-100`, `agent/apex.py:71-76`, `agent/r2d2.py:91-92`)
with optax transforms composed around jit-compiled pure loss functions.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import optax
from flax import struct

from distributed_reinforcement_learning_tpu.observability import scopes


@struct.dataclass
class TrainState:
    """Learner state: params + optimizer state + step counter.

    The reference kept these as TF global variables on the learner device;
    here it is an explicit pytree that pjit shards/replicates.
    """

    params: Any
    opt_state: Any
    step: jax.Array

    @classmethod
    def create(cls, params, tx: optax.GradientTransformation) -> "TrainState":
        return cls(params=params, opt_state=tx.init(params), step=jnp.zeros((), jnp.int32))


@struct.dataclass
class TargetTrainState:
    """TrainState plus a target network (Ape-X / R2D2)."""

    params: Any
    target_params: Any
    opt_state: Any
    step: jax.Array

    @classmethod
    def create(cls, params, tx: optax.GradientTransformation) -> "TargetTrainState":
        return cls(
            params=params,
            target_params=jax.tree.map(jnp.copy, params),
            opt_state=tx.init(params),
            step=jnp.zeros((), jnp.int32),
        )

    def sync_target(self) -> "TargetTrainState":
        """Copy main -> target, the reference's `main_to_target` grouped assign
        (`utils.py:23-31`)."""
        return self.replace(target_params=jax.tree.map(jnp.copy, self.params))


def polynomial_lr(start: float, end: float, transition_steps: int) -> optax.Schedule:
    """Linear (power-1 polynomial) decay, parity with `tf.train.polynomial_decay`
    as used at `agent/impala.py:96`.

    `transition_steps` is clamped to int32 range: the reference's apex config
    uses `learning_frame=1e14` (`config.json:102`), which no int32 step counter
    ever reaches — numerically identical, and keeps optax's schedule arithmetic
    in-range without enabling x64.
    """
    return optax.polynomial_schedule(
        init_value=start,
        end_value=end,
        power=1.0,
        transition_steps=min(int(transition_steps), 2**31 - 1),
    )


def rmsprop_with_clip(
    lr: optax.Schedule | float,
    clip_norm: float,
    decay: float = 0.99,
    eps: float = 0.1,
) -> optax.GradientTransformation:
    """IMPALA optimizer: global-norm clip -> RMSProp(decay, eps) -> lr.

    Matches `agent/impala.py:95-100`: RMSPropOptimizer(decay=.99, momentum=0,
    epsilon=.1) on globally-clipped gradients. optax's `scale_by_rms` uses
    `g * rsqrt(nu + eps)` — the same eps-inside-sqrt convention as TF1 —
    and `initial_scale=1.0` matches TF1's ones-initialized mean-square slot
    (optax defaults to 0, which would make the first updates ~3x larger).
    """
    return optax.chain(
        optax.clip_by_global_norm(clip_norm),
        optax.scale_by_rms(decay=decay, eps=eps, initial_scale=1.0),
        optax.scale_by_learning_rate(lr),
    )


def adam_with_clip(lr: optax.Schedule | float, clip_norm: float | None) -> optax.GradientTransformation:
    """Ape-X optimizer: global-norm clip -> Adam (`agent/apex.py:71-76`).

    Pass `clip_norm=None` for R2D2, whose reference applies plain Adam with
    no clipping (`agent/r2d2.py:91-92` — config's clip value is unused there).
    """
    steps = [optax.scale_by_adam(), optax.scale_by_learning_rate(lr)]
    if clip_norm is not None:
        steps.insert(0, optax.clip_by_global_norm(clip_norm))
    return optax.chain(*steps)


def clip_rewards(rewards: jax.Array, mode: str) -> jax.Array:
    """Reward clipping, parity with `agent/impala.py:45-49` / `agent/apex.py:38-42`.

    - `abs_one`: clip to [-1, 1]
    - `soft_asymmetric`: 5*tanh(r/5), scaled by 0.3 for negative rewards
    - `none`: pass through
    """
    if mode == "abs_one":
        return jnp.clip(rewards, -1.0, 1.0)
    if mode == "soft_asymmetric":
        squeezed = jnp.tanh(rewards / 5.0)
        return jnp.where(rewards < 0, 0.3 * squeezed, squeezed) * 5.0
    if mode == "none":
        return rewards
    raise ValueError(f"unknown reward_clipping mode: {mode!r}")


def normalize_obs(obs: jax.Array, dtype: Any = jnp.float32) -> jax.Array:
    """uint8 frames -> `dtype` in [0, 1]; float observations cast through.

    The reference normalizes `/255` at every feed (`agent/impala.py:119,133`);
    keeping frames uint8 until this point minimizes host->HBM bandwidth.
    Integer IMAGES do not come here any more (`prep_obs`): this is for
    integer vector observations and for floats.

    `dtype` should be the model's compute dtype: normalizing straight
    into bf16 (a bf16 multiply by the constant 1/255) avoids
    materializing an fp32 copy of the frame tensor — 4x the uint8 batch
    in HBM traffic — when XLA does not fuse the convert chain into the
    first conv. The 1/255-scaled uint8 lattice is not exactly
    representable either way; in bf16 adjacent high-intensity levels can
    round together, which is the standard bf16-frames trade every TPU RL
    stack makes.
    """
    if jnp.issubdtype(obs.dtype, jnp.integer):
        return obs.astype(dtype) * jnp.asarray(1.0 / 255.0, dtype)
    return obs.astype(dtype)


def prep_obs(obs: jax.Array, obs_shape: tuple[int, ...], dtype: Any) -> jax.Array:
    """What an agent hands its network: integer IMAGE observations raw
    (they stay bytes until conv0, whose kernel carries the /255: see
    `models.torso.NatureConv`), everything else through `normalize_obs`.
    Decided by the observation's own dtype, not by a configuration key."""
    if len(obs_shape) == 3 and jnp.issubdtype(obs.dtype, jnp.integer):
        return obs
    return normalize_obs(obs, dtype)


def global_norm(tree) -> jax.Array:
    return optax.global_norm(tree)


def scan_learn(learn_fn):
    """Wrap `(state, batch) -> (state, metrics)` into a K-step
    `(state, stacked_batches[K, ...]) -> (state, stacked_metrics)`.

    `lax.scan` runs K optimizer steps back-to-back in ONE compiled
    dispatch — the math is identical to K sequential `learn` calls (the
    step counter, LR schedule, and optimizer moments all advance inside
    the scan), but the host never intervenes between steps: this strips
    the per-step dispatch gap (its size is not measured on the attached
    chip). The trade is freshness: weights
    publish at K-step granularity (IMPALA's V-trace corrects exactly
    this off-policy staleness).
    """

    def many(state, batches):
        return jax.lax.scan(lambda s, b: learn_fn(s, b), state, batches)

    return many


def scan_learn_weighted(learn_fn):
    """`scan_learn` for the replay agents' `(state, batch, is_weight) ->
    (state, priorities, metrics)` signature.

    Returns `(state, stacked_priorities[K, B], stacked_metrics)`. Note
    the replay semantics under K>1: all K batches are sampled BEFORE any
    of the K updates, so priority updates land K-1 steps stale — the
    same staleness distributed Ape-X already accepts from its actors
    (`/root/reference/train_apex.py:207-217` pushes transitions scored
    by old weights); keep K well under the target-sync interval.
    """

    def many(state, batches, is_weights):
        def body(s, bw):
            s, priorities, metrics = learn_fn(s, *bw)
            return s, (priorities, metrics)

        state, (priorities, metrics) = jax.lax.scan(body, state, (batches, is_weights))
        return state, priorities, metrics

    return many


def epsilon_greedy(
    q_values: jax.Array, epsilon: jax.Array | float, num_actions: int, rng: jax.Array
) -> jax.Array:
    """Batched epsilon-greedy action selection over `[N, A]` Q-values.

    Shared by Ape-X (`agent/apex.py:92-107`) and R2D2 (`agent/r2d2.py:166-186`);
    epsilon enters as data so one compiled act function serves the whole
    exploration schedule.
    """
    greedy = jnp.argmax(q_values, axis=-1)
    key_e, key_a = jax.random.split(rng)
    explore = jax.random.uniform(key_e, greedy.shape) <= epsilon
    random_action = jax.random.randint(key_a, greedy.shape, 0, num_actions)
    return jnp.where(explore, random_action, greedy)


def sequence_double_q_td(main_q, target_q, action, reward, discounts,
                         *, burn_in: int, rescale_eps: float, n_step: int = 1):
    """Shared R2D2-family target math (`agent/r2d2.py:64-87`).

    Burn-in slice, (t, t+1) alignment, double-Q action selection on the
    main net, value-function rescaling on the bootstrapped target.
    Inputs are full-sequence `[B, T, ...]`; returns (target_value, sav)
    over the supervised positions. One implementation serves both the
    LSTM and the transformer agents so the replay semantics cannot drift.

    `n_step` > 1 (paper: 5): the target at t is
    h(sum_{k<n} (prod_{j<k} d_{t+j}) r_{t+k}
      + (prod_{j<n} d_{t+j}) h^-1(Q_target(s_{t+n}, argmax_a Q(s_{t+n}, a))))
    with d = `discounts` (gamma, 0 after a done). Where t + n runs past
    the sequence the horizon is cut at its last step, as
    `rlax.n_step_bootstrapped_returns` does: the last positions bootstrap
    from the last state over fewer steps.
    """
    from distributed_reinforcement_learning_tpu.ops import dqn, value_rescale

    b = burn_in
    main_b, target_b = main_q[:, b:], target_q[:, b:]
    reward_b, disc_b, action_b = reward[:, b:], discounts[:, b:], action[:, b:]

    sav = dqn.take_state_action_value(main_b[:, :-1], action_b[:, :-1])
    next_action = jnp.argmax(main_b[:, 1:], axis=-1)
    next_sav = dqn.take_state_action_value(target_b[:, 1:], next_action)

    descaled = value_rescale.inverse_value_rescale(next_sav, rescale_eps)
    if n_step == 1:
        raw_target = descaled * disc_b[:, :-1] + reward_b[:, :-1]
    else:
        # descaled[:, t] is the value of s_{t+1}. Start from the value
        # n steps ahead (the last one where that runs off the end) and
        # fold the rewards in back to front; the padding (reward 0,
        # discount 1) makes the steps past the end the identity.
        steps = descaled.shape[1]
        pad = ((0, 0), (0, n_step - 1))
        raw_target = jnp.pad(descaled, pad, mode="edge")[:, n_step - 1:]
        rew = jnp.pad(reward_b[:, :-1], pad)
        disc = jnp.pad(disc_b[:, :-1], pad, constant_values=1.0)
        for k in reversed(range(n_step)):
            raw_target = rew[:, k:k + steps] + disc[:, k:k + steps] * raw_target
    raw_target = jax.lax.stop_gradient(raw_target)
    target_value = value_rescale.value_rescale(raw_target, rescale_eps)
    return target_value, sav


class SequenceReplayLearnMixin:
    """td_error/loss/learn shared by the sequence-replay agents.

    Host class provides `_sequence_td(params, target_params, batch,
    unroll_scope=None, online_q=None)` -> (target_value, sav) — optionally
    with a third scalar model aux loss (e.g. the MoE router's
    load-balancing term), added to the TD loss as-is — and `self.tx`.
    `unroll_scope` is the profile name for a sequential recurrence inside
    the forward, given by the learn step only (a family without one
    ignores it); `online_q` is `_td_error`'s. Loss = IS-weighted mean
    over time of squared TD (`agent/r2d2.py:88-89`).

    Priority: the reference's quirk |mean_t TD| (`agent/r2d2.py:151-153`
    — signed TDs cancel across the sequence, so a high-error sequence
    can score ~0 and starve) is the default for parity. Setting
    `cfg.priority_eta` switches to the R2D2 paper's stable mixture
    p = eta*max_t|TD| + (1-eta)*mean_t|TD| (Kapturowski et al. 2019,
    eta=0.9) — the known fix for the reference's replay-collapse cycles
    (VERDICT r3 item 5).
    """

    def _seq_priority(self, tv, sav):
        delta = tv - sav
        eta = getattr(self.cfg, "priority_eta", None)
        if eta is None:
            return jnp.abs(jnp.mean(delta, axis=1))  # reference parity
        ad = jnp.abs(delta)
        return eta * jnp.max(ad, axis=1) + (1.0 - eta) * jnp.mean(ad, axis=1)

    def _td_error(self, state, batch, online_q=None):
        """Priority of each sequence of `batch`. `online_q` `[B, T, A]`:
        the online net's Q-values over the batch where the caller holds
        them (a fused loop whose actors ran `state.params` on these very
        inputs); the forward then runs the target net alone."""
        tv, sav = self._sequence_td(state.params, state.target_params, batch,
                                    online_q=online_q)[:2]
        return self._seq_priority(tv, sav)

    @jax.named_scope(scopes.LOSS)
    def _loss(self, params, target_params, batch, is_weight):
        out = self._sequence_td(params, target_params, batch,
                                unroll_scope=scopes.UNROLL)
        tv, sav = out[:2]
        aux = out[2] if len(out) > 2 else 0.0
        per_seq = jnp.mean(jnp.square(tv - sav), axis=1)
        loss = jnp.mean(per_seq * is_weight) + aux
        priorities = self._seq_priority(tv, sav)
        return loss, priorities

    @jax.named_scope(scopes.LEARN)
    def _learn(self, state, batch, is_weight, axis_name: str | None = None):
        (loss, priorities), grads = jax.value_and_grad(self._loss, has_aux=True)(
            state.params, state.target_params, batch, is_weight
        )
        if axis_name is not None:
            # shard_map data-parallel callers (runtime/anakin_r2d2.py mesh
            # mode): pmean turns per-shard gradients into the global-batch
            # gradient so replicated params stay identical across devices.
            grads = jax.lax.pmean(grads, axis_name)
            loss = jax.lax.pmean(loss, axis_name)
        with jax.named_scope(scopes.OPTIMIZER):
            updates, opt_state = self.tx.update(grads, state.opt_state, state.params)
            params = jax.tree.map(lambda p, u: p + u, state.params, updates)
        new_state = state.replace(params=params, opt_state=opt_state, step=state.step + 1)
        metrics = {"loss": loss, "grad_norm": global_norm(grads)}
        return new_state, priorities, metrics

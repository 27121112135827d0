"""R2D2 agent: recurrent Q-learning with stored state, burn-in, rescaling.

Re-design of `/root/reference/agent/r2d2.py` as jitted pure functions.
Semantics preserved:

- Main and target nets are unrolled over the full sequence from the
  **sequence-start stored state** h[0], c[0] (`agent/r2d2.py:110-111,135-136`),
  with done-masked state resets inside the unroll (`model/r2d2_lstm.py:78-80`).
- Burn-in: the first `burn_in` steps are sliced out of the loss, not the
  unroll (`agent/r2d2.py:64-68`).
- Double-Q over sequences + value-function rescaling on the target
  (`agent/r2d2.py:70-87`): target = h(h^{-1}(Q_target(s', a*)) * gamma + r).
- Loss: mean over time of squared TD, weighted per-sequence by IS weight
  (`agent/r2d2.py:88-89`); priority = |mean TD| per sequence
  (`agent/r2d2.py:151-153`).
- Optimizer: plain Adam(1e-4), no clipping (`agent/r2d2.py:91-92`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.models.r2d2_net import R2D2Net


@dataclasses.dataclass(frozen=True)
class R2D2Config:
    """Hyperparameters, mirroring the `r2d2` block of `config.json:2-24`."""

    obs_shape: tuple[int, ...] = (2,)
    num_actions: int = 2
    seq_len: int = 10
    burn_in: int = 5
    lstm_size: int = 512
    discount_factor: float = 0.997
    learning_rate: float = 1e-4
    rescale_eps: float = 1e-3
    dtype: Any = jnp.float32
    # None = the reference's |mean TD| sequence priority (parity quirk);
    # a float (paper: 0.9) = eta*max|TD| + (1-eta)*mean|TD| stable mode
    # (common.SequenceReplayLearnMixin._seq_priority).
    priority_eta: float | None = None
    # None = the reference's plain unclipped Adam (`agent/r2d2.py:91-92`);
    # a float adds global-norm clipping in front (stable mode — the
    # unclipped TD spikes at target syncs are a collapse driver).
    gradient_clip_norm: float | None = None
    # "mlp" = reference parity (its R2D2 is CartPole-only); "nature" /
    # "resnet" = conv torsos for pixel envs (the R2D2 paper's Atari
    # configuration — see models/r2d2_net.py).
    torso: str = "mlp"
    torso_width: int = 1
    # n-step double-Q targets (paper: 5); 1 = the reference's 1-step.
    n_step: int = 1
    # None = the reference's Dense(128) head with a learned mean; an
    # integer = the paper's two dueling streams of that width (512).
    dueling_hidden: int | None = None

    @property
    def fold_normalize(self) -> bool:
        """Not a field: uint8 frames always reach the model raw. Read by
        `perfbench/families/r2d2.py`, which a `benchmark` PR owns."""
        return True


class R2D2Batch(NamedTuple):
    """Sequence batch (queue payload of `distributed_queue/buffer_queue.py:7-91`)."""

    state: jax.Array  # [B, T, *obs] (int32-quantized *255 upstream, like the ref)
    previous_action: jax.Array  # [B, T] i32
    action: jax.Array  # [B, T] i32
    reward: jax.Array  # [B, T] f32
    done: jax.Array  # [B, T] bool
    initial_h: jax.Array  # [B, H] sequence-start stored h
    initial_c: jax.Array  # [B, H]


def _bt(x: jax.Array) -> jax.Array:
    """`[T, B, ...]` <-> `[B, T, ...]`."""
    return jnp.swapaxes(x, 0, 1)


class R2D2Rollout(NamedTuple):
    """New sequences time-major, `[T, B, ...]`: a rollout as `lax.scan`
    stacks it, with the Q-values its actors computed. What
    `_td_error_time_major` takes; `batch()` is the ring's entry."""

    state: jax.Array  # [T, B, *obs]
    previous_action: jax.Array  # [T, B] i32
    action: jax.Array  # [T, B] i32
    reward: jax.Array  # [T, B] f32
    done: jax.Array  # [T, B] bool
    initial_h: jax.Array  # [B, H] sequence-start stored h
    initial_c: jax.Array  # [B, H]
    online_q: jax.Array  # [T, B, A] f32, the online net's, from acting

    def batch(self) -> R2D2Batch:
        """The sequences as `[B, T, ...]` entries: what the ring stores
        (acting's Q-values score them and are not stored)."""
        return R2D2Batch(
            state=_bt(self.state), previous_action=_bt(self.previous_action),
            action=_bt(self.action), reward=_bt(self.reward),
            done=_bt(self.done), initial_h=self.initial_h,
            initial_c=self.initial_c)


class R2D2Agent(common.SequenceReplayLearnMixin):
    def __init__(self, cfg: R2D2Config):
        self.cfg = cfg
        self.model = R2D2Net(num_actions=cfg.num_actions, lstm_size=cfg.lstm_size,
                             dtype=cfg.dtype, torso=cfg.torso,
                             torso_width=cfg.torso_width,
                             dueling_hidden=cfg.dueling_hidden)
        self.tx = common.adam_with_clip(cfg.learning_rate,
                                        clip_norm=cfg.gradient_clip_norm)
        self.act = jax.jit(self._act)
        self.td_error = jax.jit(self._td_error)
        self.learn = jax.jit(self._learn, donate_argnums=(0,))
        self.learn_many = jax.jit(
            common.scan_learn_weighted(self._learn), donate_argnums=(0,)
        )
        self.sync_target = jax.jit(lambda s: s.sync_target())

    def init_state(self, rng: jax.Array) -> common.TargetTrainState:
        # Pixel observations are stored and fed as bytes.
        dtype = jnp.uint8 if len(self.cfg.obs_shape) == 3 else jnp.float32
        obs = jnp.zeros((1, *self.cfg.obs_shape), dtype)
        pa = jnp.zeros((1,), jnp.int32)
        h = c = jnp.zeros((1, self.cfg.lstm_size), jnp.float32)
        params = self.model.init(rng, obs, pa, h, c)
        return common.TargetTrainState.create(params, self.tx)

    def _prep_obs(self, obs):
        """Integer frames go to the model raw (conv0 owns their /255)."""
        return common.prep_obs(obs, self.cfg.obs_shape, self.cfg.dtype)

    def initial_lstm_state(self, batch_size: int) -> tuple[jax.Array, jax.Array]:
        z = jnp.zeros((batch_size, self.cfg.lstm_size), jnp.float32)
        return z, z

    # -- act -------------------------------------------------------------
    def _act(self, params, obs, h, c, prev_action, epsilon, rng):
        """Batched epsilon-greedy single step (`agent/r2d2.py:166-186`)."""
        q, new_h, new_c = self.model.apply(params, self._prep_obs(obs), prev_action, h, c)
        action = common.epsilon_greedy(q, epsilon, self.cfg.num_actions, rng)
        return action, q, new_h, new_c

    # -- shared sequence target math -------------------------------------
    # _td_error/_loss/_learn come from SequenceReplayLearnMixin; this
    # supplies the model forward. Burn-in, double-Q, and rescaling live
    # in `common.sequence_double_q_td` (`agent/r2d2.py:64-87`).
    def _sequence_td(self, params, target_params, batch: R2D2Batch,
                     unroll_scope: str | None = None, online_q=None):
        """`online_q`: `unroll(params)` where the caller already holds it
        (`runtime/anakin_r2d2.py`: acting's Q-values of the collect scan)."""
        cfg = self.cfg
        obs = self._prep_obs(batch.state)
        unroll = lambda p: self.model.apply(
            p, obs, batch.previous_action, batch.done, batch.initial_h, batch.initial_c,
            unroll_scope, method=self.model.unroll)
        discounts = (~batch.done).astype(jnp.float32) * cfg.discount_factor
        return common.sequence_double_q_td(
            unroll(params) if online_q is None else online_q,
            unroll(target_params), batch.action, batch.reward,
            discounts, burn_in=cfg.burn_in, rescale_eps=cfg.rescale_eps,
            n_step=cfg.n_step)

    def _td_error_time_major(self, state, rollout: R2D2Rollout):
        """`_td_error(state, rollout.batch(), online_q [B, T, A])`, `[B]`,
        with the frames left in the order the scan wrote them: the target
        net unrolls time-major (`R2D2Net.unroll_time_major`), and only
        what the TD arithmetic reads (two `[T, B, A]` sets of Q-values and
        the per-step scalars) is swapped to the `[B, T]` order that
        `common.sequence_double_q_td` and `_seq_priority` take.

        Called by the fused loop (`runtime/anakin_r2d2.py`), which holds a
        rollout; whoever holds an `R2D2Batch` calls `_td_error`.
        """
        cfg = self.cfg
        target_q = self.model.apply(
            state.target_params, self._prep_obs(rollout.state),
            rollout.previous_action, rollout.done, rollout.initial_h,
            rollout.initial_c, method=self.model.unroll_time_major)
        discounts = (~rollout.done).astype(jnp.float32) * cfg.discount_factor
        tv, sav = common.sequence_double_q_td(
            _bt(rollout.online_q), _bt(target_q), _bt(rollout.action),
            _bt(rollout.reward), _bt(discounts), burn_in=cfg.burn_in,
            rescale_eps=cfg.rescale_eps, n_step=cfg.n_step)
        return self._seq_priority(tv, sav)

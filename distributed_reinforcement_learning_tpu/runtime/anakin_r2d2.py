"""Anakin R2D2: recurrent replay training entirely on-device.

`runtime/anakin.py` fuses the ON-POLICY family (IMPALA) into one
compiled program; this module does the same for the replay family. The
host topology's queue + SumTree + learner loop
(`runtime/r2d2_runner.py`, `data/replay.py`) becomes a fixed-capacity
ring of sequences living in HBM with prioritized sampling *inside* the
jit — nothing crosses the host boundary between env step and optimizer
update. This is the TPU-native expression of the reference's
`train_r2d2.py` stack for jittable envs; the socket topology remains
for everything else.

Replay semantics mirror `data/replay.py` (itself the re-design of
`distributed_queue/buffer_queue.py:256-346`):
- priority `(|err| + 0.001) ** 0.6`, stratified sampling over `total/n`
  segments, IS weights `(N * p) ** -beta` batch-max-normalized, beta
  annealed 0.4 -> 1.0 by 0.001 per sample;
- new sequences scored under the current params (what the host learner
  does at ingest with `agent._td_error`, `runtime/r2d2_runner.py:274`).
  The online net's Q-values are the ones acting computed in the collect
  scan (same params, inputs, start state and resets, and no optimizer
  step between), so the scoring pass unrolls the target net alone;
- every sampled index's priority updated after the step (the
  `update_batch` fix of `train_r2d2.py:159`).

Actor semantics mirror `R2D2Actor`: per-episode epsilon decay
`1/(0.1*episodes+1)` with an optional floor, stored sequence-start LSTM
state, done-masked carries, prev-action reset.

Axis orders. The collect scan stacks its record time-major: `_collect`
gives an `R2D2Rollout`, `[T, B, ...]`, and the score takes it in that
order (`agent._td_error_time_major` -> `R2D2Net.unroll_time_major`), so
the new frames are never transposed on their way to conv0. `[B, T, ...]`
(`R2D2Batch`) is the order of the ring, whose entries are whole
sequences: `_ingest` makes it for the write alone, and everything that
reads the ring (`_sample`, the learn step's `agent._learn`) keeps it.
One chip or a mesh alike (a shard scores its `[T, B/n]`); `score_order`
says so at start-up.

Differences from the host stack, by construction:
- the ring overwrites oldest entries FIFO (the SumTree does too);
- collection and training interleave at a fixed `updates_per_collect`
  ratio instead of queue backpressure;
- insert-time TD scores use the learner's own current params (the
  distributed path scores with possibly-stale actor weights).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.agents.r2d2 import (
    R2D2Agent, R2D2Batch, R2D2Rollout)
from distributed_reinforcement_learning_tpu.data import device_replay
from distributed_reinforcement_learning_tpu.data.device_replay import (
    BETA0,
    BETA_INCREMENT,
    PER_ALPHA,
    PER_EPS,
    DeviceReplay,
)
from distributed_reinforcement_learning_tpu.envs import cartpole_jax
from distributed_reinforcement_learning_tpu.observability import scopes
from distributed_reinforcement_learning_tpu.parallel.mesh import DATA_AXIS as _DATA_AXIS, P
from distributed_reinforcement_learning_tpu.runtime.anakin_mesh import (
    DataMeshReplayMixin,
    batched_specs,
    replay_specs,
)

_priority = device_replay.priority


class AnakinR2D2State(NamedTuple):
    train: Any  # common.TargetTrainState
    replay: DeviceReplay
    env: Any
    obs: jax.Array
    prev_action: jax.Array
    h: jax.Array
    c: jax.Array
    episodes: jax.Array  # [B] i32 recorded episodes (epsilon schedule)
    last_sync: jax.Array  # i32 train step of the last target sync
    rng: jax.Array


class AnakinR2D2(DataMeshReplayMixin):
    """R2D2 over a pure-JAX env with on-device prioritized replay.

    `num_envs` parallel envs collect one `seq_len` sequence each per
    update; `updates_per_collect` prioritized batches of `batch_size`
    train per collect. `capacity` must be a multiple of `num_envs` (ring
    writes stay aligned, no wrap-around split).
    """

    def __init__(self, agent: R2D2Agent, num_envs: int, batch_size: int = 32,
                 capacity: int = 4096, target_sync_interval: int = 100,
                 updates_per_collect: int = 1, epsilon_decay: float = 0.1,
                 epsilon_floor: float = 0.0, env=None, obs_transform=None,
                 mesh=None):
        self.env = env if env is not None else cartpole_jax
        self.agent = agent
        self.num_envs = num_envs
        self.batch_size = batch_size
        if capacity % num_envs != 0:
            raise ValueError(f"capacity ({capacity}) must be a multiple of "
                             f"num_envs ({num_envs})")
        self.capacity = capacity
        self.target_sync_interval = target_sync_interval
        if updates_per_collect > target_sync_interval:
            # Mirror of replay_train._init_stride: the learn scan cannot
            # target-sync mid-call, so K must not swallow whole intervals.
            raise ValueError(
                f"updates_per_collect ({updates_per_collect}) must not "
                f"exceed target_sync_interval ({target_sync_interval})")
        self.updates_per_collect = updates_per_collect
        self.epsilon_decay = epsilon_decay
        self.epsilon_floor = epsilon_floor
        self.obs_transform = obs_transform or (lambda x: x)
        if agent.cfg.num_actions < self.env.NUM_ACTIONS:
            raise ValueError(
                f"Q head ({agent.cfg.num_actions}) narrower than the env's "
                f"action set ({self.env.NUM_ACTIONS})")
        # Multi-chip: data-axis shard_map with per-device replay shards —
        # same design and argument as AnakinApex (runtime/anakin_mesh.py).
        self._setup_mesh(mesh, num_envs=num_envs, batch_size=batch_size,
                         capacity=capacity)
        self._greedy_eval_jit = jax.jit(self._greedy_eval,
                                        static_argnums=(1, 2))

    # -- sharding --------------------------------------------------------
    def _state_specs(self) -> AnakinR2D2State:
        """PartitionSpecs: per-env leaves and the sequence rings shard
        over `data`; TrainState and ring bookkeeping replicate."""
        train_abs = jax.eval_shape(self.agent.init_state, jax.random.PRNGKey(0))
        env_abs, _ = jax.eval_shape(
            lambda k: self.env.reset(k, self.num_envs), jax.random.PRNGKey(0))
        return AnakinR2D2State(
            train=jax.tree.map(lambda _: P(), train_abs),
            replay=replay_specs(R2D2Batch(0, 0, 0, 0, 0, 0, 0)),
            env=batched_specs(env_abs),
            obs=P(_DATA_AXIS), prev_action=P(_DATA_AXIS),
            h=P(_DATA_AXIS), c=P(_DATA_AXIS),
            episodes=P(_DATA_AXIS), last_sync=P(),
            rng=P(_DATA_AXIS),
        )

    # -- init ------------------------------------------------------------
    def init(self, rng: jax.Array) -> AnakinR2D2State:
        k_train, k_env, k_run = jax.random.split(rng, 3)
        train = self.agent.init_state(k_train)
        env, obs = self.env.reset(k_env, self.num_envs)
        obs = self.obs_transform(obs)
        h, c = self.agent.initial_lstm_state(self.num_envs)
        replay = device_replay.make(self._sequence_entries(), self.capacity)
        state = AnakinR2D2State(
            train=train, replay=replay, env=env, obs=obs,
            prev_action=jnp.zeros(self.num_envs, jnp.int32),
            h=h, c=c,
            episodes=jnp.zeros(self.num_envs, jnp.int32),
            last_sync=jnp.int32(0),
            rng=k_run,
        )
        return self._place_init(state, k_run)

    def _sequence_entries(self) -> R2D2Batch:
        """Shape and dtype of ONE stored sequence, leaf by leaf."""
        cfg = self.agent.cfg
        obs = jax.eval_shape(
            lambda: self.obs_transform(self.env.reset(
                jax.random.PRNGKey(0), 1)[1]))
        T = cfg.seq_len
        entry = jax.ShapeDtypeStruct
        return R2D2Batch(
            state=entry((T, *obs.shape[1:]), obs.dtype),
            previous_action=entry((T,), jnp.int32),
            action=entry((T,), jnp.int32),
            reward=entry((T,), jnp.float32),
            done=entry((T,), jnp.bool_),
            initial_h=entry((cfg.lstm_size,), jnp.float32),
            initial_c=entry((cfg.lstm_size,), jnp.float32),
        )

    # -- collection ------------------------------------------------------
    def _epsilon(self, episodes: jax.Array) -> jax.Array:
        return jnp.maximum(1.0 / (self.epsilon_decay * episodes + 1.0),
                           self.epsilon_floor)

    def _env_step(self, params, carry, _):
        env, obs, prev_action, h, c, episodes, rng = carry
        rng, k_act, k_env = jax.random.split(rng, 3)
        with jax.named_scope(scopes.ACT):
            action, q, new_h, new_c = self.agent._act(
                params, obs, h, c, prev_action, self._epsilon(episodes), k_act)
        with jax.named_scope(scopes.ENV):
            env_action = (action % self.env.NUM_ACTIONS
                          if self.agent.cfg.num_actions != self.env.NUM_ACTIONS
                          else action)
            env, next_obs, reward, done, ep_ret = self.env.step(
                env, env_action, k_env)
        with jax.named_scope(scopes.RECORD):
            mask_fn = getattr(self.env, "completed_episode_mask",
                              lambda done, _state: done)
            record = dict(
                state=obs, previous_action=prev_action, action=action,
                reward=reward, done=done, online_q=q, episode_return=ep_ret,
                episode_completed=mask_fn(done, env),
            )
            keep = (~done).astype(new_h.dtype)[:, None]
            carry = (env, self.obs_transform(next_obs),
                     jnp.where(done, 0, action).astype(jnp.int32),
                     new_h * keep, new_c * keep,
                     episodes + done.astype(jnp.int32), rng)
        return carry, record

    def _collect(self, state: AnakinR2D2State):
        """One seq_len unroll from all envs -> (state', R2D2Rollout
        `[T, B, ...]`, episode stats): the scan's own stacked record,
        nothing swapped.

        `rollout.online_q` is what `R2D2Net.unroll` gives over the
        sequences under `state.train.params`: it scores them and is not
        stored. Called by `_update` and `_collect_only`.
        """
        cfg = self.agent.cfg
        carry = (state.env, state.obs, state.prev_action, state.h, state.c,
                 state.episodes, state.rng)
        with jax.named_scope(scopes.COLLECT):
            carry, rec = jax.lax.scan(
                functools.partial(self._env_step, state.train.params), carry,
                None, length=cfg.seq_len)
            env, obs, prev_action, h, c, episodes, rng = carry
            rollout = R2D2Rollout(
                state=rec["state"], previous_action=rec["previous_action"],
                action=rec["action"], reward=rec["reward"], done=rec["done"],
                initial_h=state.h, initial_c=state.c,  # sequence-start state
                online_q=rec["online_q"])
        stats = {
            "episode_return_sum": rec["episode_return"].sum(),
            "episodes_done": rec["episode_completed"].sum().astype(jnp.float32),
            "boundaries_done": rec["done"].sum().astype(jnp.float32),
        }
        new_state = state._replace(env=env, obs=obs, prev_action=prev_action,
                                   h=h, c=c, episodes=episodes, rng=rng)
        return new_state, rollout, stats

    score_order = "time_major"  # static: `_ingest` is the one scoring path

    def _ingest(self, train, replay: DeviceReplay,
                rollout: R2D2Rollout) -> DeviceReplay:
        """Score + write B new sequences into the ring at `ptr`.

        The score reads the rollout as the scan wrote it, `[T, B, ...]`;
        the `[B, T, ...]` batch is made for the ring's write alone, which
        packs the frames to words on its own way from the same record.
        """
        with jax.named_scope(scopes.REPLAY_SCORE):
            errs = self.agent._td_error_time_major(train, rollout)  # [B]
        with jax.named_scope(scopes.REPLAY_WRITE):
            return device_replay.ingest(replay, rollout.batch(), errs)

    @jax.named_scope(scopes.REPLAY_SAMPLE)
    def _sample(self, replay: DeviceReplay, rng: jax.Array):
        return device_replay.sample(replay, rng, self.batch_local,
                                    axis_name=self._axis)

    # -- one update: collect, ingest, K prioritized steps ----------------
    def _update(self, state: AnakinR2D2State, _):
        state, rollout, stats = self._collect(state)
        replay = self._ingest(state.train, state.replay, rollout)
        train = state.train

        def one_learn(carry, _):
            train, replay, rng = carry
            rng, k = jax.random.split(rng)
            replay, batch, idx, weights = self._sample(replay, k)
            # `_learn` names itself (scopes.LEARN and below).
            train, new_err, metrics = self.agent._learn(
                train, batch, weights, axis_name=self._axis)
            with jax.named_scope(scopes.REPLAY_PRIORITIES):
                replay = device_replay.update_priorities(replay, idx, new_err)
            return (train, replay, rng), (metrics, weights.min())

        rng, k_learn = jax.random.split(state.rng)
        (train, replay, _), (metrics, weight_min) = jax.lax.scan(
            one_learn, (train, replay, k_learn), None,
            length=self.updates_per_collect)
        metrics = jax.tree.map(lambda m: m[-1], metrics)

        # Target sync on a steps-since-last cadence (the host stack's
        # replay_train._finish_train_call: a modulo misfires when K does
        # not divide the interval).
        do_sync = (train.step - state.last_sync) >= self.target_sync_interval
        train = jax.lax.cond(do_sync, lambda t: t.sync_target(), lambda t: t,
                             train)
        last_sync = jnp.where(do_sync, train.step, state.last_sync)
        metrics.update(self._psum(stats))
        metrics["replay_size"] = self._psum(replay.size.astype(jnp.float32))
        # Counters of the ring (alpha-transformed priorities; empty slots
        # hold 0) and of the learn steps of this update.
        metrics["priority_mean"] = self._pmean(
            replay.priorities.sum() / jnp.maximum(replay.size, 1))
        metrics["priority_max"] = self._pmax(replay.priorities.max())
        metrics["is_weight_min"] = -self._pmax(-weight_min.min())
        metrics["target_syncs"] = do_sync.astype(jnp.float32)
        metrics["epsilon_mean"] = self._pmean(
            self._epsilon(state.episodes).mean())
        return state._replace(train=train, replay=replay, rng=rng,
                              last_sync=last_sync), metrics

    def _train_chunk(self, state: AnakinR2D2State, num_updates: int):
        """U x (collect + K prioritized learns) in one compiled program."""
        return jax.lax.scan(self._update, state, None, length=num_updates)

    def _collect_only(self, state: AnakinR2D2State, _):
        state, rollout, stats = self._collect(state)
        replay = self._ingest(state.train, state.replay, rollout)
        return state._replace(replay=replay), self._psum(stats)

    def _collect_chunk(self, state: AnakinR2D2State, num_collects: int):
        """Warm-up: fill the ring without training (the host learner's
        `train_start_factor` gate, expressed as an explicit phase)."""
        return jax.lax.scan(self._collect_only, state, None, length=num_collects)

    # -- greedy evaluation (argmax-Q, fresh envs + LSTM, on-device) ------
    def _greedy_eval(self, params, num_envs: int, num_steps: int, rng):
        k_reset, k_run = jax.random.split(rng)
        env, obs = self.env.reset(k_reset, num_envs)
        obs = self.obs_transform(obs)
        h, c = self.agent.initial_lstm_state(num_envs)
        pa = jnp.zeros(num_envs, jnp.int32)
        mask_fn = getattr(self.env, "completed_episode_mask",
                          lambda done, _state: done)

        def step_fn(carry, k):
            env, obs, pa, h, c = carry
            # epsilon = 0 through the shared act path: pure argmax-Q.
            action, _q, new_h, new_c = self.agent._act(
                params, obs, h, c, pa, 0.0, k)
            env_action = (action % self.env.NUM_ACTIONS
                          if self.agent.cfg.num_actions != self.env.NUM_ACTIONS
                          else action)
            env, next_obs, _r, done, ep = self.env.step(env, env_action, k)
            keep = (~done).astype(new_h.dtype)[:, None]
            carry = (env, self.obs_transform(next_obs),
                     jnp.where(done, 0, action).astype(jnp.int32),
                     new_h * keep, new_c * keep)
            return carry, (ep, mask_fn(done, env))

        keys = jax.random.split(k_run, num_steps)
        _, (eps, completed) = jax.lax.scan(step_fn, (env, obs, pa, h, c), keys)
        return {
            "return_sum": (eps * completed.astype(jnp.float32)).sum(),
            "episodes": completed.sum().astype(jnp.int32),
        }

    def greedy_eval(self, params, num_envs: int, num_steps: int, rng) -> dict:
        """Deterministic (argmax-Q) score on fresh envs with the recurrent
        state carried across steps (same contract as AnakinImpala)."""
        out = self._greedy_eval_jit(params, num_envs, num_steps, rng)
        episodes = int(out["episodes"])
        return {
            "mean_return": float(out["return_sum"]) / max(episodes, 1),
            "episodes": episodes,
        }

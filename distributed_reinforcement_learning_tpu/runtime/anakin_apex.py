"""Anakin Ape-X: prioritized DQN training entirely on-device.

The third on-device family (after `runtime/anakin.py` IMPALA and
`runtime/anakin_r2d2.py` recurrent replay): the reference's
`train_apex.py` stack — epsilon-ladder actors pushing TD-scored
transitions into prioritized replay, a double-DQN learner with IS
weights and target syncs — expressed as one compiled program over a
jittable env. With the pixel envs (`envs/{breakout,pong}_jax.py`) this
trains the dueling conv network on real game dynamics at chip rate,
replay included: the transition ring (uint8 frame stacks) lives in
device memory via `data/device_replay.py`.

Semantics:
- actors: per-episode epsilon decay `1/(0.05*episodes+1)` (the
  reference's schedule, `train_apex.py:69`) with an optional floor;
  life-loss boundaries arrive as `done` from the pixel envs exactly as
  the host path's life-loss shaping records them;
- transitions: (s, prev_a, a, r, s', done) — `prev_a` embeds for s and
  `a` for s' (`agents/apex.py` ApexBatch contract); the auto-reset
  observation standing in for a terminal s' is harmless (its Q is
  masked by the zero discount);
- ingest scored by `agent.td_error` under current params; sampled
  priorities refreshed every step; IS-weighted double-DQN updates;
  target syncs on a steps-since-last cadence.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.agents.apex import ApexAgent, ApexBatch
from distributed_reinforcement_learning_tpu.data import device_replay
from distributed_reinforcement_learning_tpu.data.device_replay import DeviceReplay
from distributed_reinforcement_learning_tpu.envs import cartpole_jax
from distributed_reinforcement_learning_tpu.observability import scopes
from distributed_reinforcement_learning_tpu.runtime.anakin_mesh import (
    DataMeshReplayMixin,
    batched_specs,
    replay_specs,
)
from distributed_reinforcement_learning_tpu.parallel.mesh import DATA_AXIS as _DATA_AXIS, P


class AnakinApexState(NamedTuple):
    train: Any  # common.TargetTrainState
    replay: DeviceReplay
    env: Any
    obs: jax.Array
    prev_action: jax.Array
    episodes: jax.Array  # [B] i32 (epsilon schedule)
    last_sync: jax.Array  # i32 train step of the last target sync
    rng: jax.Array


class AnakinApex(DataMeshReplayMixin):
    """Ape-X over a pure-JAX env with on-device prioritized replay.

    Each update collects `steps_per_collect` transitions from all
    `num_envs` envs (write width W = num_envs * steps_per_collect;
    `capacity` must be a multiple of W), then runs
    `updates_per_collect` prioritized batches.
    """

    def __init__(self, agent: ApexAgent, num_envs: int, batch_size: int = 32,
                 capacity: int = 8192, steps_per_collect: int = 16,
                 target_sync_interval: int = 100, updates_per_collect: int = 1,
                 epsilon_decay: float = 0.05, epsilon_floor: float = 0.0,
                 env=None, obs_transform=None, mesh=None):
        self.env = env if env is not None else cartpole_jax
        self.agent = agent
        self.num_envs = num_envs
        self.batch_size = batch_size
        self.steps_per_collect = steps_per_collect
        self.write_width = num_envs * steps_per_collect
        if capacity % self.write_width != 0:
            raise ValueError(
                f"capacity ({capacity}) must be a multiple of num_envs * "
                f"steps_per_collect ({self.write_width})")
        self.capacity = capacity
        self.target_sync_interval = target_sync_interval
        if updates_per_collect > target_sync_interval:
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
        # Multi-chip: shard over the `data` axis ONLY, with PER-DEVICE
        # replay shards (see _state_specs). The replay families scale by
        # replicating the (small) dueling net and splitting envs + ring;
        # a global prioritized sampler over a capacity-sharded ring would
        # serialize every learn batch behind cross-chip gathers of frame
        # stacks, so each device samples its own shard locally and only
        # the GRADIENTS cross ICI (pmean in agents/apex.py _learn).
        # Tensor/pipeline axes stay with the IMPALA/transformer families.
        self._setup_mesh(mesh, num_envs=num_envs, batch_size=batch_size,
                         capacity=capacity)
        self.write_width_local = self.write_width // self.dshard
        self._greedy_eval_jit = jax.jit(self._greedy_eval,
                                        static_argnums=(1, 2))

    # -- sharding --------------------------------------------------------
    def _state_specs(self) -> AnakinApexState:
        """PartitionSpecs: per-env leaves and the replay rings shard over
        `data`; the TrainState and ring bookkeeping replicate (see
        runtime/anakin_mesh.py for the design argument)."""
        train_abs = jax.eval_shape(self.agent.init_state, jax.random.PRNGKey(0))
        env_abs, _ = jax.eval_shape(
            lambda k: self.env.reset(k, self.num_envs), jax.random.PRNGKey(0))
        return AnakinApexState(
            train=jax.tree.map(lambda _: P(), train_abs),
            replay=replay_specs(ApexBatch(0, 0, 0, 0, 0, 0)),
            env=batched_specs(env_abs),
            obs=P(_DATA_AXIS), prev_action=P(_DATA_AXIS),
            episodes=P(_DATA_AXIS), last_sync=P(),
            rng=P(_DATA_AXIS),
        )

    # -- init ------------------------------------------------------------
    def init(self, rng: jax.Array) -> AnakinApexState:
        k_train, k_env, k_run = jax.random.split(rng, 3)
        train = self.agent.init_state(k_train)
        env, obs = self.env.reset(k_env, self.num_envs)
        obs = self.obs_transform(obs)
        replay = device_replay.make(self._transition_entries(obs), self.capacity)
        state = AnakinApexState(
            train=train, replay=replay, env=env, obs=obs,
            prev_action=jnp.zeros(self.num_envs, jnp.int32),
            episodes=jnp.zeros(self.num_envs, jnp.int32),
            last_sync=jnp.int32(0),
            rng=k_run,
        )
        return self._place_init(state, k_run)

    def _transition_entries(self, obs: jax.Array) -> ApexBatch:
        """Shape and dtype of ONE stored transition, leaf by leaf."""
        entry = jax.ShapeDtypeStruct
        frame = entry(obs.shape[1:], obs.dtype)
        return ApexBatch(
            state=frame, next_state=frame,
            previous_action=entry((), jnp.int32),
            action=entry((), jnp.int32),
            reward=entry((), jnp.float32),
            done=entry((), jnp.bool_),
        )

    # -- collection ------------------------------------------------------
    def _epsilon(self, episodes: jax.Array) -> jax.Array:
        return jnp.maximum(1.0 / (self.epsilon_decay * episodes + 1.0),
                           self.epsilon_floor)

    def _env_step(self, params, carry, _):
        env, obs, prev_action, episodes, rng = carry
        rng, k_act, k_env = jax.random.split(rng, 3)
        action, _q = self.agent._act(
            params, obs, prev_action, self._epsilon(episodes), k_act)
        env_action = (action % self.env.NUM_ACTIONS
                      if self.agent.cfg.num_actions != self.env.NUM_ACTIONS
                      else action)
        env, next_obs, reward, done, ep_ret = self.env.step(env, env_action, k_env)
        next_obs = self.obs_transform(next_obs)
        mask_fn = getattr(self.env, "completed_episode_mask",
                          lambda done, _state: done)
        record = dict(
            state=obs, next_state=next_obs, previous_action=prev_action,
            action=action, reward=reward, done=done,
            episode_return=ep_ret, episode_completed=mask_fn(done, env),
        )
        carry = (env, next_obs, jnp.where(done, 0, action).astype(jnp.int32),
                 episodes + done.astype(jnp.int32), rng)
        return carry, record

    def _collect(self, state: AnakinApexState):
        """steps_per_collect env steps -> (state', flat ApexBatch [W],
        episode stats). Under a mesh this body runs per-device on the
        local env shard, so the flat width is the LOCAL one."""
        carry = (state.env, state.obs, state.prev_action, state.episodes,
                 state.rng)
        with jax.named_scope(scopes.COLLECT):
            carry, rec = jax.lax.scan(
                functools.partial(self._env_step, state.train.params), carry,
                None, length=self.steps_per_collect)
        env, obs, prev_action, episodes, rng = carry
        flat = lambda name: rec[name].reshape((self.write_width_local,)
                                              + rec[name].shape[2:])
        batch = ApexBatch(
            state=flat("state"), next_state=flat("next_state"),
            previous_action=flat("previous_action"), action=flat("action"),
            reward=flat("reward"), done=flat("done"),
        )
        stats = {
            "episode_return_sum": rec["episode_return"].sum(),
            "episodes_done": rec["episode_completed"].sum().astype(jnp.float32),
            "boundaries_done": rec["done"].sum().astype(jnp.float32),
        }
        new_state = state._replace(env=env, obs=obs, prev_action=prev_action,
                                   episodes=episodes, rng=rng)
        return new_state, batch, stats

    @jax.named_scope(scopes.REPLAY)
    def _ingest(self, train, replay: DeviceReplay, batch: ApexBatch
                ) -> DeviceReplay:
        errs = self.agent._td_error(train, batch)  # [W]
        return device_replay.ingest(replay, batch, errs)

    # -- one update: collect, ingest, K prioritized steps ----------------
    def _update(self, state: AnakinApexState, _):
        state, trans, stats = self._collect(state)
        replay = self._ingest(state.train, state.replay, trans)
        train = state.train

        def one_learn(carry, _):
            train, replay, rng = carry
            rng, k = jax.random.split(rng)
            with jax.named_scope(scopes.REPLAY):
                replay, batch, idx, weights = device_replay.sample(
                    replay, k, self.batch_local, axis_name=self._axis)
            with jax.named_scope(scopes.LEARN):
                train, td, metrics = self.agent._learn(
                    train, batch, weights, axis_name=self._axis)
            with jax.named_scope(scopes.REPLAY):
                replay = device_replay.update_priorities(replay, idx, td)
            return (train, replay, rng), metrics

        rng, k_learn = jax.random.split(state.rng)
        (train, replay, _), metrics = jax.lax.scan(
            one_learn, (train, replay, k_learn), None,
            length=self.updates_per_collect)
        metrics = jax.tree.map(lambda m: m[-1], metrics)

        do_sync = (train.step - state.last_sync) >= self.target_sync_interval
        train = jax.lax.cond(do_sync, lambda t: t.sync_target(), lambda t: t,
                             train)
        last_sync = jnp.where(do_sync, train.step, state.last_sync)
        metrics.update(self._psum(stats))
        metrics["replay_size"] = self._psum(replay.size.astype(jnp.float32))
        metrics["epsilon_mean"] = self._pmean(
            self._epsilon(state.episodes).mean())
        return state._replace(train=train, replay=replay, rng=rng,
                              last_sync=last_sync), metrics

    def _train_chunk(self, state: AnakinApexState, num_updates: int):
        """U x (collect + K prioritized learns) in one compiled program."""
        return jax.lax.scan(self._update, state, None, length=num_updates)

    def _collect_only(self, state: AnakinApexState, _):
        state, trans, stats = self._collect(state)
        replay = self._ingest(state.train, state.replay, trans)
        return state._replace(replay=replay), self._psum(stats)

    def _collect_chunk(self, state: AnakinApexState, num_collects: int):
        """Warm-up: fill the ring without training."""
        return jax.lax.scan(self._collect_only, state, None, length=num_collects)

    # -- greedy evaluation (argmax-Q, fresh envs, all on-device) ---------
    def _greedy_eval(self, params, num_envs: int, num_steps: int, rng):
        k_reset, k_run = jax.random.split(rng)
        env, obs = self.env.reset(k_reset, num_envs)
        obs = self.obs_transform(obs)
        pa = jnp.zeros(num_envs, jnp.int32)
        mask_fn = getattr(self.env, "completed_episode_mask",
                          lambda done, _state: done)

        def step_fn(carry, k):
            env, obs, pa = carry
            # epsilon = 0 through the shared act path: pure argmax-Q.
            action, _q = self.agent._act(params, obs, pa, 0.0, k)
            env_action = (action % self.env.NUM_ACTIONS
                          if self.agent.cfg.num_actions != self.env.NUM_ACTIONS
                          else action)
            env, next_obs, _r, done, ep = self.env.step(env, env_action, k)
            carry = (env, self.obs_transform(next_obs),
                     jnp.where(done, 0, action).astype(jnp.int32))
            return carry, (ep, mask_fn(done, env))

        keys = jax.random.split(k_run, num_steps)
        _, (eps, completed) = jax.lax.scan(step_fn, (env, obs, pa), keys)
        return {
            "return_sum": (eps * completed.astype(jnp.float32)).sum(),
            "episodes": completed.sum().astype(jnp.int32),
        }

    def greedy_eval(self, params, num_envs: int, num_steps: int, rng) -> dict:
        """Deterministic (argmax-Q) score on fresh envs — the ground-truth
        metric behind the behavior curves, which keep the epsilon ladder's
        exploration mixed in (same contract as AnakinImpala.greedy_eval)."""
        out = self._greedy_eval_jit(params, num_envs, num_steps, rng)
        episodes = int(out["episodes"])
        return {
            "mean_return": float(out["return_sum"]) / max(episodes, 1),
            "episodes": episodes,
        }

"""Anakin-style fully-on-device IMPALA: collect + learn inside one jit.

The reference's architecture (and this repo's runner topology) moves
trajectories host->queue->device every step. The Podracer "Anakin"
pattern (arXiv:2104.06272) removes the host entirely for jittable envs:
the env step, the act step, the trajectory buffer, and the optimizer
update all live inside ONE compiled program — `train_chunk` runs U
updates x T env steps x B envs per dispatch with zero host round-trips
and zero H2D traffic. This is the configuration the TPU makes possible
and a process-per-actor design cannot express; it complements (not
replaces) the socket topology, which exists for envs that aren't pure
functions (ALE, robotics).

Semantics per update, matching `runtime/impala_runner.py`:
- on-policy collection with the CURRENT params (behavior == target
  policy, so V-trace's importance ratios are exactly 1 — the off-policy
  correction margin exists for the distributed topology's staleness);
- stored-state LSTM: each timestep records the pre-act (h, c), the
  learner re-applies from those (SURVEY §2 rows 2/12);
- (h, c) zeroed and prev_action reset at episode boundaries.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.agents.common import TrainState
from distributed_reinforcement_learning_tpu.agents.impala import (
    ImpalaAgent, ImpalaBatch, ImpalaRollout)
from distributed_reinforcement_learning_tpu.envs import cartpole_jax
from distributed_reinforcement_learning_tpu.observability import scopes


class AnakinState(NamedTuple):
    train: TrainState
    env: Any  # the env module's own state NamedTuple
    obs: jax.Array  # [B, *obs_shape]
    prev_action: jax.Array  # [B] i32
    h: jax.Array  # [B, H]
    c: jax.Array  # [B, H]
    rng: jax.Array


class AnakinImpala:
    """IMPALA over a pure-JAX env, everything on-device.

    `env` is any module following the `cartpole_jax` contract
    (`OBS_SHAPE`, `NUM_ACTIONS`, `reset(rng, n) -> (state, obs)`,
    `step(state, actions, rng) -> (state, obs, reward, done, ep_ret)`) —
    `envs.cartpole_jax` (default) or `envs.breakout_jax`, the pixel env
    that makes chip-rate Breakout training possible in this image.
    `num_envs` is the batch dim B; `agent.cfg.trajectory` the unroll T.
    A policy head wider than the env's action set is aliased with
    `action % NUM_ACTIONS`, the reference's convention
    (`train_impala.py:145`).
    """

    def __init__(self, agent: ImpalaAgent, num_envs: int, mesh=None, env=None):
        self.env = env if env is not None else cartpole_jax
        if tuple(agent.cfg.obs_shape) != tuple(self.env.OBS_SHAPE):
            raise ValueError(
                f"env obs shape {self.env.OBS_SHAPE} != "
                f"config obs_shape={agent.cfg.obs_shape}")
        if agent.cfg.num_actions < self.env.NUM_ACTIONS:
            raise ValueError(
                f"policy head ({agent.cfg.num_actions}) narrower than the "
                f"env's action set ({self.env.NUM_ACTIONS})")
        self.agent = agent
        self.num_envs = num_envs
        self.mesh = mesh
        # No donation: the freshly-init state's zero-filled leaves (env
        # counters, LSTM state, prev_action) can alias one deduped
        # constant buffer, which donation rejects. The undonated state is
        # held twice: 0.154 GB each at 2,048 Breakout envs (PR 25).
        if mesh is None:
            self.train_chunk = jax.jit(scopes.tagged(self._train_chunk),
                                       static_argnums=(1,))
        else:
            # Multi-chip Anakin: envs shard over the `data` axis (each
            # chip steps + acts on its env shard), the TrainState follows
            # the structural mesh rule (replicated, or model-sharded
            # kernels) — XLA inserts the gradient psum over ICI. Same
            # program, N chips, no host between them.
            from distributed_reinforcement_learning_tpu.parallel import (
                data_sharding, replicated)
            from distributed_reinforcement_learning_tpu.parallel.mesh import traced_on
            from distributed_reinforcement_learning_tpu.parallel.learner import (
                train_state_sharding)

            data = data_sharding(mesh)
            repl = replicated(mesh)
            if num_envs % mesh.shape.get("data", 1) != 0:
                raise ValueError(
                    f"num_envs ({num_envs}) must divide over the data axis "
                    f"({mesh.shape.get('data', 1)})")
            abstract = jax.eval_shape(agent.init_state, jax.random.PRNGKey(0))
            train_sh = train_state_sharding(mesh, abstract)
            env_abstract, _ = jax.eval_shape(
                lambda k: self.env.reset(k, num_envs), jax.random.PRNGKey(0))
            self._state_sharding = AnakinState(
                train=train_sh,
                env=jax.tree.map(lambda _: data, env_abstract),
                obs=data, prev_action=data, h=data, c=data, rng=repl,
            )
            # The V-trace kernel wraps itself per device only under a
            # context mesh; without one the chunk does not lower on a
            # multi-chip TPU host.
            self.train_chunk = jax.jit(
                scopes.tagged(traced_on(mesh, self._train_chunk)),
                static_argnums=(1,),
                in_shardings=(self._state_sharding,),
                out_shardings=(self._state_sharding, repl),
            )
        self._greedy_eval_jit = jax.jit(self._greedy_eval, static_argnums=(1, 2))

    @property
    def handoff(self) -> str:
        """How `_update` hands the rollout to the learner, a static fact
        of the compiled chunk. One chip: `time_major`, as the scan wrote
        it, nothing transposed. A mesh shards B over `data`, and
        `[T, B/n]` flattened to `T*B` is not contiguous per shard: the
        partitioner all-gathers the whole frame batch (compiled for a
        described v5e:2x2, PR 29), where `[B/n, T]` flattens in place; so
        a mesh swaps every field to `[B, T, ...]`: `batch_major`.
        Then the dtype the learner's network gets the frames in: `uint8`
        says they stay bytes until conv0 (`agents/common.prep_obs`)."""
        layout = "time_major" if self.mesh is None else "batch_major"
        _, obs = jax.eval_shape(
            lambda k: self.env.reset(k, self.num_envs), jax.random.PRNGKey(0))
        frames = jax.eval_shape(self.agent._prep_obs, obs).dtype
        return f"{layout}, frames {frames}"

    def init(self, rng: jax.Array) -> AnakinState:
        # Three distinct streams: params init, env reset, and the ongoing
        # rollout chain (reusing the parent key would make the first act
        # key collide with the env-reset key under partitionable threefry).
        k_train, k_env, k_run = jax.random.split(rng, 3)
        train = self.agent.init_state(k_train)
        env, obs = self.env.reset(k_env, self.num_envs)
        h, c = self.agent.initial_lstm_state(self.num_envs)
        state = AnakinState(
            train=train,
            env=env,
            obs=obs,
            prev_action=jnp.zeros(self.num_envs, jnp.int32),
            h=h,
            c=c,
            rng=k_run,
        )
        if self.mesh is not None:
            state = jax.device_put(state, self._state_sharding)
        return state

    def _env_action(self, action: jax.Array) -> jax.Array:
        """Alias a wider policy head onto the env's action set
        (`action % available_action`, `train_impala.py:145`)."""
        if self.agent.cfg.num_actions != self.env.NUM_ACTIONS:
            return action % self.env.NUM_ACTIONS
        return action

    # -- one env step (scanned T times per update) -----------------------
    def _env_step(self, params, carry, _):
        env, obs, prev_action, h, c, rng = carry
        rng, k_act, k_env = jax.random.split(rng, 3)
        with jax.named_scope(scopes.ACT):
            out = self.agent._act(params, obs, prev_action, h, c, k_act)
        with jax.named_scope(scopes.ENV):
            env, next_obs, reward, done, ep_ret = self.env.step(
                env, self._env_action(out.action), k_env)
        with jax.named_scope(scopes.RECORD):
            mask_fn = getattr(self.env, "completed_episode_mask",
                              lambda done, _state: done)
            record = dict(
                state=obs,
                reward=reward,
                done=done,
                action=out.action,
                behavior_policy=out.policy,
                previous_action=prev_action,
                initial_h=h,
                initial_c=c,
                episode_return=ep_ret,
                # True episode ends (life-loss `done`s excluded), so chunk
                # metrics can report a real mean completed-episode return.
                episode_completed=mask_fn(done, env),
            )
            keep = (~done).astype(out.h.dtype)[:, None]
            carry = (env, next_obs,
                     jnp.where(done, 0, out.action).astype(jnp.int32),
                     out.h * keep, out.c * keep, rng)
        return carry, record

    # -- one update: T-step collect then learn ---------------------------
    def _update(self, state: AnakinState, _):
        T = self.agent.cfg.trajectory
        carry = (state.env, state.obs, state.prev_action, state.h, state.c, state.rng)
        with jax.named_scope(scopes.COLLECT):
            carry, rec = jax.lax.scan(
                functools.partial(self._env_step, state.train.params), carry,
                None, length=T)
        env, obs, prev_action, h, c, rng = carry
        # rec fields are [T, B, ...]. The learners name themselves
        # (scopes.LEARN and below).
        if self.mesh is None:  # see `handoff`
            rollout = ImpalaRollout(**{f: rec[f] for f in ImpalaRollout._fields})
            train, metrics = self.agent._learn_time_major(state.train, rollout)
        else:
            with jax.named_scope(scopes.TO_BATCH_MAJOR):
                batch = ImpalaBatch(**{f: jnp.swapaxes(rec[f], 0, 1)
                                       for f in ImpalaBatch._fields})
            train, metrics = self.agent._learn(state.train, batch)
        metrics["episode_return_sum"] = rec["episode_return"].sum()
        # Real episode ends; for life-loss envs rec["done"] also fires on
        # boundaries, which would skew a mean-return-per-episode metric.
        metrics["episodes_done"] = rec["episode_completed"].sum().astype(jnp.float32)
        metrics["boundaries_done"] = rec["done"].sum().astype(jnp.float32)
        new_state = AnakinState(train, env, obs, prev_action, h, c, rng)
        return new_state, metrics

    def _train_chunk(self, state: AnakinState, num_updates: int):
        """U updates in one compiled program -> (state, stacked metrics)."""
        return jax.lax.scan(self._update, state, None, length=num_updates)

    # -- greedy evaluation (argmax policy, fresh envs, all on-device) ----
    def _greedy_eval(self, params, num_envs: int, num_steps: int, rng):
        k_reset, k_run = jax.random.split(rng)
        env, obs = self.env.reset(k_reset, num_envs)
        h, c = self.agent.initial_lstm_state(num_envs)
        pa = jnp.zeros(num_envs, jnp.int32)
        mask_fn = getattr(self.env, "completed_episode_mask",
                          lambda done, _state: done)

        def step_fn(carry, k):
            env, obs, pa, h, c = carry
            out = self.agent.model.apply(
                params, self.agent._prep_obs(obs), pa, h, c)
            action = jnp.argmax(out.policy, axis=-1).astype(jnp.int32)
            env, next_obs, _r, done, ep = self.env.step(
                env, self._env_action(action), k)
            keep = (~done).astype(out.h.dtype)[:, None]
            carry = (env, next_obs, jnp.where(done, 0, action),
                     out.h * keep, out.c * keep)
            return carry, (ep, mask_fn(done, env))

        keys = jax.random.split(k_run, num_steps)
        _, (eps, completed) = jax.lax.scan(
            step_fn, (env, obs, pa, h, c), keys)
        return {
            "return_sum": (eps * completed.astype(jnp.float32)).sum(),
            "episodes": completed.sum().astype(jnp.int32),
        }

    def greedy_eval(self, params, num_envs: int, num_steps: int, rng) -> dict:
        """Deterministic (argmax) policy score on fresh envs.

        -> {"mean_return", "episodes"}: completed-episode mean over a
        `num_steps`-step rollout of `num_envs` parallel games — the
        ground-truth score metric the behavior-policy return curves
        approximate (`benchmarks/longrun/ANALYSIS.md` showed best-window
        behavior returns can be pure order-statistic noise).
        """
        out = self._greedy_eval_jit(params, num_envs, num_steps, rng)
        episodes = int(out["episodes"])
        return {
            "mean_return": float(out["return_sum"]) / max(episodes, 1),
            "episodes": episodes,
        }

"""IMPALA actor/learner loops.

Re-design of the reference's `train_impala.py:89-194` launcher bodies as
composable runner objects:

- `ImpalaActor`: N batched envs, ONE jitted act per timestep (vs one
  `sess.run` per env step, SURVEY §3.5), per-unroll weight pull
  (`train_impala.py:135`), life-loss shaping (`:149-154`), T-step unroll
  accumulation, trajectory put with backpressure.
- `ImpalaLearner`: drains stacked batches from the queue (one host call,
  not 32 RPCs — `buffer_queue.py:416-435`), runs the jitted learn step,
  publishes versioned weights.
- `run_sync`: deterministic interleaved actor/learner stepping (tests,
  single-process training). `run_async`: free-running threads, the
  reference's process topology collapsed to one process.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

from distributed_reinforcement_learning_tpu.agents.impala import ActOutput, ImpalaAgent, ImpalaConfig
from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue, put_round, stack_pytrees
from distributed_reinforcement_learning_tpu.data.structures import (
    ImpalaTrajectoryAccumulator,
    SlicedAccumulators,
)
from distributed_reinforcement_learning_tpu.runtime.actor_pipeline import (
    PipelineSlice,
    run_actor_thread,
    shape_life_loss,
    slice_seed,
    split_batched_env,
    sync_slices_params,
)
from distributed_reinforcement_learning_tpu.envs.batched import completed_returns
from distributed_reinforcement_learning_tpu.observability import TELEMETRY as _OBS
from distributed_reinforcement_learning_tpu.runtime.publishing import PublishCadenceMixin
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore
from distributed_reinforcement_learning_tpu.utils.logger import MetricsLogger
from distributed_reinforcement_learning_tpu.utils.profiling import ProfilerSession, StageTimer


class ImpalaActor:
    def __init__(
        self,
        agent: ImpalaAgent,
        env,  # VectorEnv-like: reset() -> [N, ...], step([N]) -> obs, r, done, infos
        queue: TrajectoryQueue,
        weights: WeightStore,
        seed: int = 0,
        available_action: int | None = None,
        life_loss_shaping: bool = False,
        remote_act=None,  # SEED-style: RemoteInference; no weight pulls at all
    ):
        self.agent = agent
        self.env = env
        self.queue = queue
        self.weights = weights
        self.available_action = available_action
        self.life_loss_shaping = life_loss_shaping
        self.remote_act = remote_act

        self._seed = seed  # slice seeds derive from it (actor_pipeline)
        self._rng = jax.random.PRNGKey(seed)
        self._obs = env.reset()
        n = self._obs.shape[0]
        self._prev_action = np.zeros(n, np.int32)
        h, c = agent.initial_lstm_state(n)
        self._h, self._c = np.asarray(h), np.asarray(c)
        self._params = None
        self._version = -1
        self._lives = np.full(n, -1)
        self.episode_returns: list[float] = []

    def _sync_params(self) -> None:
        """Per-unroll weight pull (`train_impala.py:135`)."""
        got = self.weights.get_if_newer(self._version)
        if got is not None:
            self._params, self._version = got

    def run_unroll(self) -> int:
        """Collect one T-step unroll from all N envs; enqueue N trajectories.

        Returns the number of env frames generated (N * T).
        """
        cfg = self.agent.cfg
        if self.remote_act is None:
            self._sync_params()
            if self._params is None:
                raise RuntimeError("no weights published yet")
        acc = ImpalaTrajectoryAccumulator()
        n = self._obs.shape[0]

        for _ in range(cfg.trajectory):
            if self.remote_act is not None:
                # Centralized inference: the learner acts for us with its
                # newest weights (zero staleness, no local params).
                r = self.remote_act({"obs": self._obs, "prev_action": self._prev_action,
                                     "h": self._h, "c": self._c})
                out = ActOutput(r["action"], r["policy"], r["h"], r["c"])
            else:
                self._rng, sub = jax.random.split(self._rng)
                out = self.agent.act(
                    self._params, self._obs, self._prev_action, self._h, self._c, sub)
            actions = np.asarray(out.action)
            env_actions = actions % self.available_action if self.available_action else actions
            next_obs, reward, done, infos = self.env.step(env_actions)

            # Life-loss shaping (`train_impala.py:149-154`): a lost life is
            # recorded as r=-1, done=True while the env keeps running.
            # One definition for sequential and slice paths (actor_pipeline).
            rec_reward, rec_done = reward.astype(np.float32), done.copy()
            if self.life_loss_shaping:
                rec_reward, rec_done, self._lives = shape_life_loss(
                    self._lives, reward, done, infos)

            acc.append(
                state=self._obs,
                reward=rec_reward,
                done=rec_done,
                action=actions,
                behavior_policy=np.asarray(out.policy),
                previous_action=self._prev_action,
                initial_h=self._h,
                initial_c=self._c,
            )

            keep = (~done).astype(np.float32)[:, None]
            self._h = np.asarray(out.h) * keep
            self._c = np.asarray(out.c) * keep
            self._prev_action = np.where(done, 0, actions).astype(np.int32)
            self._obs = next_obs
            # No positivity filter: Pong-class envs finish with NEGATIVE
            # returns, and a 0-point Breakout episode is still an episode
            # (the old `ret > 0` guard silently recorded "no episodes" on
            # Pong and inflated Breakout stats).
            for ret in completed_returns(infos, done):
                self.episode_returns.append(float(ret))

        # Timed separately from the enclosing actor_round span: this is
        # the encode+PUT stage the codec fast path (schema cache /
        # DRL_OBS_DEDUP dedup / DRL_PUT_BATCH) optimizes — obs_report's
        # stage table shows its p50/p99 directly.
        with _OBS.span("actor_put"):
            put_round(self.queue, acc.extract())
        return n * cfg.trajectory

    # -- slice protocol (runtime/actor_pipeline.py) --------------------
    # Each slice is the sequential loop's per-step math over its own
    # env subset, RNG stream, carry and accumulator: with frozen
    # weights, a pipelined slice's trajectories are bit-identical to a
    # plain ImpalaActor built over that slice (test-pinned).

    def pipeline_round_steps(self) -> int:
        return self.agent.cfg.trajectory

    def pipeline_make_slices(self, k: int) -> list[PipelineSlice]:
        self._slice_accs = SlicedAccumulators(ImpalaTrajectoryAccumulator, k)
        slices = []
        lo = 0
        for i, env in enumerate(split_batched_env(self.env, k)):
            hi = lo + env.num_envs
            h, c = self.agent.initial_lstm_state(env.num_envs)
            seed = slice_seed(self._seed, i)
            slices.append(PipelineSlice(
                i, env, seed,
                rng=jax.random.PRNGKey(seed),
                obs=self._obs[lo:hi].copy(),
                prev_action=np.zeros(env.num_envs, np.int32),
                h=np.asarray(h), c=np.asarray(c),
                lives=np.full(env.num_envs, -1),
            ))
            lo = hi
        return slices

    # One weights RPC per round, shared by all slices (actor_pipeline
    # calls this before any slice_begin_round).
    pipeline_sync_weights = sync_slices_params

    def slice_begin_round(self, sl: PipelineSlice, steps: int) -> None:
        if self.remote_act is None and sl.params is None:
            raise RuntimeError("no weights published yet")
        self._slice_accs.reset_slice(sl.index)

    def slice_act(self, sl: PipelineSlice) -> ActOutput:
        """Runs on the pipeline's act worker thread; returns HOST arrays
        so the main thread's step never blocks on XLA."""
        if self.remote_act is not None:
            r = self.remote_act({"obs": sl.obs, "prev_action": sl.prev_action,
                                 "h": sl.h, "c": sl.c})
            out = ActOutput(r["action"], r["policy"], r["h"], r["c"])
        else:
            sl.rng, sub = jax.random.split(sl.rng)
            out = self.agent.act(
                sl.params, sl.obs, sl.prev_action, sl.h, sl.c, sub)
        return ActOutput(np.asarray(out.action), np.asarray(out.policy),
                         np.asarray(out.h), np.asarray(out.c))

    def slice_step(self, sl: PipelineSlice, out: ActOutput) -> tuple:
        actions = out.action
        env_actions = actions % self.available_action if self.available_action else actions
        next_obs, reward, done, infos = sl.env.step(env_actions)
        rec_reward, rec_done = reward.astype(np.float32), done.copy()
        if self.life_loss_shaping:
            rec_reward, rec_done, sl.lives = shape_life_loss(
                sl.lives, reward, done, infos)
        self._slice_accs.append_slice(
            sl.index,
            state=sl.obs,
            reward=rec_reward,
            done=rec_done,
            action=actions,
            behavior_policy=out.policy,
            previous_action=sl.prev_action,
            initial_h=sl.h,
            initial_c=sl.c,
        )
        keep = (~done).astype(np.float32)[:, None]
        sl.h = out.h * keep
        sl.c = out.c * keep
        sl.prev_action = np.where(done, 0, actions).astype(np.int32)
        sl.obs = next_obs
        for ret in completed_returns(infos, done):
            sl.episode_returns.append(float(ret))
        return ()

    def slice_end_round(self, sl: PipelineSlice) -> tuple:
        return (("round", self._slice_accs.extract_slice(sl.index)),)


class ImpalaLearner(PublishCadenceMixin):
    def __init__(
        self,
        agent: ImpalaAgent,
        queue: TrajectoryQueue,
        weights: WeightStore,
        batch_size: int,
        logger: MetricsLogger | None = None,
        rng: jax.Array | None = None,
        prefetch: bool = False,
        mesh=None,
        publish_interval: int = 1,
        updates_per_call: int = 1,
    ):
        self.agent = agent
        self.queue = queue
        self.weights = weights
        self.batch_size = batch_size
        # K>1: dequeue K batches and run them as ONE lax.scan dispatch
        # (learn_many). Strips the per-step dispatch gap (not measured
        # on the attached chip) at the price of weights publishing at
        # K-step granularity. Works single-jit and over a
        # mesh (ShardedLearner.learn_many scans the pjit-sharded step).
        self.updates_per_call = max(1, int(updates_per_call))
        self.logger = logger or MetricsLogger(None)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        # Multi-chip learner: pjit the learn step over the mesh, batch
        # sharded on the data axis, params/moments replicated or
        # model-sharded (parallel/learner.py). The reference has no
        # equivalent — its learner is one process's TF variables.
        self._batch_sharding = None
        if mesh is not None:
            from distributed_reinforcement_learning_tpu.parallel import ShardedLearner, data_sharding

            self._sharded = ShardedLearner(agent, mesh)
            self._learn = self._sharded.learn
            self._learn_many = self._sharded.learn_many
            self._batch_sharding = data_sharding(mesh)
        else:
            self._sharded = None
            self._learn = agent.learn
            self._learn_many = agent.learn_many
        # Double-buffered host->device pipeline (SURVEY §7 hard part (a)):
        # batch k+1 is dequeued/stacked/device_put while batch k trains.
        # Off in sync/test mode (a background consumer would race the
        # deterministic interleave).
        self._prefetcher = None
        if prefetch:
            from distributed_reinforcement_learning_tpu.data.prefetch import DevicePrefetcher

            # With updates_per_call=K the prefetcher stacks K dequeues into
            # one [K, B, ...] batch on its background thread, feeding
            # learn_many directly (over a mesh, with the stack's own spec).
            self._prefetcher = DevicePrefetcher(
                queue, batch_size, sharding=self._batch_sharding,
                stack_calls=self.updates_per_call,
                stack_sharding=(self._sharded.stacked_data_sharding
                                if self._sharded is not None
                                and self.updates_per_call > 1 else None))
        # Publish cadence: every step (interval=1, reference-parity
        # freshness) forces a full D2H param copy + device sync per step.
        # interval=K lets K device steps pipeline back-to-back before the
        # next host sync — a real TPU throughput lever at the cost of
        # actors acting on weights up to K-1 updates staler (V-trace
        # already corrects exactly this off-policyness).
        self.publish_interval = max(1, publish_interval)
        self.state = (
            self._sharded.init_state(rng) if self._sharded is not None
            else agent.init_state(rng)
        )
        self.train_steps = 0
        self.frames_learned = 0
        self.timer = StageTimer(self.logger)
        self._profiler = ProfilerSession.from_env()
        weights.publish(self.state.params, 0)  # pump is mixin-lazy

    def save_checkpoint(self, ckpt) -> None:
        """Persist TrainState + host counters (the checkpoint the reference
        built a Saver for but never invoked, `agent/impala.py:103`)."""
        ckpt.save(self.train_steps, self.state,
                  {"train_steps": self.train_steps, "frames_learned": self.frames_learned})

    def restore_checkpoint(self, ckpt) -> bool:
        """Resume from the latest checkpoint; republishes restored weights."""
        got = ckpt.restore(self.state)
        if got is None:
            return False
        self.state, extra, _ = got
        self.train_steps = int(extra.get("train_steps", 0))
        self.frames_learned = int(extra.get("frames_learned", 0))
        self.weights.publish(self.state.params, self.train_steps)
        self._last_publish_step = self.train_steps  # the line above IS a publish
        return True

    def step(self, timeout: float | None = None) -> dict | None:
        """One train call: drain a batch (or K batches), learn, publish.

        With `updates_per_call` K > 1 this is K optimizer steps in one
        `learn_many` dispatch; the returned metrics are the LAST scanned
        step's (device arrays on non-publish steps, as for K=1)."""
        K = self.updates_per_call
        parts: list = []
        with self.timer.stage("dequeue"):
            if self._prefetcher is not None:
                batch = self._prefetcher.get_batch(timeout=timeout)
            elif K > 1:
                # One deadline across the whole drain: `timeout` bounds
                # this call, not each of the K dequeues.
                deadline = None if timeout is None else time.monotonic() + timeout
                while len(parts) < K:
                    left = (None if deadline is None
                            else max(0.0, deadline - time.monotonic()))
                    b = self.queue.get_batch(self.batch_size, timeout=left)
                    if b is None:
                        break
                    parts.append(b)
                # Full drain -> one [K, ...] scan; partial drain -> the
                # drained batches train sequentially below (never dropped).
                batch = stack_pytrees(parts) if len(parts) == K else None
            else:
                batch = self.queue.get_batch(self.batch_size, timeout=timeout)
        if batch is None and not parts:
            return None
        steps_done = K if batch is not None or K == 1 else len(parts)
        with self.timer.stage("learn"):
            place = None
            if self._batch_sharding is not None and self._prefetcher is None:
                from distributed_reinforcement_learning_tpu.parallel import place_local_batch

                place = place_local_batch
            if K > 1 and batch is not None:
                if place is not None:
                    batch = place(batch, self._sharded.stacked_data_sharding)
                self.state, stacked = self._learn_many(self.state, batch)
                metrics = jax.tree.map(lambda x: x[-1], stacked)
            elif K > 1:
                for b in parts:
                    if place is not None:
                        b = place(b, self._batch_sharding)
                    self.state, metrics = self._learn(self.state, b)
            else:
                if place is not None:
                    batch = place(batch, self._batch_sharding)
                self.state, metrics = self._learn(self.state, batch)
        self.train_steps += steps_done
        self.frames_learned += steps_done * self.batch_size * self.agent.cfg.trajectory
        if _OBS.enabled:  # run-wide telemetry (off = one attribute read)
            _OBS.count("learner/train_steps", steps_done)
            _OBS.count("learner/frames_learned",
                       steps_done * self.batch_size * self.agent.cfg.trajectory)
        if self.maybe_publish():
            # Sync publish is this step's device sync (so "learn" above
            # measured dispatch, "publish" compute+D2H, and the float()
            # after it is free). With async publication the float() here
            # would become the learn thread's only device sync — so the
            # free-running path hands the DEVICE arrays to the bounded
            # MetricsPump (the pump's depth still caps how far ahead the
            # host loop can dispatch); sync loops keep the blocking
            # float, which doubles as their pipelining bound. One
            # definition for all learners: PublishCadenceMixin.
            metrics = self.log_step_metrics(metrics)
        # Non-publish steps return the metrics as DEVICE arrays and log
        # nothing: forcing a float() here would block on the step and
        # defeat the whole point of the interval (letting K device steps
        # pipeline back-to-back with no host sync between them). Callers
        # that read a value pay the sync themselves.
        self.timer.step_done(self.train_steps)
        self._profiler.on_step(self.train_steps)
        return metrics

    def close(self) -> None:
        """Stop the prefetch thread and flush any open profiler trace.

        Called by every run path (run_sync/run_async/run_role) on exit."""
        self.flush_publish()
        self.close_metrics()  # drain pending pump log lines
        if self._prefetcher is not None:
            self._prefetcher.close()
        self._profiler.close()


def run_sync(
    learner: ImpalaLearner,
    actors: list[ImpalaActor],
    num_updates: int,
    close_learner: bool = True,
) -> dict:
    """Deterministic interleaving: actors fill the queue, learner drains it.

    Mirrors the steady state of the reference topology without thread
    nondeterminism; used by tests and single-host training. The queue must
    be able to absorb one full actor round past the batch size, or puts
    would block with no consumer running.
    """
    learner.sync_publish = True  # deterministic staleness in the sync loop
    production_per_round = sum(a.env.num_envs for a in actors)
    # A learner draining K batches per call (updates_per_call) needs K
    # full batches queued before its step can complete without blocking
    # on producers that only run between steps in this interleave.
    need = learner.batch_size * getattr(learner, "updates_per_call", 1)
    if learner.queue.capacity < need + production_per_round:
        raise ValueError(
            "sync mode needs queue capacity >= batch_size*updates_per_call "
            f"+ one actor round ({need} + {production_per_round})"
        )
    frames = 0
    metrics: dict = {}
    try:
        while learner.train_steps < num_updates:
            while learner.queue.size() < need:
                for actor in actors:
                    frames += actor.run_unroll()
            m = learner.step(timeout=10.0)
            if m is not None:
                metrics = m
    finally:
        # close_learner=False: chunked callers (train_local checkpoint
        # loop) re-enter with the same learner and close it themselves.
        if close_learner:
            learner.close()
    returns = [r for a in actors for r in a.episode_returns]
    # On a non-publish step `metrics` holds device arrays (the interval's
    # pipelining contract); the public result is always host floats.
    metrics = {k: float(v) for k, v in metrics.items()}
    return {"frames": frames, "last_metrics": metrics, "episode_returns": returns}


def run_async(
    learner: ImpalaLearner,
    actors: list[ImpalaActor],
    num_updates: int,
    queue: TrajectoryQueue,
) -> dict:
    """Free-running actor threads + learner loop (reference topology in one
    process; the multi-process version goes through runtime/transport)."""
    stop = threading.Event()

    # Shared free-running loop (actor_pipeline.run_actor_thread): a
    # dying actor logs its traceback and bumps `actor/deaths` instead
    # of silently vanishing into a throughput dip.
    threads = [threading.Thread(target=run_actor_thread, args=(a, stop),
                                daemon=True) for a in actors]
    for t in threads:
        t.start()
    try:
        while learner.train_steps < num_updates:
            learner.step(timeout=30.0)
    finally:
        stop.set()
        learner.close()
        queue.close()
        for t in threads:
            t.join(timeout=5.0)
    returns = [r for a in actors for r in a.episode_returns]
    return {"last_metrics": {}, "episode_returns": returns}

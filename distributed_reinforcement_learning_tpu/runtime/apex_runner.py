"""Ape-X actor/learner loops.

Re-design of `train_apex.py:82-231`:

- `ApexActor`: N batched envs, epsilon-greedy act with per-env epsilon
  `1/(0.05*episode+1)` (`train_apex.py:229`), life-loss shaping, local
  uniform buffer; once warm, pushes a random `trajectory`-sized
  re-sample of its buffer to the queue every env step
  (`train_apex.py:207-217` — the reference's distributed-replay
  approximation, kept for parity).
- `ApexLearner`: ingests unrolls, scores TD, inserts per-transition into
  prioritized replay (`train_apex.py:106-122`), trains with IS weights,
  updates priorities, syncs the target net every `target_sync_interval`
  steps (`train_apex.py:151-155`).
"""

from __future__ import annotations

from typing import Any

import numpy as np

import jax

from distributed_reinforcement_learning_tpu.agents.apex import ApexAgent, ApexBatch
from distributed_reinforcement_learning_tpu.data.fifo import (
    TrajectoryQueue,
    put_batch_size,
    put_round,
    stack_pytrees,
)
from distributed_reinforcement_learning_tpu.data.replay import UniformBuffer, make_replay
from distributed_reinforcement_learning_tpu.envs.batched import completed_returns
from distributed_reinforcement_learning_tpu.observability import TELEMETRY as _OBS
from distributed_reinforcement_learning_tpu.runtime.actor_pipeline import (
    PipelineSlice,
    run_async_loop,
    shape_life_loss,
    slice_seed,
    split_batched_env,
)
from distributed_reinforcement_learning_tpu.runtime.publishing import PublishCadenceMixin
from distributed_reinforcement_learning_tpu.runtime.replay_train import ReplayTrainMixin
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore
from distributed_reinforcement_learning_tpu.utils.environ import env_int
from distributed_reinforcement_learning_tpu.utils.logger import MetricsLogger
from distributed_reinforcement_learning_tpu.utils.profiling import ProfilerSession, StageTimer


class ApexActor:
    def __init__(
        self,
        agent: ApexAgent,
        env,
        queue: TrajectoryQueue,
        weights: WeightStore,
        seed: int = 0,
        unroll_size: int = 32,  # "trajectory" in the apex config (`config.json:99`)
        local_capacity: int = 10_000,  # `train_apex.py:159-160`
        warmup_factor: int = 3,  # push once len > 3*unroll (`train_apex.py:207`)
        epsilon_decay: float = 0.05,  # `train_apex.py:229`
        sync_every_steps: int = 100,
        life_loss_shaping: bool = False,
        remote_act=None,  # SEED-style: RemoteInference; no weight pulls at all
    ):
        self.agent = agent
        self.env = env
        self.queue = queue
        self.weights = weights
        self.unroll_size = unroll_size
        self.warmup = warmup_factor * unroll_size
        self.epsilon_decay = epsilon_decay
        self.sync_every_steps = sync_every_steps
        self.life_loss_shaping = life_loss_shaping
        self.remote_act = remote_act

        self._seed = seed  # slice seeds derive from it (actor_pipeline)
        self._local_capacity = local_capacity
        self._rng = jax.random.PRNGKey(seed)
        self._buffer = UniformBuffer(local_capacity, seed=seed)
        self._obs = env.reset()
        n = self._obs.shape[0]
        self._prev_action = np.zeros(n, np.int32)
        self._episodes = np.zeros(n, np.int64)
        self._lives = np.full(n, -1)
        self._params = None
        self._version = -1
        self._steps = 0
        self.episode_returns: list[float] = []

    @property
    def epsilon(self) -> np.ndarray:
        """Per-env epsilon from per-env episode counts (`train_apex.py:229`)."""
        return 1.0 / (self.epsilon_decay * self._episodes + 1.0)

    def _sync_params(self) -> None:
        got = self.weights.get_if_newer(self._version)
        if got is not None:
            self._params, self._version = got

    def run_steps(self, num_steps: int) -> int:
        """Step the envs `num_steps` times; push buffer re-samples when warm.

        PUT batching: `DRL_PUT_BATCH=k` aggregates the per-step sampled
        unrolls into k-unroll batched exchanges (`put_round` ->
        OP_PUT_TRAJ_N over the wire) instead of one request/reply per
        unroll; unset keeps the reference's per-step put. Pending
        unrolls are flushed before a normal return; an exception
        mid-round (transport outage) abandons the local pending list —
        harmless, since these are RE-SAMPLES of the actor's buffer, not
        the only copy (at-most-once, like every PUT on this path)."""
        if self.remote_act is None:
            if self._steps % self.sync_every_steps == 0 or self._params is None:
                self._sync_params()
            if self._params is None:
                raise RuntimeError("no weights published yet")
        put_batch = max(1, put_batch_size())
        pending: list = []

        for _ in range(num_steps):
            if self.remote_act is not None:
                # The epsilon schedule stays actor-side: exploration is
                # the actor's identity even with centralized inference.
                r = self.remote_act({"obs": self._obs, "prev_action": self._prev_action,
                                     "epsilon": self.epsilon.astype(np.float32)})
                actions = r["action"]
            else:
                self._rng, sub = jax.random.split(self._rng)
                actions, _ = self.agent.act(
                    self._params, self._obs, self._prev_action, self.epsilon, sub
                )
            actions = np.asarray(actions)
            next_obs, reward, done, infos = self.env.step(actions)

            rec_reward, rec_done = reward.astype(np.float32), done.copy()
            if self.life_loss_shaping:
                rec_reward, rec_done, self._lives = shape_life_loss(
                    self._lives, reward, done, infos)

            for i in range(self._obs.shape[0]):
                self._buffer.append(
                    ApexBatch(
                        state=self._obs[i],
                        next_state=next_obs[i],
                        previous_action=self._prev_action[i],
                        action=actions[i],
                        reward=rec_reward[i],
                        done=rec_done[i],
                    )
                )

            self._episodes += done
            for ret in completed_returns(infos, done):
                self.episode_returns.append(float(ret))
            self._prev_action = np.where(done, 0, actions).astype(np.int32)
            self._obs = next_obs
            self._steps += 1

            if len(self._buffer) > self.warmup:
                unroll = stack_pytrees(self._buffer.sample(self.unroll_size))
                if put_batch <= 1:
                    with _OBS.span("actor_put"):
                        self.queue.put(unroll)
                else:
                    pending.append(unroll)
                    if len(pending) >= put_batch:
                        with _OBS.span("actor_put"):
                            put_round(self.queue, pending)
                        pending.clear()
        if pending:
            with _OBS.span("actor_put"):
                put_round(self.queue, pending)
        return num_steps * self._obs.shape[0]

    # -- slice protocol (runtime/actor_pipeline.py) --------------------
    # A slice mirrors run_steps over its own env subset, RNG stream,
    # LOCAL BUFFER (own re-sample RandomState) and epsilon schedule:
    # with frozen weights a pipelined slice's puts are bit-identical to
    # a plain ApexActor built over that slice (test-pinned). The
    # publication unit is the per-step warm re-sample (or the
    # DRL_PUT_BATCH pending round), exactly the sequential shapes.

    def pipeline_round_steps(self) -> None:
        return None  # step-driven family: the caller passes run_steps(n)

    def pipeline_make_slices(self, k: int) -> list[PipelineSlice]:
        total = self.env.num_envs
        slices = []
        lo = 0
        for i, env in enumerate(split_batched_env(self.env, k)):
            hi = lo + env.num_envs
            seed = slice_seed(self._seed, i)
            # Warmup and capacity scale by the slice's env fraction
            # (ceil): a slice appends env.num_envs transitions per step
            # instead of the full actor's N, so unscaled knobs would
            # delay first publication k-fold and retain k x the replay
            # window vs the sequential actor this replaces.
            frac_w = -(-self.warmup * env.num_envs // total)
            frac_cap = max(self.unroll_size,
                           -(-self._local_capacity * env.num_envs // total))
            slices.append(PipelineSlice(
                i, env, seed,
                rng=jax.random.PRNGKey(seed),
                buffer=UniformBuffer(frac_cap, seed=seed),
                warmup=frac_w,
                obs=self._obs[lo:hi].copy(),
                prev_action=np.zeros(env.num_envs, np.int32),
                episodes=np.zeros(env.num_envs, np.int64),
                lives=np.full(env.num_envs, -1),
                steps=0,
                pending=[],
            ))
            lo = hi
        return slices

    def pipeline_sync_weights(self, slices: list) -> None:
        """One weights RPC per round shared by every due slice —
        preserving the sequential loop's `sync_every_steps` cadence
        (slices step in lockstep, so dueness is identical across
        them)."""
        if self.remote_act is not None:
            return
        due = [sl for sl in slices
               if sl.steps % self.sync_every_steps == 0 or sl.params is None]
        if not due:
            return
        self._sync_params()
        if self._params is None:
            raise RuntimeError("no weights published yet")
        for sl in due:
            if sl.version < self._version:
                sl.params, sl.version = self._params, self._version

    def slice_begin_round(self, sl: PipelineSlice, steps: int) -> None:
        if self.remote_act is None and sl.params is None:
            raise RuntimeError("no weights published yet")
        sl.put_batch = max(1, put_batch_size())
        sl.pending = []

    def slice_act(self, sl: PipelineSlice) -> np.ndarray:
        epsilon = 1.0 / (self.epsilon_decay * sl.episodes + 1.0)
        if self.remote_act is not None:
            r = self.remote_act({"obs": sl.obs, "prev_action": sl.prev_action,
                                 "epsilon": epsilon.astype(np.float32)})
            actions = r["action"]
        else:
            sl.rng, sub = jax.random.split(sl.rng)
            actions, _ = self.agent.act(
                sl.params, sl.obs, sl.prev_action, epsilon, sub)
        return np.asarray(actions)

    def slice_step(self, sl: PipelineSlice, actions: np.ndarray) -> tuple:
        next_obs, reward, done, infos = sl.env.step(actions)
        rec_reward, rec_done = reward.astype(np.float32), done.copy()
        if self.life_loss_shaping:
            rec_reward, rec_done, sl.lives = shape_life_loss(
                sl.lives, reward, done, infos)
        for i in range(sl.obs.shape[0]):
            sl.buffer.append(
                ApexBatch(
                    state=sl.obs[i],
                    next_state=next_obs[i],
                    previous_action=sl.prev_action[i],
                    action=actions[i],
                    reward=rec_reward[i],
                    done=rec_done[i],
                )
            )
        sl.episodes += done
        for ret in completed_returns(infos, done):
            sl.episode_returns.append(float(ret))
        sl.prev_action = np.where(done, 0, actions).astype(np.int32)
        sl.obs = next_obs
        sl.steps += 1
        if len(sl.buffer) > sl.warmup:  # slice-scaled (pipeline_make_slices)
            unroll = stack_pytrees(sl.buffer.sample(self.unroll_size))
            if sl.put_batch <= 1:
                return (("put", unroll),)
            sl.pending.append(unroll)
            if len(sl.pending) >= sl.put_batch:
                payload = ("round", sl.pending)
                sl.pending = []
                return (payload,)
        return ()

    def slice_end_round(self, sl: PipelineSlice) -> tuple:
        if sl.pending:
            payload = ("round", sl.pending)
            sl.pending = []
            return (payload,)
        return ()


class ApexLearner(PublishCadenceMixin, ReplayTrainMixin):
    def __init__(
        self,
        agent: ApexAgent,
        queue: TrajectoryQueue,
        weights: WeightStore,
        batch_size: int = 32,
        replay_capacity: int = 100_000,
        target_sync_interval: int = 100,
        train_start_unrolls: int = 10,  # `train_apex.py:124` buffer_step gate
        logger: MetricsLogger | None = None,
        rng: jax.Array | None = None,
        seed: int = 0,
        mesh=None,
        publish_interval: int = 1,
        updates_per_call: int = 1,
        replay_service=None,
    ):
        self.agent = agent
        self.queue = queue
        self.weights = weights
        self.batch_size = batch_size
        # Monolithic replay is ALWAYS built: it is the normal path when
        # sharding is off, and the demotion target when a sharded
        # service (data/replay_service.py) loses every shard.
        self.replay = make_replay(replay_capacity)
        self.replay_service = replay_service
        self.target_sync_interval = target_sync_interval
        # K>1: K prioritized updates per learn_many dispatch
        # (runtime/replay_train.py; K-1-step-stale priorities).
        self._init_stride(updates_per_call, mesh)
        self.train_start_unrolls = train_start_unrolls
        self.logger = logger or MetricsLogger(None)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        # Multi-chip learn step: batch + IS weights sharded over the data
        # axis; state replicated/model-sharded (parallel/learner.py).
        self._batch_sharding = None
        if mesh is not None:
            from distributed_reinforcement_learning_tpu.parallel import ShardedLearner, data_sharding

            self._sharded = ShardedLearner(agent, mesh, num_data_args=2, num_aux_outputs=2)
            self._learn = self._sharded.learn
            self._batch_sharding = data_sharding(mesh)
            self.state = self._sharded.init_state(rng)
        else:
            self._sharded = None
            self._learn = agent.learn
            self.state = agent.init_state(rng)
        self.state = agent.sync_target(self.state)
        self._np_rng = np.random.RandomState(seed)
        # Publish cadence (see ImpalaLearner): here the step syncs on the
        # TD/priority read regardless, so interval>1 saves only the
        # per-step D2H params copy.
        self.publish_interval = max(1, publish_interval)
        self.ingested_unrolls = 0
        self.train_steps = 0
        # One-deep ingest pipeline: batch k's H2D +
        # TD forward are dispatched, then batch k-1's TD is materialized
        # and replay-added — so the transfer/compute of k overlaps the
        # host-side sum-tree work of k-1 instead of serializing behind a
        # np.asarray() sync per batch. None = auto (on for single-device
        # accelerators; off on mesh learners, whose batches need explicit
        # sharding placement, and off on CPU where there is no transfer
        # to hide).
        self.ingest_pipeline: bool | None = None
        # K>1 batched ingest is opt-in (see ingest_many's adjudication
        # note); resolved once here so the hot drain loops don't re-parse
        # the environment per call and a malformed value fails at
        # construction, not mid-training.
        self.ingest_unrolls = env_int("DRL_APEX_INGEST_UNROLLS", 1)
        if self.ingest_unrolls < 1:
            raise ValueError(
                "DRL_APEX_INGEST_UNROLLS must be >= 1, got "
                f"{self.ingest_unrolls}")
        self._pending_ingest: tuple[Any, Any, int] | None = None
        self.timer = StageTimer(self.logger)
        self._profiler = ProfilerSession.from_env()
        weights.publish(self.state.params, 0)

    def _warm_unrolls(self) -> int:
        """Unrolls available to the warm-up gate: shard-side ingest
        counts when the service is active, plus this learner's own
        queue-path ingest (both feed training after a demotion)."""
        svc = self.replay_service
        shard_blobs = (svc.ingested_blobs()
                       if svc is not None and svc.healthy else 0)
        return max(self.ingested_unrolls, shard_blobs)

    def save_checkpoint(self, ckpt) -> None:
        """Persist TrainState (main+target nets, Adam moments) + host
        counters + a replay snapshot (contents AND priorities — without it
        a restarted learner resumes with an empty Memory while actors keep
        pushing stale-policy re-samples). The snapshot is size-capped /
        disableable via DRL_CKPT_REPLAY* (utils/checkpoint.py). With the
        sharded service active, the snapshot is the merged shard state
        (pending async priority updates flushed first)."""
        from distributed_reinforcement_learning_tpu.utils.checkpoint import encode_replay_snapshot

        self._flush_pending_ingest()  # snapshot must include in-flight unrolls
        replay = self._active_replay()
        blob = encode_replay_snapshot(replay)
        ckpt.save(self.train_steps, self.state, {
            "train_steps": self.train_steps,
            "replay_beta": float(replay.beta),
            "ingested_unrolls": self._warm_unrolls(),
            **self._cadence_extra(),
        }, blobs={"replay": blob} if blob is not None else None)

    def restore_checkpoint(self, ckpt) -> bool:
        from distributed_reinforcement_learning_tpu.utils.checkpoint import decode_replay_snapshot

        got = ckpt.restore(self.state)
        if got is None:
            return False
        self.state, extra, step = got
        self.train_steps = int(extra.get("train_steps", 0))
        replay = self._active_replay()
        blob = ckpt.load_blob(step, "replay")
        if blob is not None:
            replay.restore(decode_replay_snapshot(blob))
            self.ingested_unrolls = int(extra.get("ingested_unrolls", 0))
        else:
            # No snapshot: the warm-up gate restarts, buffer refills live.
            self.ingested_unrolls = 0
        replay.beta = float(extra.get("replay_beta", replay.beta))
        self.weights.publish(self.state.params, self.train_steps)
        self._restore_cadence(extra)
        return True

    def ingest(self, timeout: float | None = 0.0) -> bool:
        """Drain one unroll, score TD per transition, insert into replay
        (`train_apex.py:98-122`)."""
        return self.ingest_many(max_unrolls=1, timeout=timeout) > 0

    def ingest_many(self, max_unrolls: int | None = None,
                    timeout: float | None = 0.0) -> int:
        """Drain up to `max_unrolls` unrolls and score them in ONE device
        call; returns the number of unrolls ingested.

        The reference scores one 32-transition unroll per `sess.run`
        (`train_apex.py:98-112`) — on TPU that is a tiny-batch dispatch
        plus a host sync per unroll, and at the 50k frames/s target
        (~80 unrolls/s) the per-call overhead alone dominates. Here K
        unrolls are dequeued strided in one native pop, flattened to a
        single `[K*32]` TD forward, and batch-added to the replay through
        the C++ sum-tree. K snaps down to a power of two so the forward
        compiles at most log2(max_unrolls)+1 distinct shapes.

        DEFAULT = 1 (per-unroll), from `DRL_APEX_INGEST_UNROLLS`: the
        batched path's gain is not measured on the attached chip, so
        like the Pallas LSTM it stays opt-in
        (`DRL_APEX_INGEST_UNROLLS=8`).
        """
        if max_unrolls is None:
            max_unrolls = self.ingest_unrolls
        pipeline = self.ingest_pipeline
        if pipeline is None:  # auto: overlap only where there is a transfer
            pipeline = (self._batch_sharding is None
                        and jax.default_backend() not in ("cpu",))
        # Pipelined mode loops until it can report >=1 COMPLETED unroll
        # (or the queue is truly drained), preserving the
        # `while ingest_many(): pass` contract: a zero return always
        # means "nothing left anywhere" — never "progress in flight".
        # The priming pass may therefore pop up to 2 chunks.
        done = 0
        while True:
            with self.timer.stage("ingest_dequeue"):
                k = 1
                while k * 2 <= min(self.queue.size(), max_unrolls):
                    k *= 2
                stacked = self.queue.get_batch(k, timeout=timeout)
            if stacked is None:
                # Queue drained: complete whatever is still in flight.
                return done + self._flush_pending_ingest()
            with self.timer.stage("ingest_td"):
                # [K, U, ...] -> [K*U, ...]: one forward for everything.
                # Host arrays by design: the dequeued batch is already
                # host numpy and the sum-tree add below is host memory.
                flat = jax.tree.map(
                    lambda x: np.asarray(x).reshape(-1, *np.asarray(x).shape[2:]),  # drlint: disable=host-sync
                    stacked)
                if pipeline:
                    # Dispatch k's H2D + TD forward, then materialize
                    # k-1's: the device works on k while the host
                    # sum-tree adds k-1.
                    dev = jax.device_put(flat)
                    td_dev = self.agent.td_error(self.state, dev)
                    done += self._flush_pending_ingest()
                    self._pending_ingest = (td_dev, flat, k)
                    if done:
                        return done
                    continue  # primed the pipeline; pop the next chunk
                # Deliberate sync (non-pipelined path only): priorities
                # must reach the host sum-tree before the add.
                td = np.asarray(self.agent.td_error(self.state, flat))  # drlint: disable=host-sync
            self._replay_add(td, flat)
            self.ingested_unrolls += k
            if _OBS.enabled:
                _OBS.count("learner/ingested_unrolls", k)
            return done + k

    def _replay_add(self, td: np.ndarray, flat) -> None:
        with self.timer.stage("ingest_replay_add"):
            if getattr(self.replay, "stacked_samples", False):
                # SoA backend: one vectorized slice-assign per field —
                # no per-transition Python objects at all.
                self.replay.add_batch_stacked(td, flat)
            else:
                self.replay.add_batch(
                    td, [jax.tree.map(lambda x: x[i], flat) for i in range(len(td))]
                )

    def _flush_pending_ingest(self) -> int:
        """Materialize the in-flight TD batch and add it to replay;
        returns the number of unrolls completed (0 if none pending)."""
        if self._pending_ingest is None:
            return 0
        td_dev, flat, k = self._pending_ingest
        self._pending_ingest = None
        with self.timer.stage("ingest_td_sync"):
            td = np.asarray(td_dev)
        self._replay_add(td, flat)
        self.ingested_unrolls += k
        if _OBS.enabled:
            _OBS.count("learner/ingested_unrolls", k)
        return k

    def train(self) -> dict | None:
        """One prioritized train call (`train_apex.py:124-155`); with
        `updates_per_call` K > 1, K scanned updates (replay_train.py)."""
        if self._warm_unrolls() < self.train_start_unrolls:
            return None
        replay = self._active_replay()
        if len(replay) == 0:
            # Demotion raced the warm gate (the service counted warm,
            # then lost its last shard): the monolithic replay is still
            # empty — wait for it to refill through the demoted facade.
            return None
        # None = the service lost its last shard mid-call; the next
        # train() resolves to the monolithic path.
        metrics = self._train_guarded(replay)
        if metrics is None:
            return None
        self._finish_train_call()
        if _OBS.enabled:
            _OBS.count("learner/train_steps", self.updates_per_call)
        self.timer.step_done(self.train_steps)
        self._profiler.on_step(self.train_steps)
        # Off the learn thread: async mode hands the DEVICE arrays to the
        # bounded MetricsPump (as the IMPALA learner does) instead of the
        # old per-step float() sync; sync loops still get host floats.
        return self.log_step_metrics(metrics)

    def _train_once(self, replay) -> dict:
        """The sample -> learn -> re-prioritize body of one train call,
        against whichever replay `_active_replay()` resolved."""
        path = self._device_path_for(replay)
        if path is not None:
            # Fused device path (data/device_path.py): the gather +
            # stack + H2D already happened on the path's thread,
            # overlapped with the PREVIOUS call's learn scan.
            from distributed_reinforcement_learning_tpu.runtime.replay_train import (
                device_train_call)

            return device_train_call(self, path, replay)
        if self.updates_per_call > 1:
            from distributed_reinforcement_learning_tpu.runtime.replay_train import (
                prioritized_train_call)

            return prioritized_train_call(self, self.updates_per_call,
                                          replay=replay)
        with self.timer.stage("replay_sample"):
            items, idxs, is_weight = replay.sample(self.batch_size, self._np_rng)
            # SoA backend (and the sharded service over it) returns the
            # stacked batch directly.
            batch = items if getattr(replay, "stacked_samples", False) \
                else stack_pytrees(items)
        with self.timer.stage("learn"):
            if self._batch_sharding is not None:
                from distributed_reinforcement_learning_tpu.parallel import place_local_batch

                batch, is_weight = place_local_batch((batch, is_weight), self._batch_sharding)
            self.state, td, metrics = self._learn(self.state, batch, is_weight)
        with self.timer.stage("replay_update"):
            # Deliberate sync: the re-prioritization targets the host
            # sum-tree, so the TD errors must materialize here. (The
            # sharded service only enqueues here — its router thread
            # walks the trees off the learn thread.)
            replay.update_batch(idxs, np.asarray(td))  # drlint: disable=host-sync
        return metrics

    def close(self) -> None:
        self.flush_publish()
        self.close_metrics()
        self._close_device_path()  # join the gather thread
        self._profiler.close()


def run_sync(learner: ApexLearner, actors: list[ApexActor], num_updates: int,
             actor_steps_per_round: int = 8, close_learner: bool = True) -> dict:
    """Interleaved stepping for tests/single-host training."""
    metrics: dict = {}
    frames = 0
    learner.sync_publish = True  # deterministic staleness in the sync loop
    try:
        while learner.train_steps < num_updates:
            for actor in actors:
                frames += actor.run_steps(actor_steps_per_round)
            while learner.ingest_many(timeout=0.0):
                pass
            m = learner.train()
            if m is not None:
                metrics = m
    finally:
        if close_learner:
            learner.close()
    returns = [r for a in actors for r in a.episode_returns]
    # Under async metrics `metrics` may hold device arrays (the pump owns
    # materialization); the public result is always host floats.
    metrics = {k: float(v) for k, v in metrics.items()}
    return {"frames": frames, "last_metrics": metrics, "episode_returns": returns}


def run_async(learner: ApexLearner, actors: list[ApexActor], num_updates: int,
              queue: TrajectoryQueue, actor_steps_per_round: int = 8) -> dict:
    """Free-running actor threads + the ingest/train learner loop (one
    copy in actor_pipeline.run_async_loop; actor deaths log and count
    `actor/deaths` via the shared run_actor_thread body)."""

    def drain_ingest(ln) -> bool:
        drained = False
        while ln.ingest_many(timeout=0.05):
            drained = True
        return drained

    return run_async_loop(
        learner, actors, num_updates, queue, ingest_fn=drain_ingest,
        round_fn=lambda a: a.run_steps(actor_steps_per_round))

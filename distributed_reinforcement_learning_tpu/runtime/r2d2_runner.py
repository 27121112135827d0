"""R2D2 actor/learner loops.

Re-design of `train_r2d2.py:86-238`:

- `R2D2Actor`: N batched envs on the CartPole POMDP projection
  (`train_r2d2.py:176-178`), per-env epsilon `1/(0.1*episode+1)`
  (`train_r2d2.py:221`), seq_len unrolls carrying the sequence-start
  LSTM state, per-unroll weight pull.
- `R2D2Learner`: drains sequences, scores |mean TD| priorities
  (`train_r2d2.py:100-119`), trains with IS weights once warm
  (`:121-154`), updates ALL sampled priorities (fixing the `:159`
  single-index bug), target sync every `target_sync_interval` steps.
"""

from __future__ import annotations

import collections

import numpy as np

import jax

from distributed_reinforcement_learning_tpu.agents.r2d2 import R2D2Agent, R2D2Batch
from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue, stack_pytrees, put_round
from distributed_reinforcement_learning_tpu.data.replay import make_replay
from distributed_reinforcement_learning_tpu.data.structures import (
    R2D2SequenceAccumulator,
    SlicedAccumulators,
)
from distributed_reinforcement_learning_tpu.envs.batched import completed_returns
from distributed_reinforcement_learning_tpu.observability import TELEMETRY as _OBS
from distributed_reinforcement_learning_tpu.runtime.actor_pipeline import (
    PipelineSlice,
    run_async_loop,
    shape_timeout,
    slice_seed,
    split_batched_env,
    sync_slices_params,
)
from distributed_reinforcement_learning_tpu.runtime.publishing import PublishCadenceMixin
from distributed_reinforcement_learning_tpu.runtime.replay_train import ReplayTrainMixin
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore
from distributed_reinforcement_learning_tpu.utils.environ import env_float, env_int
from distributed_reinforcement_learning_tpu.utils.logger import MetricsLogger
from distributed_reinforcement_learning_tpu.utils.profiling import ProfilerSession, StageTimer


class R2D2Actor:
    def __init__(
        self,
        agent: R2D2Agent,
        env,  # VectorEnv over full observations
        queue: TrajectoryQueue,
        weights: WeightStore,
        seed: int = 0,
        epsilon_decay: float = 0.1,  # `train_r2d2.py:221`
        epsilon_floor: float = 0.0,  # 0 = reference parity; >0 keeps a
        # residual exploration floor (stable mode, VERDICT r3 item 5 —
        # `1/(0.1*ep+1)` decays to ~0 and the greedy policy then feeds
        # replay nothing but its own on-policy loop)
        timeout_nonterminal: bool = False,  # stable mode: record a
        # TIME-LIMIT truncation (env info `truncated`) as non-terminal —
        # done stays False in the recorded stream (LSTM carries and
        # prev_action continue across the env's silent reset, exactly as
        # if the episode had kept going). Measured on CartPole-POMDP:
        # recording the 200-cap as a true terminal aliases "about to time
        # out" with "just started" states and drives the periodic
        # collapse-recover cycle (time-limit aliasing, Pardo et al. 2018);
        # this option removes the collapse. False = reference parity.
        obs_transform=None,  # e.g. envs.cartpole.pomdp_project
        remote_act=None,  # SEED-style: RemoteInference; no weight pulls at all
    ):
        self.agent = agent
        self.env = env
        self.queue = queue
        self.weights = weights
        self.epsilon_decay = epsilon_decay
        self.epsilon_floor = epsilon_floor
        self.timeout_nonterminal = timeout_nonterminal
        self.obs_transform = obs_transform or (lambda x: x)
        self.remote_act = remote_act

        self._seed = seed  # slice seeds derive from it (actor_pipeline)
        self._rng = jax.random.PRNGKey(seed)
        self._obs = self.obs_transform(env.reset())
        n = self._obs.shape[0]
        self._prev_action = np.zeros(n, np.int32)
        h, c = agent.initial_lstm_state(n)
        self._h, self._c = np.asarray(h), np.asarray(c)
        self._episodes = np.zeros(n, np.int64)
        self._params = None
        self._version = -1
        self.episode_returns: list[float] = []

    @property
    def epsilon(self) -> np.ndarray:
        return np.maximum(
            1.0 / (self.epsilon_decay * self._episodes + 1.0),
            self.epsilon_floor)

    def _sync_params(self) -> None:
        got = self.weights.get_if_newer(self._version)
        if got is not None:
            self._params, self._version = got

    def run_unroll(self) -> int:
        """One seq_len unroll from all envs -> N sequences into the queue."""
        cfg = self.agent.cfg
        if self.remote_act is None:
            self._sync_params()
            if self._params is None:
                raise RuntimeError("no weights published yet")
        acc = R2D2SequenceAccumulator()
        acc.reset(self._h, self._c)
        n = self._obs.shape[0]

        for _ in range(cfg.seq_len):
            if self.remote_act is not None:
                r = self.remote_act({
                    "obs": self._obs, "h": self._h, "c": self._c,
                    "prev_action": self._prev_action,
                    "epsilon": self.epsilon.astype(np.float32)})
                action, h, c = r["action"], r["h"], r["c"]
            else:
                self._rng, sub = jax.random.split(self._rng)
                action, _, h, c = self.agent.act(
                    self._params, self._obs, self._h, self._c, self._prev_action,
                    self.epsilon, sub
                )
            action = np.asarray(action)
            next_obs_raw, reward, done, infos = self.env.step(action)
            next_obs = self.obs_transform(next_obs_raw)

            # Stable mode: a time-limit truncation is recorded (and
            # carried) as if the episode continued — see __init__. One
            # definition for sequential and slice paths (actor_pipeline).
            rec_done = shape_timeout(done, infos, self.timeout_nonterminal)

            acc.append(
                state=self._obs,
                previous_action=self._prev_action,
                action=action,
                reward=reward.astype(np.float32),
                done=rec_done,
            )

            keep = (~rec_done).astype(np.float32)[:, None]
            self._h = np.asarray(h) * keep
            self._c = np.asarray(c) * keep
            self._prev_action = np.where(rec_done, 0, action).astype(np.int32)
            self._obs = next_obs
            # Exploration anneals per RECORDED episode: under
            # timeout_nonterminal a truncation does not advance the
            # schedule, so epsilon keeps decaying while the agent fails
            # but FREEZES once episodes run to the cap — residual
            # exploration persists exactly when the replay is at its most
            # uniform (the measured collapse window). With the option off
            # rec_done == done: reference parity.
            self._episodes += rec_done
            for ret in completed_returns(infos, done):
                self.episode_returns.append(float(ret))

        # encode+PUT stage span (the codec fast path's target; see
        # impala_runner.run_unroll).
        with _OBS.span("actor_put"):
            put_round(self.queue, acc.extract())
        return n * cfg.seq_len

    # -- slice protocol (runtime/actor_pipeline.py) --------------------
    # Sequence-start LSTM state, per-slice epsilon schedule and the
    # stable-mode truncation recording all mirror run_unroll exactly
    # over the slice's own envs/seed (bit-identity test-pinned).

    def pipeline_round_steps(self) -> int:
        return self.agent.cfg.seq_len

    def pipeline_make_slices(self, k: int) -> list[PipelineSlice]:
        self._slice_accs = SlicedAccumulators(R2D2SequenceAccumulator, k)
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
                episodes=np.zeros(env.num_envs, np.int64),
            ))
            lo = hi
        return slices

    def _slice_epsilon(self, sl: PipelineSlice) -> np.ndarray:
        return np.maximum(
            1.0 / (self.epsilon_decay * sl.episodes + 1.0),
            self.epsilon_floor)

    # One weights RPC per round, shared by all slices (actor_pipeline
    # calls this before any slice_begin_round).
    pipeline_sync_weights = sync_slices_params

    def slice_begin_round(self, sl: PipelineSlice, steps: int) -> None:
        if self.remote_act is None and sl.params is None:
            raise RuntimeError("no weights published yet")
        self._slice_accs.reset_slice(sl.index, sl.h, sl.c)

    def slice_act(self, sl: PipelineSlice) -> tuple:
        epsilon = self._slice_epsilon(sl)
        if self.remote_act is not None:
            r = self.remote_act({
                "obs": sl.obs, "h": sl.h, "c": sl.c,
                "prev_action": sl.prev_action,
                "epsilon": epsilon.astype(np.float32)})
            action, h, c = r["action"], r["h"], r["c"]
        else:
            sl.rng, sub = jax.random.split(sl.rng)
            action, _, h, c = self.agent.act(
                sl.params, sl.obs, sl.h, sl.c, sl.prev_action, epsilon, sub)
        return np.asarray(action), np.asarray(h), np.asarray(c)

    def slice_step(self, sl: PipelineSlice, out: tuple) -> tuple:
        action, h, c = out
        next_obs_raw, reward, done, infos = sl.env.step(action)
        next_obs = self.obs_transform(next_obs_raw)
        rec_done = shape_timeout(done, infos, self.timeout_nonterminal)
        self._slice_accs.append_slice(
            sl.index,
            state=sl.obs,
            previous_action=sl.prev_action,
            action=action,
            reward=reward.astype(np.float32),
            done=rec_done,
        )
        keep = (~rec_done).astype(np.float32)[:, None]
        sl.h = h * keep
        sl.c = c * keep
        sl.prev_action = np.where(rec_done, 0, action).astype(np.int32)
        sl.obs = next_obs
        sl.episodes += rec_done
        for ret in completed_returns(infos, done):
            sl.episode_returns.append(float(ret))
        return ()

    def slice_end_round(self, sl: PipelineSlice) -> tuple:
        return (("round", self._slice_accs.extract_slice(sl.index)),)


class R2D2Learner(PublishCadenceMixin, ReplayTrainMixin):
    def __init__(
        self,
        agent: R2D2Agent,
        queue: TrajectoryQueue,
        weights: WeightStore,
        batch_size: int = 32,
        replay_capacity: int = 100_000,
        target_sync_interval: int = 100,
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
        # Recency-mixed sampling (opt-in stabilizer experiment, VERDICT r4
        # item 9): DRL_R2D2_RECENT_FRACTION=r replaces the last round(r*B)
        # rows of every prioritized batch with sequences drawn uniformly
        # from the most recent DRL_R2D2_RECENT_WINDOW ingests (IS weight
        # 1.0 for those rows — a deliberate bias; the hypothesis under
        # test is that the collapse cycle is driven by replay staleness/
        # diversity, so the knob trades strict prioritized-IS semantics
        # for guaranteed fresh-data coverage). Forces the list-backed
        # replay so batch rows are replaceable pre-stack.
        self.recent_fraction = env_float("DRL_R2D2_RECENT_FRACTION", 0.0)
        # Window clamped to the ring capacity: a deque entry's tree idx is
        # only valid until the ring overwrites that leaf (capacity ingests
        # after its write); with maxlen <= capacity the oldest cached
        # entry can never be a recycled slot.
        self._recent: collections.deque = collections.deque(
            maxlen=min(env_int("DRL_R2D2_RECENT_WINDOW", 8 * batch_size),
                       replay_capacity))
        # Monolithic replay is ALWAYS built: the normal path when
        # sharding is off, and the demotion target when a sharded
        # service (data/replay_service.py) loses every shard.
        self.replay = make_replay(
            replay_capacity,
            backend="python" if self.recent_fraction > 0 else "auto")
        self.replay_service = replay_service
        if self.recent_fraction > 0 and updates_per_call > 1:
            raise ValueError(
                "DRL_R2D2_RECENT_FRACTION does not compose with "
                "updates_per_call > 1 (the scanned train call samples "
                "inside one dispatch)")
        if self.recent_fraction > 0 and replay_service is not None:
            # Recent-mixing swaps rows via queue-path ingest bookkeeping
            # the shards never populate; fail loudly instead of silently
            # degrading to a plain prioritized sample.
            raise ValueError(
                "DRL_R2D2_RECENT_FRACTION does not compose with "
                "DRL_REPLAY_SHARDS (shard ingest bypasses the recent "
                "deque)")
        self.target_sync_interval = target_sync_interval
        # K>1: K prioritized updates per learn_many dispatch
        # (runtime/replay_train.py; K-1-step-stale priorities).
        self._init_stride(updates_per_call, mesh)
        self.logger = logger or MetricsLogger(None)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
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
        # Publish cadence (see ImpalaLearner): the step syncs on the
        # priority read regardless, so interval>1 saves only the per-step
        # D2H params copy.
        self.publish_interval = max(1, publish_interval)
        self.ingested_sequences = 0
        self.train_steps = 0
        self.timer = StageTimer(self.logger)
        self._profiler = ProfilerSession.from_env()
        weights.publish(self.state.params, 0)

    def _warm_sequences(self) -> int:
        svc = self.replay_service
        shard_blobs = (svc.ingested_blobs()
                       if svc is not None and svc.healthy else 0)
        return max(self.ingested_sequences, shard_blobs)

    def save_checkpoint(self, ckpt) -> None:
        """Persist TrainState + host counters + a replay snapshot of the
        sequence Memory (the reference's R2D2 agent had no Saver at all —
        SURVEY §5.4). Snapshot gated by DRL_CKPT_REPLAY* (utils/checkpoint.py).
        With the sharded service active, the snapshot is the merged shard
        state (pending async priority updates flushed first)."""
        from distributed_reinforcement_learning_tpu.utils.checkpoint import encode_replay_snapshot

        replay = self._active_replay()
        blob = encode_replay_snapshot(replay)
        ckpt.save(self.train_steps, self.state, {
            "train_steps": self.train_steps,
            "replay_beta": float(replay.beta),
            "ingested_sequences": self._warm_sequences(),
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
            self.ingested_sequences = int(extra.get("ingested_sequences", 0))
        else:
            self.ingested_sequences = 0  # replay refills from live traffic
        replay.beta = float(extra.get("replay_beta", replay.beta))
        self.weights.publish(self.state.params, self.train_steps)
        self._restore_cadence(extra)
        return True

    def ingest_batch(self, timeout: float | None = 0.0) -> int:
        """Drain up to batch_size sequences; priority-score them in ONE
        batched td_error call (vs per-sequence `sess.run`s at
        `train_r2d2.py:104-119`)."""
        with self.timer.stage("ingest_dequeue"):
            seqs = []
            for _ in range(self.batch_size):
                seq = self.queue.get(timeout=timeout)
                if seq is None:
                    break
                seqs.append(seq)
        if not seqs:
            return 0
        with self.timer.stage("ingest_td"):
            # Pad the stack to the next power of two (capped at
            # batch_size, so a non-power-of-two batch_size still tops
            # out at its own full-drain shape): the drain count varies
            # per call (1..batch_size), and each distinct count would
            # otherwise compile its own td_error executable on TPU.
            # Padding rows are copies of row 0; their TDs are computed
            # and discarded, and per-sequence math is batch-independent
            # so real rows' priorities are bit-identical — EXCEPT under
            # MoE, where expert capacity scales with the total token
            # count and padding would shift real tokens' overflow; MoE
            # configs skip padding and accept the recompiles.
            n = len(seqs)
            if getattr(self.agent.cfg, "num_experts", 0):
                k = n
            else:
                k = 1
                while k < n:
                    k *= 2
                # next_pow2(n) and batch_size are both >= n (the drain
                # loop caps n at batch_size), so the cap never undershoots.
                k = min(k, self.batch_size)
            padded = seqs if k == n else seqs + [seqs[0]] * (k - n)
            batch = stack_pytrees(padded)
            # Deliberate sync: initial priorities feed the host sum-tree
            # add directly below.
            td = np.asarray(self.agent.td_error(self.state, batch))[:n]  # drlint: disable=host-sync
        with self.timer.stage("ingest_replay_add"):
            if getattr(self.replay, "stacked_samples", False):
                if k > n:
                    batch = jax.tree.map(lambda x: x[:n], batch)
                self.replay.add_batch_stacked(td, batch)  # one slice-assign/field
            else:
                new_idxs = self.replay.add_batch(td, seqs)
                if self.recent_fraction > 0:
                    self._recent.extend(zip(new_idxs, seqs))
        self.ingested_sequences += n
        if _OBS.enabled:
            _OBS.count("learner/ingested_sequences", n)
        return n

    def _mix_recent(self, items, idxs, is_weight):
        """Swap the tail of a prioritized sample for uniform-recent rows
        (see the __init__ knob comment). Tree idxs come along, so the
        post-step priority refresh covers the recent rows too."""
        k = int(round(self.recent_fraction * len(items)))
        if k == 0 or len(self._recent) < k:
            return items, idxs, is_weight
        pick = self._np_rng.choice(len(self._recent), size=k, replace=False)
        idxs = np.asarray(idxs).copy()
        is_weight = np.asarray(is_weight).copy()
        for j, slot in enumerate(pick):
            ridx, rseq = self._recent[int(slot)]
            items[len(items) - k + j] = rseq
            idxs[len(items) - k + j] = ridx
            is_weight[len(items) - k + j] = 1.0
        return items, idxs, is_weight

    def train(self) -> dict | None:
        """One prioritized train step over sequences (`train_r2d2.py:121-164`)."""
        if self._warm_sequences() < 2 * self.batch_size:  # `train_r2d2.py:121`
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
            # Fused device path (data/device_path.py): gather + stack +
            # H2D happened on the path's thread, overlapped with the
            # previous call's learn. (Shards-only, so recent-mixing —
            # which refuses to compose with shards — can never race it.)
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
            if self.recent_fraction > 0:
                items, idxs, is_weight = self._mix_recent(items, idxs, is_weight)
            # SoA backend (and the sharded service over it) returns the
            # stacked batch directly.
            batch = items if getattr(replay, "stacked_samples", False) \
                else stack_pytrees(items)
        with self.timer.stage("learn"):
            if self._batch_sharding is not None:
                from distributed_reinforcement_learning_tpu.parallel import place_local_batch

                batch, is_weight = place_local_batch((batch, is_weight), self._batch_sharding)
            self.state, priorities, metrics = self._learn(self.state, batch, is_weight)
        with self.timer.stage("replay_update"):
            # Deliberate sync: re-prioritization targets the host
            # sum-tree, so the priorities must materialize here. (The
            # sharded service only enqueues — its router thread walks
            # the trees off the learn thread.)
            replay.update_batch(idxs, np.asarray(priorities))  # drlint: disable=host-sync
        return metrics

    def close(self) -> None:
        self.flush_publish()
        self.close_metrics()
        self._close_device_path()  # join the gather thread
        self._profiler.close()


def run_sync(learner: R2D2Learner, actors: list[R2D2Actor], num_updates: int,
             close_learner: bool = True) -> dict:
    metrics: dict = {}
    frames = 0
    learner.sync_publish = True  # deterministic staleness in the sync loop
    try:
        while learner.train_steps < num_updates:
            for actor in actors:
                frames += actor.run_unroll()
            learner.ingest_batch(timeout=0.0)
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


def run_async(learner: R2D2Learner, actors: list[R2D2Actor],
              num_updates: int, queue: TrajectoryQueue) -> dict:
    """Free-running actor threads + the ingest/train learner loop (one
    copy in actor_pipeline.run_async_loop; actor deaths log and count
    `actor/deaths` via the shared run_actor_thread body). Shared by the
    Transformer-R2D2 family (xformer_runner re-exports)."""
    return run_async_loop(
        learner, actors, num_updates, queue,
        ingest_fn=lambda ln: ln.ingest_batch(timeout=0.05))

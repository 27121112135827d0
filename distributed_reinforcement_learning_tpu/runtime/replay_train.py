"""K-step scanned training for the prioritized-replay learners.

The replay analogue of ImpalaLearner's `updates_per_call`: sample K
prioritized batches up front, run them as ONE `learn_many` dispatch
(`agents/common.scan_learn_weighted`), then apply all K priority
updates. Relative to K sequential `train()` calls the only semantic
difference is priority staleness — batches 2..K are sampled under
priorities that predate updates 1..K-1, the same staleness distributed
Ape-X already accepts from its actors (`/root/reference/
train_apex.py:207-217` pushes transitions scored by old weights).
Single-jit learners only (the pjit ShardedLearner keeps per-step calls);
keep K well under the target-sync interval.

`ReplayTrainMixin` centralizes the stride bookkeeping shared by
ApexLearner and R2D2Learner (and its Xformer subclass): the K clamp +
mesh guard, the steps-since-last target-sync cadence (a modulo goes
off-grid under stride-K counters), and that cadence's checkpoint
round-trip (without it, a restore would see _last_target_sync=0 and
overwrite the restored target net up to interval-1 steps early).

It also owns the FUSED device sample path (data/device_path.py):
`_device_path_for` lazily builds a `DeviceSamplePath` over the healthy
sharded service on the first gated train call, renegotiates K after a
learner-tier attach, and demotes PERMANENTLY (one log line) when the
path latches dead — `device_train_call` below is its train-call body
(one `learn_many` scan per pre-transferred entry, ONE D2H per K).
"""

from __future__ import annotations

import sys
import time

import jax
import numpy as np

from distributed_reinforcement_learning_tpu.observability import TELEMETRY as _OBS


class ReplayTrainMixin:
    """Stride accounting for prioritized learners. Host-class contract:
    `agent` / `state` / `timer` / `replay` / `batch_size` / `_np_rng` /
    `target_sync_interval` / `replay_service` / `_train_once` /
    PublishCadenceMixin."""

    def _active_replay(self):
        """The replay the train path samples/updates: the sharded
        service (data/replay_service.py, wired by runtime/replay_shard)
        while it is healthy, the monolithic backend otherwise — the
        same permanent demote-on-failure shape as the ring and board
        transports."""
        svc = self.replay_service
        return svc if svc is not None and svc.healthy else self.replay

    def _train_guarded(self, replay):
        """`_train_once(replay)` with the service-demotion escape hatch:
        the sharded service's own empty/dead signal is converted to None
        (next train() resolves to the monolithic path, or waits for
        re-ingest after a fleet revive emptied the shards mid-call);
        any other RuntimeError — e.g. jax's XlaRuntimeError from the
        learn step, which subclasses RuntimeError — propagates."""
        from distributed_reinforcement_learning_tpu.data.replay_service import (
            ReplayServiceEmpty)
        try:
            return self._train_once(replay)
        except ReplayServiceEmpty:
            if replay is self.replay:
                raise  # not the service's signal to swallow
            return None
        except RuntimeError:
            svc = self.replay_service
            if replay is self.replay or (svc is not None and svc.healthy):
                raise
            return None

    def _init_stride(self, updates_per_call: int, mesh) -> None:
        self.updates_per_call = max(1, int(updates_per_call))
        if self.updates_per_call > 1 and mesh is not None:
            raise ValueError(
                "updates_per_call > 1 is not supported with a sharded mesh "
                "(the weighted learn_many is single-jit only)")
        if self.updates_per_call > self.target_sync_interval:
            # Every scanned update inside one call trains against a frozen
            # target net; a K that swallows whole sync intervals silently
            # degrades replay-family dynamics (IMPALA has no target net,
            # which is why the shared config key can carry such a K).
            raise ValueError(
                f"updates_per_call ({self.updates_per_call}) must not exceed "
                f"target_sync_interval ({self.target_sync_interval}) — the "
                "scan cannot target-sync mid-call")
        self._last_target_sync = 0
        # Fused device sample path (data/device_path.py): built lazily
        # on the first gated train call — by then a learner tier has
        # attached (it may force K=1).
        # `device_path_force` overrides the env gate (tests set it;
        # None = resolve DRL_DEVICE_PATH normally).
        self._device_path = None
        self._device_path_demoted = False
        self.device_path_force: bool | None = None

    # -- fused device sample path ------------------------------------------

    def _device_path_for(self, replay):
        """The device path for THIS train call, or None (host loop).

        Requires the already-resolved active replay to be the healthy
        sharded service: its per-shard locks make the background gather
        safe, while the monolithic backends are learner-thread-only by
        contract (a demotion closes the path BEFORE the host loop takes
        the sampling RNG back). Mesh learners stay on the host path —
        their batches need explicit sharding placement."""
        if self._device_path_demoted:
            return None
        dp = self._device_path
        svc = self.replay_service
        if svc is None or replay is not svc:
            if dp is not None:
                self._demote_device_path(
                    "replay service demoted to the monolithic backend")
            return None
        if dp is None:
            from distributed_reinforcement_learning_tpu.data.device_path import (
                device_path_enabled)

            force = self.device_path_force
            enabled = device_path_enabled() if force is None else bool(force)
            if not enabled or self._batch_sharding is not None:
                self._device_path_demoted = True  # resolve the gate once
                return None
            from distributed_reinforcement_learning_tpu.data.device_path import (
                DeviceSamplePath)

            self._device_path = dp = DeviceSamplePath(
                svc, self.batch_size, self.updates_per_call, self._np_rng)
        elif dp.k != self.updates_per_call:
            # A learner-tier attach forced K=1 after the path was built:
            # renegotiate — stale-K entries are epoch-dropped inside the
            # path, never fed to the K==1 learn seam.
            dp.reconfigure(self.updates_per_call)
        if dp.dead:
            self._demote_device_path(dp.dead_reason or "gather died")
            return None
        return dp

    def _demote_device_path(self, reason: str) -> None:
        """Permanent demote-to-host-path (the ring/board ladder shape):
        close() JOINS the gather thread, so the learner's `_np_rng` is
        exclusively the host loop's again before it samples. If the
        join times out (a wedged gather round), the shared stream is
        ABANDONED to the zombie thread and the host loop continues on a
        fresh one — RandomState is not thread-safe, and a corrupted
        sampling stream is worse than a one-time reseed (the stream
        carries no replay semantics beyond stratified-draw positions)."""
        dp, self._device_path = self._device_path, None
        self._device_path_demoted = True
        if dp is not None and not dp.close():
            self._np_rng = np.random.RandomState()
            print("[device_path] WARNING: gather thread did not join; "
                  "host loop continues on a fresh sampling stream",
                  file=sys.stderr)
        print(f"[device_path] WARNING: fused sample path demoted to the "
              f"host loop: {reason}", file=sys.stderr)

    def _close_device_path(self) -> None:
        if self._device_path is not None:
            self._device_path.close()
            self._device_path = None

    def _finish_train_call(self) -> None:
        """Advance counters by the call's K steps; publish and target-sync
        on steps-since-last cadences."""
        self.train_steps += self.updates_per_call
        self.maybe_publish()
        if self.train_steps - self._last_target_sync >= self.target_sync_interval:
            self._last_target_sync = self.train_steps
            self.state = self.agent.sync_target(self.state)

    def _cadence_extra(self) -> dict:
        """Checkpoint fields for the cadence counters."""
        return {"last_target_sync": self._last_target_sync}

    def _restore_cadence(self, extra: dict) -> None:
        """Resume cadences; absent fields fall back to `train_steps` (next
        sync/publish a full interval away — never an early overwrite)."""
        self._last_target_sync = int(extra.get("last_target_sync", self.train_steps))
        self._last_publish_step = self.train_steps  # restore just republished


def prioritized_train_call(learner, k: int, replay=None) -> dict:
    """Run `k` prioritized updates as one scan on `learner`; returns the
    last step's metrics (device arrays; callers float them).

    Samples and re-prioritizes against `replay` — the caller's already-
    resolved ACTIVE replay (the `_train_guarded` demotion guard reasons
    about the same object it passed down; re-resolving here could race
    a mid-call demotion onto a different replay than the guard checks).
    With the sharded service, the K-update writeback below only
    ENQUEUES: the service's router thread applies each batch's
    priorities to the owning shard asynchronously (latest-wins), so the
    learn thread never walks a sum tree here. Batches 2..K were sampled
    before any of the K updates landed either way — the same
    K-1-step priority staleness the scan always had."""
    from distributed_reinforcement_learning_tpu.data.device_path import (
        gather_scan_batch)

    if replay is None:
        replay = learner._active_replay()
    with learner.timer.stage("replay_sample"):
        # Host-side batch assembly belongs to the sample stage (the K=1
        # path stacks there too): keep the learn stage device-only. ONE
        # gather definition shared with the device path (device_path.py),
        # so the two paths cannot drift.
        stacked, weights, idx_list = gather_scan_batch(
            replay, learner.batch_size, k, learner._np_rng)
    with learner.timer.stage("learn"):
        learner.state, prio_stack, metrics_stack = learner.agent.learn_many(
            learner.state, stacked, weights)
        metrics = jax.tree.map(lambda x: x[-1], metrics_stack)
    with learner.timer.stage("replay_update"):
        prio_stack = np.asarray(prio_stack)
        for idxs, prio in zip(idx_list, prio_stack):
            replay.update_batch(idxs, prio)
    return metrics


def device_train_call(learner, path, replay) -> dict | None:
    """One train call off the fused device path: the entry's batch and
    IS weights are ALREADY device-resident (the path's gather thread
    sampled, stacked, and issued the H2D while the previous call's scan
    ran), so the learn stage is dispatch-only. K>1 runs as one jitted
    `learn_many` scan; K==1 goes through the learner's `_learn` seam so
    a tier's collective wrap still applies (the degrade contract). The
    K-step priorities come back in a SINGLE D2H and fan out to the
    replay's writeback router per sampled batch — with the sharded
    service those are the packed (tag|epoch|shard|tree_idx) indexes, so
    a shard death mid-K drops only its own stale-epoch updates.

    Returns None when the gather is behind (the caller's train() skips
    the step; a DEAD path was already demoted by `_device_path_for`)."""
    with learner.timer.stage("replay_sample"):
        entry = path.next_entry(timeout=1.0)
    if entry is None:
        return None
    k, batch, weights, idx_list = entry
    with learner.timer.stage("learn"):
        if k > 1:
            learner.state, prio_stack, metrics_stack = learner.agent.learn_many(
                learner.state, batch, weights)
            metrics = jax.tree.map(lambda x: x[-1], metrics_stack)
        else:
            learner.state, prio, metrics = learner._learn(
                learner.state, batch, weights)
            prio_stack = prio[None]
    with learner.timer.stage("replay_update"):
        t0 = time.perf_counter()
        prio_host = np.asarray(prio_stack)  # THE single D2H per K
        if _OBS.enabled:
            _OBS.gauge("devpath/d2h_ms", (time.perf_counter() - t0) * 1e3)
            _OBS.gauge("devpath/scan_k", k)
        for idxs, prio in zip(idx_list, prio_host):
            replay.update_batch(idxs, prio)
    return metrics

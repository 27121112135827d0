"""Publish-cadence gating shared by the three learners.

One place for the every-K-steps weight-publication semantics (the
`publish_interval` throughput knob) and its close()-time flush, so the
three runner classes cannot drift apart on them. Mixin contract: the
host class provides `weights`, `state`, `train_steps`,
`publish_interval`, and `timer`.
"""

from __future__ import annotations

import os
import queue as _queue
import threading
import time

from distributed_reinforcement_learning_tpu.observability import TELEMETRY as _OBS
from distributed_reinforcement_learning_tpu.utils.environ import env_flag


def _async_publish(sync_default: bool) -> bool:
    """Async by default: hand the params D2H + store to the weight
    store's background worker (an on-device copy is the only cost on
    the learn thread) — measured 2316ms -> 3.6ms/step at publish
    interval 1. DRL_ASYNC_PUBLISH=0 restores the synchronous path,
    whose host snapshot doubles as a per-step device sync (useful when
    timing individual steps). An explicit env setting always wins;
    `sync_default` only flips the unset-env default (run_sync loops)."""
    return env_flag("DRL_ASYNC_PUBLISH", not sync_default)


class MetricsPump:
    """Background metrics materialization for free-running learners.

    With async publication, the publish-step `float(metric)` becomes the
    learn thread's only device sync — on a thin-pipe host that is a
    hundreds-of-ms stall per publish for numbers only a logger reads.
    The pump takes the DEVICE arrays off the learn thread and floats +
    logs them on a worker. Bounded: at most `depth` batches pending —
    past that submit() blocks, which also caps how far ahead the host
    loop can dispatch device steps.
    """

    # Concurrency map (tools/drlint lock-discipline): empty on purpose,
    # and kept as documentation — the pump owns no lock because all of
    # its mutable attributes (`_thread`, `_logger`, `_prefix`) are
    # touched only by the learn thread (submit/close callers); the
    # internally-synchronized `_q` is the single cross-thread channel,
    # and the worker reads nothing else.
    _GUARDED_BY: dict = {}

    def __init__(self, logger, prefix: str = "learner/", depth: int = 4):
        self._logger = logger
        self._prefix = prefix
        self._q: _queue.Queue = _queue.Queue(maxsize=depth)
        self._thread: threading.Thread | None = None

    def submit(self, metrics: dict, step: int) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="metrics-pump")
            self._thread.start()
        self._q.put((metrics, step))

    def _loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            metrics, step = item
            try:
                floats = {k: float(v) for k, v in metrics.items()}
                self._logger.add_scalars(
                    {f"{self._prefix}{k}": v for k, v in floats.items()}, step)
            except Exception as e:  # noqa: BLE001 — logging must not kill training
                import sys

                print(f"[metrics] WARNING: drop step {step}: {e!r}", file=sys.stderr)

    def close(self) -> None:
        if self._thread is not None:
            try:
                # Bounded: a worker wedged inside float(v) (stuck device
                # sync) with a full queue must not hang shutdown forever.
                self._q.put(None, timeout=10.0)
            except _queue.Full:
                pass
            self._thread.join(timeout=10.0)
            self._thread = None


def _async_metrics(sync_default: bool) -> bool:
    """Follows the async-publish gate unless DRL_ASYNC_METRICS overrides.

    Additionally defaults OFF on the CPU backend: there the "device"
    compute shares the host cores, so a metrics worker thread contends
    with the very compute it is trying not to block (measured slower on
    a 1-core host); on TPU/GPU the compute is elsewhere and the float()
    it absorbs is a pure stall."""
    if os.environ.get("DRL_ASYNC_METRICS", "").strip():
        return env_flag("DRL_ASYNC_METRICS", False)
    import jax

    return jax.default_backend() not in ("cpu",) and _async_publish(sync_default)


class PublishCadenceMixin:
    # Single-threaded run_sync loops set this True: there the learner and
    # actors interleave on one thread, so async publication buys nothing
    # and only makes the weight-staleness sequence nondeterministic.
    sync_publish = False
    # Lazily-created MetricsPump (free-running async-metrics path); the
    # class default keeps __init__-less adoption safe across learners.
    _metrics_pump = None
    # Step count at the last publish. Cadence is "at least every
    # `publish_interval` steps since the last publish", NOT a modulo on
    # train_steps: learners advancing in strides (updates_per_call K, or
    # a partial drain of K' < K) would alias a modulo to lcm(K, interval)
    # — or miss it forever once the counter goes off-grid.
    _last_publish_step = 0

    def maybe_publish(self) -> bool:
        """Publish once `publish_interval` steps accumulate since the last.

        The publish's host snapshot (np.asarray) is the step's device
        sync, so with K>1 the intervening learn steps pipeline on-device
        with no host sync between them. Returns True when it published.
        """
        if self.train_steps - self._last_publish_step < self.publish_interval:
            return False
        self._last_publish_step = self.train_steps
        t0 = time.perf_counter()  # unconditional: telemetry enablement can
        with self.timer.stage("publish"):  # race the post-publish check
            if _async_publish(self.sync_publish):
                # Sub-stages so a fat `publish` mean is attributable: the
                # handoff (device-side copy dispatch) vs the bounded-
                # staleness stall (r4's shm-mode 2278 ms publish row was
                # unexplained for lack of exactly this split).
                with self.timer.stage("publish_handoff"):
                    self.weights.publish_async(self.state.params, self.train_steps)
                # Bounded staleness: latest-wins async publication may
                # drop intermediate versions, but actors must never act
                # on weights more than ~3 publish intervals old (the
                # off-policyness V-trace's truncated-IS correction
                # targets). If the background worker lags past that,
                # wait for it here — the common case never blocks.
                if self.train_steps - self.weights.version > 3 * self.publish_interval:
                    with self.timer.stage("publish_stall"):
                        ok = self.weights.flush_async(timeout=10.0)
                    if not ok:
                        import sys

                        print(f"[publish] WARNING: async weight publication "
                              f"stalled; actors hold version "
                              f"{self.weights.version} at step {self.train_steps}",
                              file=sys.stderr)
            else:
                self.weights.publish(self.state.params, self.train_steps)
        if _OBS.enabled:
            # Learn-thread cost of publication (async: handoff + any
            # bounded-staleness stall; sync: the full D2H). The landed
            # version's timeline is the weights/version gauge.
            _OBS.gauge("publish/latency_ms", (time.perf_counter() - t0) * 1e3)
            _OBS.count("publish/count")
        return True

    def log_step_metrics(self, metrics: dict) -> dict:
        """Per-train-step metrics to the logger WITHOUT stalling the learn
        thread (the replay learners' old unconditional `float()` per step
        was a per-step device sync — the two grandfathered drlint
        baseline entries this method retired). Async mode hands the
        DEVICE arrays to the bounded MetricsPump, which floats + logs
        them on its worker (the returned dict stays un-materialized);
        sync mode floats inline — that deliberate device sync doubles as
        the sync loop's pipelining bound, exactly like ImpalaLearner's —
        and logs host floats."""
        if _async_metrics(self.sync_publish):
            if self._metrics_pump is None:
                self._metrics_pump = MetricsPump(self.logger)
            with self.timer.stage("metrics_sync"):
                self._metrics_pump.submit(dict(metrics), self.train_steps)
            return metrics
        with self.timer.stage("metrics_sync"):
            metrics = {k: float(v) for k, v in metrics.items()}
        self.logger.add_scalars(
            {f"learner/{k}": v for k, v in metrics.items()}, self.train_steps)
        return metrics

    def close_metrics(self) -> None:
        """Drain any pending pump lines at close() (safe when unused)."""
        if self._metrics_pump is not None:
            self._metrics_pump.close()

    def flush_publish(self) -> None:
        """close()-time flush: any updates since the last publish would
        otherwise never reach the store."""
        if self.train_steps > self._last_publish_step:
            self.weights.publish(self.state.params, self.train_steps)
            self._last_publish_step = self.train_steps
        if _async_publish(self.sync_publish):
            # Retire the worker, not just drain it: the learner is the
            # store's only publisher, so past this point the worker
            # would idle on its condvar forever (the sanitizer's leak
            # census flags exactly that). Store close() drains pending
            # then joins; any later publish falls back to the sync path.
            self.weights.close()

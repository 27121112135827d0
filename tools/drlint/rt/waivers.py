"""Reconciliation waivers: the committed list of static-model entries
the sanitized suites are NOT expected to exercise, each with a human
justification (the `--reconcile` analog of the 10-entry lint baseline,
and like it, a list that should shrink).

A ``_GUARDED_BY`` entry proves its worth by being OBSERVED — a guarded
attribute that no sanitized suite ever touches with its lock held is
either dead annotation or dead code, and ``python -m tools.drlint
--reconcile`` flags it. Some entries are legitimately unobservable on
this container (error-path-only state, fields only touched under
chaos schedules the bounded suites don't run); they live here, keyed
``(ClassName, attr)``, value = justification (>= 10 chars, enforced by
the reconciler).

``EDGE_WAIVERS`` plays the same role for observed-edge model gaps: an
acquisition edge the runtime lawfully observes but the static
lock-order pass cannot resolve (cross-object calls through untyped
attributes). Key: ``((src_owner, src_name), (dst_owner, dst_name))``.

``LIFECYCLE_WAIVERS`` covers the census diff: a class the static
lifecycle passes model as owning a thread / shm segment / socket that
the sanitized suites legitimately never construct (gated features,
chaos-only paths). Key: ``(ClassName, res)`` with res in
``thread | shm | socket``.
"""

from __future__ import annotations

GUARDED_WAIVERS: dict[tuple[str, str], str] = {
    # Native (C++) backends are availability-dependent: make_replay /
    # NativeTrajectoryQueue fall back to the pure-python paths when the
    # in-tree lib doesn't build, so the nine concurrency suites cannot
    # pin these on every container. test_native/test_data own them.
    ("NativeTrajectoryQueue", "_pool"):
        "native-lib-only path; exercised by test_native when cpp builds",
    ("NativeTrajectoryQueue", "_pool_idx"):
        "native-lib-only path; exercised by test_native when cpp builds",
    ("NativeTrajectoryQueue", "_pool_sig"):
        "native-lib-only path; exercised by test_native when cpp builds",
    ("NativeTrajectoryQueue", "_scratch"):
        "native-lib-only path; exercised by test_native when cpp builds",
    ("NativePrioritizedReplay", "_data"):
        "native-lib-only path; exercised by test_data when cpp builds",
    ("NativePrioritizedReplay", "beta"):
        "native-lib-only path; exercised by test_data when cpp builds",
    ("_CodecCaches", "_dedup"):
        "populated only under DRL_OBS_DEDUP=1 (off by default)",
    ("ShardedReplayService", "updates_dropped"):
        "written only when the async priority-writeback ring overflows "
        "(latest-wins drop); the bounded suites never saturate it",
    ("RingDrainer", "_dropped"):
        "corruption-only accounting; healthy-suite rings drop nothing — "
        "the slow-marked chaos drill is the owning exercise",
    # Telemetry is off (DRL_TELEMETRY unset) in the nine concurrency
    # suites — instruments are no-ops before configure(). The maps were
    # added by ISSUE 13's guardedby-completeness pass; test_observability
    # is the owning exercise.
    ("Telemetry", "_counters"):
        "telemetry disabled in the sanitized suites; test_observability "
        "exercises the instrument maps",
    ("Telemetry", "_gauges"):
        "telemetry disabled in the sanitized suites; test_observability "
        "exercises the instrument maps",
    ("Telemetry", "_providers"):
        "telemetry disabled in the sanitized suites; test_observability "
        "exercises the instrument maps",
    ("TraceEmitter", "dropped"):
        "telemetry disabled in the sanitized suites; test_observability "
        "exercises the trace buffer",
    ("TraceEmitter", "_pending"):
        "telemetry disabled in the sanitized suites; test_observability "
        "exercises the trace buffer",
    ("TraceEmitter", "_written"):
        "telemetry disabled in the sanitized suites; test_observability "
        "exercises the trace buffer",
    ("TraceEmitter", "_file"):
        "telemetry disabled in the sanitized suites; test_observability "
        "exercises the trace buffer",
    ("TraceEmitter", "_closed"):
        "telemetry disabled in the sanitized suites; test_observability "
        "exercises the trace buffer",
    ("Telemetry", "_flush_errors"):
        "error-path-only counter (flush loop failure); telemetry is "
        "disabled in the sanitized suites anyway",
    ("Telemetry", "_provider_errors"):
        "error-path-only counter (provider callback failure); telemetry "
        "is disabled in the sanitized suites anyway",
}

EDGE_WAIVERS: dict[tuple[tuple[str, str], tuple[str, str]], str] = {
    # Layered component->leaf acquisitions the static resolver cannot
    # follow (factory-returned backends, ctor-param objects, cross-
    # module function calls). In each, the inner lock is a LEAF that
    # never calls back out of its class, so the edge cannot close a
    # cycle; the runtime cycle checker still watches the real order.
    (("ReplayShard", "_lock"), ("ArrayPrioritizedReplay", "_lock")):
        "shard wraps a make_replay backend (dynamic factory); backend "
        "lock is a leaf — its methods make no outward calls",
    (("ReplayShard", "_lock"), ("NativePrioritizedReplay", "_lock")):
        "same layered shard->backend edge with the native backend",
    (("ReplayShard", "_lock"), ("TieredStore", "_io_lock")):
        "restart() closes the old factory-returned tiered backend under "
        "the shard lock; _io_lock is a leaf (manifest write cursor + "
        "closed flag, no outward calls), so the edge cannot cycle",
    (("ReplayShard", "_lock"),
     ("distributed_reinforcement_learning_tpu/data/native.py", "_lib_lock")):
        "backend probe compiles the cpp lib exactly once under the "
        "module lock; compile makes no outward calls to runtime locks",
    (("ReplayIngestFifo", "_lock"), ("ReplayShard", "_lock")):
        "ingest fifo routes to shards passed in via ctor param (untyped "
        "for the static pass); shard lock is a leaf on this path",
    (("WeightStore", "_lock"), ("_CodecCaches", "_lock")):
        "store encodes under its lock via module-level codec functions; "
        "the codec cache lock is a leaf (pure encode/decode, no "
        "outward calls)",
}

LIFECYCLE_WAIVERS: dict[tuple[str, str], str] = {
    ("Telemetry", "thread"):
        "flush/provider loops only spawn after configure(); telemetry "
        "is disabled in the sanitized suites — test_observability owns",
    ("MetricsPump", "thread"):
        "pump spawns only under DRL_ASYNC_METRICS with a live logger; "
        "the sanitized suites run learners sync — test_observability "
        "owns the pump",
    ("DevicePrefetcher", "thread"):
        "legacy host-batch prefetcher superseded by DeviceSamplePath "
        "in the sanitized device-path suite; test_prefetch owns it",
}

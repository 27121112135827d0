"""Bounded trajectory FIFO with blocking backpressure.

Host-side replacement for the reference's learner-placed `tf.FIFOQueue`
(`distributed_queue/buffer_queue.py:28-36,153-160,368-378`): a
thread-safe bounded queue of numpy pytrees. Producers (actor threads or
the transport server) block when full — the same backpressure the TF
queue kernel gave the reference. The learner drains whole batches in one
call and gets stacked arrays ready for one host->device transfer,
replacing the reference's 32 sequential dequeue round-trips per batch
(`buffer_queue.py:416-435`, the anti-pattern called out in SURVEY §7).

A C++ ring-buffer backend (cpp/) slots in behind the same interface for
the multi-process data plane.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any

import numpy as np

from distributed_reinforcement_learning_tpu.observability import TELEMETRY as _OBS
from distributed_reinforcement_learning_tpu.utils.environ import env_int


def stack_pytrees(items: list[Any]) -> Any:
    """Stack a list of identically-structured numpy pytrees along axis 0."""
    import jax

    return jax.tree.map(lambda *xs: np.stack(xs), *items)


def blob_ingest(queue: Any) -> tuple[Any, Any]:
    """-> (prepare, put) for feeding CODEC BLOBS into a trajectory queue.

    The single definition of blob-ingest semantics, shared by the TCP
    transport server and the shm-ring drainer so the two transports
    cannot drift. Three queue shapes, most specific first:

    - replay-shard facades (`ingest_blob`,
      runtime/replay_shard.ReplayIngestFifo) take the RAW wire blob
      untouched — the owning shard decodes it ONCE on the transport
      thread (a dedup-packed blob decodes straight to the plain pytree,
      skipping the unpack->re-encode round trip blob-native queues pay);
    - blob-native queues (`put_bytes`, the C++ backend) take the raw
      bytes routed through `codec.unpack_blob` so a dedup-packed wire
      blob (DRL_OBS_DEDUP) is reconstructed to the plain layout BEFORE
      the queue (the native batch-gather assumes it; a plain blob passes
      through as the same object, no copy);
    - pytree queues take a decoded COPY — the blob's buffer may be
      reused or unmapped by the caller the moment `prepare` returns, and
      decode reconstructs packed leaves bit-identically as part of that
      copy.

    Either way, replay, prioritization, and training see byte-for-byte
    the trajectories a dedup-off run would see.
    `put(item, timeout=...)` follows the queue's blocking-put contract
    (False on timeout, RuntimeError once closed).
    """
    from distributed_reinforcement_learning_tpu.data import codec

    if hasattr(queue, "ingest_blob"):
        return (lambda blob: blob), queue.ingest_blob
    if hasattr(queue, "put_bytes"):
        # strip_stamp first: a priority-stamped wire blob (ISSUE 18,
        # data/admission.py) carries an extension frame the native
        # batch-gather must never see; the monolithic consumer behind a
        # blob-native queue re-scores at ingest anyway, so the stamp is
        # dead weight here. decode() below is stamp-transparent itself.
        return (lambda blob: codec.unpack_blob(codec.strip_stamp(blob))), \
            queue.put_bytes
    return (lambda blob: codec.decode(blob, copy=True)), queue.put


def put_batch_size() -> int:
    """The actor's PUT batch size: how many unrolls ride one batched
    exchange (`DRL_PUT_BATCH`). 0 (the default) keeps today's behavior —
    the whole extract() round in one OP_PUT_TRAJ_N exchange (and, for
    the Ape-X actor's per-step puts, one unroll per put). Sizing
    guidance vs actor count: docs/performance.md ("PUT batch sizing")."""
    return max(0, env_int("DRL_PUT_BATCH", 0))


def put_round(queue: Any, items: list[Any]) -> None:
    """Ship one actor round (the N trajectories of an `extract()`) to a
    queue, batched when the queue supports it.

    Over the socket data plane, `put_many` is ONE round trip for the
    whole round (OP_PUT_TRAJ_N) instead of N request/replies — the
    actor-side fix for the reference's per-item-RPC anti-pattern
    (`buffer_queue.py:416-435`). In-process queues just loop.
    `DRL_PUT_BATCH=k` chunks the round into k-unroll exchanges (smaller
    server-side enqueue bursts under many actors, at more round trips).
    """
    put_many = getattr(queue, "put_many", None)
    if put_many is None:
        for item in items:
            queue.put(item)
        return
    chunk = put_batch_size()
    if chunk <= 0 or chunk >= len(items):
        put_many(items)
    else:
        for i in range(0, len(items), chunk):
            put_many(items[i:i + chunk])


class TrajectoryQueue:
    """Bounded MPMC queue of trajectory pytrees.

    put() blocks when full (backpressure on actors, like the reference's
    blocking enqueue); get_batch(n) blocks until n items are available and
    returns them stacked along a new leading batch axis.
    """

    # Concurrency map (tools/drlint lock-discipline): `_not_full` and
    # `_not_empty` are Conditions over the SAME `_lock`, so any of the
    # three names is the same mutex; producers, consumers, and the
    # transport server's enqueue slices all go through it.
    _GUARDED_BY = {
        "_items": ("_lock", "_not_full", "_not_empty"),
        "_closed": ("_lock", "_not_full", "_not_empty"),
    }

    def __init__(self, capacity: int):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = capacity
        self._items: deque = deque()
        self._lock = threading.Lock()
        self._not_full = threading.Condition(self._lock)
        self._not_empty = threading.Condition(self._lock)
        self._closed = False

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)

    def size(self) -> int:
        """Queue depth, the learner's readiness poll (`buffer_queue.py:437-439`)."""
        return len(self)

    def close(self) -> None:
        """Wake all blocked producers/consumers; subsequent puts raise."""
        with self._lock:
            self._closed = True
            self._not_full.notify_all()
            self._not_empty.notify_all()

    @property
    def closed(self) -> bool:
        with self._lock:
            return self._closed

    def put(self, item: Any, timeout: float | None = None) -> bool:
        with self._not_full:
            if not self._not_full.wait_for(
                lambda: len(self._items) < self.capacity or self._closed, timeout
            ):
                return False
            if self._closed:
                raise RuntimeError("queue closed")
            self._items.append(item)
            depth = len(self._items)
            self._not_empty.notify()
        # Telemetry outside the queue lock (the telemetry lock is a leaf).
        if _OBS.enabled:
            _OBS.count("fifo/puts")
            _OBS.gauge("fifo/fill", depth / self.capacity)
        return True

    def put_many(self, items: list[Any], timeout: float | None = None) -> int:
        """Enqueue a list of items; returns how many were accepted.

        Blocks per item under backpressure like put(). Stops at the first
        timeout — the remainder is NOT enqueued (callers may retry it).
        """
        deadline = None if timeout is None else time.monotonic() + timeout
        accepted = 0
        for item in items:
            remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
            if not self.put(item, timeout=remaining):
                break
            accepted += 1
        return accepted

    def get(self, timeout: float | None = None) -> Any | None:
        with self._not_empty:
            if not self._not_empty.wait_for(lambda: self._items or self._closed, timeout):
                return None
            if not self._items:  # closed and drained
                return None
            item = self._items.popleft()
            self._not_full.notify()
        if _OBS.enabled:
            _OBS.count("fifo/gets")
        return item

    def get_batch(self, batch_size: int, timeout: float | None = None) -> Any | None:
        """Dequeue `batch_size` items and stack them into `[B, ...]` arrays.

        `timeout` is a total deadline across the whole batch. On timeout the
        already-dequeued items are pushed back to the FRONT of the queue in
        order (no data loss, no reordering) and None is returned.
        """
        deadline = None if timeout is None else time.monotonic() + timeout
        items = []
        for _ in range(batch_size):
            remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
            item = self.get(remaining)
            if item is None:
                if items:
                    with self._lock:
                        self._items.extendleft(reversed(items))
                        self._not_empty.notify_all()
                return None
            items.append(item)
        return stack_pytrees(items)

"""Device-resident sample path: fused gather -> H2D -> scanned learn.

The learn kernel is far faster than the host loop that feeds it. PR 6
moved prioritization to ingest; this module moves the REST of the
per-update host round-trip off the learn thread — the device-side mirror of
in-network experience sampling (arXiv:2110.13506) and the keep-it-on-
device discipline of Podracer (arXiv:2104.06272). The host path the
prioritized learners pay per train call is

    sample (shard gather) -> np stack -> H2D -> 1 jitted step
    -> D2H priorities -> host writeback

all serialized on the learn thread. `DeviceSamplePath` is the
`data/prefetch.DevicePrefetcher` of the REPLAY plane: a background
gather thread samples the next K prioritized batches from the
thread-safe sharded service (data/replay_service.py — per-shard locks
make concurrent gather safe; the single-thread monolithic backends stay
on the host path by contract), assembles the `[K, B, ...]` scan stack
on the host, and issues the `jax.device_put` on its own thread — so the
copy for call k+1 overlaps the jitted `learn_many` scan for call k,
while the shard ingest threads keep inserting concurrently. `depth`
bounds how many sampled calls sit device-resident beyond the one in
use (classic double buffering at the default 1).

The learn side (`runtime/replay_train.device_train_call`) runs the K
steps as ONE jitted `lax.scan` (`agent.learn_many`), materializes the `[K, B]`
priority stack in a SINGLE D2H per K, and fans it back to the sharded
writeback router through the existing packed (tag|epoch|shard|tree_idx)
int64 indexes — a shard death mid-K drops only that shard's stale-epoch
updates, loss-free, exactly as the router always did.

Semantics: sampled batches are bit-identical to the host gather at a
fixed RNG (`gather_scan_batch` IS the host path's gather —
`prioritized_train_call` calls the same function). The only delta is
priority staleness: with K scanned steps and `depth` buffered calls,
a batch can be sampled up to ~K+depth updates before its priorities
refresh — the same staleness class the host K>1 scan already accepts
(batches 2..K sampled before update 1 lands) and distributed Ape-X
accepts from its actors.

Degrade ladder (all permanent, logged once by the learner mixin):
an oversize stacked call (`DRL_DEVICE_PATH_MAX_MB`) or a gather fault
latches the path dead -> the learner demotes to the host loop; a
service demotion (all shards dead) closes the path before the learner
resumes host-side sampling (the RNG hand-back). A learner-tier attach
that forces K=1 (allreduce merges per train step) RECONFIGURES the
path instead: entries stacked at the old K are epoch-dropped, never
fed to the K==1 learn seam — double-buffered H2D only, cleanly.

Gate: `DRL_DEVICE_PATH` (off by default; not measured on the chip).

Concurrency model (no class-owned locks, so the `_GUARDED_BY` map is
the documentation form): ONE gather thread produces, ONE learn thread
consumes. The handoff is a bounded `queue.Queue` (internally locked);
`_cfg` is an immutable `(k, epoch)` tuple swapped atomically by the
consumer (reconfigure) and read once per round by the producer —
entries carry the epoch they were stacked under, and the consumer
drops mismatches. `dead_reason` is a write-once str published by
whichever side latches the path; all remaining counters are
single-writer (noted per attribute in `_NOT_GUARDED`).
"""

from __future__ import annotations

import queue as _queue
import threading
import time
from typing import Any, Callable

import numpy as np

from distributed_reinforcement_learning_tpu.observability import TELEMETRY as _OBS
from distributed_reinforcement_learning_tpu.utils.environ import (
    env_flag,
    env_float,
    env_int,
)


def device_path_enabled() -> bool:
    """`DRL_DEVICE_PATH`: the device-resident sample path. Off by
    default; not measured on the chip."""
    return env_flag("DRL_DEVICE_PATH", False)


def path_depth() -> int:
    """`DRL_DEVICE_PATH_DEPTH`: device-resident sampled calls beyond the
    one in use (1 = classic double buffering)."""
    return max(1, env_int("DRL_DEVICE_PATH_DEPTH", 1))


def path_max_bytes() -> int:
    """`DRL_DEVICE_PATH_MAX_MB`: stacked-call size past which the path
    demotes to the host loop instead of risking a device OOM."""
    mb = env_float("DRL_DEVICE_PATH_MAX_MB", 256.0)
    return max(1, int(mb * 1024 * 1024))


# -- the gather (shared with the host path) -----------------------------------


def gather_scan_batch(replay, batch_size: int, k: int, rng
                      ) -> tuple[Any, np.ndarray, list[np.ndarray]]:
    """Sample `k` prioritized batches and assemble the scan inputs on
    the host: -> (stacked [k, B, ...] pytree, weights [k, B] f32,
    per-batch index arrays). THE single definition of the gather —
    `runtime/replay_train.prioritized_train_call` (host path) and the
    `DeviceSamplePath` gather thread both call it, so the device path's
    sampled batches are bit-identical to the host gather at a fixed RNG
    by construction (and test-pinned, tests/test_device_path.py)."""
    from distributed_reinforcement_learning_tpu.data.fifo import stack_pytrees

    import jax

    sampled = [replay.sample(batch_size, rng) for _ in range(k)]
    if getattr(replay, "stacked_samples", False):
        # SoA backend hands back already-stacked [B, ...] arrays.
        stacked = stack_pytrees([items for items, _, _ in sampled])
    else:
        # AoS: one copy — stack all k*B items once, view as [k, B, ...].
        flat = stack_pytrees([it for items, _, _ in sampled for it in items])
        stacked = jax.tree.map(
            lambda x: x.reshape((k, -1) + x.shape[1:]), flat)
    weights = np.stack([np.asarray(w, np.float32) for _, _, w in sampled])
    return stacked, weights, [idxs for _, idxs, _ in sampled]


def gather_single_batch(replay, batch_size: int, rng
                        ) -> tuple[Any, np.ndarray, list[np.ndarray]]:
    """The K==1 gather: -> ([B, ...] batch, weights [B] f32, [idxs]).
    No scan axis — the entry feeds the learner's `_learn` seam directly
    (which a learner tier may have wrapped with its collective), so the
    fused path under a tier-forced K=1 is H2D double buffering only."""
    from distributed_reinforcement_learning_tpu.data.fifo import stack_pytrees

    items, idxs, weights = replay.sample(batch_size, rng)
    batch = items if getattr(replay, "stacked_samples", False) \
        else stack_pytrees(items)
    return batch, np.asarray(weights, np.float32), [idxs]


def _tree_nbytes(tree: Any) -> int:
    import jax

    return sum(np.asarray(leaf).nbytes for leaf in jax.tree.leaves(tree))


# -- the path -----------------------------------------------------------------


class DeviceSamplePath:
    """Background sample + stack + device_put pipeline over a
    prioritized replay (the thread-safe sharded service in deployment).

    `next_entry(timeout)` returns `(k, device batch, device weights,
    idx_list)` — or None on timeout / after the path latched dead (the
    caller demotes to the host loop; `dead_reason` says why). `rng` is
    the learner's sampling stream: while the path is live the gather
    thread OWNS it (the learner must not host-sample), and `close()`
    joins the thread before the host path takes the stream back.
    """

    # Documentation-form concurrency map (tools/drlint lock-discipline):
    # no class-owned locks — see the module docstring's concurrency
    # model. Single-producer/single-consumer over a bounded queue.Queue;
    # `_cfg` / `dead_reason` are atomic reference swaps.
    _GUARDED_BY: dict = {}
    _NOT_GUARDED = {
        "_cfg": "immutable (k, epoch) tuple; consumer swaps the whole "
                "reference, producer reads it once per round",
        "dead_reason": "write-once latch reason (str reference), "
                       "whichever side latches first wins",
        "dropped_entries": "consumer-thread-only stale-epoch tally",
        "h2d_bytes": "gather-thread-only byte counter",
        "entries_out": "gather-thread-only entry counter",
        "gather_rounds": "gather-thread-only round counter",
    }

    def __init__(self, replay, batch_size: int, k: int, rng,
                 depth: int | None = None, max_bytes: int | None = None,
                 transfer: Callable[[Any], Any] | None = None):
        import jax

        self.replay = replay
        self.batch_size = batch_size
        self.rng = rng
        self.max_bytes = path_max_bytes() if max_bytes is None else max_bytes
        # Injectable H2D (tests stub a slow copy to pin that the overlap
        # actually overlaps); deployment is a plain device_put on this
        # background thread — the async transfer the learn dispatch then
        # waits on, never the learn THREAD.
        self._transfer = jax.device_put if transfer is None else transfer
        self._cfg: tuple[int, int] = (max(1, int(k)), 0)
        self._out: _queue.Queue = _queue.Queue(
            maxsize=path_depth() if depth is None else max(1, depth))
        self.dead_reason: str | None = None
        self.dropped_entries = 0  # stale-epoch entries (K renegotiated)
        self.h2d_bytes = 0
        self.entries_out = 0
        self.gather_rounds = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="device-sample-path")
        self._thread.start()

    # -- state -------------------------------------------------------------

    @property
    def k(self) -> int:
        return self._cfg[0]

    @property
    def dead(self) -> bool:
        return self.dead_reason is not None

    def stats(self) -> dict:
        return {"k": self._cfg[0], "depth": self._out.qsize(),
                "entries_out": self.entries_out,
                "h2d_bytes": self.h2d_bytes,
                "dropped_entries": self.dropped_entries,
                "gather_rounds": self.gather_rounds,
                "dead_reason": self.dead_reason}

    # -- consumer side -----------------------------------------------------

    def reconfigure(self, k: int) -> None:
        """Renegotiate the scan depth (the learner-tier attach forces
        K=1 under allreduce). Entries already stacked at the old K carry
        the old epoch and are dropped at `next_entry` — never fed to a
        learn path expecting the new shape (no silent K change, no
        shape crash; pinned in tests/test_device_path.py)."""
        k = max(1, int(k))
        cur_k, epoch = self._cfg
        if k == cur_k:
            return
        self._cfg = (k, epoch + 1)

    def next_entry(self, timeout: float | None = 0.5):
        """-> (k, device batch, device weights, idx_list) or None (the
        gather is behind, or the path died — check `dead`). Stale-epoch
        entries are consumed and dropped here; their sampled indexes
        lose only their (advisory) priority writeback."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            try:
                wait = (0.2 if deadline is None else
                        max(0.0, min(0.2, deadline - time.monotonic())))
                epoch, k, batch, weights, idxs = self._out.get(timeout=wait)
            except _queue.Empty:
                if self.dead:
                    return None
                if deadline is not None and time.monotonic() >= deadline:
                    return None
                continue
            if epoch != self._cfg[1]:
                self.dropped_entries += 1
                if _OBS.enabled:
                    _OBS.count("devpath/dropped_entries")
                continue
            return k, batch, weights, idxs

    def close(self) -> bool:
        """Stop and JOIN the gather thread; True when the join landed —
        only then is the learner's RNG stream exclusively the host
        path's again. A False return (the thread wedged past the
        budget, e.g. a device_put stalled behind queued device work)
        means the caller must NOT keep sampling the shared RNG
        (`ReplayTrainMixin._demote_device_path` swaps in a fresh stream
        in that case)."""
        self._stop.set()
        self._thread.join(timeout=5.0)
        return not self._thread.is_alive()

    # -- gather thread -----------------------------------------------------

    def _latch_dead(self, reason: str) -> None:
        if self.dead_reason is None:
            self.dead_reason = reason

    def _loop(self) -> None:
        try:
            self._loop_inner()
        except BaseException as e:  # noqa: BLE001 — surfaced via dead_reason
            self._latch_dead(f"gather thread died: {type(e).__name__}: {e}")

    def _loop_inner(self) -> None:
        from distributed_reinforcement_learning_tpu.data.replay_service import (
            ReplayServiceEmpty)

        while not self._stop.is_set():
            k, epoch = self._cfg
            t0 = time.perf_counter()
            try:
                if k > 1:
                    batch, weights, idxs = gather_scan_batch(
                        self.replay, self.batch_size, k, self.rng)
                else:
                    batch, weights, idxs = gather_single_batch(
                        self.replay, self.batch_size, self.rng)
            except ReplayServiceEmpty:
                # Transient while the service is healthy (a revive can
                # empty the shards mid-run); terminal once it demoted —
                # the learner is about to resolve the monolithic path.
                if not getattr(self.replay, "healthy", True):
                    self._latch_dead("replay service demoted (all shards "
                                     "dead)")
                    return
                self._stop.wait(0.005)
                continue
            gather_ms = (time.perf_counter() - t0) * 1e3
            self.gather_rounds += 1
            nbytes = _tree_nbytes(batch) + weights.nbytes
            if nbytes > self.max_bytes:
                self._latch_dead(
                    f"oversize sampled call: {nbytes / 1e6:.1f} MB > "
                    f"DRL_DEVICE_PATH_MAX_MB — demoting to the host path")
                return
            t1 = time.perf_counter()
            dev_batch, dev_weights = self._transfer((batch, weights))
            h2d_ms = (time.perf_counter() - t1) * 1e3
            self.h2d_bytes += nbytes
            if _OBS.enabled:
                _OBS.gauge("devpath/gather_ms", gather_ms)
                _OBS.gauge("devpath/h2d_ms", h2d_ms)
                _OBS.count("devpath/h2d_bytes", nbytes)
                _OBS.gauge("devpath/depth", self._out.qsize())
            entry = (epoch, k, dev_batch, dev_weights, idxs)
            while not self._stop.is_set():
                try:
                    self._out.put(entry, timeout=0.2)
                    self.entries_out += 1
                    if _OBS.enabled:
                        _OBS.count("devpath/entries")
                    break
                except _queue.Full:
                    continue

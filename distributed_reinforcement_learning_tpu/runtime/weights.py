"""Versioned weight publication: learner -> actors.

Replaces the reference's cross-process `tf.assign` pulls
(`utils.py:5-21`, run once per unroll at `train_impala.py:135`). The
learner publishes a version-stamped params snapshot; actors poll
`get_if_newer` at their unroll cadence. Same staleness semantics
(actors may act on weights a few updates old — standard IMPALA
off-policyness, corrected by V-trace), but publication is a single
atomic reference swap instead of per-variable assigns.

Publication is ENCODE-ONCE (the learner-side fix for `publish` p99
spikes on the copy path): the
background worker's D2H lands directly in a codec-layout host blob —
one buffer allocation per publish with a schema-cached frozen layout
(`data/codec.py`), not one fresh numpy array per leaf — and every
consumer reads that single materialization:

- in-process actors / the inference service get zero-copy READ-ONLY
  views into the blob (a consumer mutating pulled weights fails loudly
  instead of silently corrupting every reader of the shared snapshot);
- the transport server serves the blob bytes as-is (`get_blob`), so a
  new version never costs a full-params re-encode on a serve thread;
- the shm weight board (`runtime/weight_board.py`), when attached,
  takes one memcpy of the same bytes into its inactive slot.

Each publish gets a FRESH blob rather than literally reusing one arena:
published snapshots are shared by reference with in-process consumers
that hold them across unrolls, so rewriting a reused buffer two
publishes later would corrupt weights mid-use. The allocation is one
np.empty (lazily paged) per publish; the layout walk and header build
are cached per schema.

SHARDED publication (`DRL_WEIGHTS_SHARDED`, runtime/weight_shards.py):
the store splits the pytree along its partition-rule shards
(parallel/partition.py — the axes the learner's mesh shards over) into
per-shard encode-once blobs plus one json manifest, optionally casting
the actor-bound bytes to bf16/int8 at encode time (the f32 master copy
and in-process views never quantize) and delta-encoding changed shards
between consecutive versions. The board then memcpys only shards whose
bytes changed; the TCP server serves the shard-scoped op; `get_blob()`
keeps old whole-blob clients working by re-encoding lazily per version.
"""

from __future__ import annotations

import threading
from typing import Any

import jax
import numpy as np

from distributed_reinforcement_learning_tpu.data import codec
from distributed_reinforcement_learning_tpu.observability import TELEMETRY as _OBS
from distributed_reinforcement_learning_tpu.runtime import weight_shards


def _freeze(arr: np.ndarray) -> np.ndarray:
    arr.setflags(write=False)
    return arr


def _host_snapshot(params: Any) -> tuple[np.ndarray | None, Any]:
    """Materialize `params` on host as (codec blob, read-only pytree).

    The encode's buffer assignment IS the D2H wait (np.asarray on a
    device leaf materializes it; on the CPU backend that is a zero-copy
    view, so the blob write is the only copy). The returned pytree is
    zero-copy views into the blob payload, frozen read-only — the blob
    and the views share bytes with whatever the transport/board sends,
    so nothing may ever write through them.

    A pytree the codec cannot round-trip (e.g. a leaf dtype without
    buffer protocol, which can fail at encode OR only at decode) falls
    back to per-leaf host snapshots with blob=None: in-process consumers
    still work; the wire/board paths — which could never have carried
    such params anyway — simply have nothing to send. The fallback
    COPIES before freezing: np.asarray of a host numpy leaf is the same
    object, and freezing the caller's own array in place would make the
    learner's live params read-only.
    """
    try:
        blob = codec.encode(params, cache=True)
        params_host = jax.tree.map(_freeze, codec.decode(blob, cache=True))
    except (TypeError, ValueError):
        return None, jax.tree.map(
            lambda a: _freeze(np.array(np.asarray(a))), params)
    return blob, params_host


class WeightStore:
    # Concurrency map (tools/drlint lock-discipline): `_lock` covers the
    # published snapshot (params views + blob + version) that actor
    # pulls / the transport server / the inference service read, and the
    # attached weight board (its publish memcpy must follow the store's
    # seq arbitration, so it happens under the same lock); `_async_lock`
    # covers the async-publication worker's submission state — `_cond`
    # is a Condition over that same lock (alias), so either name is the
    # same mutex. `_copy_fn` is deliberately unannotated: it is only
    # ever touched by the learn thread (publish_async caller).
    _GUARDED_BY = {
        "_params": "_lock",
        "_blob": "_lock",
        "_version": "_lock",
        "_applied_seq": "_lock",
        "_board": "_lock",
        "_manifest": "_lock",
        "_manifest_bytes": "_lock",
        "_bcast": "_lock",
        "_prev_bcast": "_lock",
        "_prev_version": "_lock",
        "_changed": "_lock",
        "_deltas": "_lock",
        "_shard_stats": "_lock",
        "_seq": ("_async_lock", "_cond"),
        "_pending": ("_async_lock", "_cond"),
        "_busy": ("_async_lock", "_cond"),
        "_closed": ("_async_lock", "_cond"),
        "_worker": ("_async_lock", "_cond"),
    }
    _NOT_GUARDED = {
        "_copy_fn": "learn-thread-only jitted-snapshot cache (the "
                    "publish_async caller; see map comment above)",
    }

    def __init__(self, sharded: bool | None = None,
                 quant: str | None = None):
        self._lock = threading.Lock()
        self._params: Any = None
        self._blob: np.ndarray | None = None
        self._version: int = -1
        self._board = None  # optional shm WeightBoard (attach_board)
        # Sharded publication (runtime/weight_shards.py): per-shard
        # encode-once broadcast blobs + a json manifest instead of one
        # whole blob. `sharded` is PUBLIC — the transport server
        # consults it to answer ST_UNAVAILABLE for the shard-scoped op
        # before the first publish (manifest presence alone can't
        # distinguish "not sharded" from "not published yet").
        self.sharded = (weight_shards.sharded_enabled()
                        if sharded is None else bool(sharded))
        # quant: None defers to the gate, "" forces off, "bf16"/"int8"
        # force a mode.
        if not self.sharded:
            self._quant = None
        elif quant is None:
            self._quant = weight_shards.quant_mode()
        else:
            self._quant = quant or None
        self._delta_on = weight_shards.delta_enabled() if self.sharded else False
        self._manifest: dict | None = None
        self._manifest_bytes: bytes | None = None
        self._bcast: dict[str, np.ndarray] = {}        # current broadcast blobs
        self._prev_bcast: dict[str, np.ndarray] = {}   # previous version's
        self._prev_version: int = -2
        self._changed: set[str] = set()  # keys whose bytes moved last publish
        self._deltas: dict[str, bytes] = {}  # key -> delta vs _prev_version
        self._shard_stats = {"shard_publishes": 0, "shards_changed": 0,
                             "broadcast_bytes": 0, "quant_bytes_saved": 0,
                             "deltas_encoded": 0, "delta_bytes": 0,
                             "manifest_bytes": 0}
        # Async publication: one worker drains a latest-wins pending slot.
        # Races between publishes are arbitrated by SUBMISSION order
        # (`_seq`), not by version number: versions may legitimately go
        # backward (checkpoint-rollback republish at a restored step),
        # and the last submit must win either way.
        self._async_lock = threading.Lock()
        self._cond = threading.Condition(self._async_lock)
        self._seq = 0
        self._applied_seq = 0
        self._pending: tuple[Any, int, int] | None = None
        self._busy = False
        self._worker: threading.Thread | None = None
        self._closed = False
        self._copy_fn = None  # jitted device-side snapshot (publish_async)

    def _next_seq(self) -> int:
        with self._async_lock:
            self._seq += 1
            return self._seq

    def attach_board(self, board) -> None:
        """Mirror every landed publication into a shm weight board
        (`runtime/weight_board.py`). Board writes follow the store's
        seq arbitration exactly — including versions going backward on
        a rollback republish — because they happen inside `_apply`
        under `_lock`. An already-published snapshot is replayed so a
        late attach never leaves the board empty behind live actors."""
        with self._lock:
            self._board = board
            if self._manifest is not None:
                # Full replay: every shard must land for the late
                # attacher, so the changed-set is conservatively "all"
                # (which also disables unchanged-elision until the next
                # publish — correct, since this set feeds get_sharded).
                self._changed = set(self._bcast)
                self._board_publish_locked(self._version)
            elif self._blob is not None:
                self._board_publish_locked(self._version)

    def _board_publish_locked(self, version: int) -> None:
        # Failure latches the board off permanently (oversize blob,
        # unmapped segment at shutdown, a whole-blob/sharded layout
        # mismatch, ...): the store must keep publishing in-process/TCP,
        # and closing the writer side lets attached actors demote
        # themselves to TCP pulls. A single oversize SHARD is NOT a
        # board failure — the sharded board latches just that shard and
        # readers fetch it over TCP (runtime/weight_board.py).
        board = self._board
        if board is None:
            return
        try:
            if self._manifest is not None:
                if not hasattr(board, "publish_shards"):
                    raise ValueError(
                        "whole-blob board cannot carry a sharded publication")
                board.publish_shards(version, self._manifest, self._bcast,
                                     self._changed)
            elif self._blob is not None:
                board.publish_blob(self._blob, version)
            else:
                return  # un-encodable snapshot: nothing to mirror
        except Exception as e:  # noqa: BLE001 — board is an optimization
            self._board = None
            import sys

            try:
                board.close_writer()
            except Exception as ce:  # noqa: BLE001 — segment already gone,
                print(f"[weights] WARNING: board close_writer failed "
                      f"during disable: {ce!r}", file=sys.stderr)
            print(f"[weights] WARNING: shm weight board disabled "
                  f"({e}); actors fall back to TCP pulls", file=sys.stderr)

    def _apply(self, blob, host_params: Any, version: int, seq: int,
               bundle=None) -> None:
        with self._lock:
            applied = seq >= self._applied_seq
            if applied:
                prev_bcast, prev_version = self._bcast, self._version
                self._params = host_params
                self._version = version
                self._applied_seq = seq
                if bundle is None:
                    self._blob = blob
                    self._manifest = None
                    self._manifest_bytes = None
                    self._bcast, self._prev_bcast = {}, {}
                    self._deltas = {}
                    self._changed = set()
                else:
                    # Sharded publication: the whole blob is rebuilt
                    # LAZILY in get_blob() for old clients; the manifest
                    # + per-shard broadcast blobs are the plane now.
                    self._blob = None
                    manifest = bundle.manifest
                    manifest["version"] = version
                    # Changed-shard detection, EXACT but cheap: the
                    # manifest checksums (already paid in build_bundle)
                    # filter first; a byte-compare runs only when
                    # (len, crc) match — i.e. only for shards that are
                    # genuinely unchanged, which is exactly when the
                    # compare buys a skipped board memcpy + elided send.
                    prev_sums = {
                        sh["key"]: (sh["nbytes"], sh["crc"])
                        for sh in (self._manifest or {}).get("shards", [])}
                    changed = set()
                    for sh in manifest["shards"]:
                        k = sh["key"]
                        if (prev_sums.get(k) != (sh["nbytes"], sh["crc"])
                                or k not in prev_bcast
                                or not np.array_equal(bundle.blobs[k],
                                                      prev_bcast[k])):
                            changed.add(k)
                    deltas: dict[str, bytes] = {}
                    if self._delta_on and prev_version >= 0:
                        for k in changed:
                            if k in prev_bcast:
                                d = weight_shards.delta_encode(
                                    bundle.blobs[k], prev_bcast[k])
                                if d is not None:
                                    deltas[k] = d
                    self._prev_bcast = prev_bcast
                    self._prev_version = prev_version
                    self._bcast = bundle.blobs
                    self._changed = changed
                    self._deltas = deltas
                    self._manifest = manifest
                    self._manifest_bytes = weight_shards.manifest_bytes(manifest)
                    st = self._shard_stats
                    st["shard_publishes"] += 1
                    st["shards_changed"] += len(changed)
                    st["broadcast_bytes"] += sum(
                        len(bundle.blobs[k]) for k in changed)
                    st["quant_bytes_saved"] += max(
                        bundle.nbytes_f32
                        - sum(len(b) for b in bundle.blobs.values()), 0)
                    st["deltas_encoded"] += len(deltas)
                    st["delta_bytes"] += sum(len(d) for d in deltas.values())
                    st["manifest_bytes"] = len(self._manifest_bytes)
                self._board_publish_locked(version)
        # Version-landed timeline (telemetry off = one attribute read).
        if applied and _OBS.enabled:
            _OBS.gauge("weights/version", version)

    def _snapshot(self, params: Any):
        """-> (blob, host_params, bundle): the sharded bundle when this
        store publishes per-shard, else the whole-blob pair. A pytree
        the sharded path cannot carry (un-encodable leaf) falls through
        to the whole-blob snapshot, which has its own per-leaf
        fallback — demotion is per-publish and loss-free."""
        if self.sharded:
            try:
                bundle = weight_shards.build_bundle(params, quant=self._quant)
            except (TypeError, ValueError):
                pass
            else:
                host = jax.tree.map(
                    _freeze, codec.assemble(bundle.manifest["skel"],
                                            list(bundle.host_leaves)))
                return None, host, bundle
        blob, host = _host_snapshot(params)
        return blob, host, None

    def publish(self, params: Any, version: int) -> None:
        """Store a host-side snapshot of `params` (encode-once blobs +
        read-only views; device arrays land via the blob write)."""
        blob, host, bundle = self._snapshot(params)
        self._apply(blob, host, version, self._next_seq(), bundle)

    def publish_async(self, params: Any, version: int) -> None:
        """Versioned publish off the caller's critical path.

        Snapshots `params` with an on-device copy first — the learner
        donates its TrainState buffers into the next step, so the worker
        cannot safely read the originals later — then hands the D2H
        transfer + store to a single background worker. Latest submit
        wins: under a burst, intermediate versions may never become
        visible, which is exactly the semantics actors already have
        (they poll `get_if_newer`, not every version). After close(),
        falls back to a synchronous publish rather than losing the item.
        """
        import jax.numpy as jnp

        # The copy is ONE compiled dispatch, not per-leaf `jnp.copy`
        # calls: an eager copy can block behind an in-flight D2H (the
        # background worker's transfer), turning this "cheap handoff"
        # into a stall on the learn thread. A jitted executable enqueues
        # on the device stream and returns immediately.
        if self._copy_fn is None:
            self._copy_fn = jax.jit(
                lambda p: jax.tree.map(jnp.copy, p))
        snap = self._copy_fn(params)  # async device-side copy
        with self._cond:
            if self._closed:
                closed = True
            else:
                closed = False
                self._seq += 1
                self._pending = (snap, version, self._seq)
                if self._worker is None:
                    self._worker = threading.Thread(
                        target=self._drain, daemon=True, name="weights-publish")
                    self._worker.start()
                self._cond.notify_all()  # wake the idle worker NOW
        if closed:
            self.publish(params, version)

    def _drain(self) -> None:
        while True:
            with self._cond:
                # Condition-paced: woken by publish_async/close, with a
                # bounded backstop wait so a lost notify can never wedge
                # shutdown (the old 500 ms idle poll, minus the polling).
                while self._pending is None and not self._closed:
                    self._cond.wait(timeout=5.0)
                if self._pending is None:
                    return  # closed and drained
                item, self._pending = self._pending, None
                self._busy = True
            try:
                snap, version, seq = item
                # The blob write here = the D2H wait, off the learn thread.
                blob, host, bundle = self._snapshot(snap)
                self._apply(blob, host, version, seq, bundle)
            except Exception as e:  # drop the item, keep the worker alive —
                # a dead worker would freeze actor weights forever while
                # training silently continues. (stderr: stdout may carry a
                # machine-read JSON contract.)
                import sys

                print(f"[weights] WARNING: async publish of version "
                      f"{item[1]} failed: {e!r}", file=sys.stderr)
            finally:
                with self._cond:
                    self._busy = False
                    self._cond.notify_all()  # flush_async waiters

    def flush_async(self, timeout: float = 30.0) -> bool:
        """Block until every pending async publish has landed. Woken by
        the worker's completion notify, not a poll (the bounded timeout
        stays as the contract's failure mode)."""
        with self._cond:
            return self._cond.wait_for(
                lambda: self._pending is None and not self._busy, timeout)

    def close(self) -> None:
        self.flush_async()
        with self._cond:
            self._closed = True
            worker = self._worker
            self._cond.notify_all()
        # Join OUTSIDE the condvar (the worker's drain loop reacquires it
        # to observe _closed): close() must not return while the publish
        # worker may still be mid-_apply against boards being torn down.
        if worker is not None:
            worker.join(timeout=5.0)

    @property
    def version(self) -> int:
        with self._lock:
            return self._version

    def get(self) -> tuple[Any, int]:
        with self._lock:
            return self._params, self._version

    def get_blob(self) -> tuple[np.ndarray | None, int]:
        """(encoded blob, version) of the current snapshot — the exact
        bytes `codec.encode` produced at publish time. The transport
        server sends these as-is (encode-once: N actors, any number of
        pulls, one encode per version); None before the first publish.
        Callers must treat the buffer as read-only — it backs the
        published in-process views.

        SHARDED publication keeps no whole blob around; the first
        old-client GET_WEIGHTS of a version rebuilds one here from the
        in-process f32 views (bit-identical to a direct encode — the
        views are the same bytes) and caches it for the version's
        remaining pulls. The encode runs under `_lock`: it is the
        legacy-compat path, not the plane — new clients pull shards."""
        with self._lock:
            if (self._blob is None and self._manifest is not None
                    and self._params is not None):
                try:
                    self._blob = codec.encode(self._params, cache=True)
                except (TypeError, ValueError):
                    pass
            return self._blob, self._version

    def get_sharded(self, have_version: int, keys=None,
                    base_version: int = -2, accept_delta: bool = False):
        """Shard-scoped pull: None when the caller already holds the
        committed version (identity, like get_if_newer) or nothing
        sharded is published; else (version, manifest_bytes, shards)
        where shards is [(key, enc, base, payload), ...] for every
        manifest shard in `keys` (None = all).

        enc per shard (constants in runtime/weight_shards.py):
        ENC_FULL carries the broadcast blob; with `accept_delta` and
        `base_version` equal to the PREVIOUS published version (the
        normal per-publish polling cadence), an untouched shard is
        elided entirely (ENC_SKIP — the client reuses its cached blob)
        and a changed shard may carry a byte-range delta (ENC_DELTA)
        when one was worth encoding at publish time. Base matching is
        by version IDENTITY, so rollback republishes stay correct."""
        with self._lock:
            version = self._version
            if self._manifest is None or version < 0 or version == have_version:
                return None
            use_base = (accept_delta and base_version >= 0
                        and base_version == self._prev_version)
            shards = []
            for sh in self._manifest["shards"]:
                k = sh["key"]
                if keys is not None and k not in keys:
                    continue
                if use_base and k not in self._changed:
                    shards.append((k, weight_shards.ENC_SKIP, base_version, b""))
                elif use_base and k in self._deltas:
                    shards.append((k, weight_shards.ENC_DELTA, base_version,
                                   self._deltas[k]))
                else:
                    shards.append((k, weight_shards.ENC_FULL, -1,
                                   self._bcast[k]))
            return version, self._manifest_bytes, shards

    def shard_stats(self) -> dict:
        """Copy of the sharded-publication counters (telemetry
        providers, obs_report's "Weight sharding" subsection)."""
        with self._lock:
            return dict(self._shard_stats)

    def shard_stat(self, key: str) -> int:
        with self._lock:
            return self._shard_stats[key]

    def get_if_newer(self, have_version: int) -> tuple[Any, int] | None:
        """None if the caller already holds the newest version."""
        with self._lock:
            if self._version <= have_version:
                return None
            return self._params, self._version

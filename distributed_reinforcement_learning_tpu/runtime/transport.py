"""Socket data plane: the learner serves trajectories-in / weights-out.

TPU-native replacement for the reference's TF distributed runtime
(`tf.train.Server` + ClusterSpec gRPC at `train_impala.py:31-35`, shared
FIFOQueue `distributed_queue/buffer_queue.py:28-36`, cross-process weight
assigns `utils.py:5-21`). The three traffic classes SURVEY §5.8
identifies map to three ops on one length-prefixed TCP protocol:

  (i)  PUT_TRAJ   actor -> learner  bulk codec blobs, blocking enqueue
                                    (backpressure = the reply waits until
                                    the bounded queue accepts the item)
  (ii) GET_WEIGHTS learner -> actor versioned snapshot; the encoded blob
                                    is cached per version so N actors
                                    cost one encode
  (iii) QUEUE_SIZE / PING           polls & liveness

Framing: request [u8 op][u32 len][payload], response
[u8 status][u32 len][payload]. The learner binds `rt.server_port`; actors
connect with bounded-retry reconnect (the reference had none — a dead
peer hung the cluster, SURVEY §5.3).
"""

from __future__ import annotations

import os
import random
import socket
import struct
import threading
import time
from typing import Any

import numpy as np

from distributed_reinforcement_learning_tpu.data import codec
from distributed_reinforcement_learning_tpu.data.fifo import blob_ingest
from distributed_reinforcement_learning_tpu.observability import TELEMETRY as _OBS
from distributed_reinforcement_learning_tpu.observability import maybe_configure
from distributed_reinforcement_learning_tpu.observability.metrics import stale_bucket
from distributed_reinforcement_learning_tpu.utils.environ import env_float, env_int

OP_PUT_TRAJ = 1
OP_GET_WEIGHTS = 2
OP_QUEUE_SIZE = 3
OP_PING = 4
OP_ACT = 5  # SEED-style remote inference (runtime/inference.py)
OP_PUT_TRAJ_N = 6  # K unrolls per round trip (kills the per-unroll RTT)
OP_GET_WEIGHTS_SHARDED = 7  # manifest + per-shard blobs (weight_shards)
OP_REGISTER = 8   # fleet control plane: member registration (runtime/fleet.py)
OP_HEARTBEAT = 9  # fleet control plane: liveness + incarnation echo

ST_OK = 0
ST_ERROR = 1
ST_CLOSED = 2
ST_BUSY = 3  # bounded-queue timeout: retryable, not a dead learner
ST_UNAVAILABLE = 4  # op permanently not served here (e.g. no --serve_inference)

_HDR = struct.Struct("<BI")  # (op|status, payload_len)
_I64 = struct.Struct("<q")
_U32 = struct.Struct("<I")
_U16 = struct.Struct("<H")


def pack_batch(blobs: list[bytes | bytearray]) -> list[bytes | bytearray]:
    """OP_PUT_TRAJ_N payload parts: [u32 count][u32 len_i]*count [blobs...].

    Returned as parts for `_send_msg` so the (possibly multi-MB) blobs
    are never concatenated host-side just to be framed.
    """
    head = bytearray(_U32.size * (1 + len(blobs)))
    _U32.pack_into(head, 0, len(blobs))
    for i, b in enumerate(blobs):
        _U32.pack_into(head, _U32.size * (1 + i), len(b))
    return [head, *blobs]


def unpack_batch(payload: bytes) -> list[memoryview]:
    """Inverse of `pack_batch`: zero-copy views into the payload."""
    (count,) = _U32.unpack_from(payload, 0)
    view = memoryview(payload)
    offset = _U32.size * (1 + count)
    out = []
    for i in range(count):
        (n,) = _U32.unpack_from(payload, _U32.size * (1 + i))
        out.append(view[offset : offset + n])
        offset += n
    if offset != len(payload):
        raise ValueError(f"batch payload length mismatch: {offset} != {len(payload)}")
    return out


def _pack_shard_req(have_version: int, keys, base_version: int,
                    accept_delta: bool) -> bytearray:
    """OP_GET_WEIGHTS_SHARDED request:
    [i64 have][i64 base][u8 flags][u32 nkeys]{[u16 klen][key]}*nkeys.
    nkeys=0 means every manifest shard."""
    keys = keys or ()
    req = bytearray(_I64.size * 2 + 1 + _U32.size)
    _I64.pack_into(req, 0, have_version)
    _I64.pack_into(req, 8, base_version)
    req[16] = 1 if accept_delta else 0
    _U32.pack_into(req, 17, len(keys))
    for key in keys:
        kb = key.encode()
        req += _U16.pack(len(kb)) + kb
    return req


def _parse_shard_req(payload) -> tuple[int, list[str] | None, int, int]:
    have = _I64.unpack_from(payload, 0)[0]
    base = _I64.unpack_from(payload, 8)[0]
    flags = payload[16]
    (nkeys,) = _U32.unpack_from(payload, 17)
    keys: list[str] | None = None
    off = 21
    if nkeys:
        keys = []
        for _ in range(nkeys):
            (klen,) = _U16.unpack_from(payload, off)
            off += _U16.size
            keys.append(bytes(payload[off:off + klen]).decode())
            off += klen
    return have, keys, base, flags


def _pack_shard_reply(version: int, mbytes: bytes, shards
                      ) -> tuple[list, int, int, int]:
    """OP_GET_WEIGHTS_SHARDED reply payload as `_send_msg` parts (the
    multi-MB shard blobs are never concatenated host-side):
    [i64 version][u32 mlen][manifest][u32 n]
    then per shard [u16 klen][key][u8 enc][i64 base][u32 blen][bytes].
    Returns (parts, payload_bytes, n_full, n_delta, n_skip)."""
    from distributed_reinforcement_learning_tpu.runtime import weight_shards

    parts: list = [_I64.pack(version), _U32.pack(len(mbytes)), mbytes,
                   _U32.pack(len(shards))]
    nbytes = nfull = ndelta = nskip = 0
    for key, enc, base, blob in shards:
        kb = key.encode()
        parts.append(_U16.pack(len(kb)) + kb + bytes([enc]) + _I64.pack(base)
                     + _U32.pack(len(blob)))
        if len(blob):
            parts.append(blob)
        nbytes += len(blob)
        nfull += enc == weight_shards.ENC_FULL
        ndelta += enc == weight_shards.ENC_DELTA
        nskip += enc == weight_shards.ENC_SKIP
    return parts, nbytes, nfull, ndelta, nskip


def _parse_shard_reply(resp) -> tuple[int, bytes, list]:
    """Inverse of `_pack_shard_reply`; shard payloads are zero-copy
    views into `resp` (a fresh buffer per `_recv_msg`)."""
    view = memoryview(resp)
    version = _I64.unpack_from(view, 0)[0]
    (mlen,) = _U32.unpack_from(view, 8)
    off = 12
    mbytes = bytes(view[off:off + mlen])
    off += mlen
    (n,) = _U32.unpack_from(view, off)
    off += _U32.size
    shards = []
    for _ in range(n):
        (klen,) = _U16.unpack_from(view, off)
        off += _U16.size
        key = bytes(view[off:off + klen]).decode()
        off += klen
        enc = view[off]
        off += 1
        base = _I64.unpack_from(view, off)[0]
        off += _I64.size
        (blen,) = _U32.unpack_from(view, off)
        off += _U32.size
        shards.append((key, enc, base, view[off:off + blen]))
        off += blen
    if off != len(view):
        raise ValueError(f"shard reply length mismatch: {off} != {len(view)}")
    return version, mbytes, shards


class TransportError(ConnectionError):
    pass


class InferenceUnavailableError(RuntimeError):
    """OP_ACT permanently unserved (learner lacks --serve_inference).

    Deliberately NOT a TransportError/OSError: the actor's elastic-grace
    loop swallows those as transient outages, but a misconfigured
    learner never recovers — this must fail fast with the real cause.
    """


class ShardedWeightsUnavailableError(RuntimeError):
    """OP_GET_WEIGHTS_SHARDED permanently unserved here: the learner's
    store publishes whole blobs (gate off, or an old server replying
    ST_ERROR to the unknown op). Deliberately NOT a TransportError —
    the caller must demote to the whole-blob op, not treat the learner
    as a transient outage."""


class FleetUnavailableError(RuntimeError):
    """OP_REGISTER/OP_HEARTBEAT unserved here: the learner predates the
    fleet supervisor or runs with DRL_FLEET=0 (an old server answers
    ST_ERROR to the unknown op — same meaning). Deliberately NOT a
    TransportError: the heartbeat loop must fall back to plain pings,
    not treat the learner as a transient outage. `permanent` is True
    for ST_UNAVAILABLE (the server explicitly has no supervisor — latch
    immediately); ST_ERROR is ambiguous (old server vs one transient
    supervisor fault the server's own handler calls non-fatal), so the
    loop latches only after consecutive occurrences."""

    def __init__(self, msg: str, permanent: bool = True):
        super().__init__(msg)
        self.permanent = permanent


class InferenceBusyError(RuntimeError):
    """OP_ACT answered ST_BUSY: the service's admission budget is full
    (runtime/inference.InferenceBusy on the server side). Retryable —
    the service is alive, just saturated. NOT a TransportError: a busy
    replica must not be demoted as dead; RemoteActService fails the
    request over to another replica (or retries with jitter), and
    `remote_act(busy_retry=True)` absorbs it for single-endpoint
    callers."""


class RemoteActFailed(TransportError):
    """OP_ACT answered ST_ERROR: the endpoint is ALIVE but this request
    (or the batch it joined) failed application-side — a poisoned
    co-batched request, an algorithm-mismatched row dict, weights not
    published yet. Subclasses TransportError so single-endpoint callers
    keep the old behavior (the actor's elastic-grace loop retries), but
    stays distinguishable so RemoteActService does NOT demote the
    healthy replica that reported it — one bad request must not latch
    the whole tier dead."""


class _BusyBackoff:
    """The act paths' shared ST_BUSY wait: full jitter around an
    exponential base (capped at 50 ms — rejected actors must spread
    out, not re-arrive together), bounded by a deadline from the first
    busy reply."""

    def __init__(self, timeout: float, rng: random.Random):
        self.timeout = timeout
        self.deadline = time.monotonic() + timeout
        self._delay = 2e-3
        self._rng = rng

    def sleep_or_raise(self, what: str) -> None:
        if time.monotonic() >= self.deadline:
            raise TransportError(f"{what} busy for >{self.timeout:.0f}s")
        time.sleep(self._rng.uniform(0.5, 1.5) * self._delay)
        self._delay = min(2 * self._delay, 0.05)


def _recv_exact(sock: socket.socket, n: int) -> bytearray:
    """Read exactly n bytes. Returns the bytearray itself — NOT a bytes()
    copy: a 16-unroll PUT payload is ~9 MB, and the copy was pure waste
    on the 1-core host (every consumer — struct.unpack, slicing,
    codec.decode, unpack_batch — is buffer-protocol-happy)."""
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        k = sock.recv_into(view[got:], n - got)
        if k == 0:
            raise TransportError("peer closed")
        got += k
    return buf


def _send_msg(sock: socket.socket, tag: int, *parts: bytes | bytearray) -> None:
    """One framed message; multi-part payloads are sent without
    concatenating (no copy of multi-MB weight blobs just to prefix an
    8-byte version) AND without one syscall per part: `sendmsg` is
    writev(2), so header + K length-prefixes + K blobs go to the kernel
    in one vectored call (a batched PUT was 2K+1 sendall syscalls)."""
    bufs = [memoryview(_HDR.pack(tag, sum(len(p) for p in parts)))]
    bufs += [memoryview(p).cast("B") for p in parts if len(p)]
    while bufs:
        sent = sock.sendmsg(bufs[:1024])  # IOV_MAX caps one writev
        if sent == 0:
            raise TransportError("peer closed")
        # Drop fully-sent buffers; trim a partially-sent head.
        i = 0
        while i < len(bufs) and sent >= len(bufs[i]):
            sent -= len(bufs[i])
            i += 1
        bufs = bufs[i:]
        if sent and bufs:
            bufs[0] = bufs[0][sent:]


def _recv_msg(sock: socket.socket) -> tuple[int, bytearray]:
    tag, length = _HDR.unpack(_recv_exact(sock, _HDR.size))
    payload = _recv_exact(sock, length) if length else bytearray()
    return tag, payload


def _recv_into_exact(sock: socket.socket, view: memoryview) -> None:
    got, n = 0, len(view)
    while got < n:
        k = sock.recv_into(view[got:], n - got)
        if k == 0:
            raise TransportError("peer closed")
        got += k


class _ConnRecvBuf:
    """Per-connection reusable receive buffer for the server loop.

    A 16-unroll PUT payload is ~9 MB; allocating (and first-touching)
    a fresh bytearray per request was a measurable slice of the
    host-side wire budget. Every server op copies what it keeps (queue
    put / decode(copy=True)) before the next request is read, so the
    buffer may be reused across requests of one connection."""

    __slots__ = ("hdr", "buf")

    def __init__(self):
        self.hdr = bytearray(_HDR.size)
        self.buf = bytearray(1 << 16)

    def recv_msg(self, sock: socket.socket) -> tuple[int, memoryview]:
        _recv_into_exact(sock, memoryview(self.hdr))
        tag, length = _HDR.unpack(self.hdr)
        if length > len(self.buf):
            self.buf = bytearray(max(length, 2 * len(self.buf)))
        view = memoryview(self.buf)[:length]
        if length:
            _recv_into_exact(sock, view)
        return tag, view


class _LockedStatsMixin:
    """Lock-guarded counter surface shared by the server and the client.

    Host class provides `self.stats` (a plain dict of int counters) and
    `self._stats_lock`. Writers go through _bump; cross-thread readers
    (stats loops, telemetry providers) through stat()/snapshot_stats() —
    dict-item += is a load/add/store, and unlocked reads against it tear.
    """

    def _bump(self, key: str, by: int = 1) -> None:
        with self._stats_lock:
            self.stats[key] += by

    def stat(self, key: str) -> int:
        """One counter, read under the lock (telemetry providers poll
        this from the flush thread)."""
        with self._stats_lock:
            return self.stats[key]

    def snapshot_stats(self) -> dict:
        """Consistent copy of the whole stats dict (periodic stat lines
        and the scale-demo reporting read this, never the live dict)."""
        with self._stats_lock:
            return dict(self.stats)


class TransportServer(_LockedStatsMixin):
    """Learner-side service: owns nothing, serves the queue + weight store."""

    # Concurrency map (enforced by tools/drlint's lock-discipline pass;
    # docs/static_analysis.md): per-connection _serve threads, the
    # accept loop, the stats loop, and telemetry flushes all touch this
    # state. `_threads` shares _conns_lock — both are the accept loop's
    # connection bookkeeping and are read together at stop().
    _GUARDED_BY = {
        "stats": "_stats_lock",
        "_conns": "_conns_lock",
        "_threads": "_conns_lock",
        "_enc_cache": "_enc_lock",
        "_encoding": "_enc_lock",
    }
    _NOT_GUARDED = {
        "_sock": "bound in start() before the accept thread spawns; "
                 "stop() closes it cross-thread ON PURPOSE to break "
                 "the accept loop out of its timed accept()",
    }

    def __init__(self, queue, weights, host: str = "0.0.0.0", port: int = 8000,
                 inference=None, fleet=None):
        # queue=None: an act-serving endpoint with no trajectory ingest
        # (an inference replica, runtime/serving.py) — PUT/QUEUE_SIZE
        # ops answer ST_UNAVAILABLE so a misrouted actor fails fast
        # instead of silently dropping unrolls.
        self.queue = queue
        self.weights = weights
        self.inference = inference  # optional InferenceServer for OP_ACT
        self.fleet = fleet  # optional FleetSupervisor for OP_REGISTER/HEARTBEAT
        self.host, self.port = host, port
        self._sock: socket.socket | None = None
        self._threads: list[threading.Thread] = []
        self._stop = threading.Event()
        self._conns: set[socket.socket] = set()
        self._conns_lock = threading.Lock()
        self._enc_lock = threading.Lock()
        self._enc_cache: tuple[int, bytes] = (-1, b"")
        self._encoding = False  # one thread encodes; the rest stale-serve
        # Data-plane observability (the 20-actor scale demo and
        # tests/test_actor_scale.py read these): accepted unrolls,
        # ST_BUSY replies, partial batched accepts, weight sends.
        # Lock-guarded: dict-item += is a load/add/store and the
        # per-connection serve threads would otherwise lose increments.
        self.stats = {"unrolls_accepted": 0, "busy_replies": 0,
                      "partial_accepts": 0, "weight_sends": 0,
                      "weight_bytes_sent": 0, "shard_sends": 0,
                      "shard_bytes_sent": 0, "shard_full_sends": 0,
                      "shard_delta_sends": 0, "shard_skip_sends": 0,
                      "acts_served": 0, "act_busy_replies": 0,
                      "fleet_msg_errors": 0}
        self._stats_lock = threading.Lock()

    def start(self) -> "TransportServer":
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((self.host, self.port))
        self._sock.listen(128)
        self._sock.settimeout(0.5)
        t = threading.Thread(target=self._accept_loop, daemon=True, name="transport-accept")
        t.start()
        # The accept loop is already running and prunes/extends _threads
        # on every accepted connection — appending the stats thread below
        # unlocked could lose it to a concurrent prune-rebuild and leave
        # stop() unable to join it.
        with self._conns_lock:
            self._threads.append(t)
        stats_s = env_float("DRL_TRANSPORT_STATS_S", 0.0)
        if stats_s > 0:
            t2 = threading.Thread(target=self._stats_loop, args=(stats_s,),
                                  daemon=True, name="transport-stats")
            t2.start()
            with self._conns_lock:
                self._threads.append(t2)
        return self

    def _stats_loop(self, interval: float) -> None:
        """Periodic one-line data-plane stats on stderr (opt-in via
        DRL_TRANSPORT_STATS_S=<seconds>; the actor-scale demo's learner
        side of the fairness/backpressure record)."""
        import sys as _sys

        while not self._stop.wait(interval):
            # Locked copy: the per-connection _serve threads _bump these
            # concurrently, and an unlocked dict read here could tear
            # against a resize or report a half-applied +=.
            s = self.snapshot_stats()
            try:
                depth = self.queue.size() if self.queue is not None else 0
            except Exception as e:  # noqa: BLE001 — closed queue at shutdown
                if not self._stop.is_set():
                    # Mid-run death of the stats thread must not be
                    # mistaken for clean shutdown: say why it stopped.
                    print(f"[transport] WARNING: stats loop exiting: "
                          f"{e!r}", file=_sys.stderr)
                return
            print(f"[transport] depth={depth} "
                  f"unrolls={s['unrolls_accepted']} busy={s['busy_replies']} "
                  f"partial={s['partial_accepts']} "
                  f"weight_sends={s['weight_sends']}",
                  file=_sys.stderr, flush=True)

    def stop(self) -> None:
        self._stop.set()
        if self._sock is not None:
            self._sock.close()
        # Closing the listener alone is not enough: _serve threads sit
        # blocked in _recv_msg on their accepted sockets and would outlive
        # this incarnation, still answering a surviving actor from the OLD
        # WeightStore after a learner restart. Close every accepted conn so
        # the handlers unblock (OSError) and exit now.
        with self._conns_lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        with self._conns_lock:
            threads = list(self._threads)
        for t in threads:
            t.join(timeout=2.0)

    def _accept_loop(self) -> None:
        assert self._sock is not None
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            with self._conns_lock:
                if self._stop.is_set():  # raced with stop(): don't serve
                    conn.close()
                    return
                self._conns.add(conn)
            t = threading.Thread(target=self._serve, args=(conn,), daemon=True)
            t.start()
            # Prune finished connection threads so reconnect churn over a
            # long-running learner doesn't accumulate dead Thread objects.
            with self._conns_lock:
                self._threads = [x for x in self._threads if x.is_alive()]
                self._threads.append(t)

    def _weights_blob(self) -> tuple[int, bytes]:
        # Fast path: the weight store publishes pre-encoded blobs
        # (encode-ONCE per version, at publish time, off the serve
        # threads — runtime/weights.py) and this just hands them out.
        # No cache to keep coherent, and a rollback republish serves the
        # store's truth (the backward version) instead of a pinned max.
        get_blob = getattr(self.weights, "get_blob", None)
        if get_blob is not None:
            blob, version = get_blob()
            if blob is None:
                return -1, b""
            return version, blob
        # Fallback for stores without blobs: encode OUTSIDE `_enc_lock`,
        # double-checked, only-forward (a preempted thread holding an
        # older (params, version) pair must not regress the cache). While
        # one thread encodes a new version, concurrent pulls serve the
        # PREVIOUS cached version instead of stalling N actors behind one
        # full-params encode — weights are stale-tolerant by design, a
        # serialized encode convoy is the publish-p99 spike this exists
        # to kill.
        with self._enc_lock:
            version, blob = self._enc_cache
            if self._encoding:
                return version, blob  # stale-serve while the encoder runs
            params, cur = self.weights.get()
            if cur <= version or params is None:
                return version, blob
            self._encoding = True
        try:
            new_blob = codec.encode(params)
        except BaseException:
            with self._enc_lock:
                self._encoding = False
            raise
        with self._enc_lock:
            self._encoding = False
            if cur > self._enc_cache[0]:  # double-checked, only-forward
                self._enc_cache = (cur, new_blob)
            return self._enc_cache

    def _serve(self, conn: socket.socket) -> None:
        try:
            self._serve_inner(conn)
        finally:
            with self._conns_lock:
                self._conns.discard(conn)
            try:
                conn.close()
            except OSError:
                pass

    def _enqueue(self, payload: bytes, total_wait: float = 30.0) -> bool:
        """Blocking enqueue in _stop-aware slices. The bounded total wait
        keeps a stalled learner (e.g. a minutes-long first jit compile with
        a full queue) surfacing as retryable ST_BUSY; the slicing keeps
        stop() from being ignored by a handler parked in queue.put (the
        socket close only interrupts recv, not a queue wait)."""
        deadline = time.monotonic() + total_wait
        prepare, put = blob_ingest(self.queue)
        item = prepare(payload)
        # Timed region = the put loop ONLY (decode above is excluded):
        # this gauge quantifies backpressure, and conflating it with
        # deserialization cost would corrupt the ring-vs-socket decision
        # it exists to inform (ROADMAP open items).
        t0 = time.perf_counter()
        try:
            while not self._stop.is_set():
                slice_t = min(0.5, deadline - time.monotonic())
                if slice_t <= 0:
                    return False
                if put(item, timeout=slice_t):
                    return True
            return False
        finally:
            if _OBS.enabled:
                _OBS.gauge("transport/enqueue_wait_ms",
                           (time.perf_counter() - t0) * 1e3)

    def _enqueue_many(self, payload: bytes, total_wait: float = 30.0
                      ) -> tuple[int, int]:
        """Enqueue every blob of an OP_PUT_TRAJ_N payload; returns
        (accepted, total) — acceptance stops at the first refusal (the
        tail is NOT enqueued, so the client may safely resend it)."""
        deadline = time.monotonic() + total_wait
        blobs = unpack_batch(payload)
        prepare, put = blob_ingest(self.queue)
        accepted = 0
        for blob in blobs:
            item = prepare(blob)
            ok = False
            # Per-BLOB wait, same unit as _enqueue's single-PUT gauge
            # (decode above excluded): summing K blobs into one
            # observation would inflate batched runs' stats ~K×.
            t0 = time.perf_counter()
            while not self._stop.is_set():
                slice_t = min(0.5, deadline - time.monotonic())
                if slice_t <= 0:
                    break
                if put(item, timeout=slice_t):
                    ok = True
                    break
            if _OBS.enabled:
                _OBS.gauge("transport/enqueue_wait_ms",
                           (time.perf_counter() - t0) * 1e3)
            if not ok:
                break
            accepted += 1
        return accepted, len(blobs)

    def _observe_put(self, accepted: int, conn_version: int) -> None:
        """Weight-staleness at queue ingest — learner's current version
        minus the version this connection last confirmed holding (the
        actor's pull and its PUTs share one socket, so no wire-format
        change is needed to attribute staleness per actor). Weighted by
        `accepted` so a batched PUT's K unrolls count as K observations.
        A LOWER BOUND on staleness at train time: the unroll still has
        its queue residency ahead of it, during which more versions may
        publish. (Enqueue-wait is gauged inside _enqueue/_enqueue_many,
        timing the put loop only; accepted-unroll throughput comes from
        the server.stats provider run_role registers.)"""
        if accepted > 0 and conn_version >= 0:
            staleness = max(self.weights.version - conn_version, 0)
            _OBS.gauge("learner/weight_staleness", staleness, weight=accepted)
            # Exact histogram: bucketed at OBSERVATION time. The gauge's
            # per-window means would average a rare staleness-16 stall
            # into the window's bulk of zeros and hide the tail the
            # histogram exists to reveal.
            _OBS.count(f"staleness_bucket/{stale_bucket(staleness)}",
                       accepted)

    def _pressure_permille(self) -> int:
        """Learner ingest pressure for PUT replies, 0..1000.

        Sharded ingest facades expose their own meter
        (`ReplayIngestFifo.ingest_pressure` — busy fraction; depth is
        always 0 there); bounded queues fall back to fill fraction,
        the signal their blocking-put backpressure already implies."""
        queue = self.queue
        meter = getattr(queue, "ingest_pressure", None)
        if meter is not None:
            return max(0, min(1000, int(meter())))
        capacity = getattr(queue, "capacity", 0)
        if capacity:
            return int(min(1.0, queue.size() / capacity) * 1000)
        return 0

    def _serve_inner(self, conn: socket.socket) -> None:
        rbuf = _ConnRecvBuf()  # reused across this connection's requests
        # Newest weight version this peer confirmed holding (via
        # GET_WEIGHTS on this same connection); -1 = never pulled
        # (e.g. remote_act actors), for which staleness is undefined.
        conn_version = -1
        while not self._stop.is_set():
            try:
                op, payload = rbuf.recv_msg(conn)
            except (TransportError, OSError):
                return
            try:
                if self.queue is None and op in (OP_PUT_TRAJ, OP_PUT_TRAJ_N,
                                                 OP_QUEUE_SIZE):
                    # Queue-less endpoint (inference replica): trajectory
                    # ops are permanently unserved here, same contract as
                    # OP_ACT on a learner without --serve_inference.
                    _send_msg(conn, ST_UNAVAILABLE)
                elif op == OP_PUT_TRAJ:
                    # Replying only after acceptance is the actors'
                    # backpressure (reference: blocking enqueue op,
                    # buffer_queue.py:398-414). The reply carries the
                    # learner's ingest pressure (u16 permille) — the
                    # feedback edge of actor-side admission
                    # (data/admission.py); pre-pressure clients ignore
                    # the payload.
                    ok = self._enqueue(payload)
                    self._bump("unrolls_accepted" if ok else "busy_replies")
                    if _OBS.enabled:
                        self._observe_put(1 if ok else 0, conn_version)
                    _send_msg(conn, ST_OK if ok else ST_BUSY,
                              _U16.pack(self._pressure_permille()))
                elif op == OP_PUT_TRAJ_N:
                    # The batched PUT: K unrolls in one round trip. The
                    # reply carries the accepted count (then the ingest
                    # pressure, appended — clients parse with
                    # unpack_from so later fields never break them); a
                    # partial accept (bounded queue refused the tail) is
                    # the batched analogue of ST_BUSY and the client
                    # retries the rest.
                    accepted, n_in = self._enqueue_many(payload)
                    self._bump("unrolls_accepted", accepted)
                    if accepted < n_in:
                        self._bump("partial_accepts")
                    if _OBS.enabled:
                        self._observe_put(accepted, conn_version)
                    _send_msg(conn, ST_OK, _I64.pack(accepted),
                              _U16.pack(self._pressure_permille()))
                elif op == OP_GET_WEIGHTS:
                    # Versions are snapshot IDENTITIES across the wire,
                    # not an ordering: a restarted learner republishes
                    # from version 0, and a surviving actor holding the
                    # old incarnation's higher version must still be
                    # updated — so send whenever version != have.
                    have = _I64.unpack(payload)[0]
                    version, blob = self._weights_blob()
                    if version == have or version < 0:
                        conn_version = have
                        _send_msg(conn, ST_OK, _I64.pack(have))
                    else:
                        self._bump("weight_sends")
                        self._bump("weight_bytes_sent", len(blob))
                        conn_version = version
                        _send_msg(conn, ST_OK, _I64.pack(version), blob)
                elif op == OP_GET_WEIGHTS_SHARDED:
                    # Shard-scoped pull (runtime/weight_shards.py):
                    # manifest + the requested shards, each FULL, a
                    # byte-range DELTA against the client's base
                    # version, or elided entirely when unchanged since
                    # that base. Version-identity semantics match
                    # OP_GET_WEIGHTS exactly. ST_UNAVAILABLE when this
                    # store publishes whole blobs — the client demotes
                    # to the old op permanently.
                    if not getattr(self.weights, "sharded", False):
                        _send_msg(conn, ST_UNAVAILABLE)
                    else:
                        have, keys, base, flags = _parse_shard_req(payload)
                        got = self.weights.get_sharded(
                            have, keys=keys, base_version=base,
                            accept_delta=bool(flags & 1))
                        if got is None:
                            conn_version = have
                            _send_msg(conn, ST_OK, _I64.pack(have))
                        else:
                            version, mbytes, shards = got
                            parts, nbytes, nfull, ndelta, nskip = \
                                _pack_shard_reply(version, mbytes, shards)
                            with self._stats_lock:
                                self.stats["shard_sends"] += 1
                                self.stats["shard_bytes_sent"] += nbytes
                                self.stats["shard_full_sends"] += nfull
                                self.stats["shard_delta_sends"] += ndelta
                                self.stats["shard_skip_sends"] += nskip
                            conn_version = version
                            _send_msg(conn, ST_OK, *parts)
                elif op == OP_ACT:
                    # Own RuntimeError handling: an inference failure (e.g.
                    # weights not published yet) must reply ST_ERROR, not
                    # fall into the queue-closed ST_CLOSED arm below and
                    # kill the actor's connection. An admission reject
                    # (InferenceBusy, duck-typed `retryable` so this
                    # jax-free module needs no inference import) maps to
                    # ST_BUSY: the client retries with jitter or fails
                    # over to another replica instead of queueing
                    # unboundedly on a saturated service.
                    if self.inference is None:
                        _send_msg(conn, ST_UNAVAILABLE)
                    else:
                        try:
                            out = self.inference.submit(codec.decode(payload, copy=True))
                        except RuntimeError as e:
                            if getattr(e, "retryable", False):
                                self._bump("act_busy_replies")
                                _send_msg(conn, ST_BUSY)
                            else:
                                _send_msg(conn, ST_ERROR)
                        else:
                            self._bump("acts_served")
                            _send_msg(conn, ST_OK, codec.encode(out))
                elif op in (OP_REGISTER, OP_HEARTBEAT):
                    # Fleet control plane (runtime/fleet.py): tiny json
                    # request/reply pairs on the existing framing. A
                    # supervisor fault must answer ST_ERROR, never fall
                    # into the queue-closed arm and kill the member's
                    # control connection.
                    if self.fleet is None:
                        _send_msg(conn, ST_UNAVAILABLE)
                    else:
                        from distributed_reinforcement_learning_tpu.runtime import (
                            fleet as _fleet)

                        try:
                            info = _fleet.unpack_fleet_msg(payload)
                            reply = (self.fleet.register(info)
                                     if op == OP_REGISTER
                                     else self.fleet.heartbeat(info))
                            blob = _fleet.pack_fleet_msg(reply)
                        except Exception:  # noqa: BLE001 — malformed
                            self._bump("fleet_msg_errors")  # member,
                            _send_msg(conn, ST_ERROR)       # not fatal
                        else:
                            _send_msg(conn, ST_OK, blob)
                elif op == OP_QUEUE_SIZE:
                    _send_msg(conn, ST_OK, _I64.pack(self.queue.size()))
                elif op == OP_PING:
                    _send_msg(conn, ST_OK)
                else:
                    _send_msg(conn, ST_ERROR)
            except RuntimeError:  # queue closed -> learner shutting down
                try:
                    _send_msg(conn, ST_CLOSED)
                except OSError:
                    pass
                return
            except (TransportError, OSError):
                return


class TransportClient(_LockedStatsMixin):
    """Actor-side connection with bounded-retry reconnect."""

    # Concurrency map (tools/drlint lock-discipline): `_lock` serializes
    # the request/reply exchange and owns the socket lifecycle;
    # `_stats_lock` covers the counters, which the actor loop's stat
    # line and the telemetry flush thread read while call paths bump
    # them. Methods named *_locked are called with `_lock` already held.
    _GUARDED_BY = {
        "_sock": "_lock",
        "stats": "_stats_lock",
    }
    _NOT_GUARDED = {
        "_admission": "set once by the owning actor runner "
                      "(set_admission) before the publish thread starts; "
                      "read-only on the PUT paths thereafter",
    }

    def __init__(
        self,
        host: str,
        port: int,
        connect_retries: int = 60,
        retry_interval: float = 1.0,
        busy_timeout: float = 90.0,
        connect: bool = True,
    ):
        self.host, self.port = host, port
        self.connect_retries = connect_retries
        self.retry_interval = retry_interval
        self.busy_timeout = busy_timeout
        self._sock: socket.socket | None = None
        self._lock = threading.Lock()
        self._admission = None  # data/admission.AdmissionController
        # Per-actor observability (read by the actor loop's periodic stat
        # line; fairness evidence for the 20-actor topology demo).
        self.stats = {"unrolls_sent": 0, "busy_waits": 0,
                      "partial_accepts": 0, "weight_pulls": 0,
                      "acts": 0, "act_busy_waits": 0,
                      "unrolls_admission_dropped": 0}
        self._stats_lock = threading.Lock()
        # Jittered act-busy backoff: deterministic seeds would march a
        # fleet of rejected actors back in lockstep (the thundering herd
        # ST_BUSY exists to break up).
        self._jitter = random.Random()
        if connect:  # __init__ happens-before any sharing
            self._connect_locked()
        # connect=False: lazy — _exchange connects on first use (the
        # RemoteActService builds its endpoint set without serializing
        # N blocking connects at actor startup).

    def _connect_locked(self) -> None:
        # Deliberate blocking-under-lock (drlint): reconnect runs under
        # the exchange lock BY DESIGN — `_lock` serializes the whole
        # request/reply exchange including the socket lifecycle, so a
        # concurrent caller must wait for the reconnect outcome rather
        # than race a half-open socket. The lock-free escape for
        # shutdown paths is abort() below; see its docstring.
        last: Exception | None = None
        for _ in range(self.connect_retries):
            try:
                sock = socket.create_connection(  # drlint: disable=blocking-under-lock
                    (self.host, self.port), timeout=300.0)
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                self._sock = sock
                return
            except OSError as e:
                last = e
                time.sleep(self.retry_interval)  # drlint: disable=blocking-under-lock
        raise TransportError(f"cannot reach learner at {self.host}:{self.port}: {last}")

    def _exchange(self, op: int, payload, retry: bool, resend: bool) -> tuple[int, bytes]:
        """One request/response; on a dropped connection, reconnect and (for
        idempotent ops) resend. Non-idempotent ops set `resend=False`: the
        server may or may not have acted on the lost request, so resending
        would give at-least-once delivery (duplicated trajectories).

        `payload` is bytes or a list of parts (sent without concatenating)."""
        parts = payload if isinstance(payload, list) else [payload]
        # Deliberate blocking-under-lock (drlint): `_lock` exists to
        # serialize the whole request/reply exchange on this socket —
        # the send, the matching recv, and any reconnect between them
        # are one atomic conversation, and a second caller interleaving
        # frames would corrupt the protocol. Watchdog/shutdown paths
        # that must not queue behind a wedged exchange use the
        # lock-free abort() instead (see its docstring). The rt-hold
        # suppression is the same design seen by the runtime sanitizer:
        # an exchange lawfully holds `_lock` for a full socket timeout.
        with self._lock:  # drlint: disable=rt-hold
            if self._sock is None:  # a prior failed reconnect left us down
                self._connect_locked()  # drlint: disable=blocking-under-lock
            try:
                _send_msg(self._sock, op, *parts)  # drlint: disable=blocking-under-lock
                return _recv_msg(self._sock)  # drlint: disable=blocking-under-lock
            except (TransportError, OSError):
                if not retry:
                    raise
                self._close_locked()
                self._connect_locked()  # drlint: disable=blocking-under-lock
                if not resend:
                    raise TransportError("connection lost mid-request") from None
                _send_msg(self._sock, op, *parts)  # drlint: disable=blocking-under-lock
                return _recv_msg(self._sock)  # drlint: disable=blocking-under-lock

    def _is_down(self) -> bool:
        """True when the last reconnect attempt failed (learner gone)."""
        with self._lock:
            return self._sock is None

    def _call(self, op: int, payload: bytes = b"", retry: bool = True) -> bytes:
        status, resp = self._exchange(op, payload, retry, resend=True)
        if status == ST_CLOSED:
            raise TransportError("learner closed the data plane")
        if status != ST_OK:
            raise TransportError(f"op {op} failed on the learner side")
        return resp

    def set_admission(self, controller) -> None:
        """Attach an actor-side admission controller
        (data/admission.AdmissionController): PUT paths score + stamp
        each unroll and feed reply pressure back to it. Call before the
        publish thread starts (see _NOT_GUARDED)."""
        self._admission = controller

    def put_trajectory(self, tree: Any) -> bool:
        """Ship one trajectory; blocks (via ST_BUSY retries) while the
        learner's bounded queue is full — the reference's blocking-enqueue
        backpressure. At-most-once: if the connection drops mid-request the
        unroll is dropped, not resent (returns False); losing one off-policy
        unroll is harmless, training on a duplicate is not.

        ST_BUSY retries are bounded by `busy_timeout`: a wedged-but-alive
        learner (queue permanently full) must surface as TransportError so
        the actor-side elastic-recovery grace deadline owns the failure,
        instead of this loop blocking the actor forever."""
        ctrl = self._admission
        payload: Any
        if ctrl is not None:
            decision = ctrl.admit(tree)
            if not decision.send:  # dropped at source; mass folded into
                self._bump("unrolls_admission_dropped")  # the next stamp
                return True
            if decision.tree is not None:
                tree = decision.tree
            # Stamp frame as a separate send part: the blob bytes are
            # untouched (zero-copy on the wire path).
            payload = [codec.stamp_frame(decision.stamp),
                       codec.encode(tree, dedup=codec.obs_dedup_enabled())]
            ctrl.note_wire(len(payload[0]) + len(payload[1]), decision)
        else:
            # Trajectory PUTs are the dedup-eligible wire traffic
            # (frame-stacked observation leaves); weights/inference
            # encodes stay plain.
            payload = codec.encode(tree, dedup=codec.obs_dedup_enabled())
        busy_since: float | None = None
        while True:
            try:
                status, resp = self._exchange(OP_PUT_TRAJ, payload, retry=True, resend=False)
            except TransportError:
                if self._is_down():  # reconnect failed: learner is gone
                    raise
                return False
            if ctrl is not None and len(resp) >= _U16.size:
                # Ingest-pressure feedback rides every PUT reply
                # (ST_BUSY included — that IS maximal pressure).
                ctrl.observe_pressure(_U16.unpack_from(resp, 0)[0])
            if status == ST_OK:
                self._bump("unrolls_sent")
                return True
            if status == ST_BUSY:  # learner alive but queue full: keep pushing
                self._bump("busy_waits")
                now = time.monotonic()
                busy_since = busy_since or now
                if now - busy_since > self.busy_timeout:
                    raise TransportError(
                        f"learner queue busy for >{self.busy_timeout:.0f}s"
                    )
                continue
            if status == ST_CLOSED:
                raise TransportError("learner closed the data plane")
            raise TransportError("put_trajectory failed on the learner side")

    def put_trajectories(self, trees: list[Any]) -> int:
        """Ship K trajectories in one round trip (OP_PUT_TRAJ_N); returns
        how many the learner accepted.

        The per-unroll request/reply of put_trajectory is the reference's
        32-RPC `sample_batch` anti-pattern at one remove
        (`buffer_queue.py:416-435`) — on a 20ms RTT it caps one actor at
        50 unrolls/s no matter how fast the envs step. Batching the
        whole `extract()` round into one exchange removes that cap.

        Semantics match put_trajectory: at-most-once per blob (a dropped
        connection loses the in-flight batch, returns the count shipped
        so far), bounded ST-BUSY-equivalent retries of the NOT-enqueued
        tail on partial acceptance. Unrolls the admission controller
        drops at source count as accepted in the return value — they
        were disposed of by design, not refused.
        """
        ctrl = self._admission
        dedup = codec.obs_dedup_enabled()
        dropped = 0
        if ctrl is not None:
            blobs = []
            for t in trees:
                decision = ctrl.admit(t)
                if not decision.send:
                    dropped += 1
                    continue
                sent_tree = t if decision.tree is None else decision.tree
                # One contiguous buffer per unroll: pack_batch frames
                # each blob by length, stamp included.
                blob = codec.stamp_blob(
                    codec.encode(sent_tree, dedup=dedup), decision.stamp)
                ctrl.note_wire(len(blob), decision)
                blobs.append(blob)
            if dropped:
                self._bump("unrolls_admission_dropped", dropped)
            if not blobs:
                return dropped
        else:
            blobs = [codec.encode(t, dedup=dedup) for t in trees]
        sent = 0
        busy_since: float | None = None
        while sent < len(blobs):
            try:
                status, resp = self._exchange(
                    OP_PUT_TRAJ_N, pack_batch(blobs[sent:]), retry=True, resend=False)
            except TransportError:
                if self._is_down():  # reconnect failed: learner is gone
                    raise
                return sent + dropped  # batch fate unknown: drop, never duplicate
            if status == ST_CLOSED:
                raise TransportError("learner closed the data plane")
            if status != ST_OK:
                raise TransportError("put_trajectories failed on the learner side")
            # unpack_from, never strict unpack: the reply grows trailing
            # fields (pressure today) and must keep parsing on clients
            # that predate them.
            accepted = _I64.unpack_from(resp, 0)[0]
            if ctrl is not None and len(resp) >= _I64.size + _U16.size:
                ctrl.observe_pressure(_U16.unpack_from(resp, _I64.size)[0])
            sent += accepted
            self._bump("unrolls_sent", accepted)
            if sent < len(blobs):
                self._bump("partial_accepts")
                # Partial acceptance = the bounded queue refused the tail
                # (the batched ST_BUSY). The tail was not enqueued, so
                # resending it cannot duplicate.
                now = time.monotonic()
                busy_since = busy_since or now
                if now - busy_since > self.busy_timeout:
                    raise TransportError(
                        f"learner queue busy for >{self.busy_timeout:.0f}s")
                if accepted:
                    busy_since = now  # progress resets the wedge clock
        return sent + dropped

    def get_weights_if_newer(self, have_version: int) -> tuple[Any, int] | None:
        t0 = time.perf_counter()  # unconditional: enablement can race the
        resp = self._call(OP_GET_WEIGHTS, _I64.pack(have_version))  # check below
        version = _I64.unpack(resp[: _I64.size])[0]
        if _OBS.enabled:
            _OBS.gauge("actor/weight_pull_ms", (time.perf_counter() - t0) * 1e3)
            _OBS.gauge("actor/weight_version", version)
        if version == have_version:  # identity match (see server comment)
            return None
        self._bump("weight_pulls")
        return codec.decode(resp[_I64.size :], copy=True), version

    def get_weights_sharded(self, have_version: int, keys=None,
                            base_version: int = -2,
                            accept_delta: bool = False
                            ) -> tuple[int, bytes, list] | None:
        """Raw shard-scoped pull (OP_GET_WEIGHTS_SHARDED): None on
        version identity, else (version, manifest_bytes, shards) with
        shards = [(key, enc, base, payload-view), ...]. Raises
        ShardedWeightsUnavailableError when the learner's store is not
        sharded — callers latch over to the whole-blob op permanently
        (ShardedRemoteWeights does; a misrouted ST_ERROR from an old
        server means the same thing)."""
        req = _pack_shard_req(have_version, keys, base_version, accept_delta)
        status, resp = self._exchange(OP_GET_WEIGHTS_SHARDED, req,
                                      retry=True, resend=True)
        if status == ST_CLOSED:
            raise TransportError("learner closed the data plane")
        if status != ST_OK:
            raise ShardedWeightsUnavailableError(
                "endpoint does not serve sharded weight pulls")
        if len(resp) == _I64.size:  # identity: nothing newer to carry
            return None
        return _parse_shard_reply(resp)

    def remote_act(self, request: dict, busy_retry: bool = True) -> dict:
        """SEED-style inference: ship observation rows, get action rows.

        Request/reply are the algorithm-specific row dicts of
        `runtime/inference.py` — always computed with the service's
        newest published weights, so the actor never pulls params.

        ST_BUSY (the service's admission budget is full) is retried
        with exponential jittered backoff, bounded by `busy_timeout` —
        the act-path analogue of put_trajectory's ST_BUSY loop. Pass
        `busy_retry=False` to get InferenceBusyError instead, so a
        multi-endpoint caller (RemoteActService) can fail the request
        over to another replica rather than camping on this one.
        """
        blob = codec.encode(request)
        backoff: _BusyBackoff | None = None
        while True:
            status, resp = self._exchange(OP_ACT, blob, retry=True, resend=True)
            if status == ST_BUSY:
                self._bump("act_busy_waits")
                if not busy_retry:
                    raise InferenceBusyError(
                        "inference service admission budget full")
                backoff = backoff or _BusyBackoff(self.busy_timeout,
                                                  self._jitter)
                backoff.sleep_or_raise("inference service")
                continue
            if status == ST_UNAVAILABLE:
                raise InferenceUnavailableError(
                    "endpoint does not serve inference "
                    "(start the learner with --serve_inference)")
            if status == ST_CLOSED:
                raise TransportError("learner closed the data plane")
            if status != ST_OK:
                raise RemoteActFailed("remote act failed on the serving side")
            self._bump("acts")
            return codec.decode(resp, copy=True)

    def queue_size(self) -> int:
        return _I64.unpack(self._call(OP_QUEUE_SIZE))[0]

    def ping(self) -> bool:
        try:
            self._call(OP_PING, retry=False)
            return True
        except (TransportError, OSError):
            return False

    def _fleet_call(self, op: int, info: dict) -> dict:
        """OP_REGISTER/OP_HEARTBEAT exchange (runtime/fleet.py). Raises
        FleetUnavailableError on ST_UNAVAILABLE or ST_ERROR — an old
        server replies ST_ERROR to the unknown op, and the heartbeat
        loop must latch over to plain pings, not retry forever."""
        from distributed_reinforcement_learning_tpu.runtime import fleet as _fleet

        status, resp = self._exchange(op, _fleet.pack_fleet_msg(info),
                                      retry=True, resend=True)
        if status == ST_CLOSED:
            raise TransportError("learner closed the data plane")
        if status != ST_OK:
            raise FleetUnavailableError(
                "endpoint does not serve the fleet control plane",
                permanent=(status == ST_UNAVAILABLE))
        return _fleet.unpack_fleet_msg(resp)

    def fleet_register(self, info: dict) -> dict:
        return self._fleet_call(OP_REGISTER, info)

    def fleet_heartbeat(self, info: dict) -> dict:
        return self._fleet_call(OP_HEARTBEAT, info)

    def abort(self) -> None:
        """Best-effort LOCK-FREE teardown for watchdog/shutdown paths.
        A thread stuck inside `_exchange` holds `_lock` for up to the
        socket timeout (300s), so `close()` would block its caller
        behind the outage that prompted the shutdown. Shutting the
        socket down out-of-band makes the blocked recv/send raise
        immediately; the owning thread then tears down under the lock
        as usual. An in-flight `create_connection` cannot be
        interrupted this way — callers must not wait on it."""
        sock = self._sock  # drlint: disable=lock-discipline — see above
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass

    def close(self) -> None:
        with self._lock:
            self._close_locked()

    def _close_locked(self) -> None:
        # Split from close(): _exchange already holds _lock when it tears
        # down a dead socket, and threading.Lock is not reentrant.
        if self._sock is not None:
            try:
                self._sock.close()
            finally:
                self._sock = None


class RemoteQueue:
    """`TrajectoryQueue` put/size surface for actor runners, over the wire."""

    def __init__(self, client: TransportClient):
        self._client = client

    def set_admission(self, controller) -> None:
        """Delegate to the client: its PUT paths own scoring/stamping
        (data/admission.py)."""
        self._client.set_admission(controller)

    def put(self, item: Any, timeout: float | None = None) -> bool:
        return self._client.put_trajectory(item)  # False = dropped (at-most-once)

    def put_many(self, items: list[Any], timeout: float | None = None) -> int:
        return self._client.put_trajectories(items)

    def size(self) -> int:
        return self._client.queue_size()


class RemoteWeights:
    """`WeightStore.get_if_newer` surface for actor runners, over the wire."""

    def __init__(self, client: TransportClient):
        self._client = client

    def get_if_newer(self, have_version: int) -> tuple[Any, int] | None:
        return self._client.get_weights_if_newer(have_version)


class ShardedRemoteWeights(_LockedStatsMixin):
    """`get_if_newer` over the shard-scoped op (runtime/weight_shards):
    pulls the manifest + per-shard blobs, keeps a per-shard cache so
    the next pull can receive byte-range DELTAS and skip untouched
    shards entirely, dequantizes a bf16/int8 broadcast back to f32,
    and assembles the pytree via `weight_shards.materialize`.

    Demotes to the whole-blob op on the first ST_UNAVAILABLE/ST_ERROR
    (the learner's store is not sharded, or an old server), so
    pre-shard topologies pay one round trip at startup and nothing
    after. The latch is re-probeable on a bounded RetryLadder
    (runtime/fleet.py): `reattach()` — driven from the fleet heartbeat
    cadence — clears it so the NEXT pull retries the sharded op (one
    extra round trip per probe, on the pull cadence, never a second
    hot-path exchange); a restarted learner that now publishes per
    shard re-promotes this client, while a genuinely un-sharded
    learner re-latches and the exhausted ladder restores the old
    permanent behavior. Any cache/protocol inconsistency (a delta
    whose base this client no longer holds) is repaired with ONE full
    sharded pull, never an actor kill.

    `keys` scopes REFRESHES to the listed shard keys after the first
    full pull (`DRL_WEIGHTS_KEYS`): unlisted shards stay pinned at
    their last-pulled bytes — for roles that deliberately freeze part
    of the tree. A pinned shard materializes with the manifest entry
    CACHED from the version its bytes came from (crc, quant scales):
    decoding old int8 codes with the current version's scales would
    silently drift the "frozen" leaves every pull.

    Concurrency map (tools/drlint lock-discipline): `stats` is bumped
    on the actor loop thread and polled by the telemetry flush thread
    (accessors from _LockedStatsMixin); `_plain`/`_reprobe` share that
    lock because the fleet heartbeat thread's reattach() clears the
    latch while the actor loop reads it. `_blobs`/`_cache_version` are
    only ever touched by the actor loop thread — same single-thread
    contract as BoardWeights' cache."""

    _GUARDED_BY = {
        "stats": "_stats_lock",
        "_plain": "_stats_lock",
        "_reprobe": "_stats_lock",
    }
    _NOT_GUARDED = {
        "_blobs": "actor-loop-thread-only shard cache (same "
                  "single-thread contract as BoardWeights' cache)",
        "_metas": "actor-loop-thread-only manifest-entry cache",
        "_cache_version": "actor-loop-thread-only cache version",
    }

    telemetry_prefix = "wshard"
    surface_name = "wshard"  # fleet heartbeat registration label

    def __init__(self, client: TransportClient, keys=None):
        from distributed_reinforcement_learning_tpu.runtime.fleet import RetryLadder

        self._client = client
        self._keys = list(keys) if keys else None
        self._plain = False    # whole-blob demote latch (ladder-probed)
        self._reprobe = False  # a reattach probe is pending on the pull path
        self._ladder = RetryLadder("wshard-op")
        self._blobs: dict[str, np.ndarray] = {}
        self._metas: dict[str, dict] = {}  # manifest entry per cached blob
        self._cache_version = -2
        self.stats = {"shard_pulls": 0, "shards_full": 0, "shards_delta": 0,
                      "shards_skipped": 0, "bytes_received": 0,
                      "repair_pulls": 0, "whole_fallbacks": 0,
                      "reattaches": 0}
        self._stats_lock = threading.Lock()

    def _resolve(self, shards):
        """Wire shards -> (owned blob dict, cache_derived) against the
        cache; None when the cache cannot honor a delta/skip (repair
        with a full pull). `cache_derived` drives checksum
        verification: blobs rebuilt from cached bases (delta/skip) are
        the case the manifest crc exists for — a reused version number
        against a stale cache; an all-FULL pull is plain TCP bytes."""
        from distributed_reinforcement_learning_tpu.runtime import weight_shards

        out = dict(self._blobs) if self._keys is not None else {}
        nfull = ndelta = nskip = nbytes = 0
        for key, enc, base, payload in shards:
            if enc == weight_shards.ENC_FULL:
                out[key] = np.frombuffer(bytes(payload), np.uint8)
                nfull += 1
                nbytes += len(payload)
            elif enc == weight_shards.ENC_DELTA:
                if base != self._cache_version or key not in self._blobs:
                    return None
                out[key] = weight_shards.delta_apply(self._blobs[key], payload)
                ndelta += 1
                nbytes += len(payload)
            elif enc == weight_shards.ENC_SKIP:
                if base != self._cache_version or key not in self._blobs:
                    return None
                out[key] = self._blobs[key]
                nskip += 1
            else:
                return None
        with self._stats_lock:
            self.stats["shards_full"] += nfull
            self.stats["shards_delta"] += ndelta
            self.stats["shards_skipped"] += nskip
            self.stats["bytes_received"] += nbytes
        return out, (ndelta + nskip) > 0

    def _merged_manifest(self, mbytes, shards) -> dict:
        """Parse the pulled manifest; with role-scoped `keys`, PINNED
        shards (absent from this reply) swap in the manifest entry
        cached from the version their bytes came from — crc and quant
        scales must describe the cached blob, not the current one."""
        from distributed_reinforcement_learning_tpu.runtime import weight_shards

        manifest = weight_shards.parse_manifest(mbytes)
        if self._keys is None:
            return manifest
        refreshed = {k for k, _, _, _ in shards}
        manifest["shards"] = [
            sh if sh["key"] in refreshed or sh["key"] not in self._metas
            else self._metas[sh["key"]]
            for sh in manifest["shards"]]
        return manifest

    def reattach(self, ctx=None) -> None:
        """Clear the whole-blob latch (bounded ladder) so the NEXT pull
        re-probes the sharded op. Driven from the fleet heartbeat
        cadence; the probe itself rides the normal pull path — one
        extra round trip against a still-unsharded learner, never a
        hot-path reconnect storm."""
        del ctx  # nothing shm-backed to validate: the op IS the probe
        with self._stats_lock:
            plain = self._plain
        if not plain or not self._ladder.try_acquire():
            return
        with self._stats_lock:
            self._plain = False
            self._reprobe = True

    def reset_reattach(self) -> None:
        """Fresh probe budget (learner epoch change: the restarted
        learner may publish sharded where the old one did not)."""
        self._ladder.reset()

    def _note_sharded_ok(self) -> None:
        """The sharded op answered: if a reattach probe was pending,
        the re-promotion is confirmed."""
        with self._stats_lock:
            confirmed = self._reprobe
            self._reprobe = False
            if confirmed:
                self.stats["reattaches"] += 1
        if confirmed:
            self._ladder.note_success()
            import sys

            print("[wshard] sharded weight pulls re-promoted (learner "
                  "serves the shard-scoped op again)", file=sys.stderr)

    def get_if_newer(self, have_version: int) -> tuple[Any, int] | None:
        from distributed_reinforcement_learning_tpu.runtime import weight_shards

        with self._stats_lock:
            plain = self._plain
        if plain:
            return self._client.get_weights_if_newer(have_version)
        t0 = time.perf_counter()
        keys = self._keys if self._cache_version >= 0 else None
        try:
            got = self._client.get_weights_sharded(
                have_version, keys=keys,
                base_version=self._cache_version, accept_delta=True)
        except ShardedWeightsUnavailableError:
            with self._stats_lock:
                self._plain = True
                reprobe = self._reprobe
                self._reprobe = False
                self.stats["whole_fallbacks"] += 1
            if reprobe:  # a failed reattach probe burns a ladder slot
                self._ladder.note_failure()
            return self._client.get_weights_if_newer(have_version)
        self._note_sharded_ok()
        if got is None:
            if _OBS.enabled:
                _OBS.gauge("actor/weight_pull_ms",
                           (time.perf_counter() - t0) * 1e3)
            return None
        version, mbytes, shards = got
        params = blobs = manifest = None
        resolved = self._resolve(shards)
        if resolved is not None:
            blobs, derived = resolved
            try:
                manifest = self._merged_manifest(mbytes, shards)
                # Checksums run only for cache-DERIVED pulls (delta/
                # skip): that is where a reused version number against
                # a stale cache can silently mispair bytes. An all-FULL
                # pull is plain framed TCP, and a crc pass would re-read
                # every transferred byte for nothing.
                params = weight_shards.materialize(manifest, blobs,
                                                   verify=derived)
            except (KeyError, ValueError):
                # Checksum/coverage failure: the cache paired a stale
                # blob with a reused version number (restarted learner
                # republishing from 0 — version IDENTITY has no global
                # uniqueness). Repair below.
                params = None
        if params is None:
            # ONE full sharded pull (no deltas, no elision) repairs any
            # cache inconsistency; a second failure is a real server
            # fault and surfaces as a ConnectionError for the actor's
            # elastic-grace loop.
            self._bump("repair_pulls")
            self._blobs, self._metas, self._cache_version = {}, {}, -2
            got = self._client.get_weights_sharded(have_version)
            if got is None:
                return None
            version, mbytes, shards = got
            resolved = self._resolve(shards)
            if resolved is None:
                raise TransportError("sharded weight pull unresolvable "
                                     "after a full repair pull")
            blobs, _ = resolved
            try:
                manifest = weight_shards.parse_manifest(mbytes)
                params = weight_shards.materialize(manifest, blobs,
                                                   verify=False)
            except (KeyError, ValueError) as e:
                raise TransportError(
                    f"sharded weight pull corrupt after repair: {e}") from e
        self._blobs = blobs
        self._metas = {sh["key"]: sh for sh in manifest["shards"]}
        self._cache_version = version
        self._bump("shard_pulls")
        if _OBS.enabled:
            _OBS.gauge("actor/weight_pull_ms", (time.perf_counter() - t0) * 1e3)
            _OBS.gauge("actor/weight_version", version)
        return params, version


class RemoteInference:
    """Actor-side act surface over OP_ACT (SEED-style remote inference).

    Callable with the algorithm's row dict; returns the reply dict."""

    def __init__(self, client: TransportClient):
        self._client = client

    def __call__(self, request: dict) -> dict:
        return self._client.remote_act(request)


class RemoteActService(_LockedStatsMixin):
    """Actor-side act surface over a REPLICATED inference tier
    (runtime/serving.py): N replica endpoints plus the learner's
    in-process service as the fallback of last resort.

    Selection per request: round-robin with a least-pending bias (the
    live endpoint with the fewest in-flight requests wins; the rotating
    cursor breaks ties so equal-pending replicas share load). Failure
    handling per the tier's contract:

    - ST_BUSY (admission reject): fail over IMMEDIATELY to a live
      replica that has not rejected this round; only when every live
      replica has rejected does the request back off with jitter
      (bounded by `busy_timeout`) before starting a fresh round.
    - A dead replica (TransportError/OSError after the client's own
      bounded reconnect) is demoted — acts skip it from that moment on,
      so a flapping replica never absorbs act-path retries. Demotion is
      no longer permanent, though: `reattach()` (driven from the fleet
      heartbeat cadence, runtime/fleet.py — never the act path) pings
      demoted endpoints on a bounded per-replica RetryLadder and
      re-promotes one the moment it answers, so a respawned replica
      re-enters rotation. An exhausted ladder restores the old
      permanent latch (logged once).
    - With every replica demoted, requests fall back to the learner
      client, so pre-replica topologies (and a fully-dead tier) keep
      working exactly as before; learner failures propagate as
      TransportError for the actor's elastic-grace loop to own.

    Concurrency map (tools/drlint lock-discipline): `_sel_lock` covers
    the selection state (pending counts, demote latches, cursor) that
    concurrent actor threads race on; `stats` follows the shared
    _LockedStatsMixin contract (bumped on call paths, polled by the
    telemetry flush thread). The endpoint list itself is immutable
    after construction.
    """

    _GUARDED_BY = {
        "stats": "_stats_lock",
        "_pending": "_sel_lock",
        "_dead": "_sel_lock",
        "_rr": "_sel_lock",
    }
    _NOT_GUARDED = {
        "_endpoints": "immutable after construction (see map comment); "
                      "each client serializes itself via its own _lock",
        "_ladders": "fixed list assigned once in __init__; RetryLadder "
                    "instances carry their own lock",
    }

    def __init__(self, endpoints: list[TransportClient],
                 fallback: TransportClient | None = None,
                 busy_timeout: float = 90.0):
        self._endpoints = list(endpoints)
        self._fallback = fallback
        self.busy_timeout = busy_timeout
        self._sel_lock = threading.Lock()
        self._pending = [0] * len(self._endpoints)
        self._dead = [False] * len(self._endpoints)
        self._rr = 0
        self.stats = {"acts": 0, "busy_failovers": 0, "replica_demotes": 0,
                      "fallback_acts": 0, "replica_repromotes": 0}
        self._stats_lock = threading.Lock()
        self._jitter = random.Random()
        # One bounded re-promote ladder per endpoint (runtime/fleet.py);
        # the list is immutable after construction, each ladder locks
        # itself. Probes run from reattach() only — the fleet control
        # cadence — never from the act path.
        from distributed_reinforcement_learning_tpu.runtime.fleet import RetryLadder

        self._ladders = [
            RetryLadder(f"replica-{c.host}:{c.port}") for c in self._endpoints]

    @classmethod
    def from_addrs(cls, addrs: list[str],
                   fallback: TransportClient | None = None,
                   connect_retries: int = 60, **kwargs) -> "RemoteActService":
        """Build from "host:port" strings. Endpoints connect LAZILY (on
        their first selected act), so actor startup never serializes N
        blocking connects; a replica that stays unreachable past the
        bounded retries demotes permanently through the normal failure
        path and the service works on through the survivors/fallback.

        The default retry budget is deliberately the client's generous
        60 x 1 s: a replica binds its port only after the LEARNER's
        first weight publish, so at topology start the first act may
        legitimately race a learner still initializing — a short budget
        would permanently demote a healthy tier. The cost is a one-time
        bounded stall on a replica that really is dead, after which the
        demote latch makes every later act skip it."""
        clients = []
        for addr in addrs:
            host, _, p = addr.rpartition(":")
            clients.append(TransportClient(host, int(p), connect=False,
                                           connect_retries=connect_retries))
        return cls(clients, fallback=fallback, **kwargs)

    def _pick(self, skip: set | frozenset = frozenset()) -> int | None:
        """Acquire a slot on the live endpoint with the fewest in-flight
        requests (rotating cursor breaks ties); None = every live
        endpoint is demoted or in `skip` (the caller's set of endpoints
        that already busy-rejected this round)."""
        with self._sel_lock:
            n = len(self._endpoints)
            best: int | None = None
            for off in range(n):
                i = (self._rr + off) % n
                if self._dead[i] or i in skip:
                    continue
                if best is None or self._pending[i] < self._pending[best]:
                    best = i
            if best is None:
                return None
            self._rr += 1
            self._pending[best] += 1
            return best

    def _release(self, i: int) -> None:
        with self._sel_lock:
            self._pending[i] -= 1

    def _demote(self, i: int) -> None:
        import sys

        with self._sel_lock:
            was_dead, self._dead[i] = self._dead[i], True
        if not was_dead:
            self._bump("replica_demotes")
            print(f"[remote_act] WARNING: inference replica "
                  f"{self._endpoints[i].host}:{self._endpoints[i].port} "
                  f"demoted (dead)", file=sys.stderr)
            try:
                self._endpoints[i].close()
            except OSError:
                pass

    def __call__(self, request: dict) -> dict:
        backoff: _BusyBackoff | None = None
        busy_round: set[int] = set()
        while True:
            i = self._pick(skip=busy_round)
            if i is None:
                if busy_round and self.live_endpoints() > 0:
                    # EVERY live replica busy-rejected this round: only
                    # now back off with jitter, then start a fresh round
                    # — a request rejected by one saturated replica must
                    # fail over to an idle sibling immediately, not
                    # sleep first.
                    backoff = backoff or _BusyBackoff(self.busy_timeout,
                                                      self._jitter)
                    backoff.sleep_or_raise("inference tier")
                    busy_round.clear()
                    continue
                # Tier fully demoted (or built with no replicas): the
                # learner's in-process service keeps the topology alive.
                if self._fallback is None:
                    raise TransportError("no live inference replicas "
                                         "and no learner fallback")
                self._bump("fallback_acts")
                out = self._fallback.remote_act(request)
                self._bump("acts")
                return out
            try:
                out = self._endpoints[i].remote_act(request, busy_retry=False)
            except InferenceBusyError:
                # Saturated, not dead: mark it for this round and
                # re-select — the skip set sends the retry straight to
                # a sibling that has not rejected yet.
                self._bump("busy_failovers")
                busy_round.add(i)
            except RemoteActFailed:
                # The replica is ALIVE but this request (or the batch
                # it joined) failed application-side. Propagate like
                # the single-endpoint path always has — the actor's
                # elastic loop owns the retry — and do NOT demote: one
                # poisoned co-batched request latching healthy
                # replicas dead would let a single bad actor take the
                # whole tier down.
                raise
            except (InferenceUnavailableError, TransportError, OSError):
                # Dead or misrouted replica: permanent demote, then
                # retry on a survivor. remote_act is resend-safe
                # (acting twice on the same rows is just a fresh
                # sample), so failing the request over cannot corrupt
                # anything — no request is lost with a survivor up.
                self._demote(i)
            else:
                self._bump("acts")
                return out
            finally:
                self._release(i)

    def live_endpoints(self) -> int:
        with self._sel_lock:
            return sum(not d for d in self._dead)

    surface_name = "remote_act"  # fleet heartbeat registration label

    def reattach(self, ctx=None) -> None:
        """Probe demoted replicas (bounded per-endpoint ladder) and
        re-promote any that answer a ping — a respawned replica
        re-enters rotation instead of staying latched dead. Called from
        the fleet heartbeat loop's cadence, NEVER the act path: a probe
        against a still-dead replica costs its bounded connect attempt
        on the control thread only."""
        import sys

        del ctx  # replicas carry no shm identity to validate
        with self._sel_lock:
            dead = [i for i, d in enumerate(self._dead) if d]
        for i in dead:
            ladder = self._ladders[i]
            if not ladder.try_acquire():
                continue
            ep = self._endpoints[i]
            # Short probe budget: the generous from_addrs budget exists
            # for topology start; a re-promote probe must return to the
            # control loop quickly and lean on the ladder for pacing.
            # RESTORED afterwards — a re-promoted replica must keep its
            # original reconnect budget on the act path, or one blip
            # re-demotes it and the flapping burns the ladder.
            saved_retries = ep.connect_retries
            ep.connect_retries = 1
            try:
                alive = ep.ping()
            finally:
                ep.connect_retries = saved_retries
            if alive:
                with self._sel_lock:
                    self._dead[i] = False
                ladder.note_success()
                self._bump("replica_repromotes")
                print(f"[remote_act] inference replica {ep.host}:{ep.port} "
                      f"re-promoted (answered ping)", file=sys.stderr)
            else:
                ladder.note_failure()

    def reset_reattach(self) -> None:
        """Fresh probe budgets (learner epoch change: the tier may have
        been respawned wholesale)."""
        for ladder in self._ladders:
            ladder.reset()

    def close(self) -> None:
        """Close the replica clients this service owns (the fallback
        client belongs to the caller)."""
        with self._sel_lock:
            dead = list(self._dead)
        for i, client in enumerate(self._endpoints):
            if not dead[i]:
                try:
                    client.close()
                except OSError:
                    pass


def resolve_learner_addr(rt) -> tuple[str, int]:
    """The non-learner roles' learner addressing contract, single
    source (actors in run_role, inference replicas in
    runtime/serving.py):

      DRL_LEARNER_ADDR=host:port — full address (learners on different
        machines, the normal TPU-pod layout);
      DRL_LEARNER_INDEX=k — port offset against the config's
        server_ip/server_port (learner processes co-hosted: tests,
        single-host multi-chip).
    """
    addr = os.environ.get("DRL_LEARNER_ADDR")
    if addr:
        host, _, p = addr.rpartition(":")
        return host, int(p)
    return rt.server_ip, rt.server_port + env_int("DRL_LEARNER_INDEX", 0)


def _make_queue(capacity: int):
    from distributed_reinforcement_learning_tpu.data.native import native_available

    if native_available():
        from distributed_reinforcement_learning_tpu.data.native import NativeTrajectoryQueue

        return NativeTrajectoryQueue(capacity)
    from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue

    return TrajectoryQueue(capacity)


def run_role(
    algo: str,
    config_path: str,
    section: str,
    mode: str,
    task: int,
    num_updates: int = 1000,
    run_dir: str | None = None,
    seed: int = 0,
    checkpoint_dir: str | None = None,
    checkpoint_interval: int = 500,
    actor_grace: float = 120.0,
    serve_inference: bool = False,
    remote_act: bool = False,
) -> None:
    """One process of the reference topology: `--mode learner`,
    `--mode actor --task k` (reference role flags, `train_impala.py:16-20`),
    or `--mode inference --task k` (an act-serving replica of the
    inference tier, runtime/serving.py)."""
    if mode == "inference":
        from distributed_reinforcement_learning_tpu.runtime import serving

        serving.run_replica(algo, config_path, section, task=task, seed=seed,
                            run_dir=run_dir, grace=actor_grace)
        return
    import jax

    from distributed_reinforcement_learning_tpu.runtime import launch
    from distributed_reinforcement_learning_tpu.utils.config import load_config
    from distributed_reinforcement_learning_tpu.utils.device import open_devices
    from distributed_reinforcement_learning_tpu.utils.logger import MetricsLogger

    agent_cfg, rt = load_config(config_path, section)
    # Staleness-budget override (scripts/launch_local_cluster.py
    # --staleness_budget): the launcher derives a publish cadence from
    # the `learner/weight_staleness` semantics and exports it here,
    # replacing the config section's fixed per-recipe default.
    interval = env_int("DRL_PUBLISH_INTERVAL", 0)
    if interval:
        import dataclasses as _dc

        rt = _dc.replace(rt, publish_interval=max(1, interval))

    if mode == "learner":
        # Sharded learner tier (runtime/learner_tier.py): when the
        # launcher exported a seat identity, this process is ONE of N
        # cooperating learner seats — own data plane on server_port +
        # rank, own replay shards, gradients exchanged through the host
        # collective, exactly one elected seat publishing to the shared
        # weight plane. None = the pre-tier single learner, untouched.
        from distributed_reinforcement_learning_tpu.runtime import learner_tier

        tier = learner_tier.build_tier()
        if tier is not None:
            # Endpoint up FIRST (before the seconds of jit init below):
            # peers' startup barriers probe it, and a seat that binds
            # late eats into everyone's await_peers budget.
            tier.start()
            print(f"[learner] tier seat {tier.rank}/{tier.seats} "
                  f"(sync={tier.sync}, publisher={tier.is_publisher()})")
        # Multi-chip / multi-host learner. parallel.distributed.initialize
        # joins the JAX runtime when DRL_COORDINATOR/DRL_NUM_PROCESSES are
        # set (no-op single-host); with N processes x M devices the learn
        # step pjits over the GLOBAL (data,) mesh, each process dequeues
        # its batch_size/N share from its own socket data plane, and
        # place_local_batch assembles the global batch via
        # jax.make_array_from_process_local_data. Single-host multi-chip
        # (a TPU slice, or the CPU simulation) is the N=1 special case.
        from distributed_reinforcement_learning_tpu.parallel import distributed

        multihost = distributed.initialize()
        open_devices("learner")
        if tier is not None and multihost:
            raise ValueError(
                "the learner tier (DRL_LEARNER_SEATS) and the jax.distributed "
                "multihost learner (DRL_COORDINATOR) are different scale-out "
                "planes — pick one")
        local_batch = rt.batch_size
        mesh = None
        devs = jax.devices() if multihost else jax.local_devices()
        if multihost:
            nproc = jax.process_count()
            if rt.batch_size % nproc != 0:
                raise ValueError(
                    f"batch_size {rt.batch_size} not divisible by {nproc} processes")
            local_batch = rt.batch_size // nproc
            print(f"[learner] multi-host: process {jax.process_index()}/{nproc}, "
                  f"{len(jax.local_devices())} local of {len(devs)} devices, "
                  f"local batch {local_batch}")
        # The batch only needs to divide the mesh's DATA axis — with
        # pipeline/expert/seq axes carved out, that is a fraction of the
        # device count, not len(devs).
        seq, pipe, expert = launch.mesh_axes_for(agent_cfg, rt)
        inner = pipe * expert * seq
        data_axis = len(devs) // inner if len(devs) % inner == 0 else 0
        if len(devs) > 1 and data_axis > 0 and rt.batch_size % data_axis == 0:
            from distributed_reinforcement_learning_tpu.parallel import make_mesh

            if pipe > 1:
                micro = agent_cfg.pipeline_microbatches
                if (rt.batch_size // data_axis) % micro != 0:
                    raise ValueError(
                        f"pipeline needs the per-device batch "
                        f"({rt.batch_size}/{data_axis}) divisible by "
                        f"pipeline_microbatches={micro}")
            mesh = make_mesh(devices=devs, seq_parallel=seq,
                             pipe_parallel=pipe, expert_parallel=expert)
            print(f"[learner] mesh: {dict(mesh.shape)}")
        elif inner > 1 and launch.needs_sharded_learner(algo, agent_cfg, rt):
            # The learn step requires sharding (ring/pipeline/expert) over
            # multi-device axes but no valid mesh fits here. Without this
            # refusal, make_agent would size the same mesh internally —
            # bypassing the divisibility checks above — and the mismatch
            # would surface as an opaque GSPMD/shard_map shape error
            # instead of a config error. (A dense config with leftover
            # seq_parallel>1 stays on the old unsharded fallback.)
            if len(devs) % inner != 0:
                why = (f"device count {len(devs)} is not divisible by the "
                       f"inner axes product {inner} — adjust "
                       f"seq_parallel/pipeline_stages/expert_parallel")
            else:
                why = (f"batch_size {rt.batch_size} is not divisible by the "
                       f"data axis ({len(devs)}//{inner} = {len(devs) // inner})")
            raise ValueError(
                f"config requires a sharded learner "
                f"(seq={seq}, pipe={pipe}, expert={expert}) but no valid mesh "
                f"fits on {len(devs)} devices: {why}")
        elif multihost:
            # Refuse rather than silently run N independent un-psum'd
            # learners whose weight copies would diverge.
            raise ValueError(
                f"multi-host learner needs batch_size divisible by the global "
                f"device count ({rt.batch_size * jax.process_count()} global batch, "
                f"{len(devs)} devices)")
        if local_batch != rt.batch_size:
            import dataclasses

            rt = dataclasses.replace(rt, batch_size=local_batch)
        logger = MetricsLogger(run_dir)  # actors log nothing: no writer for them
        queue = _make_queue(rt.queue_size)
        # Which plane this run is on: _make_queue falls back to the
        # Python queue without a word when cpp/*.cc does not build.
        print(f"[learner] data plane: {type(queue).__name__}")
        from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

        weights = WeightStore()
        # Co-hosted actors' publish-once weight plane (runtime/
        # weight_board.py): the launcher names one board per learner;
        # this side creates the segment and the WeightStore mirrors every
        # landed publication into it (one memcpy, independent of actor
        # count). Failure leaves TCP-only weight pulls.
        board = None
        board_name = os.environ.get("DRL_SHM_WEIGHTS_CREATE", "").strip()
        if board_name and (tier is None or tier.is_publisher()):
            from distributed_reinforcement_learning_tpu.runtime import weight_board

            board = weight_board.serve_board(board_name)
            if board is not None:
                weights.attach_board(board)
                print("[learner] shm weight board serving co-hosted actors")
        # Non-publisher seats hold the SAME board name unused: on
        # publisher death the tier's election fires the promote callback
        # below, which re-creates the segment (creator-pid reclaim) and
        # replays the current snapshot into it — actors reattach through
        # their fleet ladders exactly as after a learner restart.
        # Sharded replay with ingest-time prioritization (data/
        # replay_service.py; gate + facade in runtime/replay_shard.py):
        # when enabled, every transport ingest thread decodes, scores,
        # and inserts into its OWN shard, and the learner's ingest
        # stages shrink to a gather-from-shards sample. The facade
        # replaces the queue for the TCP server and the ring drainer;
        # the REAL queue stays built as the demotion fallback (the
        # learner keeps draining it — normally idle).
        from distributed_reinforcement_learning_tpu.runtime import replay_shard

        # The spill tier anchors its segment manifests next to the
        # checkpoints (when checkpointing is on): a restarted learner
        # recovers the spilled experience from the same durable root it
        # resumes weights from.
        spill_dir = (os.path.join(checkpoint_dir, "replay_spill")
                     if checkpoint_dir else None)
        replay_service = replay_shard.build_service(algo, rt, seed=seed,
                                                    spill_dir=spill_dir)
        ingest_queue: Any = queue
        if replay_service is not None:
            ingest_queue = replay_shard.ReplayIngestFifo(replay_service, queue)
            print(f"[learner] sharded replay: "
                  f"{len(replay_service.shards)} ingest shard(s), "
                  f"scorer {replay_service.scorer_name}")
        learner = launch.make_learner(
            algo, agent_cfg, rt, queue, weights, logger=logger,
            rng=jax.random.PRNGKey(seed),
            # Free-running learner: overlap H2D of batch k+1 with step k.
            prefetch=(algo in ("impala", "ximpala")),
            mesh=mesh,
            replay_service=replay_service,
        )
        if tier is not None:
            # Wrap the learn step with the collective exchange and arm
            # the publication takeover: on promotion (lowest live rank
            # after a death) this seat re-creates the shared board under
            # the SAME name (creator-pid reclaim) and the WeightStore
            # replays its current snapshot into it — surviving actors'
            # reattach ladders find it exactly like a restarted learner.
            tier.attach(learner)

            def _on_promoted():
                nonlocal board
                if not board_name or board is not None:
                    return
                from distributed_reinforcement_learning_tpu.runtime import (
                    weight_board)

                board = weight_board.serve_board(board_name)
                if board is not None:
                    weights.attach_board(board)
                    print("[learner] tier takeover: shm weight board "
                          "re-created for co-hosted actors", flush=True)

            tier.set_promote_cb(_on_promoted)
        ckpt = None
        if checkpoint_dir is not None:
            from distributed_reinforcement_learning_tpu.utils.checkpoint import Checkpointer

            ckpt = Checkpointer(checkpoint_dir)
            if learner.restore_checkpoint(ckpt):
                print(f"[learner] resumed from step {learner.train_steps}")
            if multihost and jax.process_index() != 0:
                ckpt = None  # every process restores; only process 0 writes
        inference = None
        if serve_inference:
            from distributed_reinforcement_learning_tpu.runtime.inference import InferenceServer

            inference = InferenceServer.for_agent(algo, learner.agent, weights,
                                                  seed=seed + 7777)
            print("[learner] SEED-style inference service enabled")
        # Fleet supervisor (runtime/fleet.py): the control-channel
        # roster actors/replicas register + heartbeat against, the
        # launcher's respawn loop reads, and the learner-side
        # re-promote sweep (replay-shard revive) runs on. DRL_FLEET=0
        # restores the pre-fleet one-way demotions.
        from distributed_reinforcement_learning_tpu.runtime import fleet as fleet_mod

        supervisor = None
        member_loop = None
        if fleet_mod.fleet_enabled():
            # Every learner (and every tier SEAT) supervises its own
            # members: the seat's actors register and heartbeat HERE,
            # and in tier mode the reply's `board_pid` names the
            # elected PUBLISHER seat so board reattach probes validate
            # the shared segment against its real creator.
            supervisor = fleet_mod.FleetSupervisor(
                board_pid_fn=(tier.publisher_pid if tier is not None
                              else None)).start()
            if replay_service is not None:
                supervisor.watch(ingest_queue)  # ReplayIngestFifo revive
            if tier is not None and tier.rank != 0:
                # Learner seats are additionally first-class MEMBERS of
                # seat 0's roster (role "learner", rank k): one roster
                # shows the whole tier to obs_report and chaos drills.
                member_loop = fleet_mod.start_member_loop(
                    rt, "learner", tier.rank,
                    version_fn=lambda: weights.version)
        # Each multihost learner process (and each tier seat) serves its
        # own data plane on server_port + index: globally unambiguous
        # (actors pick a learner via DRL_LEARNER_INDEX) and
        # collision-free when the processes share one machine.
        serve_port = rt.server_port + (
            tier.rank if tier is not None
            else (jax.process_index() if multihost else 0))
        server = TransportServer(ingest_queue, weights, host="0.0.0.0",
                                 port=serve_port, inference=inference,
                                 fleet=supervisor).start()
        # Co-hosted actors' zero-copy data plane (runtime/shm_ring.py):
        # the launcher names one ring per co-hosted actor; this side
        # creates the segments and drains them into the same bounded
        # queue the TCP server feeds. Failure leaves TCP-only operation.
        ring_drainer = None
        ring_names = [n for n in
                      os.environ.get("DRL_SHM_RING_CREATE", "").split(",") if n]
        if ring_names:
            from distributed_reinforcement_learning_tpu.runtime import shm_ring

            ring_drainer = shm_ring.serve_rings(ring_names, ingest_queue)
            if ring_drainer is not None:
                print(f"[learner] shm rings serving {len(ring_names)} "
                      f"co-hosted actor(s)")
        # Run-wide telemetry (observability/): env-gated, off by default.
        # The data-plane signals the paper's argument turns on — queue
        # depth, weight version — are polled per flush, never on the
        # learn thread's hot path.
        if maybe_configure("learner",
                           tier.rank if tier is not None
                           else (jax.process_index() if multihost else 0),
                           run_dir):
            _OBS.sample("transport/queue_depth", queue.size)
            _OBS.sample("learner/weight_version", lambda: weights.version)
            if weights.sharded:
                # Sharded-publication counters (obs_report's "Weight
                # sharding" subsection): per-publish changed-shard
                # bytes, quant savings, delta encodes.
                for key in weights.shard_stats():
                    _OBS.sample(f"weights/{key}",
                                lambda k=key: weights.shard_stat(k),
                                kind="counter")
            # The server's cumulative stats (unrolls_accepted,
            # busy_replies, weight_sends, ...) become report throughput
            # via counter providers — no second hot-path counter. The
            # providers poll from the telemetry flush thread, so they go
            # through the locked stat() accessor, not the live dict.
            for key in server.snapshot_stats():
                _OBS.sample(f"transport/{key}",
                            lambda k=key: server.stat(k), kind="counter")
            if ring_drainer is not None:
                # The ring next to the TCP stats in obs_report: in-flight
                # bytes (depth), drained unrolls/bytes as throughput.
                _OBS.sample("ring/depth", ring_drainer.depth_bytes)
                for key in ring_drainer.snapshot_stats():
                    _OBS.sample(f"ring/{key}",
                                lambda k=key: ring_drainer.stat(k),
                                kind="counter")
            # Codec fast-path counters (data/codec.py): decode layout-cache
            # hits on the serve/drain threads; the locked accessor is
            # polled from the telemetry flush thread.
            for key in codec.cache_stats():
                _OBS.sample(f"codec/{key}", lambda k=key: codec.cache_stat(k),
                            kind="counter")
            if replay_service is not None:
                # Per-shard fill / priority-mass / ingest counters — the
                # obs_report "Replay shards" section.
                replay_shard.register_telemetry(replay_service)
            if inference is not None:
                # Learner-hosted act service counters (the obs_report
                # "Inference serving" section reads the same names a
                # replica process registers).
                _OBS.sample("inference/rows_served",
                            lambda: inference.rows_served, kind="counter")
                _OBS.sample("inference/batches_run",
                            lambda: inference.batches_run, kind="counter")
                _OBS.sample("inference/admission_rejects",
                            inference.admission_reject_count, kind="counter")
            if supervisor is not None:
                # Roster gauges + join/suspect/dead/rejoin counters —
                # the obs_report "Fleet health" section.
                fleet_mod.register_supervisor_telemetry(supervisor)
            if member_loop is not None:
                fleet_mod.register_member_telemetry(member_loop)
            if tier is not None:
                # Collective round latency + membership/publisher
                # timeline — the obs_report "Learner tier" section.
                learner_tier.register_telemetry(tier)
        if tier is not None and not tier.await_peers():
            print(f"[learner] tier seat {tier.rank}: some peers never "
                  f"answered the startup barrier; starting degraded over "
                  f"{tier.collective.membership.live()}", flush=True)
        print(f"[learner] serving on :{serve_port}; training {num_updates} updates")
        try:
            _learner_loop(algo, learner, num_updates, ckpt, checkpoint_interval,
                          bounded_drain=tier is not None)
        finally:
            if ckpt is not None and learner.train_steps > 0:
                learner.save_checkpoint(ckpt)
            learner.close()  # stop prefetch thread, flush open profiler trace
            queue.close()
            server.stop()
            if ring_drainer is not None:
                ring_drainer.stop()  # closes, unlinks the shm segments
            if board is not None:
                weights.close()        # drain pending async publishes
                board.close_writer()   # attached actors demote to TCP
                board.close()
                board.unlink()
            if inference is not None:
                inference.stop()
            if replay_service is not None:
                replay_service.close()  # stop the update-router thread
            if supervisor is not None:
                supervisor.stop()
            if member_loop is not None:
                member_loop.stop()
            if tier is not None:
                tier.close()  # stop the sweep + the collective endpoint
            _OBS.close()  # final shard flush + trace terminator
        print(f"[learner] done: {learner.train_steps} updates")
    elif mode == "actor":
        if task < 0:
            raise ValueError("actor mode needs --task k")
        open_devices(f"actor {task}")
        # Multi-learner topology: each learner process needs its local
        # batch share fed, so launch scripts partition actors across the
        # learners (addressing contract: resolve_learner_addr).
        server_ip, port = resolve_learner_addr(rt)
        client = TransportClient(server_ip, port)
        # Zero-copy data plane for co-hosted actors: when the launcher
        # named a ring for this task, trajectory PUTs become one memcpy
        # into shared memory (control traffic stays on this TCP client).
        # Attach failure or a mid-run ring death falls back to TCP.
        actor_queue: Any = RemoteQueue(client)
        ring_name = os.environ.get("DRL_SHM_RING_NAME")
        if ring_name:
            from distributed_reinforcement_learning_tpu.runtime import shm_ring

            rq = shm_ring.attach_ring_queue(ring_name, client)
            if rq is not None:
                actor_queue = rq
                print(f"[actor {task}] shm ring attached: {ring_name}"
                      if rq.attached else
                      f"[actor {task}] shm ring {ring_name} unavailable; "
                      f"starting demoted to TCP (reattach ladder armed)")
        # Publish-once weight plane: when the launcher named a board, a
        # weight pull becomes a shared-memory version peek (no syscall)
        # plus one memcpy only when the version actually changed. Attach
        # failure or a dead board falls back to TCP pulls. The TCP pull
        # itself is shard-scoped when the learner publishes per shard
        # (manifest + changed shards only; ShardedRemoteWeights demotes
        # itself to the whole-blob op against an un-sharded store), and
        # DRL_WEIGHTS_KEYS scopes this role's refreshes to named shards.
        from distributed_reinforcement_learning_tpu.runtime import weight_shards

        tcp_weights = ShardedRemoteWeights(
            client, keys=weight_shards.role_keys())
        actor_weights: Any = tcp_weights
        board_name = os.environ.get("DRL_SHM_WEIGHTS_NAME")
        if board_name:
            from distributed_reinforcement_learning_tpu.runtime import weight_board

            # fallback: a demoted board keeps the shard-scoped TCP pull
            # path (and its own reattach ladder) instead of regressing
            # to whole-blob transfers. (In learner-TIER topologies the
            # shared board's creator is the elected PUBLISHER seat; the
            # reattach ladder validates against the heartbeat reply's
            # board_pid field — BoardWeights._pid_field — so no special
            # casing here.)
            bw = weight_board.attach_board_weights(board_name, client,
                                                   fallback=tcp_weights)
            if bw is not None:
                actor_weights = bw
                print(f"[actor {task}] shm weight board attached: "
                      f"{board_name}" if bw.attached else
                      f"[actor {task}] shm weight board {board_name} "
                      f"unavailable; starting demoted to TCP pulls "
                      f"(reattach ladder armed)")
        # Remote acting: with DRL_INFER_ADDRS (the launcher's replica
        # tier) acts go through RemoteActService — round-robin/least-
        # pending over the replicas, permanent demote of dead ones, the
        # learner's in-process service as fallback. Without it, the
        # single-endpoint learner service (pre-replica topologies).
        # Sample-at-source (data/admission.py): score + stamp initial
        # priorities on this side of the wire, and thin low-priority
        # unrolls under learner backpressure. One controller per actor,
        # shared with the pipeline publisher's queue below (the folded-
        # mass ledger and the pressure EWMA must be one account).
        from distributed_reinforcement_learning_tpu.data import admission

        admission_ctrl = admission.configure(actor_queue, algo,
                                             seed=seed + 1 + task)
        if admission_ctrl is not None:
            print(f"[actor {task}] actor-side priority stamping on "
                  f"(scorer={admission_ctrl.scorer_name}, "
                  f"admission={'on' if admission.admission_enabled() else 'off'})")
        remote: Any = None
        if remote_act:
            infer_addrs = [a for a in
                           os.environ.get("DRL_INFER_ADDRS", "").split(",") if a]
            if infer_addrs:
                remote = RemoteActService.from_addrs(infer_addrs, fallback=client)
                print(f"[actor {task}] remote act via "
                      f"{len(infer_addrs)} inference replica(s)")
            else:
                remote = RemoteInference(client)
        actor = launch.make_actor(
            algo, agent_cfg, rt, task, actor_queue, actor_weights,
            seed=seed + 1 + task,
            remote_act=remote,
        )
        # Pipelined actor data plane (runtime/actor_pipeline.py):
        # double-buffered env slices + an async bounded publisher, so
        # the jitted/remote act and the encode+PUT overlap the host env
        # stepping. Off unless DRL_ACTOR_PIPE is set. On the TCP
        # data plane the publisher gets its OWN client: the shared
        # client's request/reply lock would otherwise serialize a
        # publisher PUT against remote acts and weight pulls — exactly
        # the blocking the pipeline exists to hide. (Ring PUTs are a
        # lock-free memcpy; no second client needed.)
        from distributed_reinforcement_learning_tpu.runtime import actor_pipeline

        pub_client = None
        if (actor_pipeline.pipeline_enabled()
                and type(actor_queue) is RemoteQueue):
            pub_client = TransportClient(server_ip, port)
            pub_queue = RemoteQueue(pub_client)
            if admission_ctrl is not None:
                # SAME controller as the step-loop queue: stamping and
                # the folded-mass ledger follow the unrolls to whichever
                # client ships them.
                pub_queue.set_admission(admission_ctrl)
            actor = actor_pipeline.maybe_wrap(
                actor, label=f"actor {task}",
                publisher_queue=pub_queue)
        else:
            actor = actor_pipeline.maybe_wrap(actor, label=f"actor {task}")
        if pub_client is not None and not isinstance(
                actor, actor_pipeline.ActorPipeline):
            pub_client.close()  # wrap declined (unsliceable env)
            pub_client = None
        # Fleet membership (runtime/fleet.py): register with the
        # learner's supervisor and heartbeat on a control connection;
        # each reply drives the demoted surfaces' bounded reattach
        # probes (ring, board, sharded pull, replica rotation) so a
        # respawned learner segment or replica re-enters service
        # instead of staying demoted forever. DRL_FLEET=0 disables.
        from distributed_reinforcement_learning_tpu.runtime import fleet as fleet_mod

        heartbeats = fleet_mod.start_member_loop(
            rt, "actor", task,
            surfaces=[s for s in (actor_queue, actor_weights,
                                  None if tcp_weights is actor_weights
                                  else tcp_weights, remote)
                      if hasattr(s, "reattach")],
            version_fn=lambda: getattr(actor, "_version", -1))
        # Per-actor telemetry shard (observability/): this is the half of
        # the topology the old MetricsLogger never covered (actors log
        # nothing). The client's cumulative stats become per-flush
        # timelines via providers — zero cost on the act/step path.
        if maybe_configure("actor", task, run_dir):
            for key in client.snapshot_stats():
                _OBS.sample(f"actor/{key}", lambda k=key: client.stat(k),
                            kind="counter")
            if hasattr(actor_queue, "snapshot_stats"):  # RingQueue only
                for key in actor_queue.snapshot_stats():
                    _OBS.sample(f"ring/{key}",
                                lambda k=key: actor_queue.stat(k),
                                kind="counter")
            if hasattr(actor_weights, "snapshot_stats"):
                # "board/" for BoardWeights, "wshard/" for the TCP
                # shard-scoped pull surface (telemetry_prefix attr).
                wprefix = getattr(actor_weights, "telemetry_prefix", "board")
                for key in actor_weights.snapshot_stats():
                    _OBS.sample(f"{wprefix}/{key}",
                                lambda k=key: actor_weights.stat(k),
                                kind="counter")
            if tcp_weights is not actor_weights:
                # The board's demoted-pull fallback surface: its own
                # wshard/ counters (demote->re-promote rows in the
                # obs_report "Fleet health" section).
                for key in tcp_weights.snapshot_stats():
                    _OBS.sample(f"wshard/{key}",
                                lambda k=key: tcp_weights.stat(k),
                                kind="counter")
            if hasattr(remote, "snapshot_stats"):  # RemoteActService only
                for key in remote.snapshot_stats():
                    _OBS.sample(f"remote_act/{key}",
                                lambda k=key: remote.stat(k),
                                kind="counter")
            # Actor-side codec counters: schema-cache hit rate on the
            # encode path and dedup bytes saved (the wire-byte cut the
            # obs_report "Codec" section renders).
            for key in codec.cache_stats():
                _OBS.sample(f"codec/{key}", lambda k=key: codec.cache_stat(k),
                            kind="counter")
            _OBS.sample("actor/weight_version_held",
                        lambda: getattr(actor, "_version", -1))
            if heartbeats is not None:
                # fleet/heartbeats + registration/restart counters (the
                # obs_report "Fleet health" member rows).
                fleet_mod.register_member_telemetry(heartbeats)
        print(f"[actor {task}] connected to {server_ip}:{port}")
        # Elastic recovery (SURVEY §5.3 — the reference had none: a dead
        # learner left actors blocked forever): on transport failure the
        # actor keeps retrying for `actor_grace` seconds, riding out a
        # learner restart (checkpoint resume), and only then exits. The
        # initial connect above kept the client's generous 60-retry budget
        # (learner may start after the actors); from here each reconnect
        # attempt is kept short so THIS loop owns the grace deadline.
        client.connect_retries = 3
        frames = 0
        down_since: float | None = None
        stats_s = env_float("DRL_TRANSPORT_STATS_S", 0.0)
        next_stats = time.monotonic() + stats_s
        try:
            while True:
                try:
                    t0 = time.perf_counter()
                    with _OBS.span("actor_round"):
                        got = _actor_round(algo, actor)
                    frames += got
                    if _OBS.enabled:
                        dt = time.perf_counter() - t0
                        _OBS.count("actor/env_frames", got)
                        if dt > 0:
                            _OBS.gauge("actor/env_steps_per_s", got / dt)
                    down_since = None
                except (TransportError, OSError):  # incl. socket timeouts
                    now = time.time()
                    down_since = down_since or now
                    if now - down_since > actor_grace:
                        print(f"[actor {task}] learner gone >{actor_grace:.0f}s "
                              f"after {frames} frames; exiting")
                        return
                    time.sleep(1.0)
                if stats_s > 0 and time.monotonic() >= next_stats:
                    # Per-actor fairness/staleness record (scale demo):
                    # machine-grepped as `[actor k] stats {...}` lines.
                    next_stats = time.monotonic() + stats_s
                    s = client.snapshot_stats()
                    s["frames"] = frames
                    s["weight_version"] = getattr(actor, "_version", None)
                    print(f"[actor {task}] stats {s}", flush=True)
        finally:
            if heartbeats is not None:  # stop probes before surfaces close
                heartbeats.stop()
            if hasattr(actor, "close"):  # ActorPipeline: drain the publisher
                actor.close()
            if pub_client is not None:  # the publisher's dedicated lane
                pub_client.close()
            if hasattr(actor_queue, "close"):  # RingQueue: release the shm map
                actor_queue.close()
            if hasattr(actor_weights, "close"):  # BoardWeights: ditto
                actor_weights.close()
            if hasattr(remote, "close"):  # RemoteActService: replica clients
                remote.close()
            client.close()
            _OBS.close()  # final shard flush + trace terminator
    else:
        raise ValueError(f"unknown mode {mode!r}")


def _learner_loop(
    algo: str,
    learner,
    num_updates: int,
    ckpt=None,
    checkpoint_interval: int = 500,
    bounded_drain: bool = False,
) -> None:
    last_saved = learner.train_steps

    def maybe_checkpoint() -> None:
        nonlocal last_saved
        if ckpt is not None and learner.train_steps - last_saved >= checkpoint_interval:
            learner.save_checkpoint(ckpt)
            last_saved = learner.train_steps

    # Learner-TIER seats (bounded_drain): the allreduce collective
    # couples the seats' TRAIN cadences — an unbounded ingest drain
    # under actors that produce faster than one unroll per drain slice
    # would starve this seat's rounds and stall every peer mid-round
    # (BSP livelock). Cap the unrolls consumed per train call; the solo
    # learner keeps the historical drain-until-empty behavior.
    drain_cap = 8 if bounded_drain else None

    if algo in ("impala", "ximpala"):  # same FIFO learner loop
        while learner.train_steps < num_updates:
            learner.step(timeout=5.0)
            maybe_checkpoint()
    elif algo == "apex":
        while learner.train_steps < num_updates:
            drained = False
            budget = drain_cap
            while learner.ingest_many(timeout=0.05):
                drained = True
                if budget is not None:
                    budget -= 1
                    if budget <= 0:
                        break
            if learner.train() is None and not drained:
                time.sleep(0.05)
            maybe_checkpoint()
    elif algo in ("r2d2", "xformer"):  # same prioritized sequence-replay loop
        while learner.train_steps < num_updates:
            got = learner.ingest_batch(timeout=0.05)
            if learner.train() is None and not got:
                time.sleep(0.05)
            maybe_checkpoint()
    else:
        raise ValueError(f"unknown algorithm {algo!r}")


def _actor_round(algo: str, actor) -> int:
    if algo == "apex":
        return actor.run_steps(64)
    return actor.run_unroll()

"""Inference serving tier: replicated act service with continuous batching.

`runtime/inference.py` made the act path SEED-style (actors ship
observation rows, a learner-side service batches them into jitted acts,
SURVEY §3.5) — but as ONE batcher thread inside the one learner process,
fed by the same TCP transport as trajectories, with no replication and
run-at-`max_batch` batching. SEED RL (arXiv:1910.06591) shows
centralized inference wins only when the service itself scales past one
host, and IMPACT (arXiv:1912.00167) shows actors tolerate bounded weight
staleness — which is exactly what lets inference move OUT of the
learner: a replica acting on weights a publish or two old is the same
off-policyness V-trace/TD already corrects. This module is that tier:

- **Replica host** (`run_replica`, CLI `--mode inference --task k`): a
  separate process that attaches READ-ONLY to the learner's shm weight
  board (PR 5 made reads a version peek + one memcpy) with TCP
  weight-pull fallback — the same demote-on-failure discipline as the
  ring/board planes — mirrors each new version into a local WeightStore,
  and serves OP_ACT on its own port through the standard
  `TransportServer` (queue-less: PUTs answer ST_UNAVAILABLE).
- **Continuous batcher** (`ContinuousInferenceServer`): replaces the
  run-at-`max_batch` barrier. A dispatch thread takes whatever rows are
  pending the moment a pipeline slot frees and dispatches the jitted act
  (same padded power-of-two buckets); a completion thread materializes
  and scatters results. The next batch ASSEMBLES WHILE THE PREVIOUS ACT
  IS IN FLIGHT, so batch size adapts to load: light traffic gets
  latency-optimal small batches, heavy traffic coalesces into full
  buckets without any wait-window tuning.
- **Admission control**: a bounded pending-rows budget
  (`DRL_INFER_BUDGET`, default 4x max_batch). A submit that would exceed
  it raises `InferenceBusy` -> the transport replies ST_BUSY -> the
  client retries with jitter or fails over to another replica
  (`transport.RemoteActService`), instead of thousands of env
  connections queueing unbounded latency onto a saturated service.

Actor-side replica selection lives in `transport.RemoteActService`
(round-robin with least-pending bias, permanent demote of dead replicas,
fall back to the learner's in-process service), so existing
topologies never import this module.

Equivalence: a replica's acts are pinned to the learner-hosted service's
(identical params + rng -> identical action rows;
tests/test_serving.py's two-process test), because both run the same
adapters, the same PRNG split discipline, and the same bucketed shapes.

No replicas by default (not measured on the chip):
`launch_local_cluster --inference_replicas N` or `DRL_INFER_REPLICAS`
sets a replica count.
"""

from __future__ import annotations

import os
import queue as _queuemod
import threading
import time

import numpy as np

from distributed_reinforcement_learning_tpu.observability import TELEMETRY as _OBS
from distributed_reinforcement_learning_tpu.observability import maybe_configure
from distributed_reinforcement_learning_tpu.runtime.inference import (
    InferenceServer,
    make_act_adapter,
)
from distributed_reinforcement_learning_tpu.utils.environ import env_float, env_int

# -- gate ---------------------------------------------------------------------


def replica_count() -> int:
    """`DRL_INFER_REPLICAS`: act-serving replicas for --remote_act
    topologies; 0 = acts stay on the learner's in-process service.
    0 by default; not measured on the chip."""
    return max(0, env_int("DRL_INFER_REPLICAS", 0))


def admission_budget(max_batch: int) -> int:
    """Pending-rows budget for the serving tier (`DRL_INFER_BUDGET`
    overrides; default 4x max_batch — enough pending work to keep the
    two-deep dispatch pipeline full at max occupancy, small enough that
    a rejected client's jittered retry lands in the next batch or two
    instead of minutes of queue)."""
    return env_int("DRL_INFER_BUDGET", 4 * max_batch)


# -- continuous batcher -------------------------------------------------------


class ContinuousInferenceServer(InferenceServer):
    """InferenceServer with the run-at-max_batch barrier replaced by a
    two-stage pipeline:

        submitters -> pending deque -> [dispatch thread] -> in-flight
        queue (bounded, `depth`) -> [completion thread] -> waiters

    The dispatch thread takes whatever requests are pending (up to
    `max_batch` rows, same power-of-two padding) the moment the
    in-flight queue has a free slot and dispatches the jitted act; the
    completion thread blocks on materializing the device outputs and
    scatters them. While batch k computes, batch k+1 assembles from the
    rows that arrived meanwhile — the assembly window IS the previous
    batch's compute time, so there is no max_wait barrier to tune and no
    idle device while requests sit waiting for a quorum.

    `depth` bounds dispatched-but-unmaterialized batches (the device-
    side pipeline): the dispatch thread blocks on the in-flight queue's
    put when it runs ahead, which is exactly when arriving rows coalesce
    into bigger batches.

    Concurrency map (tools/drlint lock-discipline): same pending-state
    contract as the base class. The in-flight handoff is a stdlib
    queue.Queue (its own lock); `_rng`/`_device_params`/
    `_cached_version` stay dispatch-thread-only, and the cumulative
    counters (`batches_run`, `rows_served`) move to the completion
    thread — still a single writer.
    """

    _GUARDED_BY = {
        "_pending": ("_lock", "_batch_ready"),
        "_pending_rows": ("_lock", "_batch_ready"),
        "_stop": ("_lock", "_batch_ready"),
        "_admission_rejects": ("_lock", "_batch_ready"),
    }

    def __init__(
        self,
        act_fn,
        weights,
        max_batch: int = 256,
        seed: int = 0,
        admission_rows: int | None = None,
        depth: int | None = None,
    ):
        # No max_wait_ms here ON PURPOSE: the continuous _take_batch has
        # no wait window (assembly time IS the previous batch's compute
        # time), so accepting the knob would be dead configuration
        # surface that misleads tuning.
        if depth is None:
            depth = env_int("DRL_INFER_DEPTH", 2)
        self._inflight: _queuemod.Queue = _queuemod.Queue(maxsize=max(1, depth))
        self._completer: threading.Thread | None = None
        # Base __init__ starts the dispatch thread (targeting our
        # overridden _loop) — _inflight must exist first; early batches
        # just park in the queue until the completer starts below.
        super().__init__(act_fn, weights, max_batch=max_batch, seed=seed,
                         admission_rows=admission_rows)
        self._completer = threading.Thread(
            target=self._complete_loop, daemon=True, name="inference-complete")
        self._completer.start()

    @classmethod
    def for_agent(cls, algo: str, agent, weights, **kwargs) -> "ContinuousInferenceServer":
        return cls(make_act_adapter(algo, agent), weights, **kwargs)

    def _take_batch(self) -> list[dict]:
        """Continuous policy: return pending requests AS SOON AS any
        exist (up to max_batch rows, whole requests — oversized submits
        were already chunked). No deadline: coalescing happens naturally
        while the dispatch pipeline is full, and an idle service serves
        a lone request at the latency floor instead of holding it
        max_wait hostage."""
        with self._batch_ready:
            while not self._stop:
                if self._pending:
                    batch, rows = [], 0
                    while self._pending:
                        k = self._pending[0]["n"]
                        if batch and rows + k > self.max_batch:
                            break
                        rows += k
                        batch.append(self._pending.popleft())
                    self._pending_rows -= rows
                    return batch
                # Bounded wait (drlint blocking-under-lock): a lost
                # notify — stop() racing a submit's early return — must
                # not park the dispatch thread forever; the loop
                # re-checks _stop/_pending each wakeup.
                self._batch_ready.wait(timeout=0.5)
            return []

    def _loop(self) -> None:
        while True:
            reqs = self._take_batch()
            if not reqs:
                # Stopped: wake the completion thread after any
                # still-in-flight batches drain through the queue.
                self._inflight.put(None)
                return
            try:
                out, n = self._dispatch(reqs)
            except Exception as e:  # noqa: BLE001 — deliver to every waiter
                for r in reqs:
                    r["error"] = e
                    r["event"].set()
                continue
            # Blocks while `depth` batches are already in flight — the
            # backpressure that turns a busy device into bigger batches.
            self._inflight.put((reqs, out, n))

    def _complete_loop(self) -> None:
        while True:
            item = self._inflight.get()
            if item is None:
                return
            reqs, out, n = item
            try:
                host = {k: np.asarray(v)[:n] for k, v in out.items()}
            except Exception as e:  # noqa: BLE001 — deliver to every waiter
                for r in reqs:
                    r["error"] = e
                    r["event"].set()
                continue
            self._scatter(reqs, host, n)

    def stop(self) -> None:
        super().stop()  # stops dispatch (which enqueues the sentinel),
        #                 then errors out still-pending submits
        if self._completer is not None:
            self._completer.join(timeout=5.0)


# -- replica host -------------------------------------------------------------


def run_replica(
    algo: str,
    config_path: str,
    section: str,
    task: int = 0,
    seed: int = 0,
    run_dir: str | None = None,
    grace: float = 120.0,
    num_updates: int | None = None,
) -> None:
    """One inference replica process (`--mode inference --task k`).

    Builds the algorithm's plain-apply actor-twin agent, attaches to the
    learner's weight plane (shm board when `DRL_SHM_WEIGHTS_NAME` is
    set, TCP pulls otherwise — attach failure or a mid-run board death
    demotes to TCP permanently, PRs 3/5 discipline), republishes each
    new version into a LOCAL WeightStore, and serves OP_ACT on this
    replica's own port (`DRL_INFER_PORT`, default server_port+1000+task)
    through a queue-less TransportServer. The replica also answers
    GET_WEIGHTS from its local store — a free second weight-distribution
    tier for pull-mode actors.

    Exits when the learner stays unreachable past `grace` seconds (the
    actor-mode elastic-recovery contract); the local-cluster launcher
    additionally terminates replicas when the topology comes down.
    `num_updates` is accepted for launcher symmetry and ignored — a
    replica serves for the life of the run.
    """
    from distributed_reinforcement_learning_tpu.runtime import launch, weight_shards
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        ShardedRemoteWeights,
        TransportClient,
        TransportError,
        TransportServer,
        resolve_learner_addr,
    )
    from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore
    from distributed_reinforcement_learning_tpu.utils.config import load_config
    from distributed_reinforcement_learning_tpu.utils.device import open_devices

    del num_updates  # replicas serve until the topology stops
    task = max(task, 0)
    open_devices(f"infer {task}")
    agent_cfg, rt = load_config(config_path, section)
    port = env_int("DRL_INFER_PORT", 0) or (rt.server_port + 1000 + task)
    host, lport = resolve_learner_addr(rt)
    client = TransportClient(host, lport)
    # The initial connect above kept the client's generous 60-retry
    # budget (the learner may start after the replicas); from here each
    # reconnect attempt is kept short so the grace loops below own the
    # failure deadline — the actor-mode elastic-recovery precedent.
    client.connect_retries = 3
    # Weight source: the shm board when the launcher named one (reads
    # are a version peek + one memcpy, cost independent of replica
    # count), else TCP pulls from the learner — shard-scoped when the
    # learner publishes per shard (ShardedRemoteWeights demotes itself
    # to the whole-blob op otherwise; DRL_WEIGHTS_KEYS scopes this
    # replica's refreshes). BoardWeights demotes ITSELF to the TCP
    # client permanently on any board failure.
    tcp_weights = ShardedRemoteWeights(client, keys=weight_shards.role_keys())
    weights_src = tcp_weights
    board_name = os.environ.get("DRL_SHM_WEIGHTS_NAME")
    if board_name:
        from distributed_reinforcement_learning_tpu.runtime import weight_board

        # fallback: a demoted board keeps the shard-scoped TCP pull
        # path (and its own reattach ladder) instead of regressing to
        # whole-blob transfers.
        bw = weight_board.attach_board_weights(board_name, client,
                                               fallback=tcp_weights)
        if bw is not None:
            weights_src = bw
            print(f"[infer {task}] shm weight board attached: {board_name}"
                  if bw.attached else
                  f"[infer {task}] shm weight board {board_name} "
                  f"unavailable; starting demoted to TCP pulls "
                  f"(reattach ladder armed)")
    agent = launch.make_agent(algo, agent_cfg, rt, actor=True)
    local = WeightStore()
    # First weights BEFORE serving: a replica that answered ST_ERROR
    # while the learner warms up would look dead to RemoteActService
    # and be demoted permanently for a transient condition.
    version = -1
    deadline = time.monotonic() + grace
    while True:
        # Same grace discipline as the refresh loop below: a learner
        # that dies (or restarts) during replica startup must produce
        # the bounded "no weights in Ns" exit, not an unhandled
        # reconnect traceback at the client's retry exhaustion.
        try:
            got = weights_src.get_if_newer(version)
        except (ConnectionError, OSError):
            got = None
        if got is not None:
            local.publish(got[0], got[1])
            version = got[1]
            break
        if time.monotonic() >= deadline:
            raise TransportError(
                f"learner at {host}:{lport} published no weights in "
                f"{grace:.0f}s")
        time.sleep(0.2)
    max_batch = env_int("DRL_INFER_MAX_BATCH", 256)
    inference = ContinuousInferenceServer.for_agent(
        algo, agent, local, max_batch=max_batch,
        admission_rows=admission_budget(max_batch),
        # Offset per replica: N replicas acting on the same rows must
        # not explore in lockstep.
        seed=seed + 7777 + 131 * task)
    server = TransportServer(None, local, host="0.0.0.0", port=port,
                             inference=inference).start()
    # Fleet membership (runtime/fleet.py): register + heartbeat with the
    # learner's supervisor; replies drive the weight surface's bounded
    # reattach probes (a respawned learner's board/sharded op re-enters
    # service instead of this replica staying on TCP whole-blob pulls
    # forever). DRL_FLEET=0 disables.
    from distributed_reinforcement_learning_tpu.runtime import fleet as fleet_mod

    heartbeats = fleet_mod.start_member_loop(
        rt, "inference", task,
        surfaces=[s for s in (weights_src,
                              None if tcp_weights is weights_src
                              else tcp_weights)
                  if hasattr(s, "reattach")],
        version_fn=lambda: local.version)
    # Per-replica telemetry shard (obs_report "Inference serving"):
    # cumulative service counters become per-flush timelines via
    # providers polled from the telemetry flush thread.
    if maybe_configure("inference", task, run_dir):
        _OBS.sample("inference/rows_served",
                    lambda: inference.rows_served, kind="counter")
        _OBS.sample("inference/batches_run",
                    lambda: inference.batches_run, kind="counter")
        _OBS.sample("inference/admission_rejects",
                    inference.admission_reject_count, kind="counter")
        _OBS.sample("inference/weight_version", lambda: local.version)
        for key in server.snapshot_stats():
            _OBS.sample(f"transport/{key}", lambda k=key: server.stat(k),
                        kind="counter")
        if hasattr(weights_src, "snapshot_stats"):
            # "board/" for BoardWeights, "wshard/" for shard-scoped TCP.
            wprefix = getattr(weights_src, "telemetry_prefix", "board")
            for key in weights_src.snapshot_stats():
                _OBS.sample(f"{wprefix}/{key}",
                            lambda k=key: weights_src.stat(k),
                            kind="counter")
        if heartbeats is not None:
            fleet_mod.register_member_telemetry(heartbeats)
    pull_s = env_float("DRL_INFER_PULL_S", 0.2)
    print(f"[infer {task}] serving acts on :{port} "
          f"(weights v{version} from {host}:{lport}, "
          f"max_batch {max_batch}, budget {inference.admission_rows} rows)")
    down_since: float | None = None
    try:
        while True:
            # Weight refresh at a bounded-staleness cadence: versions
            # are identities (a rollback republish lands like any other
            # new version — the board/TCP sources both honor that), and
            # the service's device cache re-uploads on identity change.
            try:
                got = weights_src.get_if_newer(version)
                if got is not None:
                    local.publish(got[0], got[1])
                    version = got[1]
                down_since = None
            except (ConnectionError, OSError):
                now = time.monotonic()  # NTP steps must not bend grace
                down_since = down_since or now
                if now - down_since > grace:
                    print(f"[infer {task}] learner gone >{grace:.0f}s; "
                          f"exiting ({inference.rows_served} rows served)")
                    return
            time.sleep(pull_s)
    finally:
        if heartbeats is not None:  # stop probes before surfaces close
            heartbeats.stop()
        server.stop()
        inference.stop()
        if hasattr(weights_src, "close"):
            weights_src.close()
        client.close()
        _OBS.close()

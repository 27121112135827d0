"""Sharded learner tier: N cooperating learner seats, one publisher.

The single learner process was the last singleton in the topology — the
SPOF the fleet supervisor babysits and the host-data-plane ceiling of
the host loop. Following Podracer's Sebulba split (arXiv:2104.06272), this
module turns `--mode learner` into one SEAT of an N-seat tier:

- each seat owns its own transport server (data port `server_port +
  rank` — the existing `DRL_LEARNER_INDEX` actor-partitioning
  contract), its own replay shards (`ReplayIngestFifo` unchanged), and
  its own train loop;
- train steps exchange gradients through a host-side collective
  (`parallel/collective.py`) in one of two modes (`DRL_LEARNER_SYNC`):
  `allreduce` — lockstep gradient exchange (mean), numerically the
  union-batch gradient, requiring the split learn step
  (`agent.grads`/`agent.apply_grads` on a plain seat,
  `ShardedLearner.grads`/`apply_grads` on a mesh-sharded one). By
  default the exchange is PARTITION-AWARE: attach classifies every
  gradient leaf through `parallel/partition.py`, replicated segments
  ride the ring, model/expert/pipe-sharded classes go owner-scoped,
  optionally bf16-encoded (`DRL_COLL_QUANT`) and overlapped with the
  next step's backward (`DRL_COLL_OVERLAP`); the plan hash rides the
  HELLOs, and disagreement refuses loudly. `DRL_COLL_PARTITION=0`
  restores the whole-vector f32 ring byte-for-byte —
  or `async` — IMPACT-style (arXiv:1912.00167) bounded-staleness
  parameter merging: seats train free-running and every
  `DRL_LEARNER_MERGE_STEPS` steps push their params to peers and
  average in every peer whose latest push is fresher than
  `DRL_LEARNER_STALE_MAX` of the receiver's merge rounds;
- exactly ONE seat publishes to the shared weight plane (the PR 5 shm
  board under the launcher's single shared name): seat 0 by default,
  the lowest live rank after a death — the tier's liveness sweep
  promotes the survivor, which re-creates the board under the same
  name (creator-pid reclaim) and republishes under version-identity
  semantics, exactly the re-promotion path actors already ride;
- a dead peer demotes the tier to N-1 (membership epoch bump aborts
  in-flight rounds; survivors re-form), down to SOLO — a one-seat tier
  trains and publishes exactly like the pre-tier learner.

Priority writeback routing is local by construction: every seat samples
from its OWN replay (shards or monolithic), so `update_batch` lands in
the seat that sampled — loss-free across seats, pinned in
tests/test_learner_tier.py.

Gate: the launcher spawns seats with `DRL_LEARNER_SEATS`/`DRL_LEARNER_RANK`/
`DRL_LEARNER_PEERS` set (`launch_local_cluster --learners N` with seat
mode); a learner process without them runs exactly as before. No tier
by default; not measured on the chip.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Any

import numpy as np

from distributed_reinforcement_learning_tpu.observability import TELEMETRY as _OBS
from distributed_reinforcement_learning_tpu.parallel.collective import (
    HostCollective,
    PeerLost,
    RoundAborted,
)
from distributed_reinforcement_learning_tpu.utils.environ import (
    env_flag,
    env_float,
    env_int,
)


def seat_count() -> int:
    """`DRL_LEARNER_SEATS`: the seat count the LAUNCHER resolves (0/1 =
    no tier). 0 by default; not measured on the chip."""
    return max(0, env_int("DRL_LEARNER_SEATS", 0))


def sync_mode() -> str:
    """`DRL_LEARNER_SYNC`: `allreduce` (lockstep ring, the default) or
    `async` (bounded-staleness parameter merging)."""
    mode = os.environ.get("DRL_LEARNER_SYNC", "").strip().lower() or "allreduce"
    if mode not in ("allreduce", "async"):
        raise ValueError(
            f"DRL_LEARNER_SYNC must be allreduce|async, got {mode!r}")
    return mode


def merge_steps() -> int:
    """Async-mode merge cadence in train steps (`DRL_LEARNER_MERGE_STEPS`)."""
    return max(1, env_int("DRL_LEARNER_MERGE_STEPS", 8))


def stale_max() -> int:
    """Async-mode bounded staleness in merge rounds
    (`DRL_LEARNER_STALE_MAX`): a peer that has not pushed a NEW
    contribution within this many of the receiver's merge rounds ages
    out of the average until it pushes again (per-sender freshness —
    see LearnerTier._maybe_async_merge)."""
    return max(0, env_int("DRL_LEARNER_STALE_MAX", 4))


# -- partition-aware collective gates ------------------------------------------


def coll_partition() -> bool:
    """`DRL_COLL_PARTITION`: on by default — attach builds a plan
    whenever the learner exposes a params schema, and a seat with no
    schema falls back to the ring regardless. `=0` forces every
    allreduce round through the whole-vector f32 ring."""
    return env_flag("DRL_COLL_PARTITION", True)


def coll_quant() -> str:
    """Gradient transport encoding for partitioned rounds: "f32" (the
    default) or "bf16" (half the wire bytes through the shared RNE
    codec, f32 master accumulation; not measured on the chip).
    `DRL_COLL_QUANT` names a mode or is a flag (`1` means bf16). The
    mode is folded into the plan hash, so seats resolving differently
    refuse loudly instead of merging mixed encodings."""
    env = os.environ.get("DRL_COLL_QUANT", "").strip().lower()
    if env in ("bf16", "f32"):
        return env
    try:
        return "bf16" if env_flag("DRL_COLL_QUANT", False) else "f32"
    except ValueError as e:
        raise ValueError(f"{e} (or a mode: bf16|f32)") from None


def coll_overlap() -> int:
    """Bounded in-flight exchange depth (`DRL_COLL_OVERLAP`): 0 (the
    default) runs the exchange inline in the learn step; 1 hands it to
    the tier's collective worker so round t's wire time overlaps round
    t+1's backward (delayed apply — one-step-stale pipelined SGD, the
    same staleness class the async mode already tolerates; not
    measured on the chip). Depth is capped at 1: a deeper pipeline
    multiplies gradient staleness for no additional overlap (one
    exchange already hides behind one backward). Folded into the plan
    hash like the quant mode."""
    return min(1, max(0, env_int("DRL_COLL_OVERLAP", 0)))


# -- gradient pytree <-> flat f32 vector --------------------------------------


def flatten_tree(tree: Any) -> tuple[np.ndarray, tuple]:
    """Flatten a pytree of arrays into ONE contiguous f32 vector for the
    host collective; meta round-trips shapes/dtypes/structure. The
    np.asarray per leaf is the deliberate host sync — the collective is
    host-side by design."""
    import jax

    leaves, treedef = jax.tree.flatten(tree)
    hosts = [np.asarray(leaf) for leaf in leaves]  # drlint: disable=host-sync
    metas = [(h.shape, h.dtype.str) for h in hosts]
    if hosts:
        vec = np.concatenate(
            [h.ravel().astype(np.float32, copy=False) for h in hosts])
    else:
        vec = np.zeros((0,), np.float32)
    return vec, (treedef, metas)


def unflatten_tree(vec: np.ndarray, meta: tuple) -> Any:
    """Inverse of `flatten_tree` (dtypes restored per leaf)."""
    import jax

    treedef, metas = meta
    leaves = []
    off = 0
    for shape, dtype in metas:
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        leaves.append(vec[off:off + n].reshape(shape).astype(dtype, copy=False))
        off += n
    if off != len(vec):
        raise ValueError(f"vector length {len(vec)} != tree size {off}")
    return jax.tree.unflatten(treedef, leaves)


class LearnerTier:
    """One seat's tier membership: the collective, the liveness sweep,
    publisher election, and the learn-step wrap (`attach`).

    Concurrency map (tools/drlint lock-discipline): the sweep thread
    and the learn thread both drive `_check_membership` (promotion
    state), and telemetry providers poll `stats` from the flush thread
    — that state lives under `_lock`/`_stats_lock`. The per-peer miss
    counters belong to the sweep thread alone; the merge cadence
    counters to the learn thread alone.
    """

    _GUARDED_BY = {
        "stats": "_stats_lock",
        "_is_pub": "_lock",
        "_promote_cb": "_lock",
        "_cb_fired": "_lock",
        "_epoch_seen": "_lock",
        "_solo_logged": "_lock",
    }
    _NOT_GUARDED = {
        "_misses": "sweep-thread-only per-peer miss counters",
        "_merge_step": "learn-thread-only async merge-round counter",
        "_merge_seen": "learn-thread-only per-sender freshness clock",
        "_steps_since_merge": "learn-thread-only cadence counter",
        "_learner": "attach()-time wiring handle, controlling thread "
                    "only",
        "_sweeper": "start()/close() lifecycle handle, controlling "
                    "thread only",
        "_plan": "attach()-time exchange layout, read-only afterwards "
                 "(learn + collective-worker threads)",
        "_coll_worker": "attach()/close() lifecycle handle, controlling "
                        "thread only",
        "_coll_in": "queue.Queue — internally locked",
        "_coll_out": "queue.Queue — internally locked",
        "_inflight": "learn-thread-only overlap credit (one exchange "
                     "in flight at most)",
    }

    def __init__(self, rank: int, addrs: list[str], sync: str | None = None,
                 probe_interval_s: float | None = None,
                 dead_after_s: float | None = None):
        from distributed_reinforcement_learning_tpu.runtime.fleet import (
            heartbeat_interval_s)

        self.rank = rank
        self.seats = len(addrs)
        self.sync = sync_mode() if sync is None else sync
        self.merge_steps = merge_steps()
        self.stale_max = stale_max()
        self.collective = HostCollective(rank, addrs)
        self.probe_interval_s = (heartbeat_interval_s()
                                 if probe_interval_s is None
                                 else probe_interval_s)
        # Same missed-beat vocabulary as the fleet supervisor: a peer
        # unreachable for the DEAD window is out of the membership.
        self.dead_after_s = (env_float("DRL_FLEET_DEAD_S",
                                        10.0 * self.probe_interval_s)
                             if dead_after_s is None else dead_after_s)
        self._lock = threading.Lock()
        # Seat 0 starts as publisher (lowest rank of the full roster).
        self._is_pub = (rank == 0)
        self._promote_cb = None
        self._cb_fired = False
        self._epoch_seen = 0
        self._solo_logged = False
        self._misses: dict[int, int] = {}
        self._merge_step = 0
        self._steps_since_merge = 0
        # sender -> (stamp, OUR merge round when first seen): the async
        # per-sender freshness clock (see _maybe_async_merge).
        self._merge_seen: dict[int, tuple[int, int]] = {}
        self._learner = None
        self._plan = None
        self._coll_worker: threading.Thread | None = None
        self._coll_in: queue.Queue = queue.Queue(maxsize=1)
        self._coll_out: queue.Queue = queue.Queue(maxsize=1)
        self._inflight = False
        self.stats = {"rounds": 0, "round_retries": 0, "round_giveups": 0,
                      "promotions": 0, "merge_rounds": 0,
                      "merges_applied": 0, "merges_skipped_stale": 0,
                      "overlap_rounds": 0}
        self._stats_lock = threading.Lock()
        self._stop = threading.Event()
        self._sweeper: threading.Thread | None = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "LearnerTier":
        self.collective.start()
        self._sweeper = threading.Thread(target=self._sweep_loop, daemon=True,
                                         name=f"tier-sweep-{self.rank}")
        self._sweeper.start()
        return self

    def await_peers(self, timeout_s: float = 30.0) -> bool:
        """Bounded startup barrier: wait for every roster peer to answer
        a HELLO (seats start simultaneously but jit-init at different
        speeds). Peers still unreachable past the budget are marked
        dead — the tier STARTS degraded rather than wedging the seat."""
        pending = [r for r in self.collective.membership.live()
                   if r != self.rank]
        deadline = time.monotonic() + timeout_s
        while pending and time.monotonic() < deadline:
            pending = [r for r in pending
                       if not self.collective.probe_peer(r, timeout=1.0)]
            if pending:
                time.sleep(0.2)
        for rank in pending:
            self.collective._note_dead(rank)
        if pending:
            self._check_membership()
        # Plan negotiation rides the HELLOs just exchanged: every live
        # peer has reported its partition-plan hash by now, and a clash
        # is a LOUD refusal (PlanMismatch) — two seats quietly running
        # different layouts/encodings would merge garbage.
        self.collective.check_plan_agreement()
        return not pending

    def close(self) -> None:
        self._stop.set()
        if self._sweeper is not None:
            self._sweeper.join(timeout=2.0)
        if self._coll_worker is not None:
            self._coll_worker.join(timeout=2.0)
        self.collective.close()

    # -- stats -------------------------------------------------------------

    def _bump(self, key: str, by: int = 1) -> None:
        with self._stats_lock:
            self.stats[key] += by

    def stat(self, key: str) -> int:
        with self._stats_lock:
            return self.stats[key]

    def snapshot_stats(self) -> dict:
        with self._stats_lock:
            return dict(self.stats)

    # -- publisher election ------------------------------------------------

    def is_publisher(self) -> bool:
        """True when this seat owns the shared weight plane: the lowest
        LIVE rank (seat 0 until it dies)."""
        live = self.collective.membership.live()
        return bool(live) and min(live) == self.rank

    def publisher_pid(self) -> int | None:
        """The elected publisher seat's pid — the creator of the SHARED
        weight board. Wired into this seat's FleetSupervisor as
        `board_pid_fn`, so members' board reattach probes validate the
        segment against its real creator (None until a HELLO exchange
        proved the publisher's pid; probes then skip pid validation)."""
        live = self.collective.membership.live()
        if not live:
            return None
        leader = min(live)
        if leader == self.rank:
            return os.getpid()
        return self.collective.peer_pid(leader)

    def set_promote_cb(self, cb) -> None:
        """Takeover hook (run_role wires board re-creation here). Fires
        immediately if this seat was ALREADY promoted past its starting
        role (a peer died between start() and wiring)."""
        fire = False
        with self._lock:
            self._promote_cb = cb
            if self._is_pub and self.rank != 0 and not self._cb_fired:
                self._cb_fired = True
                fire = True
        if fire:
            self._fire_promote(cb)

    def _fire_promote(self, cb) -> None:
        import sys

        print(f"[learner_tier] seat {self.rank} promoted to publisher "
              f"(lowest live rank; membership "
              f"{self.collective.membership.live()})", file=sys.stderr)
        self._bump("promotions")
        try:
            cb()
        except Exception as e:  # noqa: BLE001 — promotion must not kill
            print(f"[learner_tier] WARNING: promote callback failed: "  # the seat
                  f"{e!r}", file=sys.stderr)

    def _check_membership(self) -> None:
        """React to an epoch change: publisher re-election + the
        demote-to-solo log line (once)."""
        epoch = self.collective.membership.epoch
        now_pub = self.is_publisher()
        solo = self.collective.membership.solo
        cb = None
        with self._lock:
            if epoch == self._epoch_seen and now_pub == self._is_pub:
                pass
            else:
                self._epoch_seen = epoch
                if (now_pub and not self._is_pub and not self._cb_fired
                        and self._promote_cb is not None):
                    # A promotion with no callback wired yet leaves
                    # _cb_fired False: set_promote_cb fires on arrival.
                    self._cb_fired = True
                    cb = self._promote_cb
                self._is_pub = now_pub
            log_solo = solo and not self._solo_logged and self.seats > 1
            if log_solo:
                self._solo_logged = True
        if cb is not None:
            # (Promotion BEFORE run_role wires the callback is covered
            # by set_promote_cb's fire-on-arrival check.)
            self._fire_promote(cb)
        if log_solo:
            import sys

            print(f"[learner_tier] seat {self.rank} demoted to SOLO "
                  f"(every peer dead) — training and publishing alone",
                  file=sys.stderr)

    # -- liveness sweep ----------------------------------------------------

    def _sweep_loop(self) -> None:
        while not self._stop.wait(self.probe_interval_s):
            self.sweep()

    def sweep(self) -> None:
        """One liveness pass (split from the loop so tests drive it
        deterministically): probe every live peer; consecutive misses
        past the dead window mark it dead (epoch bump) and re-run the
        election."""
        changed = False
        misses_to_dead = max(1, int(round(
            self.dead_after_s / self.probe_interval_s)))
        for peer in self.collective.membership.live():
            if peer == self.rank:
                continue
            if self.collective.probe_peer(peer, timeout=2.0):
                self._misses[peer] = 0
                continue
            self._misses[peer] = self._misses.get(peer, 0) + 1
            if self._misses[peer] >= misses_to_dead:
                self.collective._note_dead(peer)
                changed = True
        if changed or self.collective.membership.epoch != self._epoch_locked():
            self._check_membership()

    def _epoch_locked(self) -> int:
        with self._lock:
            return self._epoch_seen

    # -- learn-step wrap ---------------------------------------------------

    def attach(self, learner) -> None:
        """Wire the tier into a prioritized-replay learner: wrap its
        `_learn` with the collective exchange. `allreduce` needs the
        split learn step — `agent.grads`/`apply_grads` (ApexAgent) on a
        single-device seat, `ShardedLearner.grads`/`apply_grads` (the
        pjit pair) on a mesh-sharded one; `async` wraps any
        `_learn`-shaped learner. Attaching also builds the partition
        plan (`_build_plan`) and pins it into the collective's HELLO
        negotiation — seats with differing plans refuse loudly at
        `await_peers`.

        Host-loop contract under `allreduce`: the collective couples
        the seats' TRAIN cadences, so the driving loop must BOUND how
        many unrolls it ingests per train call (`_learner_loop`'s
        `bounded_drain`) — an unbounded drain-until-empty under actors
        that produce faster than the drain slice starves this seat's
        rounds and stalls every peer mid-round (BSP livelock)."""
        self._learner = learner
        if not hasattr(learner, "_learn"):
            raise ValueError(
                f"{type(learner).__name__} has no `_learn` seam for the "
                f"tier to wrap")
        if getattr(learner, "updates_per_call", 1) > 1:
            if self.sync == "allreduce" or not hasattr(learner,
                                                      "_learn_many"):
                # allreduce needs a host boundary per update; and the
                # replay family's K>1 path (prioritized_train_call ->
                # agent.learn_many) bypasses every wrappable seam, so
                # async would silently never merge there. Forcing K=1
                # is safe for these learners — the K path is chosen per
                # train call: the impala prefetcher RENEGOTIATES its
                # stack depth (PR 13 refused here before the depth
                # became reconfigurable — stale [K, B, ...] stacks are
                # epoch-dropped, never fed to the K==1 learn path), and
                # the replay family's fused device path renegotiates
                # the same way on its next train call
                # (ReplayTrainMixin._device_path_for), degrading to
                # double-buffered H2D only.
                import sys

                pf = getattr(learner, "_prefetcher", None)
                if pf is not None:
                    pf.reconfigure(stack_calls=1)
                print("[learner_tier] WARNING: updates_per_call forced "
                      "to 1 (the tier merges per train step)",
                      file=sys.stderr)
                learner.updates_per_call = 1
            # else: impala-family K>1 under async — _learn_many is
            # wrapped below, K preserved (one merge check per K-step
            # scan call; a prefetcher keeps stacking K).
        if self.sync == "allreduce":
            agent = learner.agent
            sharded = getattr(learner, "_sharded", None)
            if sharded is not None:
                # Mesh-sharded seat: run the split learn step THROUGH
                # the pjit wiring (ShardedLearner.grads/apply_grads, the
                # same in/out shardings as its fused learn) so device
                # sharding is preserved; the host exchange then routes
                # each gradient leaf by its partition class (replicated
                # -> ring, model/expert/pipe -> owner-scoped star) via
                # the plan built below.
                if not (hasattr(sharded, "grads")
                        and hasattr(sharded, "apply_grads")):
                    raise ValueError(
                        "DRL_LEARNER_SYNC=allreduce needs the split "
                        "learn step on the mesh learner "
                        "(ShardedLearner.grads/apply_grads — the "
                        "replay-family (state, batch, is_weight) "
                        "arity); this ShardedLearner lacks it. Use "
                        "DRL_LEARNER_SYNC=async for this family.")
                grads_fn, apply_fn = sharded.grads, sharded.apply_grads
            elif hasattr(agent, "grads") and hasattr(agent, "apply_grads"):
                grads_fn, apply_fn = agent.grads, agent.apply_grads
            else:
                raise ValueError(
                    f"DRL_LEARNER_SYNC=allreduce needs the split learn "
                    f"step (agent.grads/apply_grads — ApexAgent); "
                    f"{type(agent).__name__} lacks it. Use "
                    f"DRL_LEARNER_SYNC=async for this family.")
            self._plan = self._build_plan(learner)
            if self._plan is not None:
                self.collective.set_plan(self._plan)
                if self._plan.overlap and self._coll_worker is None:
                    self._coll_worker = threading.Thread(
                        target=self._coll_loop, daemon=True,
                        name=f"tier-coll-{self.rank}")
                    self._coll_worker.start()
            learner._learn = self._make_allreduce_learn(grads_fn, apply_fn)
        else:
            learner._learn = self._make_async_learn(learner._learn)
            if hasattr(learner, "_learn_many"):
                # The impala-family K>1 scan path trains through
                # _learn_many, never _learn — wrap both so async
                # merging reaches every train call.
                learner._learn_many = self._make_async_learn(
                    learner._learn_many)

    def _build_plan(self, learner):
        """ExchangePlan from the learner's concrete params schema (the
        gradient tree mirrors it leaf-for-leaf), or None — no schema /
        partition gate off — meaning every round rides the legacy
        whole-vector ring. The one-time np.asarray per leaf is the
        deliberate host materialization: the plan needs real
        shapes/sizes, and a mesh learner's params gather once at
        attach, never per round. `tail=1` is the loss float the learn
        wrap rides on the vector's end."""
        if not coll_partition():
            return None
        state = getattr(learner, "state", None)
        params = getattr(state, "params", None)
        if params is None:
            return None
        import jax

        from distributed_reinforcement_learning_tpu.parallel.partition import (
            build_exchange_plan)

        host = jax.tree.map(np.asarray, params)  # drlint: disable=host-sync
        return build_exchange_plan(host, quant=coll_quant(),
                                   overlap=coll_overlap(), tail=1)

    def _merged_rounds(self, vec: np.ndarray) -> np.ndarray:
        """One allreduce with membership-churn retries: an aborted round
        (epoch bump) re-runs over the survivors. Deadline-paced, not
        count-paced: survivors notice a death at different speeds (one
        hits the recv timeout, another gets NAKed immediately), so the
        retries must SPAN the slowest peer's detection latency — a
        fixed attempt count burns out in milliseconds of NAKs and
        strands the seats in different epochs. Past one wait budget of
        churn, this step trains on local gradients (solo fallback for
        the step; the next round re-pairs at (epoch, seq=0)). A
        `PlanMismatch` is NOT retried — mismatched seats must refuse,
        not spin."""
        self._bump("rounds")
        deadline = time.monotonic() + self.collective.wait_s
        while True:
            try:
                t0 = time.perf_counter()
                merged = self.collective.allreduce_mean(vec, plan=self._plan)
                if _OBS.enabled:
                    _OBS.gauge("tier/coll_round_ms",
                               (time.perf_counter() - t0) * 1e3)
                return merged
            except (RoundAborted, PeerLost):
                self._bump("round_retries")
                self._check_membership()
                if self.collective.membership.solo:
                    return vec.astype(np.float32, copy=True)
                if time.monotonic() >= deadline:
                    self._bump("round_giveups")
                    return vec.astype(np.float32, copy=True)
                time.sleep(0.1)  # let the slower survivors re-form

    def _make_allreduce_learn(self, grads_fn, apply_fn):
        overlap = self._plan is not None and self._plan.overlap > 0

        def tier_learn(state, batch, is_weight):
            grads, td, loss = grads_fn(state, batch, is_weight)
            gvec, meta = flatten_tree(grads)
            # Loss rides the vector's tail so the merged metrics carry
            # the tier-mean loss for free (one extra f32).
            vec = np.concatenate([gvec, np.float32([loss]).ravel()])
            if overlap:
                return self._overlap_step(state, vec, meta, loss, td,
                                          apply_fn)
            t0 = time.perf_counter()
            merged = self._merged_rounds(vec)
            if _OBS.enabled:
                _OBS.gauge("tier/round_ms", (time.perf_counter() - t0) * 1e3)
            mgrads = unflatten_tree(merged[:-1], meta)
            state2, metrics = apply_fn(state, mgrads,
                                       np.float32(merged[-1]))
            return state2, td, metrics

        return tier_learn

    # -- backward-overlapped rounds ----------------------------------------

    def _overlap_step(self, state, vec, meta, loc_loss, td, apply_fn):
        """One pipelined learn step: hand THIS round's vector to the
        collective worker, apply the PREVIOUS round's merged gradients
        (already exchanged while this step's backward ran). The first
        call primes the pipeline — nothing merged yet, so the state
        returns unchanged (metrics carry the local loss only) and
        every seat stays bit-identical: only merged vectors, identical
        on every seat, are ever applied. Exchange failures surface
        HERE, on the learn thread, loudly (the worker forwards its
        exception), so a PlanMismatch still refuses instead of
        training on silently-unmerged gradients."""
        prev = None
        if self._inflight:
            t0 = time.perf_counter()
            prev = self._coll_out.get()
            if _OBS.enabled:
                _OBS.gauge("tier/coll_wait_ms",
                           (time.perf_counter() - t0) * 1e3)
            if isinstance(prev, BaseException):
                self._inflight = False
                raise prev
        self._coll_in.put(vec)
        self._inflight = True
        self._bump("overlap_rounds")
        if prev is None:
            return state, td, {"loss": np.float32(loc_loss)}
        state2, metrics = apply_fn(state, unflatten_tree(prev[:-1], meta),
                                   np.float32(prev[-1]))
        return state2, td, metrics

    def _coll_loop(self) -> None:
        """Collective-worker thread: drains one in-flight vector at a
        time through `_merged_rounds` (bounded depth 1 by the
        learn-side credit). Exceptions travel to the learn thread via
        the result slot — the worker never dies silently."""
        while not self._stop.is_set():
            try:
                vec = self._coll_in.get(timeout=0.2)
            except queue.Empty:
                continue
            try:
                self._coll_out.put(self._merged_rounds(vec))
            except BaseException as e:  # noqa: BLE001 — forwarded, re-raised
                self._coll_out.put(e)   # on the learn thread

    def _make_async_learn(self, orig_learn):
        # Signature-agnostic: the learner families' `_learn` arities
        # differ (impala: (state, batch) -> (state, metrics); replay
        # family: (state, batch, is_weight) -> (state, td, metrics)).
        # The tier only touches the leading state.
        def tier_learn(state, *args):
            out = orig_learn(state, *args)
            return (self._maybe_async_merge(out[0]), *out[1:])

        return tier_learn

    def _maybe_async_merge(self, state):
        """Every `merge_steps` train steps: push params, average in the
        peers' FRESH contributions. Bounded staleness is per SENDER
        freshness, not counter alignment: a contribution is dropped
        once its sender has gone more than `stale_max` of OUR merge
        rounds without pushing a NEW stamp — so a slower-but-alive peer
        keeps being averaged (every push refreshes its stamp), while a
        stalled/dead one ages out of the average within the budget.
        (Comparing the seats' local stamp counters directly would
        permanently drop any peer with a sustained train-rate deficit —
        exactly the heterogeneous host async mode exists for.) Opt
        state stays local, the standard async-averaging shape."""
        self._steps_since_merge += 1
        if self._steps_since_merge < self.merge_steps:
            return state
        self._steps_since_merge = 0
        if self.collective.membership.solo:
            return state
        vec, meta = flatten_tree(state.params)
        self._merge_step += 1
        self._bump("merge_rounds")
        t0 = time.perf_counter()
        self.collective.push_merge(vec, self._merge_step)
        self._check_membership()  # a failed push may have re-formed us
        contribs = self.collective.take_merges(min_step=0)
        if _OBS.enabled:
            _OBS.gauge("tier/round_ms", (time.perf_counter() - t0) * 1e3)
        acc = vec.astype(np.float32, copy=True)
        used = 0
        for rank, (step, arr) in sorted(contribs.items()):
            seen = self._merge_seen.get(rank)
            if seen is None or seen[0] != step:
                # A NEW stamp from this sender: record when WE first
                # saw it — the freshness clock for the budget below.
                self._merge_seen[rank] = (step, self._merge_step)
            elif self._merge_step - seen[1] > self.stale_max:
                self._bump("merges_skipped_stale")
                continue  # sender silent past the budget: age it out
            if arr.shape != vec.shape:
                continue  # a peer mid-restart with a different policy
            acc += arr
            used += 1
        if not used:
            return state
        merged = acc / np.float32(1 + used)
        self._bump("merges_applied")
        return state.replace(params=unflatten_tree(merged, meta))


def build_tier() -> LearnerTier | None:
    """run_role wiring: a LearnerTier when the launcher exported a seat
    identity (`DRL_LEARNER_RANK` + `DRL_LEARNER_PEERS`, seats >= 2),
    else None — the pre-tier single-learner path, untouched."""
    rank_env = os.environ.get("DRL_LEARNER_RANK", "").strip()
    peers_env = os.environ.get("DRL_LEARNER_PEERS", "").strip()
    if not rank_env or not peers_env:
        return None
    addrs = [a for a in peers_env.split(",") if a]
    if len(addrs) < 2:
        return None
    rank = int(rank_env)
    return LearnerTier(rank, addrs)


def register_telemetry(tier: LearnerTier) -> None:
    """Tier counters/gauges on the seat's telemetry shard (the
    obs_report 'Learner tier' section reads these names)."""
    _OBS.sample("tier/publisher", lambda: int(tier.is_publisher()))
    _OBS.sample("tier/live_seats",
                lambda: len(tier.collective.membership.live()))
    for key in tier.snapshot_stats():
        _OBS.sample(f"tier/{key}", lambda k=key: tier.stat(k),
                    kind="counter")
    for key in tier.collective.snapshot_stats():
        _OBS.sample(f"tier/{key}",
                    lambda k=key: tier.collective.stat(k), kind="counter")

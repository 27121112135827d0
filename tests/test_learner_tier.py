"""Sharded learner tier (runtime/learner_tier.py + parallel/collective.py).

The acceptance pins of ISSUE 14:

- collective round-trip BIT-IDENTITY: every seat of a ring allreduce
  ends with the same bytes, equal to the mean;
- membership-epoch abort of stale rounds (a NAK from a re-formed peer,
  an epoch bump under an in-flight wait);
- the EQUIVALENCE pin: N=2 seats under `allreduce` produce merged
  gradients numerically equal to a single learner training on the
  union batch (pinned rtol/atol — XLA-CPU evaluates the union batch's
  mean in a different reduction order than (mean_half0 + mean_half1)/2,
  the same batch-shape-dependent float noise the apex-ingest pin
  documents; measured max |Δ| ~1.5e-8 on the gradient vector);
- async mode: bounded staleness (contributions older than the budget
  are dropped) and loss-free priority writeback routing across seats
  (each seat samples from and writes back to its OWN shards — zero
  cross-seat updates, zero drops);
- publisher re-election and demote-to-solo when all peers die;
- a TWO-PROCESS e2e worker (tests/learner_seat_worker.py), including a
  mid-round hard death the survivor must ride out solo.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.parallel.collective import (
    HostCollective,
    Membership,
    PeerLost,
    RoundAborted,
)
from distributed_reinforcement_learning_tpu.runtime import learner_tier
from distributed_reinforcement_learning_tpu.runtime.learner_tier import (
    LearnerTier,
    flatten_tree,
    unflatten_tree,
)

REPO = Path(__file__).parent.parent


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _addrs(n: int) -> list[str]:
    return [f"127.0.0.1:{_free_port()}" for _ in range(n)]


def _collectives(n: int, wait_s: float = 5.0) -> list[HostCollective]:
    addrs = _addrs(n)
    return [HostCollective(r, addrs, wait_s=wait_s).start()
            for r in range(n)]


def _run_threads(fns, timeout: float = 30.0):
    out = [None] * len(fns)
    errs = [None] * len(fns)

    def wrap(i):
        try:
            out[i] = fns[i]()
        except Exception as e:  # noqa: BLE001 — surfaced by the assert
            errs[i] = e

    threads = [threading.Thread(target=wrap, args=(i,))
               for i in range(len(fns))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    assert not any(t.is_alive() for t in threads), "a seat thread wedged"
    assert all(e is None for e in errs), errs
    return out


class TestMembership:
    def test_epoch_bumps_only_on_live_removal(self):
        m = Membership(range(3), rank=0)
        assert m.live() == [0, 1, 2] and m.epoch == 0
        assert m.mark_dead(2) is True
        assert m.epoch == 1 and m.live() == [0, 1]
        assert m.mark_dead(2) is False  # already dead: no bump
        assert m.epoch == 1

    def test_own_rank_never_dies(self):
        m = Membership(range(2), rank=0)
        assert m.mark_dead(0) is False
        assert m.live() == [0, 1]

    def test_solo_and_snapshot_coherence(self):
        m = Membership(range(2), rank=1)
        assert not m.solo
        m.mark_dead(0)
        assert m.solo
        live, epoch = m.snapshot()
        assert live == [1] and epoch == 1

    def test_own_rank_must_be_in_roster(self):
        with pytest.raises(ValueError):
            Membership([0, 1], rank=5)


class TestFlattenTree:
    def test_round_trip_shapes_and_dtypes(self):
        tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
                "b": {"c": np.float64([1.5, 2.5]),
                      "d": np.int32([[7]])}}
        vec, meta = flatten_tree(tree)
        assert vec.dtype == np.float32 and vec.shape == (9,)
        back = unflatten_tree(vec, meta)
        assert back["b"]["c"].dtype == np.float64
        assert back["b"]["d"].dtype == np.int32
        np.testing.assert_allclose(back["a"], tree["a"])
        np.testing.assert_allclose(back["b"]["c"], tree["b"]["c"])

    def test_length_mismatch_raises(self):
        vec, meta = flatten_tree({"a": np.zeros(4, np.float32)})
        with pytest.raises(ValueError):
            unflatten_tree(np.zeros(5, np.float32), meta)


class TestCollective:
    def test_allreduce_bit_identity_across_seats(self):
        """Every seat ends with the SAME bytes == the mean — for a ring
        of 2 and of 3 (chunked reduce-scatter + allgather)."""
        for n in (2, 3):
            colls = _collectives(n)
            try:
                vecs = [np.arange(23, dtype=np.float32) * (r + 1) + 0.25
                        for r in range(n)]
                out = _run_threads(
                    [lambda r=r: colls[r].allreduce_mean(vecs[r])
                     for r in range(n)])
                want = np.sum(vecs, axis=0, dtype=np.float32) / np.float32(n)
                for r in range(n):
                    np.testing.assert_array_equal(out[r], out[0])
                np.testing.assert_allclose(out[0], want, rtol=1e-6)
            finally:
                for c in colls:
                    c.close()

    def test_round_seq_advances_across_rounds(self):
        colls = _collectives(2)
        try:
            for _ in range(3):  # three back-to-back rounds must pair up
                vecs = [np.random.RandomState(7).rand(8).astype(np.float32),
                        np.random.RandomState(8).rand(8).astype(np.float32)]
                out = _run_threads(
                    [lambda r=r: colls[r].allreduce_mean(vecs[r])
                     for r in range(2)])
                np.testing.assert_array_equal(out[0], out[1])
            assert colls[0].stat("rounds_ok") == 3
        finally:
            for c in colls:
                c.close()

    def test_nak_from_reformed_peer_aborts_round(self):
        """Seat 1 re-formed without seat 0 (epoch skew): seat 0's next
        PART is NAKed and the round aborts instead of wedging."""
        colls = _collectives(2)
        try:
            colls[1].membership.mark_dead(0)  # seat 1 dropped seat 0
            with pytest.raises(RoundAborted):
                colls[0].allreduce_mean(np.ones(8, np.float32))
        finally:
            for c in colls:
                c.close()

    def test_epoch_bump_under_inflight_wait_aborts(self):
        """An epoch bump while a seat waits for a chunk aborts the
        round promptly (no timeout wait-out)."""
        colls = _collectives(3, wait_s=30.0)
        try:
            def seat0():
                return colls[0].allreduce_mean(np.ones(9, np.float32))

            t = threading.Thread(target=lambda: _swallow(seat0))
            t0 = time.monotonic()
            t.start()
            time.sleep(0.3)  # seat 0 is now parked waiting on seat 2
            colls[0]._note_dead(2)
            t.join(10.0)
            assert not t.is_alive()
            assert time.monotonic() - t0 < 10.0  # well under wait_s
        finally:
            for c in colls:
                c.close()

    def test_dead_peer_detected_and_membership_reforms(self):
        colls = _collectives(2, wait_s=1.0)
        try:
            colls[1].close()
            with pytest.raises((PeerLost, RoundAborted)):
                colls[0].allreduce_mean(np.ones(8, np.float32))
            assert colls[0].membership.solo
            # Demote-to-solo: the next round is the mean of one.
            out = colls[0].allreduce_mean(np.arange(8, dtype=np.float32))
            np.testing.assert_array_equal(out,
                                          np.arange(8, dtype=np.float32))
            assert colls[0].stat("solo_rounds") == 1
        finally:
            colls[0].close()

    def test_async_merge_latest_wins_and_staleness_filter(self):
        colls = _collectives(2)
        try:
            v5 = np.full(4, 5.0, np.float32)
            v9 = np.full(4, 9.0, np.float32)
            assert colls[0].push_merge(v5, step=5) == 1
            assert colls[0].push_merge(v9, step=9) == 1  # overwrites
            got = colls[1].take_merges(min_step=9)
            assert list(got) == [0]
            step, arr = got[0]
            assert step == 9
            np.testing.assert_array_equal(arr, v9)
            # Bounded staleness: a higher floor drops it.
            assert colls[1].take_merges(min_step=10) == {}
        finally:
            for c in colls:
                c.close()

    def test_merge_from_dropped_sender_naks(self):
        colls = _collectives(2)
        try:
            colls[1].membership.mark_dead(0)
            assert colls[0].push_merge(np.ones(4, np.float32), step=1) == 0
            assert colls[1].take_merges(min_step=0) == {}
        finally:
            for c in colls:
                c.close()

    def test_probe_reports_peer_pid_and_membership_view(self):
        colls = _collectives(2)
        try:
            assert colls[0].probe_peer(1) is True
            assert colls[0].peer_pid(1) == colls[1].peer_pid(0)  # same proc
            colls[1].membership.mark_dead(0)
            # The peer dropped US: its hello answers accepted=False.
            assert colls[0].probe_peer(1) is False
        finally:
            for c in colls:
                c.close()


def _swallow(fn):
    try:
        return fn()
    except (RoundAborted, PeerLost):
        return None


def _apex_fixture(obs_dim: int = 12, b: int = 16):
    from distributed_reinforcement_learning_tpu.agents.apex import (
        ApexAgent, ApexBatch, ApexConfig)
    import jax

    agent = ApexAgent(ApexConfig(obs_shape=(obs_dim,), num_actions=3))
    rng = np.random.RandomState(0)
    union = ApexBatch(
        state=rng.rand(2 * b, obs_dim).astype(np.float32),
        next_state=rng.rand(2 * b, obs_dim).astype(np.float32),
        previous_action=rng.randint(0, 3, 2 * b).astype(np.int32),
        action=rng.randint(0, 3, 2 * b).astype(np.int32),
        reward=rng.randn(2 * b).astype(np.float32),
        done=(rng.rand(2 * b) < 0.1))
    halves = [jax.tree.map(lambda x: x[:b], union),
              jax.tree.map(lambda x: x[b:], union)]
    isw = np.ones(2 * b, np.float32)
    state = agent.sync_target(agent.init_state(jax.random.PRNGKey(0)))
    return agent, state, union, halves, isw


class TestAllreduceEquivalence:
    """THE equivalence pin: N=2 seats with `allreduce` sync == a single
    learner on the union batch. Gradient-level equality is pinned tight
    (pure reduction-order noise: the union mean vs the mean of the two
    half-batch means — XLA-CPU's batch-size-dependent reduction order,
    same class as the documented apex-ingest rtol pin). Params after K
    steps are pinned looser: Adam's per-element normalization amplifies
    the epsilon-level gradient noise."""

    def test_merged_gradients_equal_union_batch(self):
        import jax

        agent, state, union, halves, isw = _apex_fixture()
        b = len(isw) // 2
        gu, _, lu = agent.grads(state, union, isw)
        vu, _ = flatten_tree(gu)
        colls = _collectives(2)
        try:
            parts = []
            for r in range(2):
                g, _, loss = agent.grads(state, halves[r], isw[:b])
                v, _ = flatten_tree(g)
                parts.append(np.concatenate([v, np.float32([loss]).ravel()]))
            out = _run_threads(
                [lambda r=r: colls[r].allreduce_mean(parts[r])
                 for r in range(2)])
            np.testing.assert_array_equal(out[0], out[1])  # bit-identical
            # Pinned tolerance: measured max |Δ| ~1.5e-8 on this vector.
            np.testing.assert_allclose(out[0][:-1], vu, rtol=1e-4, atol=1e-6)
            np.testing.assert_allclose(out[0][-1], float(lu), rtol=1e-5)
            del jax
        finally:
            for c in colls:
                c.close()

    def test_tiered_seats_track_union_learner(self):
        """Three tier-wrapped steps on each half-batch: the two seats'
        params stay BIT-IDENTICAL to each other and within the pinned
        tolerance of the union-batch learner (measured max relative
        diff ~6.5e-5 after 3 Adam steps)."""
        import jax

        agent, state0, union, halves, isw = _apex_fixture()
        b = len(isw) // 2
        s = state0
        for _ in range(3):
            s, _, _ = agent.learn(s, union, isw)
        union_params = jax.tree.map(np.asarray, s.params)

        addrs = _addrs(2)
        tiers = [LearnerTier(r, addrs, sync="allreduce",
                             probe_interval_s=60.0) for r in range(2)]
        for t in tiers:
            t.collective.wait_s = 20.0
            t.start()
        try:
            fns = [t._make_allreduce_learn(agent.grads, agent.apply_grads)
                   for t in tiers]
            states = [agent.sync_target(
                agent.init_state(jax.random.PRNGKey(0))) for _ in range(2)]

            def seat(r):
                st = states[r]
                for _ in range(3):
                    st, _, _ = fns[r](st, halves[r], isw[:b])
                return st

            res = _run_threads([lambda r=r: seat(r) for r in range(2)],
                               timeout=120.0)
            p0 = jax.tree.map(np.asarray, res[0].params)
            p1 = jax.tree.map(np.asarray, res[1].params)
            jax.tree.map(
                lambda a, c: np.testing.assert_array_equal(a, c), p0, p1)
            jax.tree.map(
                lambda a, c: np.testing.assert_allclose(
                    a, c, rtol=1e-3, atol=1e-6), p0, union_params)
        finally:
            for t in tiers:
                t.close()

    @pytest.mark.skipif(
        os.environ.get("DRL_SANITIZE") == "1"
        and os.environ.get("DRL_RUN_SANITIZE_MESH") != "1",
        reason="sanitized lock factories make two THREADS of pjit-mesh "
               "dispatch pathologically slow inside jax internals (both "
               "seats park in grads_fn, the collective idle — verified "
               "by faulthandler stacks); the tier's own concurrency "
               "surface is sanitized by every other suite test. "
               "DRL_RUN_SANITIZE_MESH=1 forces.")
    def test_mesh_seats_track_union_pjit_learner(self, monkeypatch):
        """The tentpole's positive mesh contract (replacing the old
        attach-time refusal): a mesh-sharded seat (ShardedLearner at
        model_parallel=2) ATTACHES under allreduce, the negotiated plan
        carries a model-sharded class, and three tier-wrapped steps on
        each half-batch keep the two seats bit-identical to each other
        and within the documented tolerance of the UNION-BATCH
        single-device learner (rtol 1e-3 / atol 1e-6 after 3 Adam steps
        — the same pin as the single-device tier)."""
        import jax

        from distributed_reinforcement_learning_tpu.parallel import (
            ShardedLearner, make_mesh)
        monkeypatch.setenv("DRL_COLL_PARTITION", "1")
        monkeypatch.setenv("DRL_COLL_QUANT", "f32")
        monkeypatch.setenv("DRL_COLL_OVERLAP", "0")

        agent, _, union, halves, isw = _apex_fixture()
        mesh = make_mesh(8, model_parallel=2)
        sl = ShardedLearner(agent, mesh, num_data_args=2, num_aux_outputs=2)
        b = len(isw) // 2

        def fresh_state():
            return sl.place_state(agent.sync_target(
                agent.init_state(jax.random.PRNGKey(0))))

        # The reference is the plain SINGLE-DEVICE learner on the union
        # batch: the mesh layout and the tier's exchange both have to
        # reproduce it.
        s = agent.sync_target(agent.init_state(jax.random.PRNGKey(0)))
        for _ in range(3):
            s, _, _ = agent.learn(s, union, isw)
        union_params = jax.tree.map(np.asarray, s.params)

        class MeshSeat:
            def __init__(self):
                self.agent = agent
                self._sharded = sl
                self.state = fresh_state()
                self._learn = agent._learn  # seam attach() rebinds

        addrs = _addrs(2)
        tiers = [LearnerTier(r, addrs, sync="allreduce",
                             probe_interval_s=60.0) for r in range(2)]
        seats = [MeshSeat() for _ in range(2)]
        for t, l in zip(tiers, seats):
            t.collective.wait_s = 20.0
            t.start()
            t.attach(l)
        try:
            # The negotiated plan: same hash on both seats, and the
            # model-sharded gradient class is in it.
            assert tiers[0]._plan is not None
            assert tiers[0]._plan.plan_hash == tiers[1]._plan.plan_hash
            assert "-,model" in tiers[0]._plan.classes
            for t in tiers:
                assert t.await_peers(20.0)

            def seat(r):
                l = seats[r]
                st = l.state
                for _ in range(3):
                    st, _, _ = l._learn(
                        st, *sl.shard_batch((halves[r], isw[:b])))
                return st

            res = _run_threads([lambda r=r: seat(r) for r in range(2)],
                               timeout=120.0)
            p0 = jax.tree.map(np.asarray, res[0].params)
            p1 = jax.tree.map(np.asarray, res[1].params)
            jax.tree.map(
                lambda a, c: np.testing.assert_array_equal(a, c), p0, p1)
            jax.tree.map(
                lambda a, c: np.testing.assert_allclose(
                    a, c, rtol=1e-3, atol=1e-6), p0, union_params)
            # The sharded class really went owner-scoped, not ring.
            assert tiers[0].collective.stat("coll_rounds_part") == 3
            assert tiers[0].collective.stat("coll_bytes_model") > 0
        finally:
            for t in tiers:
                t.close()


class TestLearnerTier:
    def test_publisher_reelection_and_demote_to_solo(self):
        addrs = _addrs(2)
        tiers = [LearnerTier(r, addrs, sync="allreduce",
                             probe_interval_s=0.25, dead_after_s=0.5)
                 for r in range(2)]
        for t in tiers:
            t.collective.wait_s = 2.0
            t.start()
        try:
            assert tiers[0].is_publisher() and not tiers[1].is_publisher()
            fired = []
            tiers[1].set_promote_cb(lambda: fired.append(True))
            tiers[0].close()
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline and not fired:
                tiers[1].sweep()
                time.sleep(0.1)
            assert fired, "promote callback never fired"
            assert tiers[1].is_publisher()
            assert tiers[1].collective.membership.solo
            assert tiers[1].stat("promotions") == 1
            # Solo allreduce = local grads; the tier keeps training.
            out = tiers[1]._merged_rounds(np.arange(4, dtype=np.float32))
            np.testing.assert_array_equal(out,
                                          np.arange(4, dtype=np.float32))
        finally:
            tiers[1].close()

    def test_promote_cb_fires_on_arrival_after_promotion(self):
        """Promotion BEFORE run_role wires the callback must not be
        lost: set_promote_cb fires immediately."""
        addrs = _addrs(2)
        tier = LearnerTier(1, addrs, sync="allreduce",
                           probe_interval_s=60.0)
        tier.start()
        try:
            tier.collective._note_dead(0)
            tier._check_membership()
            assert tier.is_publisher()
            fired = []
            tier.set_promote_cb(lambda: fired.append(True))
            assert fired, "fire-on-arrival missed the earlier promotion"
        finally:
            tier.close()

    def test_async_merge_bounded_staleness_pin(self):
        """Async mode drops contributions staler than the budget and
        averages in fresh ones (IMPACT-style bounded staleness)."""
        import jax
        import jax.numpy as jnp
        from flax import struct

        @struct.dataclass
        class S:
            params: dict

        addrs = _addrs(2)
        tiers = [LearnerTier(r, addrs, sync="async", probe_interval_s=60.0)
                 for r in range(2)]
        for t in tiers:
            t.merge_steps = 1
            t.stale_max = 2
            t.start()
        try:
            state = S(params={"w": jnp.ones(4, jnp.float32)})
            # Peer pushes a FRESH contribution (step matches ours + 1).
            peer_vec = np.full(4, 3.0, np.float32)
            tiers[1]._merge_step = 0
            assert tiers[1].collective.push_merge(peer_vec, step=1) == 1
            merged = tiers[0]._maybe_async_merge(state)
            np.testing.assert_allclose(np.asarray(merged.params["w"]),
                                       np.full(4, 2.0, np.float32))
            assert tiers[0].stat("merges_applied") == 1
            # A STALE contribution (the sender hasn't pushed a NEW
            # stamp within stale_max of OUR merge rounds) is dropped:
            # the params stay put.
            tiers[0]._merge_step = 10
            merged2 = tiers[0]._maybe_async_merge(merged)
            np.testing.assert_allclose(np.asarray(merged2.params["w"]),
                                       np.asarray(merged.params["w"]))
            assert tiers[0].stat("merges_skipped_stale") >= 1
            # Freshness is per SENDER, not counter alignment: a NEW
            # push re-includes the peer even though its own stamp
            # counter (2) lags ours (11) far beyond stale_max — the
            # slower-but-alive heterogeneous seat async mode exists
            # for must never be dropped permanently.
            assert tiers[1].collective.push_merge(
                np.full(4, 5.0, np.float32), step=2) == 1
            applied_before = tiers[0].stat("merges_applied")
            merged3 = tiers[0]._maybe_async_merge(merged2)
            assert tiers[0].stat("merges_applied") == applied_before + 1
            np.testing.assert_allclose(
                np.asarray(merged3.params["w"]),
                (np.asarray(merged2.params["w"]) + 5.0) / 2.0)
            del jax
        finally:
            for t in tiers:
                t.close()

    def test_priority_writeback_routes_to_own_seat_loss_free(self):
        """Each seat samples from its OWN replay service and writes
        priorities back to it — across a 2-seat tiered train step, every
        enqueued update lands on the sampling seat's shards (loss-free,
        zero cross-seat routing)."""
        import jax
        from distributed_reinforcement_learning_tpu.agents.apex import (
            ApexAgent, ApexBatch, ApexConfig)
        from distributed_reinforcement_learning_tpu.data.fifo import (
            TrajectoryQueue)
        from distributed_reinforcement_learning_tpu.data.replay_service import (
            ShardedReplayService)
        from distributed_reinforcement_learning_tpu.runtime import apex_runner
        from distributed_reinforcement_learning_tpu.runtime.weights import (
            WeightStore)

        agent = ApexAgent(ApexConfig(obs_shape=(8,), num_actions=2))
        addrs = _addrs(2)
        tiers, learners, services = [], [], []
        rng = np.random.RandomState(3)
        for r in range(2):
            svc = ShardedReplayService(2, 2048, mode="transition",
                                       scorer="max", seed=r)
            learner = apex_runner.ApexLearner(
                agent, TrajectoryQueue(8), WeightStore(), batch_size=16,
                replay_capacity=2048, train_start_unrolls=1,
                rng=jax.random.PRNGKey(r), replay_service=svc)
            tier = LearnerTier(r, addrs, sync="allreduce",
                               probe_interval_s=60.0)
            tier.collective.wait_s = 20.0
            tier.start()
            tier.attach(learner)
            for shard in svc.shards:
                shard.ingest(ApexBatch(
                    state=rng.rand(32, 8).astype(np.float32),
                    next_state=rng.rand(32, 8).astype(np.float32),
                    previous_action=rng.randint(0, 2, 32).astype(np.int32),
                    action=rng.randint(0, 2, 32).astype(np.int32),
                    reward=rng.randn(32).astype(np.float32),
                    done=(rng.rand(32) < 0.1)))
            learner.ingested_unrolls = 4  # past the warm gate
            tiers.append(tier)
            learners.append(learner)
            services.append(svc)
        try:
            def train(r):
                for _ in range(2):
                    assert learners[r].train() is not None
                assert services[r].flush_updates(timeout=10.0)
                return sum(s.stats()["updates_applied"]
                           for s in services[r].shards)

            applied = _run_threads([lambda r=r: train(r) for r in range(2)],
                                   timeout=180.0)
            # 2 train calls x batch 16 = 32 priority updates per seat,
            # every one applied on the seat that sampled it.
            assert applied == [32, 32]
        finally:
            for t in tiers:
                t.close()
            for lrn in learners:
                lrn.close()
            for svc in services:
                svc.close()

    def test_board_pid_probe_context_tri_state(self):
        """The heartbeat reply's board_pid contract (the shared tier
        board's creator is the PUBLISHER seat): absent -> inherit the
        learner's pid (non-tier: learner == creator); explicit 0 ->
        publisher unknown, probes must SKIP pid validation — never
        validate the shared board against the member's own seat pid
        and burn the reattach ladder on a healthy board."""
        from distributed_reinforcement_learning_tpu.runtime.fleet import (
            FleetSupervisor, ProbeContext)

        assert ProbeContext(learner_pid=5).board_pid == 5
        assert ProbeContext(learner_pid=5, board_pid=7).board_pid == 7
        assert ProbeContext(learner_pid=5, board_pid=0).board_pid is None
        assert ProbeContext().board_pid is None
        # Supervisor side: a tier whose publisher pid is unresolved
        # replies the explicit-unknown 0, never omits the field.
        sup = FleetSupervisor(heartbeat_s=60.0, board_pid_fn=lambda: None)
        reply = sup.register({"role": "actor", "rank": 0, "pid": 1})
        assert reply["board_pid"] == 0
        sup2 = FleetSupervisor(heartbeat_s=60.0, board_pid_fn=lambda: 42)
        assert sup2.register({"role": "actor", "rank": 0,
                              "pid": 1})["board_pid"] == 42
        sup3 = FleetSupervisor(heartbeat_s=60.0)  # non-tier: no field
        assert "board_pid" not in sup3.register({"role": "actor",
                                                 "rank": 0, "pid": 1})

    def test_attach_contract(self):
        """allreduce needs the split learn step; updates_per_call is
        forced to 1; a learner without `_learn` is rejected; a
        mesh-sharded learner attaches through its ShardedLearner's
        pjit grads/apply_grads pair — and is refused ONLY when that
        split seam is missing (the non-replay arity)."""
        addrs = _addrs(2)
        tier = LearnerTier(0, addrs, sync="allreduce", probe_interval_s=60.0)

        class NoSeam:
            agent = object()

        with pytest.raises(ValueError, match="_learn"):
            tier.attach(NoSeam())

        class NoSplit:
            _learn = staticmethod(lambda *a: a)
            agent = object()  # no grads/apply_grads

        with pytest.raises(ValueError, match="allreduce"):
            tier.attach(NoSplit())

        class MeshyNoSplit:
            class agent:  # noqa: N801 — stub
                grads = apply_grads = staticmethod(lambda *a: a)

            _learn = staticmethod(lambda *a: a)
            _sharded = object()  # ShardedLearner WITHOUT grads/apply_grads

        with pytest.raises(ValueError, match="ShardedLearner"):
            tier.attach(MeshyNoSplit())

        class ShardedStub:  # the pjit split seam, as parallel/learner builds it
            grads = staticmethod(lambda *a: a)
            apply_grads = staticmethod(lambda *a: a)

        class Meshy:
            agent = object()  # the tier must NOT fall back to the agent
            _learn = staticmethod(lambda *a: a)
            _sharded = ShardedStub()

        m = Meshy()
        tier.attach(m)  # positive contract: mesh seat attaches
        assert m._learn is not Meshy._learn  # wrapped

        class K8:
            class agent:  # noqa: N801 — stub
                grads = apply_grads = staticmethod(lambda *a: a)

            _learn = staticmethod(lambda *a: a)
            updates_per_call = 8

        k8 = K8()
        tier.attach(k8)
        assert k8.updates_per_call == 1

        class FakePrefetcher:
            stack_calls = 8
            reconfigured_to = None

            def reconfigure(self, stack_calls):
                self.reconfigured_to = stack_calls

        class K8Prefetching(K8):
            updates_per_call = 8  # class attr rebinding per instance

        k8p = K8Prefetching()
        k8p._prefetcher = FakePrefetcher()
        # PR 13 REFUSED this shape (flipping the counter would feed the
        # constructed [K, B, ...] stack into the K==1 learn path); the
        # reconfigurable stack depth makes attach negotiate instead.
        tier.attach(k8p)
        assert k8p.updates_per_call == 1
        assert k8p._prefetcher.reconfigured_to == 1
        tier.close()

    def test_build_tier_env_resolution(self, monkeypatch):
        monkeypatch.delenv("DRL_LEARNER_RANK", raising=False)
        monkeypatch.delenv("DRL_LEARNER_PEERS", raising=False)
        assert learner_tier.build_tier() is None
        monkeypatch.setenv("DRL_LEARNER_RANK", "1")
        monkeypatch.setenv("DRL_LEARNER_PEERS",
                           "127.0.0.1:1,127.0.0.1:2,127.0.0.1:3")
        tier = learner_tier.build_tier()
        assert tier is not None and tier.rank == 1 and tier.seats == 3
        monkeypatch.setenv("DRL_LEARNER_PEERS", "127.0.0.1:1")
        assert learner_tier.build_tier() is None  # one seat = no tier

    def test_seat_count_and_sync_gates(self, monkeypatch):
        monkeypatch.delenv("DRL_LEARNER_SEATS", raising=False)
        assert learner_tier.seat_count() == 0  # no tier by default
        monkeypatch.setenv("DRL_LEARNER_SEATS", "3")
        assert learner_tier.seat_count() == 3
        monkeypatch.setenv("DRL_LEARNER_SEATS", "-2")
        assert learner_tier.seat_count() == 0
        monkeypatch.setenv("DRL_LEARNER_SEATS", "two")
        with pytest.raises(ValueError, match="DRL_LEARNER_SEATS"):
            learner_tier.seat_count()
        monkeypatch.delenv("DRL_LEARNER_SYNC", raising=False)
        assert learner_tier.sync_mode() == "allreduce"
        monkeypatch.setenv("DRL_LEARNER_SYNC", "async")
        assert learner_tier.sync_mode() == "async"
        monkeypatch.setenv("DRL_LEARNER_SYNC", "bogus")
        with pytest.raises(ValueError):
            learner_tier.sync_mode()


class TestTwoProcessE2E:
    """Real two-process seats over tests/learner_seat_worker.py."""

    def _spawn(self, rank, peers, rounds, mode):
        import os

        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": str(REPO)}
        return subprocess.Popen(
            [sys.executable, str(REPO / "tests" / "learner_seat_worker.py"),
             str(rank), peers, str(rounds), mode],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)

    def _result(self, proc, timeout=120):
        out, err = proc.communicate(timeout=timeout)
        line = next((ln for ln in out.splitlines()
                     if ln.startswith("SEAT_OUT=")), None)
        return line, out, err

    def test_two_process_allreduce_bit_identity(self):
        peers = ",".join(_addrs(2))
        procs = [self._spawn(r, peers, 3, "ok") for r in range(2)]
        results = []
        for proc in procs:
            line, out, err = self._result(proc)
            assert proc.returncode == 0, err[-800:]
            assert line is not None, out + err[-400:]
            results.append(json.loads(line.split("=", 1)[1]))
        # The merged vectors are BIT-IDENTICAL across the two processes
        # in every round (crc over the raw bytes).
        for a, b in zip(results[0]["rounds"], results[1]["rounds"]):
            assert a["crc"] == b["crc"] and a["head"] == b["head"]
        assert results[0]["publisher"] and not results[1]["publisher"]
        assert all(not r["solo"] for r in results[0]["rounds"])

    def test_two_process_mid_round_death_survivor_goes_solo(self):
        """Seat 0 hard-exits after round 0; seat 1 must finish its
        remaining rounds solo (never wedge) and end up publisher."""
        peers = ",".join(_addrs(2))
        procs = [self._spawn(r, peers, 3, "die") for r in range(2)]
        line0, _, _ = self._result(procs[0], timeout=120)
        assert procs[0].returncode == 17  # the scripted hard death
        line1, out1, err1 = self._result(procs[1], timeout=180)
        assert procs[1].returncode == 0, err1[-800:]
        assert line1 is not None, out1 + err1[-400:]
        res = json.loads(line1.split("=", 1)[1])
        assert res["rounds"][-1]["solo"] is True
        assert res["publisher"] is True
        assert res["coll"]["peer_deaths"] == 1

    def test_seat_drill_kill_one_of_two_learners(self):
        """The kill-ONE-OF-N-learners drill (tests/seat_drill.py):
        SIGKILL the publisher seat of a real 2-seat tier mid-run — the
        survivor re-forms the collective solo, takes over publication
        (board re-created under the same name; its actor observes
        post-kill versions through the reattached board), and every
        landed trajectory still crc-verifies."""
        from seat_drill import seat_drill

        r = seat_drill(secs=16.0, steps=4, obs_dim=8,
                       repromote_deadline_s=12.0)
        assert r["corrupt"] == 0 and r["verified"] > 0, r
        assert r["survivor_solo"] and r["survivor_publisher"], r
        assert r["reelected_s"] is not None \
            and r["reelected_s"] <= r["repromote_deadline_s"], r
        assert r["post_kill_versions_observed"] >= 1, r
        assert r["survivor_board_reattaches"] >= 1, r
        assert r["pass"] is True

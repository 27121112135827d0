"""Sharded replay service (data/replay_service.py + runtime/replay_shard.py).

Pins the contracts the ISSUE demands: shard-index packing round trips,
proportional batch allocation, merged IS-weight semantics identical to
the monolithic backend's, sampling-DISTRIBUTION equivalence against
monolithic replay (chi-square over priorities), bit-identical trajectory
contents through real TCP and shm-ring drainers (two-process), async
priority-update routing (incl. the K-update writeback path), shard-death
demote-to-monolithic fallback, and the DRL_REPLAY_SHARDS gate
resolution (tests/test_gate_defaults.py pins the default).

All CPU-only, tier-1 safe.
"""

import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.data import codec
from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue
from distributed_reinforcement_learning_tpu.data.replay import (
    _is_weights,
    make_replay,
)
from distributed_reinforcement_learning_tpu.data.replay_service import (
    ReplayShard,
    ShardedReplayService,
    allocate_proportional,
    is_packed_index,
    merge_is_weights,
    pack_index,
    td_proxy_scorer,
    unpack_index,
)
from distributed_reinforcement_learning_tpu.runtime.replay_shard import (
    ReplayIngestFifo,
    shard_count,
)

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tests"))
from shm_ring_worker import make_trajectories  # noqa: E402
from test_shm_ring import assert_trees_bit_identical  # noqa: E402


def make_apex_unrolls(seed: int, count: int, steps: int = 32):
    from distributed_reinforcement_learning_tpu.agents.apex import ApexBatch

    rng = np.random.RandomState(seed)
    out = []
    for _ in range(count):
        out.append(ApexBatch(
            state=rng.rand(steps, 4).astype(np.float32),
            next_state=rng.rand(steps, 4).astype(np.float32),
            previous_action=rng.randint(0, 2, steps).astype(np.int32),
            action=rng.randint(0, 2, steps).astype(np.int32),
            reward=rng.randn(steps).astype(np.float32),
            done=(rng.rand(steps) < 0.1),
        ))
    return out


class TestIndexPacking:
    def test_round_trip_vectorized_and_extremes(self):
        idxs = np.array([0, 1, 5, (1 << 46) - 1], np.int64)
        for shard, epoch in [(0, 0), (7, 3), (255, 255)]:
            packed = pack_index(shard, epoch, idxs)
            s, e, i = unpack_index(packed)
            assert (s == shard).all() and (e == epoch).all()
            np.testing.assert_array_equal(i, idxs)
            assert is_packed_index(packed).all()

    def test_plain_tree_indexes_are_never_tagged(self):
        # Monolithic tree idxs are < 2*capacity — far below the tag bit —
        # so a post-demotion learner can split a mixed batch safely.
        plain = np.arange(0, 2_000_000, 97, dtype=np.int64)
        assert not is_packed_index(plain).any()

    def test_packed_fits_int64_positive(self):
        packed = pack_index(255, 255, (1 << 46) - 1)
        assert packed > 0  # top bit untouched: numpy int64 stays positive


class TestAllocation:
    def test_sums_exactly_and_tracks_mass(self):
        rng = np.random.RandomState(0)
        for _ in range(50):
            masses = rng.rand(rng.randint(1, 9)) * rng.choice([0.1, 10, 1000])
            n = int(rng.randint(1, 257))
            out = allocate_proportional(n, masses)
            assert out.sum() == n
            exact = n * masses / masses.sum()
            assert (np.abs(out - exact) < 1.0 + 1e-9).all()

    def test_zero_mass_shard_gets_zero(self):
        out = allocate_proportional(7, np.array([0.0, 1.0, 0.0]))
        assert out[0] == 0 and out[2] == 0 and out[1] == 7

    def test_empty_and_degenerate(self):
        assert allocate_proportional(0, np.array([1.0])).sum() == 0
        assert allocate_proportional(5, np.array([0.0, 0.0])).sum() == 0


class TestISWeightMerge:
    def test_formula_matches_monolithic(self):
        """merge_is_weights IS the monolithic `_is_weights` over the same
        global (total, count, beta) — bit-for-bit."""
        rng = np.random.RandomState(1)
        prios = rng.rand(64) + 0.01
        total, count, beta = float(prios.sum() * 3), 500, 0.47
        np.testing.assert_array_equal(
            merge_is_weights(prios, total, count, beta),
            _is_weights(prios, total, count, beta))

    def test_single_shard_service_weights_match_monolithic_semantics(self):
        """A 1-shard gather must reproduce the monolithic weight math on
        the priorities it actually drew (recomputed from the trees)."""
        svc = ShardedReplayService(1, 256, mode="transition", scorer="max",
                                   backend="python", seed=0)
        try:
            for u in make_apex_unrolls(0, 4, steps=8):
                svc.shards[0].ingest(u)
            # Spread the priorities so the weights are non-trivial.
            _, idxs, _ = svc.sample(16, np.random.RandomState(2))
            svc.update_batch(idxs, np.linspace(0.1, 3.0, 16))
            assert svc.flush_updates()
            items, idxs, weights = svc.sample(16, np.random.RandomState(3))
            _, _, tree_idxs = unpack_index(idxs)
            tree = svc.shards[0].backend.tree
            prios = np.array([tree._tree[int(t)] for t in tree_idxs])
            expect = _is_weights(prios, tree.total, len(svc), svc.beta)
            np.testing.assert_allclose(weights, expect, rtol=1e-6)
        finally:
            svc.close()


class TestDistributionEquivalence:
    def test_chi_square_against_monolithic(self):
        """Same 32 items, same raw priorities, monolithic backend vs a
        4-shard service: both samplers' item frequencies must match the
        priority distribution (chi-square, dof=31; stratified sampling
        has sub-multinomial variance, so the multinomial critical value
        is a generous pinned bar)."""
        K, draws, batch = 32, 400, 16
        errors = np.linspace(0.05, 2.0, K)
        items = [{"tag": np.int64(i), "reward": np.float32(0.0),
                  "done": np.bool_(False)} for i in range(K)]

        mono = make_replay(256, backend="python", seed=0)
        svc = ShardedReplayService(4, 256, mode="sequence", scorer="max",
                                   backend="python", seed=0)
        try:
            for i, (e, item) in enumerate(zip(errors, items)):
                mono.add(float(e), item)
                svc.shards[i % 4].backend.add(float(e), item)

            prios = np.array([mono._priority(e) for e in errors])
            probs = prios / prios.sum()

            def chi2(counts):
                exp = probs * counts.sum()
                return float(((counts - exp) ** 2 / exp).sum())

            rng_m, rng_s = np.random.RandomState(7), np.random.RandomState(8)
            counts_m = np.zeros(K)
            counts_s = np.zeros(K)
            for _ in range(draws):
                picked, _, _ = mono.sample(batch, rng_m)
                for it in picked:
                    counts_m[int(it["tag"])] += 1
                picked, _, _ = svc.sample(batch, rng_s)
                for it in picked:
                    counts_s[int(it["tag"])] += 1
            # chi2(0.999, dof=31) ~= 61.1 — pinned statistical tolerance.
            assert chi2(counts_m) < 61.1, chi2(counts_m)
            assert chi2(counts_s) < 61.1, chi2(counts_s)
        finally:
            svc.close()


class TestShardIngest:
    def test_transition_mode_contents_bit_identical_and_max_fill(self):
        unrolls = make_apex_unrolls(3, 2, steps=8)
        shard = ReplayShard(0, 64, mode="transition", scorer=None,
                            backend="python")
        for u in unrolls:
            assert shard.ingest_blob(bytes(codec.encode(u))) == 8
        snap = shard.snapshot()
        assert len(snap["items"]) == 16
        # Bit-identical contents: transition i of unroll k.
        for k, u in enumerate(unrolls):
            for i in range(8):
                stored = snap["items"][k * 8 + i]
                assert stored.state.tobytes() == u.state[i].tobytes()
                assert stored.reward == u.reward[i]
        # Max-priority fill: every item at the running max (init 1.0).
        expect = (1.0 + shard.backend.EPS) ** shard.backend.ALPHA
        np.testing.assert_allclose(snap["priorities"], expect)
        # A bigger routed error raises the fill level for LATER ingests.
        shard.update(np.array([shard.backend.tree.capacity - 1]),
                     np.array([5.0]), epoch=0)
        shard.ingest(unrolls[0])
        expect_hi = (5.0 + shard.backend.EPS) ** shard.backend.ALPHA
        np.testing.assert_allclose(shard.snapshot()["priorities"][-8:],
                                   expect_hi)

    def test_td_proxy_scorer_matches_reference_transform(self):
        u = make_apex_unrolls(4, 1, steps=8)[0]
        shard = ReplayShard(0, 64, mode="transition",
                            scorer=td_proxy_scorer, backend="python")
        shard.ingest(u)
        proxy = np.abs(np.clip(u.reward, -1, 1)) + u.done.astype(np.float64)
        expect = (np.abs(proxy) + shard.backend.EPS) ** shard.backend.ALPHA
        np.testing.assert_allclose(shard.snapshot()["priorities"], expect)

    def test_sequence_mode_one_item_per_blob(self):
        shard = ReplayShard(0, 16, mode="sequence",
                            scorer=td_proxy_scorer, backend="python")
        trajs = make_trajectories(5, 3)
        for t in trajs:
            assert shard.ingest_blob(bytes(codec.encode(t))) == 1
        snap = shard.snapshot()
        assert len(snap["items"]) == 3
        for stored, orig in zip(snap["items"], trajs):
            assert_trees_bit_identical(stored, orig)

    def test_stale_epoch_update_dropped_after_restart(self):
        shard = ReplayShard(0, 64, mode="sequence", scorer=None,
                            backend="python")
        shard.ingest(make_trajectories(6, 1)[0])
        idx = shard.backend.tree.capacity - 1
        assert shard.update(np.array([idx]), np.array([2.0]), epoch=0) == 1
        shard.restart()
        assert shard.update(np.array([idx]), np.array([9.0]), epoch=0) == 0
        assert shard.stats()["epoch"] == 1


class TestUpdateRouting:
    def test_async_updates_reach_owning_shards(self):
        svc = ShardedReplayService(3, 300, mode="transition", scorer="max",
                                   backend="python", seed=0)
        try:
            for i, u in enumerate(make_apex_unrolls(0, 9, steps=8)):
                svc.shards[i % 3].ingest(u)
            _, idxs, _ = svc.sample(24, np.random.RandomState(0))
            errors = np.linspace(0.2, 4.0, 24)
            svc.update_batch(idxs, errors)
            assert svc.flush_updates(timeout=5.0)
            applied = sum(s.stats()["updates_applied"] for s in svc.shards)
            assert applied == 24
            # The routed priorities landed exactly where they were sent.
            sid, _, tree_idxs = unpack_index(idxs)
            for j in (0, 11, 23):
                shard = svc.shards[int(sid[j])]
                got = shard.backend.tree._tree[int(tree_idxs[j])]
                expect = (abs(errors[j]) + shard.backend.EPS) ** shard.backend.ALPHA
                assert got == pytest.approx(expect, rel=1e-9)
        finally:
            svc.close()

    def test_k_update_writeback_path(self):
        """replay_train.prioritized_train_call against a sharded learner:
        every one of the K batches' priority updates reaches its owning
        shard (the ISSUE's K-update writeback pin)."""
        import jax

        from distributed_reinforcement_learning_tpu.agents.apex import (
            ApexAgent, ApexConfig)
        from distributed_reinforcement_learning_tpu.runtime import apex_runner
        from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

        cfg = ApexConfig(obs_shape=(4,), num_actions=2)
        svc = ShardedReplayService(2, 1000, mode="transition", scorer="max",
                                   seed=0)
        learner = apex_runner.ApexLearner(
            ApexAgent(cfg), TrajectoryQueue(capacity=4), WeightStore(),
            batch_size=8, replay_capacity=1000, rng=jax.random.PRNGKey(0),
            updates_per_call=2, replay_service=svc)
        try:
            facade = ReplayIngestFifo(svc, learner.queue)
            for u in make_apex_unrolls(1, 12):
                assert facade.ingest_blob(bytes(codec.encode(u)))
            assert learner._warm_unrolls() == 12
            assert learner.train() is not None
            assert learner.train_steps == 2
            assert svc.flush_updates(timeout=10.0)
            applied = sum(s.stats()["updates_applied"] for s in svc.shards)
            assert applied == 2 * 8  # K batches x batch_size
        finally:
            learner.close()
            svc.close()


class TestTwoProcessIngest:
    def test_tcp_serve_threads_feed_shards_bit_identical(self):
        """A REAL child process PUTs trajectories over loopback TCP; the
        server's serve thread (not the learner) decodes + scores +
        inserts into its shard. Stored contents must be bit-identical to
        the child's originals."""
        from distributed_reinforcement_learning_tpu.runtime.transport import (
            TransportServer)
        from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

        seed, count = 11, 7
        svc = ShardedReplayService(2, 64, mode="sequence", scorer="td_proxy",
                                   backend="python", seed=0)
        fallback = TrajectoryQueue(capacity=count + 2)
        facade = ReplayIngestFifo(svc, fallback)
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        server = TransportServer(facade, WeightStore(), host="127.0.0.1",
                                 port=port).start()
        child = (
            "import sys; sys.path.insert(0, sys.argv[4]);"
            "from shm_ring_worker import make_trajectories;"
            "from distributed_reinforcement_learning_tpu.runtime.transport"
            " import TransportClient;"
            "c = TransportClient('127.0.0.1', int(sys.argv[1]));"
            "[c.put_trajectory(t) or (_ for _ in ()).throw(AssertionError)"
            " for t in make_trajectories(int(sys.argv[2]), int(sys.argv[3]))];"
            "c.close()")
        proc = subprocess.Popen(
            [sys.executable, "-c", child, str(port), str(seed), str(count),
             str(REPO / "tests")],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        try:
            assert proc.wait(timeout=90) == 0, proc.stderr.read()[-800:]
            deadline = time.monotonic() + 10
            while (svc.ingested_blobs() < count
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            assert svc.ingested_blobs() == count
        finally:
            server.stop()
            fallback.close()
        # One connection = one serve thread = one owning shard, in order.
        stored = [it for sh in svc.shards
                  for it in sh.snapshot()["items"]]
        assert len(stored) == count
        for got, orig in zip(stored, make_trajectories(seed, count)):
            assert_trees_bit_identical(got, orig)
        assert fallback.size() == 0  # nothing leaked to the monolithic path
        svc.close()

    def test_ring_drainer_feeds_shards_bit_identical(self):
        """Same pin over the shm-ring drainer: the drain thread owns a
        shard through the same blob_ingest seam."""
        from distributed_reinforcement_learning_tpu.runtime.shm_ring import (
            RingDrainer, ShmRing)

        seed, count = 13, 6
        svc = ShardedReplayService(2, 64, mode="sequence", scorer="max",
                                   backend="python", seed=0)
        fallback = TrajectoryQueue(capacity=count + 2)
        facade = ReplayIngestFifo(svc, fallback)
        name = f"drltest-shardring-{os.getpid()}"
        ring = ShmRing.create(name, 1 << 20)
        drainer = RingDrainer([ring], facade).start()
        proc = subprocess.Popen(
            [sys.executable, str(REPO / "tests" / "shm_ring_worker.py"),
             name, str(seed), str(count)],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        try:
            assert proc.wait(timeout=90) == 0, proc.stderr.read()[-800:]
            deadline = time.monotonic() + 10
            while (svc.ingested_blobs() < count
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            assert svc.ingested_blobs() == count
        finally:
            drainer.stop()
            fallback.close()
        stored = [it for sh in svc.shards
                  for it in sh.snapshot()["items"]]
        assert len(stored) == count
        for got, orig in zip(stored, make_trajectories(seed, count)):
            assert_trees_bit_identical(got, orig)
        svc.close()


class TestShardDeathFallback:
    def test_poison_blob_dropped_without_killing_shards(self):
        """An undecodable blob is a POISON PUT: dropped and counted,
        never allowed to cascade shard-death through the fleet (the
        regression the first review pass caught)."""
        svc = ShardedReplayService(2, 64, mode="sequence", scorer="max",
                                   backend="python", seed=0)
        fallback = TrajectoryQueue(capacity=4)
        facade = ReplayIngestFifo(svc, fallback)
        try:
            assert facade.ingest_blob(b"\x00garbage-not-a-codec-blob")
            assert svc.healthy and len(svc.live_shards()) == 2
            assert not facade.demoted
            # Real traffic keeps flowing into the same (live) shard.
            good = make_trajectories(23, 1)[0]
            assert facade.ingest_blob(bytes(codec.encode(good)))
            assert svc.ingested_blobs() == 1
        finally:
            svc.close()

    def test_dead_shard_reroutes_then_full_death_demotes(self):
        svc = ShardedReplayService(2, 64, mode="sequence", scorer="max",
                                   backend="python", seed=0)
        fallback = TrajectoryQueue(capacity=16)
        facade = ReplayIngestFifo(svc, fallback)
        trajs = make_trajectories(17, 4)
        blobs = [bytes(codec.encode(t)) for t in trajs]
        assert facade.ingest_blob(blobs[0])
        # First shard dies: this thread re-maps to the survivor.
        svc.note_shard_death(facade._shard_for_thread())
        assert facade.ingest_blob(blobs[1])
        assert svc.healthy and not facade.demoted
        live = svc.live_shards()
        assert len(live) == 1 and live[0].stats()["ingested_blobs"] >= 1
        # Last shard dies: PERMANENT demotion to the monolithic queue.
        svc.note_shard_death(live[0])
        assert not svc.healthy
        assert facade.ingest_blob(blobs[2])
        assert facade.demoted and fallback.size() == 1
        assert_trees_bit_identical(fallback.get(timeout=1.0), trajs[2])
        svc.close()

    def test_learner_demotes_to_monolithic_replay(self):
        import jax

        from distributed_reinforcement_learning_tpu.agents.apex import (
            ApexAgent, ApexConfig)
        from distributed_reinforcement_learning_tpu.runtime import apex_runner
        from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

        cfg = ApexConfig(obs_shape=(4,), num_actions=2)
        svc = ShardedReplayService(1, 1000, mode="transition", scorer="max",
                                   seed=0)
        learner = apex_runner.ApexLearner(
            ApexAgent(cfg), TrajectoryQueue(capacity=32), WeightStore(),
            batch_size=8, replay_capacity=1000, rng=jax.random.PRNGKey(0),
            replay_service=svc)
        try:
            assert learner._active_replay() is svc
            svc.note_shard_death(svc.shards[0])
            assert learner._active_replay() is learner.replay
            # Warm gate follows the monolithic path after demotion: the
            # queue-fed ingest loop refills it from live traffic.
            assert learner.train() is None
            for u in make_apex_unrolls(2, 12):
                learner.queue.put(u)
            while learner.ingest_many(timeout=0.0):
                pass
            assert learner.train() is not None
        finally:
            learner.close()
            svc.close()


class TestGateResolution:
    def test_shard_count_clamps_and_rejects_non_integers(self, monkeypatch):
        monkeypatch.setenv("DRL_REPLAY_SHARDS", "-1")
        assert shard_count() == 0
        monkeypatch.setenv("DRL_REPLAY_SHARDS", "two")
        with pytest.raises(ValueError, match="DRL_REPLAY_SHARDS"):
            shard_count()

"""Tiered replay spill tier (data/replay_spill.py + shard/service wiring).

Pins the ISSUE's semantics: proportional-sampling equivalence with the
all-RAM backend under live spill/promote churn (chi-square against the
analytic priority distribution, same 61.1 pinned bar as the sharded
service's), bit-identical trajectory contents across a spill -> promote
round trip (transition trees AND sequence-mode LazyBlob wire blobs),
the loss-free priority-writeback ledger (RAM-authoritative priorities
across in-flight spills, duplicate-index last-write-wins, counted drops
for evicted segments), learner-restart recovery from manifest + crc32,
poison-blob isolation (one corrupt segment file drops ONE segment, at
promote time or at recovery time, never the shard), the shard restart
clean-slate wipe, a live-service gather/update pass with the router
thread doing the tier maintenance, and the DRL_REPLAY_SPILL gate
resolution (tests/test_gate_defaults.py pins the default).

All CPU-only, tier-1 safe; spill directories are pytest tmp_path-scoped.
"""

import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.data import codec
from distributed_reinforcement_learning_tpu.data.replay import (
    make_replay,
    priority_transform,
)
from distributed_reinforcement_learning_tpu.data.replay_service import (
    LazyBlob,
    ReplayServiceEmpty,
    ReplayShard,
    ShardedReplayService,
)
from distributed_reinforcement_learning_tpu.data.replay_spill import (
    _OFF_BITS,
    ColdStoreEmpty,
    SpillConfig,
    TieredStore,
)
from distributed_reinforcement_learning_tpu.runtime.replay_shard import (
    spill_config,
)

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tests"))
from test_replay_service import make_apex_unrolls  # noqa: E402


def drain_tier(store: TieredStore, max_jobs: int = 64) -> int:
    """Run the plan/run_io/commit protocol to (bounded) quiescence on
    the calling thread — exactly what ReplayShard.tier_step does, minus
    the shard lock (these stores are single-threaded in the tests)."""
    ran = 0
    for _ in range(max_jobs):
        job = store.plan_tier_work()
        if job is None:
            break
        job.run_io()
        snap = store.commit_tier_work(job)
        if snap is not None:
            store.write_manifest(snap)
        ran += 1
    return ran


def drain_all(store: TieredStore) -> None:
    while drain_tier(store):
        pass


def sample_full(store: TieredStore, n: int, rng):
    """Complete one batch without EVER forcing resident-only pads: a
    None step (queued cold draws) runs tier maintenance and retries, so
    every delivered item is a full-distribution draw."""
    for _ in range(2000):
        out = store.sample_step(n, rng)
        if out is not None:
            return out
        drain_tier(store)
    raise AssertionError("sample never completed (promotes wedged)")


def make_store(tmp_path, n_items, seg_items=4, hot_bytes=0, capacity=256,
               mode="transition", seed=0, errors=None, fresh=False):
    cfg = SpillConfig(directory=str(tmp_path), hot_bytes=hot_bytes,
                      seg_items=seg_items, wait_s=10.0, fresh=fresh)
    store = TieredStore(capacity, cfg, mode=mode, seed=seed)
    rng = np.random.RandomState(seed + 41)
    items, idxs = [], []
    if errors is None:
        errors = np.linspace(0.05, 2.0, n_items)
    for i in range(n_items):
        item = {"tag": np.int64(i),
                "obs": rng.rand(8, 6).astype(np.float32),
                "act": np.int32(i % 4)}
        items.append(item)
        idxs.append(store.add(float(errors[i]), item))
    return store, items, idxs, np.asarray(errors, np.float64)


def assert_item_bit_identical(a: dict, b: dict) -> None:
    assert sorted(a) == sorted(b)
    for k in a:
        x, y = np.asarray(a[k]), np.asarray(b[k])
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


class TestChiSquareUnderSpillChurn:
    def test_proportional_sampling_matches_all_ram(self, tmp_path):
        """Same 32 items / same raw priorities in the monolithic python
        backend and a TieredStore small enough that most segments live
        on disk: both samplers' item frequencies must match the priority
        distribution while segments spill and promote underneath the
        draws. chi2(0.999, dof=31) ~= 61.1 — the pinned bar
        test_replay_service.py uses for the sharded gather."""
        K, draws, batch = 32, 400, 16
        errors = np.linspace(0.05, 2.0, K)
        mono = make_replay(256, backend="python", seed=0)
        store, items, _, _ = make_store(tmp_path, K, seg_items=4,
                                        hot_bytes=1500, errors=errors)
        for e, item in zip(errors, items):
            mono.add(float(e), item)
        drain_all(store)  # payload >> budget: most segments go cold
        assert store.stats["spilled_segments"] >= 3

        prios = priority_transform(errors)
        probs = prios / prios.sum()

        def chi2(counts):
            exp = probs * counts.sum()
            return float(((counts - exp) ** 2 / exp).sum())

        rng_m, rng_t = np.random.RandomState(7), np.random.RandomState(8)
        counts_m, counts_t = np.zeros(K), np.zeros(K)
        for d in range(draws):
            picked, _, _ = mono.sample(batch, rng_m)
            for it in picked:
                counts_m[int(it["tag"])] += 1
            got, idxs, _ = sample_full(store, batch, rng_t)
            for it in got:
                counts_t[int(it["tag"])] += 1
            # The router thread's steady tick: promote parked cold draws,
            # spill back over-budget segments — churn under the draws.
            drain_tier(store, max_jobs=4)
            if d % 25 == 0:
                # Writeback churn at the ORIGINAL errors: priorities (and
                # the expected distribution) are unchanged, but cumsums
                # invalidate and spill victims reshuffle.
                tags = np.array([int(it["tag"]) for it in got])
                store.update_batch(idxs, errors[tags])
        # The tier actually churned underneath the draws, and no draw
        # was ever forced (forced pads are the one permitted bias, and
        # this test never forces).
        assert store.stats["promoted_segments"] > 0
        assert store.stats["forced_pads"] == 0
        assert chi2(counts_m) < 61.1, chi2(counts_m)
        assert chi2(counts_t) < 61.1, chi2(counts_t)


class TestSpillPromoteBitIdentity:
    def test_transition_round_trip(self, tmp_path):
        store, items, _, errors = make_store(tmp_path, 16, seg_items=4,
                                             hot_bytes=0)
        drain_all(store)
        cold = [s for s in store._segments.values() if not s.resident]
        assert len(cold) >= 2  # churn actually spilled payloads
        # Snapshot reads cold items straight from the segment files.
        snap = store.snapshot()
        assert len(snap["items"]) == 16
        for i, it in enumerate(snap["items"]):
            it = it.materialize() if hasattr(it, "materialize") else it
            assert_item_bit_identical(it, items[i])
        np.testing.assert_allclose(snap["priorities"],
                                   priority_transform(errors), rtol=1e-12)
        # Promote path: concentrate mass on the cold segments so draws
        # land there, then verify every DELIVERED item bit-identically.
        cold_idxs = np.array([(s.sid << _OFF_BITS) | off
                              for s in cold for off in range(s.count)])
        store.update_batch(cold_idxs, np.full(len(cold_idxs), 50.0))
        got, _, _ = sample_full(store, 32, np.random.RandomState(3))
        assert store.stats["promoted_segments"] >= 1
        for it in got:
            assert_item_bit_identical(it, items[int(it["tag"])])

    def test_sequence_lazyblob_round_trip(self, tmp_path):
        """Sequence-mode items are wire blobs (LazyBlob): a spill writes
        the blob, a promote re-wraps it — the materialized tree must be
        bit-identical and the store must never have decoded it."""
        cfg = SpillConfig(directory=str(tmp_path), hot_bytes=0,
                          seg_items=2, wait_s=10.0)
        store = TieredStore(64, cfg, mode="sequence", seed=0)
        rng = np.random.RandomState(9)
        trees = []
        for i in range(8):
            tree = {"obs": rng.rand(8, 16).astype(np.float32),
                    "reward": rng.randn(8).astype(np.float32),
                    "tag": np.int64(i)}
            trees.append(tree)
            store.add(0.2 + 0.1 * i, LazyBlob(bytes(codec.encode(tree))))
        drain_all(store)
        assert store.stats["spilled_segments"] >= 2
        idxs = np.array([(s.sid << _OFF_BITS) | off
                         for s in store._segments.values() if not s.resident
                         for off in range(s.count)])
        store.update_batch(idxs, np.full(len(idxs), 50.0))
        got, _, _ = sample_full(store, 16, np.random.RandomState(4))
        assert store.stats["promoted_segments"] >= 1
        for it in got:
            tree = it.materialize() if hasattr(it, "materialize") else it
            assert_item_bit_identical(tree, trees[int(tree["tag"])])


class TestWritebackLedger:
    def test_conservation_across_tiers(self, tmp_path):
        """tree.total must equal the transform of the LATEST error for
        every live item, whatever tier its payload sits in — priorities
        never move to disk-only, so no writeback can be lost."""
        store, _, idxs, errors = make_store(tmp_path, 24, seg_items=4,
                                            hot_bytes=0)
        drain_all(store)
        latest = errors.copy()
        rng = np.random.RandomState(11)
        touch = rng.choice(24, size=12, replace=False)
        latest[touch] = rng.rand(12) * 3 + 0.01
        store.update_batch(np.asarray(idxs)[touch], latest[touch])
        expect = float(priority_transform(latest).sum())
        assert store.tree.total == pytest.approx(expect, rel=1e-9)
        # Spill/promote churn moves payloads, never mass.
        store.update_batch(np.asarray(idxs), latest)  # cumsum churn
        drain_all(store)
        sample_full(store, 16, rng)
        drain_all(store)
        assert store.tree.total == pytest.approx(expect, rel=1e-9)

    def test_duplicate_index_keeps_last_write(self, tmp_path):
        store, _, idxs, _ = make_store(tmp_path, 8, hot_bytes=1 << 20)
        store.update_batch(np.array([idxs[3], idxs[3]]),
                           np.array([5.0, 0.25]))
        seg = store._segments[idxs[3] >> _OFF_BITS]
        off = idxs[3] & ((1 << _OFF_BITS) - 1)
        want = float(priority_transform(np.array([0.25]))[0])
        assert seg.prios[off] == pytest.approx(want, rel=1e-12)

    def test_update_during_inflight_spill_is_not_lost(self, tmp_path):
        """The RAM priority array stays authoritative while a spill job
        is mid-IO: the job carries a COPY, so a writeback landing between
        plan and commit survives the commit."""
        store, _, idxs, _ = make_store(tmp_path, 12, seg_items=4,
                                       hot_bytes=1 << 20)
        store.cfg = dataclasses.replace(store.cfg, hot_bytes=0)
        job = store.plan_tier_work()
        assert job is not None and job.kind == "spill"
        idx = (job.sid << _OFF_BITS) | 1
        store.update_batch(np.array([idx]), np.array([7.0]))
        job.run_io()
        snap = store.commit_tier_work(job)
        if snap is not None:
            store.write_manifest(snap)
        seg = store._segments[job.sid]
        assert not seg.resident
        want = float(priority_transform(np.array([7.0]))[0])
        assert seg.prios[1] == pytest.approx(want, rel=1e-12)
        assert seg.mass == pytest.approx(float(seg.prios[:seg.count].sum()),
                                         rel=1e-12)

    def test_evicted_segment_updates_dropped_and_counted(self, tmp_path):
        store, _, idxs, errors = make_store(tmp_path, 16, seg_items=4,
                                            capacity=8, hot_bytes=1 << 20)
        assert store.stats["evicted_segments"] >= 2
        assert store.stats["evicted_items"] == 8
        assert len(store) == 8
        total0 = store.tree.total
        # Indexes into the overwritten oldest segments: dropped, counted,
        # ledger untouched.
        store.update_batch(np.asarray(idxs[:4]), np.full(4, 99.0))
        assert store.stats["updates_dropped_evicted"] == 4
        assert store.tree.total == pytest.approx(total0, rel=1e-12)
        assert store.tree.total == pytest.approx(
            float(priority_transform(errors[8:]).sum()), rel=1e-9)


class TestRestartRecovery:
    def test_manifest_recovery_round_trip(self, tmp_path):
        store, items, _, errors = make_store(tmp_path, 16, seg_items=4,
                                             hot_bytes=0)
        drain_all(store)
        st = store.tier_stats()
        assert st["cold_items"] >= 8
        cold_mass = sum(s.mass for s in store._segments.values()
                        if not s.resident)
        store.close()
        # Process restart: same directory, fresh=False -> manifest
        # reattach. Hot-only payloads are gone (they were RAM), every
        # file-backed segment comes back cold with its priorities.
        store2 = TieredStore(256, SpillConfig(directory=str(tmp_path),
                                              hot_bytes=0, seg_items=4,
                                              wait_s=10.0),
                             mode="transition", seed=1)
        assert store2.stats["recovered_items"] == st["cold_items"]
        assert len(store2) == st["cold_items"]
        assert store2.tree.total == pytest.approx(cold_mass, rel=1e-9)
        # All-cold store: sampling completes via promotes and the
        # delivered payloads are bit-identical to the originals.
        got, _, _ = sample_full(store2, 8, np.random.RandomState(5))
        assert len(got) == 8
        for it in got:
            assert_item_bit_identical(it, items[int(it["tag"])])
        store2.close()

    def test_fresh_wipes_previous_run(self, tmp_path):
        store, _, _, _ = make_store(tmp_path, 16, seg_items=4, hot_bytes=0)
        drain_all(store)
        assert list(Path(tmp_path).glob("seg_*.bin"))
        store.close()
        store2, _, _, _ = make_store(tmp_path, 4, seg_items=4,
                                     hot_bytes=1 << 20, fresh=True)
        assert store2.stats["recovered_segments"] == 0
        assert len(store2) == 4
        store2.close()

    def test_shard_restart_wipes_spill_dir(self, tmp_path):
        """Shard restart (post-death clean slate) is DISTINCT from
        process-restart recovery: the directory is wiped, the epoch
        bumps, and nothing is recovered."""
        cfg = SpillConfig(directory=str(tmp_path), hot_bytes=0,
                          seg_items=4, wait_s=1.0)
        shard = ReplayShard(0, 64, mode="transition", scorer=None,
                            backend="python", spill=cfg)
        for i in range(16):
            shard.backend.add(0.5, {"tag": np.int64(i),
                                    "pay": np.zeros(16, np.float32)})
        while shard.tier_step():
            pass
        seg_dir = Path(tmp_path) / "shard_000"
        assert list(seg_dir.glob("seg_*.bin"))
        epoch0 = shard.epoch
        shard.restart()
        assert shard.epoch != epoch0
        assert not list(seg_dir.glob("seg_*.bin"))
        assert not (seg_dir / "manifest.json").exists()
        assert len(shard.backend) == 0


class TestPoisonIsolation:
    def test_promote_time_crc_drops_one_segment(self, tmp_path):
        store, items, _, _ = make_store(tmp_path, 32, seg_items=4,
                                        hot_bytes=0)
        drain_all(store)
        cold = [s for s in store._segments.values() if not s.resident]
        assert len(cold) >= 3
        victim = cold[0]
        data = bytearray(Path(victim.file).read_bytes())
        data[-1] ^= 0xFF  # same length, bad crc
        Path(victim.file).write_bytes(bytes(data))
        poisoned_tags = {int(items[i]["tag"]) for i in
                         range(victim.sid * 4, victim.sid * 4 + victim.count)}
        # Concentrate mass on the poisoned segment so draws land there.
        bad_idxs = np.array([(victim.sid << _OFF_BITS) | off
                             for off in range(victim.count)])
        store.update_batch(bad_idxs, np.full(victim.count, 100.0))
        n0, nseg0 = len(store), len(store._segments)
        got, _, _ = sample_full(store, 16, np.random.RandomState(6))
        assert store.stats["crc_dropped"] == 1
        assert victim.sid not in store._segments
        assert len(store) == n0 - victim.count
        assert len(store._segments) == nseg0 - 1
        # The batch still completed, from surviving segments only.
        assert len(got) == 16
        assert not any(int(it["tag"]) in poisoned_tags for it in got)

    def test_recovery_time_poison_skipped_and_counted(self, tmp_path):
        store, _, _, _ = make_store(tmp_path, 16, seg_items=4, hot_bytes=0)
        drain_all(store)
        cold = [s for s in store._segments.values() if not s.resident]
        assert len(cold) >= 2
        victim = cold[0]
        data = bytearray(Path(victim.file).read_bytes())
        data[:4] = b"XXXX"  # bad magic: unreadable at recovery
        Path(victim.file).write_bytes(bytes(data))
        store.close()
        store2 = TieredStore(256, SpillConfig(directory=str(tmp_path),
                                              hot_bytes=0, seg_items=4,
                                              wait_s=10.0),
                             mode="transition", seed=2)
        assert store2.stats["crc_dropped"] == 1
        assert store2.stats["recovered_segments"] == len(cold) - 1
        assert len(store2) == sum(s.count for s in cold) - victim.count
        store2.close()


class TestServiceWithSpill:
    def test_gather_updates_and_router_maintenance(self, tmp_path):
        """End-to-end through the service: ingest spills on the insert
        path, the ROUTER thread does the promote work for gathers that
        draw cold (the learn thread never touches disk), and the async
        priority-update path keeps working against tiered backends."""
        spill = SpillConfig(directory=str(tmp_path), hot_bytes=2048,
                            seg_items=8, wait_s=5.0)
        svc = ShardedReplayService(2, 1024, mode="transition", scorer="max",
                                   backend="python", seed=0, spill=spill)
        try:
            for i, u in enumerate(make_apex_unrolls(0, 40, steps=8)):
                svc.shards[i % 2].ingest(u)
            assert svc.flush_tier(timeout=30.0)
            stats = svc.tier_stats()
            assert stats is not None
            assert sum(s["spilled_segments"] for s in stats) >= 1
            rng = np.random.RandomState(12)
            batch = idxs = None
            for _ in range(200):
                try:
                    batch, idxs, weights = svc.sample(16, rng)
                    break
                except ReplayServiceEmpty:
                    svc.flush_tier(timeout=1.0)
            assert batch is not None and len(batch) == 16
            assert (weights > 0).all()
            svc.update_batch(idxs, np.linspace(0.1, 3.0, 16))
            assert svc.flush_updates()
            batch2, _, _ = svc.sample(16, rng)
            assert len(batch2) == 16
        finally:
            svc.close()

    def test_cold_store_empty_is_a_transient_skip(self, tmp_path):
        """An all-cold shard (restart recovery) surfaces as
        ReplayServiceEmpty — the learner's transient-skip contract —
        never as a ColdStoreEmpty leak or a short batch."""
        store, _, _, _ = make_store(tmp_path, 16, seg_items=4, hot_bytes=0)
        drain_all(store)
        store.close()
        cfg = SpillConfig(directory=str(tmp_path), hot_bytes=0,
                          seg_items=4, wait_s=0.05)
        store2 = TieredStore(256, cfg, mode="transition", seed=3)
        assert len(store2) > 0
        # force=True with nothing resident at all: ColdStoreEmpty, which
        # ReplayShard/ShardedReplayService convert to ReplayServiceEmpty.
        with pytest.raises(ColdStoreEmpty):
            store2.sample_step(8, np.random.RandomState(0), force=True)
        store2.close()


class TestSpillGate:
    def test_spill_config_resolves_knobs(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DRL_REPLAY_SPILL", "1")
        monkeypatch.setenv("DRL_REPLAY_SPILL_DIR", str(tmp_path / "d"))
        monkeypatch.setenv("DRL_REPLAY_SPILL_HOT_MB", "1.5")
        monkeypatch.setenv("DRL_REPLAY_SPILL_SEG", "128")
        cfg = spill_config("/ignored/when/dir/env/set")
        assert cfg is not None
        assert cfg.directory == str(tmp_path / "d")
        assert cfg.hot_bytes == int(1.5 * 1024 * 1024)
        assert cfg.seg_items == 128
        monkeypatch.setenv("DRL_REPLAY_SPILL", "0")
        assert spill_config(str(tmp_path)) is None

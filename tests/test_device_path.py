"""Fused device-resident sample path (data/device_path.py +
runtime/replay_train.device_train_call).

Pins the ISSUE's contracts: sampled batches bit-identical to the host
gather at a fixed RNG (one shared gather function, verified here
against an independent reimplementation), scanned-K priorities
equivalent to the sequential per-step loop (rtol pinned — XLA-CPU
reduction order, same style as the apex-ingest pin), ring wrap/refill
over many rounds at bounded depth, the H2D overlap actually
overlapping (slow-copy stub timing assertion), the demote ladder
(oversize entry -> host path, service demotion -> path closed before
the host loop reclaims the RNG), tier-forced K=1 degradation with no
shape crash and no silent K change, zero lost priority writebacks for
the surviving shard across a shard death mid-K, gate resolution
(the sizing knobs), and a two-process e2e over a
real transport server + real replay shards.

All CPU-only, tier-1 safe.
"""

import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import jax

from distributed_reinforcement_learning_tpu.agents.apex import (
    ApexAgent,
    ApexBatch,
    ApexConfig,
)
from distributed_reinforcement_learning_tpu.data import codec
from distributed_reinforcement_learning_tpu.data.device_path import (
    DeviceSamplePath,
    gather_scan_batch,
    gather_single_batch,
    path_depth,
    path_max_bytes,
)
from distributed_reinforcement_learning_tpu.data.fifo import (
    blob_ingest,
    stack_pytrees,
)
from distributed_reinforcement_learning_tpu.data.replay import make_replay
from distributed_reinforcement_learning_tpu.data.replay_service import (
    ShardedReplayService,
    unpack_index,
)
from distributed_reinforcement_learning_tpu.runtime import apex_runner
from distributed_reinforcement_learning_tpu.runtime.replay_shard import (
    ReplayIngestFifo,
)
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

REPO = Path(__file__).resolve().parent.parent

OBS = 6
STEPS = 8


def make_unrolls(seed: int, count: int, steps: int = STEPS):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(count):
        out.append(ApexBatch(
            state=rng.rand(steps, OBS).astype(np.float32),
            next_state=rng.rand(steps, OBS).astype(np.float32),
            previous_action=rng.randint(0, 2, steps).astype(np.int32),
            action=rng.randint(0, 2, steps).astype(np.int32),
            reward=rng.randn(steps).astype(np.float32),
            done=(rng.rand(steps) < 0.1),
        ))
    return out


def fill_service(num_shards=2, unrolls=8, capacity=2048, seed=0):
    svc = ShardedReplayService(num_shards, capacity, mode="transition",
                               scorer="max", seed=seed)
    for i, shard in enumerate(svc.shards):
        for tree in make_unrolls(seed + 31 * i, unrolls // num_shards or 1):
            shard.ingest(tree)
    return svc


def make_learner(svc, agent=None, batch_size=8, updates_per_call=1,
                 force=True):
    agent = agent or ApexAgent(ApexConfig(obs_shape=(OBS,), num_actions=2))
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        _make_queue)

    queue = _make_queue(16)
    learner = apex_runner.ApexLearner(
        agent, queue, WeightStore(), batch_size=batch_size,
        replay_capacity=2048, rng=jax.random.PRNGKey(0),
        replay_service=svc, updates_per_call=updates_per_call,
        train_start_unrolls=1)
    learner.device_path_force = force
    return learner, queue


def train_until(learner, min_steps=1, budget_s=60.0):
    deadline = time.monotonic() + budget_s
    last = None
    while learner.train_steps < min_steps:
        m = learner.train()
        if m is not None:
            last = m
        assert time.monotonic() < deadline, "train never progressed"
    return last


# ---------------------------------------------------------------- gather


class TestGatherEquivalence:
    def test_scan_gather_bit_identical_to_host_gather(self):
        """One gather definition serves both paths; pin it against an
        independent per-batch reimplementation at a fixed RNG so a
        refactor of either side cannot silently drift the sampled
        bytes."""
        # Two identically-built services: sampling anneals the IS beta,
        # so the reference draws must not perturb the path under test.
        svc_ref = fill_service(unrolls=8)
        ref_rng = np.random.RandomState(123)
        ref = [svc_ref.sample(8, ref_rng) for _ in range(3)]
        svc_ref.close()
        svc = fill_service(unrolls=8)
        got_stacked, got_w, got_idx = gather_scan_batch(
            svc, 8, 3, np.random.RandomState(123))
        if getattr(svc, "stacked_samples", False):
            want_stacked = stack_pytrees([items for items, _, _ in ref])
        else:
            flat = stack_pytrees([it for items, _, _ in ref for it in items])
            want_stacked = jax.tree.map(
                lambda x: x.reshape((3, -1) + x.shape[1:]), flat)
        for got, want in zip(jax.tree.leaves(got_stacked),
                             jax.tree.leaves(want_stacked)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(
            got_w, np.stack([np.asarray(w, np.float32) for _, _, w in ref]))
        for got, (_, want, _) in zip(got_idx, ref):
            np.testing.assert_array_equal(got, want)
        svc.close()

    def test_single_gather_matches_sample(self):
        svc_ref = fill_service(unrolls=8)
        items, idxs, w = svc_ref.sample(8, np.random.RandomState(7))
        svc_ref.close()
        svc = fill_service(unrolls=8)
        batch, got_w, got_idx = gather_single_batch(
            svc, 8, np.random.RandomState(7))
        want = items if getattr(svc, "stacked_samples", False) \
            else stack_pytrees(items)
        for a, b in zip(jax.tree.leaves(batch), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(got_w, np.asarray(w, np.float32))
        assert len(got_idx) == 1
        np.testing.assert_array_equal(got_idx[0], idxs)
        svc.close()

    def test_gather_works_over_monolithic_backends(self):
        """The gather is backend-agnostic (the host K>1 path runs it
        over whatever `_active_replay` resolved)."""
        replay = make_replay(256, backend="python", seed=0)
        for tree in make_unrolls(0, 2):
            for i in range(STEPS):
                replay.add(1.0, jax.tree.map(lambda x: x[i], tree))
        stacked, w, idx = gather_scan_batch(
            replay, 4, 2, np.random.RandomState(0))
        assert w.shape == (2, 4) and len(idx) == 2
        assert jax.tree.leaves(stacked)[0].shape[:2] == (2, 4)


# ------------------------------------------------ scanned-K equivalence


class TestScanPriorityEquivalence:
    def test_learn_many_matches_sequential_steps(self):
        """K scanned updates == K sequential `_learn` calls: params,
        per-step priorities, and metrics. rtol 1e-5: XLA-CPU fuses the
        scan body differently from the standalone jit, so matmul
        reduction order can differ — the same platform float noise the
        apex-ingest pin documents (_APEX_INGEST_RTOL); measured drift
        here is ~1e-7."""
        agent = ApexAgent(ApexConfig(obs_shape=(OBS,), num_actions=2))
        state_a = agent.init_state(jax.random.PRNGKey(0))
        state_a = agent.sync_target(state_a)
        state_b = jax.tree.map(lambda x: x.copy(), state_a)
        k, B = 3, 8
        rng = np.random.RandomState(5)
        batches = []
        for _ in range(k):
            u = make_unrolls(int(rng.randint(1 << 30)), 1, steps=B)[0]
            batches.append(u)
        stacked = stack_pytrees(batches)
        weights = rng.rand(k, B).astype(np.float32)

        state_a, prio_stack, _ = agent.learn_many(state_a, stacked, weights)
        prio_stack = np.asarray(prio_stack)

        seq_prios = []
        for i in range(k):
            batch = jax.tree.map(lambda x, i=i: x[i], stacked)
            state_b, td, _ = agent.learn(state_b, batch, weights[i])
            seq_prios.append(np.asarray(td))
        np.testing.assert_allclose(prio_stack, np.stack(seq_prios),
                                   rtol=1e-5, atol=1e-7)
        for a, b in zip(jax.tree.leaves(state_a.params),
                        jax.tree.leaves(state_b.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)


# ------------------------------------------------------- ring behavior


class TestRing:
    def test_wrap_refill_and_bounded_depth(self):
        """Entries keep flowing across many rounds (the ring refills
        behind the consumer) and the device-resident backlog never
        exceeds the configured depth."""
        svc = fill_service(unrolls=8)
        path = DeviceSamplePath(svc, 4, 2, np.random.RandomState(0),
                                depth=2)
        try:
            seen = 0
            for _ in range(12):
                entry = path.next_entry(timeout=10.0)
                assert entry is not None
                k, batch, weights, idxs = entry
                assert k == 2 and len(idxs) == 2
                assert np.asarray(weights).shape == (2, 4)
                assert path._out.qsize() <= 2
                seen += 1
            assert seen == 12 and not path.dead
            assert path.entries_out >= seen
        finally:
            path.close()
            svc.close()

    def test_overlap_actually_overlaps(self):
        """With a slow-copy stub, N transfers + N 'learn' sleeps must
        take well under the serial sum — the copy for entry k+1 runs on
        the gather thread while the consumer is busy with entry k."""
        svc = fill_service(unrolls=8)
        copy_s = 0.05

        def slow_transfer(tree):
            time.sleep(copy_s)
            return jax.device_put(tree)

        path = DeviceSamplePath(svc, 4, 1, np.random.RandomState(0),
                                depth=1, transfer=slow_transfer)
        try:
            n = 6
            assert path.next_entry(timeout=10.0) is not None  # pipeline primed
            t0 = time.monotonic()
            for _ in range(n):
                assert path.next_entry(timeout=10.0) is not None
                time.sleep(copy_s)  # the consumer's 'learn'
            elapsed = time.monotonic() - t0
            serial = n * 2 * copy_s
            # Full overlap would be ~n*copy_s; assert comfortably under
            # the serial bound (loaded-CI slack).
            assert elapsed < serial * 0.85, (
                f"no overlap: {elapsed:.3f}s vs serial {serial:.3f}s")
        finally:
            path.close()
            svc.close()

    def test_reconfigure_drops_stale_depth_entries(self):
        svc = fill_service(unrolls=8)
        path = DeviceSamplePath(svc, 4, 3, np.random.RandomState(0),
                                depth=1)
        try:
            entry = path.next_entry(timeout=10.0)
            assert entry is not None and entry[0] == 3
            path.reconfigure(1)
            deadline = time.monotonic() + 30.0
            while True:
                entry = path.next_entry(timeout=10.0)
                assert entry is not None
                if entry[0] == 1:
                    break  # never surfaced a stale K=3 stack
                assert time.monotonic() < deadline
            assert path.dropped_entries >= 0  # stale ones were consumed
            assert path.k == 1
        finally:
            path.close()
            svc.close()


# ---------------------------------------------------------- demote ladder


class TestDemote:
    def test_oversize_entry_latches_dead_and_learner_falls_back(self):
        svc = fill_service(unrolls=8)
        learner, queue = make_learner(svc, updates_per_call=1)
        # Force the path with an absurdly small budget: the first
        # gathered call latches it dead.
        from distributed_reinforcement_learning_tpu.data.device_path import (
            DeviceSamplePath as DSP)

        learner._device_path = DSP(svc, learner.batch_size, 1,
                                   learner._np_rng, max_bytes=8)
        deadline = time.monotonic() + 20.0
        while not learner._device_path.dead:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert "oversize" in learner._device_path.dead_reason
        # The next train call demotes permanently and trains via the
        # HOST path (no crash, real metrics).
        m = train_until(learner, min_steps=1)
        assert m is not None and learner._device_path is None
        assert learner._device_path_demoted
        learner.close()
        svc.close()
        queue.close()

    def test_service_demotion_closes_path_before_host_sampling(self):
        svc = fill_service(unrolls=8)
        learner, queue = make_learner(svc, updates_per_call=1)
        train_until(learner, min_steps=1)
        path = learner._device_path
        assert path is not None
        # Kill every shard: the service latches unhealthy and the next
        # resolution lands on the monolithic replay — the mixin must
        # CLOSE (join) the path before host-sampling with the shared
        # RNG.
        for shard in svc.shards:
            svc.note_shard_death(shard)
        assert not svc.healthy
        assert learner._active_replay() is learner.replay
        assert learner._device_path_for(learner.replay) is None
        assert learner._device_path is None and learner._device_path_demoted
        assert not path._thread.is_alive()  # RNG is the host loop's again
        learner.close()
        svc.close()
        queue.close()

    def test_sizing_knob_parsing(self, monkeypatch):
        monkeypatch.delenv("DRL_DEVICE_PATH_DEPTH", raising=False)
        monkeypatch.delenv("DRL_DEVICE_PATH_MAX_MB", raising=False)
        assert path_depth() == 1
        assert path_max_bytes() == 256 * 1024 * 1024
        monkeypatch.setenv("DRL_DEVICE_PATH_DEPTH", "3")
        assert path_depth() == 3
        monkeypatch.setenv("DRL_DEVICE_PATH_MAX_MB", "0.5")
        assert path_max_bytes() == 512 * 1024
        monkeypatch.setenv("DRL_DEVICE_PATH_DEPTH", "bogus")
        with pytest.raises(ValueError):
            path_depth()


# ------------------------------------------------------ tier interaction


class TestTierDegrade:
    def test_tier_forced_k1_renegotiates_without_shape_crash(self):
        """The learner-tier attach forces updates_per_call=1 under
        allreduce; the fused path must renegotiate to K=1 (H2D double
        buffering only) — no shape crash, no silent K change."""
        svc = fill_service(unrolls=8)
        learner, queue = make_learner(svc, updates_per_call=3)
        train_until(learner, min_steps=3)  # path built at K=3
        assert learner._device_path.k == 3
        # What LearnerTier.attach does for the replay family:
        learner.updates_per_call = 1
        steps0 = learner.train_steps
        train_until(learner, min_steps=steps0 + 2)
        assert learner._device_path.k == 1
        assert not learner._device_path.dead
        # Every post-renegotiation step advanced by exactly 1 (K=1
        # entries through the `_learn` seam a tier would wrap).
        learner.close()
        svc.close()
        queue.close()

    def test_attach_reconfigures_real_tier(self):
        """End-to-end against the real LearnerTier.attach: a K>1
        learner with the fused path degrades cleanly when the tier
        forces K=1 (allreduce merges per train step)."""
        from distributed_reinforcement_learning_tpu.runtime.learner_tier import (
            LearnerTier)

        svc = fill_service(unrolls=8)
        learner, queue = make_learner(svc, updates_per_call=2)
        train_until(learner, min_steps=2)
        assert learner._device_path.k == 2
        tier = LearnerTier(0, ["127.0.0.1:1", "127.0.0.1:2"],
                           sync="allreduce", probe_interval_s=60.0)
        tier.attach(learner)  # forces updates_per_call=1, wraps _learn
        assert learner.updates_per_call == 1
        # Solo membership: the wrapped _learn falls back to local
        # gradients without a live collective (never started).
        tier.collective._note_dead(1)
        steps0 = learner.train_steps
        train_until(learner, min_steps=steps0 + 2)
        assert learner._device_path.k == 1
        tier.close()
        learner.close()
        svc.close()
        queue.close()


# ------------------------------------------- writeback across shard death


class TestWritebackShardDeath:
    def test_surviving_shard_loses_zero_updates_mid_k(self):
        """Kill one shard between the gather and the K-step writeback:
        the surviving shard applies EVERY update addressed to it, the
        dead shard's stale-epoch updates drop loss-free (its restart
        re-ingests at max priority)."""
        svc = fill_service(num_shards=2, unrolls=16)
        stacked, weights, idx_list = gather_scan_batch(
            svc, 8, 3, np.random.RandomState(0))
        victim = svc.shards[0]
        applied0 = [s.stats()["updates_applied"] for s in svc.shards]
        victim.mark_dead()
        victim.restart()  # new epoch: in-flight updates are stale now
        for idxs in idx_list:
            svc.update_batch(idxs, np.full(len(idxs), 0.5))
        assert svc.flush_updates(timeout=10.0)
        sid_counts = {0: 0, 1: 0}
        for idxs in idx_list:
            sids, _, _ = unpack_index(idxs)
            for s in sids:
                sid_counts[int(s)] += 1
        stats = [s.stats() for s in svc.shards]
        # Survivor: every addressed update applied.
        assert stats[1]["updates_applied"] - applied0[1] == sid_counts[1]
        # Victim: all its updates dropped by the epoch check, none
        # misrouted to the survivor.
        assert stats[0]["updates_applied"] == 0
        svc.close()


# --------------------------------------------------------- two-process e2e

_PUT_CHILD = r"""
import sys
from collections import namedtuple

import numpy as np

from distributed_reinforcement_learning_tpu.runtime.transport import TransportClient

host, port, n_unrolls, steps, obs = (sys.argv[1], int(sys.argv[2]),
                                     int(sys.argv[3]), int(sys.argv[4]),
                                     int(sys.argv[5]))
ApexBatch = namedtuple("ApexBatch", ["state", "next_state", "previous_action",
                                     "action", "reward", "done"])
rng = np.random.RandomState(0)
trees = [ApexBatch(
    state=rng.rand(steps, obs).astype(np.float32),
    next_state=rng.rand(steps, obs).astype(np.float32),
    previous_action=rng.randint(0, 2, steps).astype(np.int32),
    action=rng.randint(0, 2, steps).astype(np.int32),
    reward=rng.randn(steps).astype(np.float32),
    done=(rng.rand(steps) < 0.1)) for _ in range(4)]
client = TransportClient(host, port, busy_timeout=60.0)
sent = 0
while sent < n_unrolls:
    sent += client.put_trajectories(trees[: n_unrolls - sent])
client.close()
print("PUT_DONE", sent)
"""


class TestTwoProcessE2E:
    def test_device_path_trains_against_real_shards_under_tcp_load(self):
        """A real child process PUTs unrolls over loopback TCP into the
        sharded ingest while the fused path feeds the learner: the
        learner trains through device entries only (host loop never
        sampled), the path stays alive, and the child's unrolls all
        land."""
        from distributed_reinforcement_learning_tpu.runtime.transport import (
            TransportServer, _make_queue)

        agent = ApexAgent(ApexConfig(obs_shape=(OBS,), num_actions=2))
        queue = _make_queue(32)
        svc = ShardedReplayService(2, 2048, mode="transition",
                                   scorer="max", seed=0)
        fifo = ReplayIngestFifo(svc, queue)
        learner = apex_runner.ApexLearner(
            agent, queue, WeightStore(), batch_size=8, replay_capacity=2048,
            rng=jax.random.PRNGKey(0), replay_service=svc,
            updates_per_call=2, train_start_unrolls=4)
        learner.device_path_force = True
        prepare, put = blob_ingest(fifo)
        for tree in make_unrolls(9, 6):
            put(prepare(bytes(codec.encode(tree))))
        train_until(learner, min_steps=2)  # warm: path active
        assert learner._device_path is not None

        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        server = TransportServer(fifo, learner.weights, host="127.0.0.1",
                                 port=port).start()
        n_unrolls = 24
        base = svc.ingested_blobs()
        proc = subprocess.Popen(
            [sys.executable, "-c", _PUT_CHILD, "127.0.0.1",
             str(server.port), str(n_unrolls), str(STEPS), str(OBS)],
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": str(REPO)},
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        try:
            deadline = time.monotonic() + 120.0
            while svc.ingested_blobs() < base + n_unrolls:
                assert time.monotonic() < deadline, "PUTs never all landed"
                if proc.poll() is not None and proc.returncode != 0:
                    raise AssertionError(proc.stderr.read()[-500:])
                learner.train()
            steps0 = learner.train_steps
            train_until(learner, min_steps=steps0 + 4, budget_s=60.0)
            out, _ = proc.communicate(timeout=60)
            assert f"PUT_DONE {n_unrolls}" in out
        finally:
            if proc.poll() is None:
                proc.kill()
            server.stop()
        dp = learner._device_path
        assert dp is not None and not dp.dead
        assert not learner._device_path_demoted
        assert dp.entries_out > 0 and dp.h2d_bytes > 0
        learner.close()
        svc.close()
        queue.close()

"""Sample-at-source (data/admission.py + the codec stamp extension):

- the actor-side scorer is BIT-EQUAL to the learner's ingest-side
  scorer, through the json stamp round trip and through a real stamped
  ingest (priority mass identical to the learner-scored ingest);
- the stamp extension frame's layout is pinned forever: unknown GREATER
  versions decode as a plain blob (forward compat — a new actor never
  poisons an old learner), truly corrupt frames raise;
- admission subsampling preserves the proportional-sampling
  distribution: per-transition keep counts match the analytic Bernoulli
  probabilities (chi-square, PR 6 style) and Horvitz-Thompson corrected
  priorities carry exactly p_i/q_i of transformed mass;
- zero lost priority mass: actor-side dropped mass == learner-side
  folded mass + the not-yet-drained ledger, end to end over real TCP;
- mixed stamped/unstamped fleets over real TCP and the shm-ring
  drainer: stamped connections fast-accept, unstamped ones latch to
  learner-side scoring, both land bit-identical replay contents;
- backpressure engage/release: PUT replies carry learner pressure,
  the controller's EWMA crosses the engage threshold and decays back.

All CPU-only, tier-1 safe.
"""

import json
import os
import struct
import threading
import time

import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.data import admission, codec
from distributed_reinforcement_learning_tpu.data.admission import (
    AdmissionController,
    DutyMeter,
    inverse_transform,
    transform,
)
from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue
from distributed_reinforcement_learning_tpu.data.replay_service import (
    LazyBlob,
    ReplayShard,
    ShardedReplayService,
    td_proxy_scorer,
)
from distributed_reinforcement_learning_tpu.runtime import replay_shard as rs_mod
from distributed_reinforcement_learning_tpu.runtime.replay_shard import (
    ReplayIngestFifo,
)
from distributed_reinforcement_learning_tpu.runtime.transport import (
    TransportClient,
    TransportServer,
)
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def make_unroll(rng, steps=16, obs=6, scale=1.0):
    return {
        "obs": rng.standard_normal((steps, obs)).astype(np.float32),
        "reward": (scale * rng.standard_normal(steps)).astype(np.float32),
        "done": (rng.random(steps) < 0.1).astype(np.float32),
    }


@pytest.fixture
def td_proxy_env(monkeypatch):
    """Actor-priority on, admission off, scorer pinned to td_proxy."""
    monkeypatch.setenv("DRL_REPLAY_SCORER", "td_proxy")
    monkeypatch.setenv("DRL_ACTOR_PRIORITY", "1")
    monkeypatch.setenv("DRL_ADMISSION", "0")
    monkeypatch.delenv("DRL_ADMISSION_PRESSURE", raising=False)


class TestScorerBitEquality:
    def test_stamp_round_trip_is_bit_equal_to_learner_scorer(self, td_proxy_env):
        rng = np.random.default_rng(0)
        tree = make_unroll(rng)
        ctrl = AdmissionController("transition", "td_proxy", seed=0)
        decision = ctrl.admit(tree)
        assert decision.send and decision.tree is None  # full admission
        blob = codec.stamp_blob(codec.encode(tree), decision.stamp)
        stamp, _ = codec.split_stamp(bytes(memoryview(blob)))
        got = np.asarray(stamp["pri"], np.float64)
        want = np.asarray(td_proxy_scorer(tree, True), np.float64)
        # Bit-equal through json: float64 repr round-trips exactly.
        assert got.tobytes() == want.tobytes()

    def test_stamped_ingest_priority_mass_equals_scored_ingest(self, td_proxy_env):
        rng = np.random.default_rng(1)
        trees = [make_unroll(rng, scale=s) for s in (1.0, 0.2, 3.0)]
        ctrl = AdmissionController("transition", "td_proxy", seed=0)

        def build(stamped: bool):
            svc = ShardedReplayService(1, 256, mode="transition",
                                       scorer="td_proxy", seed=0)
            fifo = ReplayIngestFifo(svc, TrajectoryQueue(8))
            for t in trees:
                if stamped:
                    d = ctrl.admit(t)
                    blob = codec.stamp_blob(codec.encode(t), d.stamp)
                else:
                    blob = codec.encode(t)
                assert fifo.ingest_blob(bytes(memoryview(blob)))
            return svc, fifo

        svc_a, fifo_a = build(stamped=True)
        svc_b, fifo_b = build(stamped=False)
        mass_a = svc_a.shards[0].mass_count()
        mass_b = svc_b.shards[0].mass_count()
        assert mass_a[1] == mass_b[1] > 0
        # Same transitions, same transform, same insert order: the sum
        # trees must agree bitwise, not approximately.
        assert mass_a[0].hex() == mass_b[0].hex()
        assert fifo_a.admission_stats()["stamped_blobs"] == len(trees)
        assert fifo_b.admission_stats()["scored_blobs"] == len(trees)
        svc_a.close()
        svc_b.close()

    def test_max_scorer_cannot_stamp(self, monkeypatch):
        monkeypatch.setenv("DRL_ACTOR_PRIORITY", "1")
        monkeypatch.setenv("DRL_REPLAY_SCORER", "max")
        assert admission.maybe_controller("apex") is None
        with pytest.raises(ValueError):
            AdmissionController("transition", "max")

    def test_algo_modes_pin_matches_runtime_map(self):
        # data/ must not import runtime/: the mode map is mirrored, and
        # this pin is what keeps the mirror honest.
        assert admission.ALGO_MODES == rs_mod._ALGO_MODE


class TestStampFrameCompat:
    """The extension frame layout is pinned FOREVER; only the json
    semantics are versioned."""

    def test_frame_layout_pinned(self):
        frame = codec.stamp_frame({"scorer": "td_proxy", "mode": "transition",
                                   "pri": [0.5], "t": 1})
        magic, version, ext_len = struct.unpack_from("<III", frame, 0)
        assert magic == 0x445254E5
        assert version == 1
        assert len(frame) == 12 + ext_len
        assert json.loads(frame[12:].decode())["t"] == 1

    def test_future_version_decodes_as_plain_blob(self):
        rng = np.random.default_rng(2)
        tree = make_unroll(rng)
        blob = bytes(memoryview(codec.encode(tree)))
        future = struct.pack("<III", 0x445254E5, 99, 4) + b"{}?!" + blob
        stamp, inner = codec.split_stamp(future)
        assert stamp is None
        got = codec.decode(future, copy=True)
        np.testing.assert_array_equal(got["reward"], tree["reward"])
        assert bytes(inner) == blob

    def test_corrupt_frame_raises_and_is_poison_dropped(self, td_proxy_env):
        blob = bytes(memoryview(codec.encode(make_unroll(np.random.default_rng(3)))))
        overrun = struct.pack("<III", 0x445254E5, 1, 1 << 20) + b"{}"
        with pytest.raises(ValueError):
            codec.split_stamp(overrun + blob)
        bad_json = struct.pack("<III", 0x445254E5, 1, 4) + b"!!!!" + blob
        with pytest.raises(ValueError):
            codec.split_stamp(bad_json)
        svc = ShardedReplayService(1, 64, mode="transition",
                                   scorer="td_proxy", seed=0)
        fifo = ReplayIngestFifo(svc, TrajectoryQueue(4))
        assert fifo.ingest_blob(overrun + blob)  # dropped, not fatal
        assert svc.shards[0].mass_count()[1] == 0
        svc.close()

    def test_unstamped_blob_latches_connection_to_scored_path(self, td_proxy_env):
        svc = ShardedReplayService(1, 64, mode="transition",
                                   scorer="td_proxy", seed=0)
        fifo = ReplayIngestFifo(svc, TrajectoryQueue(4))
        rng = np.random.default_rng(4)
        ctrl = AdmissionController("transition", "td_proxy", seed=0)
        tree = make_unroll(rng)
        plain = bytes(memoryview(codec.encode(tree)))
        assert fifo.ingest_blob(plain)  # unstamped: this thread latches
        d = ctrl.admit(tree)
        stamped = bytes(memoryview(codec.stamp_blob(codec.encode(tree), d.stamp)))
        assert fifo.ingest_blob(stamped)  # stamp now IGNORED (latched)
        stats = fifo.admission_stats()
        assert stats == {**stats, "stamped_blobs": 0, "scored_blobs": 2}
        svc.close()

    def test_unpack_blob_preserves_stamp(self, td_proxy_env):
        rng = np.random.default_rng(5)
        tree = make_unroll(rng)
        ctrl = AdmissionController("transition", "td_proxy", seed=0)
        d = ctrl.admit(tree)
        blob = codec.stamp_blob(codec.encode(tree), d.stamp)
        out = codec.unpack_blob(bytes(memoryview(blob)))
        stamp, _ = codec.split_stamp(bytes(memoryview(out)))
        assert stamp is not None and stamp["pri"] == d.stamp["pri"]


class TestAdmissionDistribution:
    def _pinned_controller(self, mu, pressure, monkeypatch, seed=0):
        monkeypatch.setenv("DRL_REPLAY_SCORER", "td_proxy")
        monkeypatch.setenv("DRL_ACTOR_PRIORITY", "1")
        monkeypatch.setenv("DRL_ADMISSION", "1")
        monkeypatch.setenv("DRL_ADMISSION_PRESSURE", str(pressure))
        ctrl = AdmissionController("transition", "td_proxy", seed=seed)
        ctrl._mu = mu  # pin the fleet mean: q_i is then analytic
        ctrl._mu_n = 1
        return ctrl

    def test_chi_square_keep_counts_match_bernoulli_probabilities(self, monkeypatch):
        rng = np.random.default_rng(6)
        tree = make_unroll(rng, steps=12, scale=0.4)
        pri = transform(td_proxy_scorer(tree, True))
        mu = float(pri.mean()) * 4.0  # low-priority unroll vs the fleet
        ctrl = self._pinned_controller(mu, pressure=0.7, monkeypatch=monkeypatch)
        # admit() advances the EWMA BEFORE the ladder reads it, so the
        # analytic q uses the post-decay mean.
        mu_eff = (AdmissionController.MU_DECAY * mu
                  + (1 - AdmissionController.MU_DECAY) * float(pri.mean()))
        s = min(1.0, (0.7 - ctrl.lo) / (ctrl.hi - ctrl.lo))
        f = 1.0 - s * (1.0 - ctrl.floor)
        q = np.minimum(np.maximum(f * pri / mu_eff, ctrl.floor), 1.0)
        n_trials = 4000
        keeps = np.zeros(len(q))
        for _ in range(n_trials):
            ctrl._mu = mu  # re-pin: admit() advances the EWMA
            d = ctrl.admit(tree)
            if not d.send:
                continue
            got = np.zeros(len(q))
            if d.tree is None:
                got[:] = 1.0
            else:
                # Identify survivors by their obs rows (bitwise unique).
                sent_rows = {r.tobytes() for r in np.asarray(d.tree["obs"])}
                for i, row in enumerate(np.asarray(tree["obs"])):
                    if row.tobytes() in sent_rows:
                        got[i] = 1.0
            keeps += got
        finally_refresh(monkeypatch)
        expected = n_trials * q
        # Chi-square over 2 cells (kept / dropped) per transition.
        chi2 = float(np.sum((keeps - expected) ** 2 / expected
                            + ((n_trials - keeps) - (n_trials - expected)) ** 2
                            / (n_trials - expected)))
        # dof = 12; P(chi2 > 32.9) ~ 0.001 — a deterministic seed keeps
        # this far below the bound in practice.
        assert chi2 < 32.9, (chi2, keeps / n_trials, q)

    def test_horvitz_thompson_corrections_preserve_expected_mass(self, monkeypatch):
        rng = np.random.default_rng(7)
        tree = make_unroll(rng, steps=10, scale=0.3)
        pri = transform(td_proxy_scorer(tree, True))
        mu = float(pri.mean()) * 3.0
        ctrl = self._pinned_controller(mu, pressure=0.8, monkeypatch=monkeypatch)
        total_mass = 0.0
        n_trials = 3000
        for _ in range(n_trials):
            ctrl._mu = mu
            d = ctrl.admit(tree)
            if not d.send:
                continue
            stamped = transform(np.asarray(d.stamp["pri"], np.float64))
            total_mass += float(stamped.sum()) - float(d.stamp.get("folded", 0.0))
        # Drops fold their mass into later stamps: add BOTH ledger ends
        # back so the estimator is unbiased over the whole window.
        snap = ctrl.snapshot()
        total_mass += snap["dropped_mass"]
        finally_refresh(monkeypatch)
        want = float(pri.sum())
        assert abs(total_mass / n_trials - want) / want < 0.02

    def test_q_equal_one_transitions_pass_through_bitwise(self, monkeypatch):
        rng = np.random.default_rng(8)
        tree = make_unroll(rng, steps=8, scale=0.5)
        err = np.asarray(td_proxy_scorer(tree, True), np.float64)
        pri = transform(err)
        # mu low enough that some q_i saturate at 1 but mean_p < mu.
        mu = float(pri.mean()) * 1.3
        ctrl = self._pinned_controller(mu, pressure=0.6, monkeypatch=monkeypatch)
        for _ in range(300):
            ctrl._mu = mu
            d = ctrl.admit(tree)
            if not d.send or d.tree is None:
                continue
            mu_eff = (AdmissionController.MU_DECAY * mu
                      + (1 - AdmissionController.MU_DECAY) * float(pri.mean()))
            s = min(1.0, (0.6 - ctrl.lo) / (ctrl.hi - ctrl.lo))
            f = 1.0 - s * (1.0 - ctrl.floor)
            q = np.minimum(np.maximum(f * pri / mu_eff, ctrl.floor), 1.0)
            sent_rows = {r.tobytes(): i for i, r in
                         enumerate(np.asarray(d.tree["obs"]))}
            for i, row in enumerate(np.asarray(tree["obs"])):
                j = sent_rows.get(row.tobytes())
                if j is None:
                    continue
                stamped = d.stamp["pri"][j]
                if q[i] >= 1.0:  # untouched: BITWISE equal
                    assert np.float64(stamped).tobytes() == err[i].tobytes()
                else:
                    np.testing.assert_allclose(
                        transform(np.float64(stamped)), pri[i] / q[i],
                        rtol=1e-12)
        finally_refresh(monkeypatch)

    def test_zero_lost_mass_ledger_local(self, monkeypatch):
        rng = np.random.default_rng(9)
        ctrl = self._pinned_controller(10.0, pressure=1.0,
                                       monkeypatch=monkeypatch)
        sent_folded = 0.0
        for i in range(400):
            ctrl._mu = 10.0  # everything far below the mean: max thinning
            d = ctrl.admit(make_unroll(rng, steps=6, scale=0.05))
            if d.send:
                sent_folded += float(d.stamp.get("folded", 0.0))
        snap = ctrl.snapshot()
        assert snap["dropped_unrolls"] > 0  # the drop path actually ran
        assert snap["dropped_mass"] == pytest.approx(
            sent_folded + ctrl.pending_folded_mass(), abs=1e-12)
        assert snap["folded_mass_sent"] == pytest.approx(sent_folded, abs=1e-12)
        finally_refresh(monkeypatch)


def finally_refresh(monkeypatch):
    """Re-resolve the gates after the monkeypatched env is gone."""
    monkeypatch.undo()


class TestLazyBlobDeferral:
    def test_sequence_opaque_backend_stores_blob_decodes_at_sample(self, td_proxy_env):
        rng = np.random.default_rng(10)
        tree = make_unroll(rng)
        shard = ReplayShard(0, 32, mode="sequence",
                            scorer=td_proxy_scorer, backend="python", seed=0)
        blob = bytes(memoryview(codec.encode(tree)))
        assert shard.ingest_stamped([0.7], blob=blob) == 1
        items, _, _, _ = shard.sample_with_priorities(1, np.random.RandomState(0))
        assert isinstance(items[0], LazyBlob)  # decode DEFERRED past ingest
        got = items[0].materialize()
        np.testing.assert_array_equal(got["reward"], tree["reward"])
        # Snapshot must never persist a LazyBlob.
        snap = shard.snapshot()
        assert all(not isinstance(it, LazyBlob) for it in snap["items"])

    def test_poison_blob_fails_on_ingest_not_at_sample(self):
        shard = ReplayShard(0, 32, mode="sequence",
                            scorer=td_proxy_scorer, backend="python", seed=0)
        with pytest.raises(ValueError):
            shard.ingest_stamped([0.7], blob=b"\x00" * 64)
        assert shard.mass_count()[1] == 0


class TestMixedFleetTcp:
    def test_stamped_and_unstamped_clients_share_one_learner(self, td_proxy_env):
        svc = ShardedReplayService(2, 512, mode="transition",
                                   scorer="td_proxy", seed=0)
        fifo = ReplayIngestFifo(svc, TrajectoryQueue(16))
        server = TransportServer(fifo, WeightStore(), host="127.0.0.1",
                                 port=_free_port()).start()
        rng = np.random.default_rng(11)
        steps = 12
        try:
            new = TransportClient("127.0.0.1", server.port)
            old = TransportClient("127.0.0.1", server.port)
            ctrl = admission.configure(new, "apex", seed=3)
            assert ctrl is not None and admission.configure(old, "x") is None
            n_new = n_old = 0
            for i in range(6):
                assert new.put_trajectory(make_unroll(rng, steps=steps))
                n_new += 1
                assert old.put_trajectories(
                    [make_unroll(rng, steps=steps)]) == 1
                n_old += 1
            deadline = time.monotonic() + 5.0
            want = (n_new + n_old) * steps
            while (sum(s.mass_count()[1] for s in svc.shards) < want
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            stats = fifo.admission_stats()
            assert stats["stamped_blobs"] == n_new
            assert stats["scored_blobs"] == n_old
            assert sum(s.mass_count()[1] for s in svc.shards) == want
            new.close()
            old.close()
        finally:
            server.stop()
            svc.close()

    def test_end_to_end_mass_conservation_across_drops(self, monkeypatch):
        monkeypatch.setenv("DRL_REPLAY_SCORER", "td_proxy")
        monkeypatch.setenv("DRL_ACTOR_PRIORITY", "1")
        monkeypatch.setenv("DRL_ADMISSION", "1")
        monkeypatch.setenv("DRL_ADMISSION_PRESSURE", "1.0")
        svc = ShardedReplayService(1, 512, mode="transition",
                                   scorer="td_proxy", seed=0)
        fifo = ReplayIngestFifo(svc, TrajectoryQueue(16))
        server = TransportServer(fifo, WeightStore(), host="127.0.0.1",
                                 port=_free_port()).start()
        rng = np.random.default_rng(12)
        try:
            client = TransportClient("127.0.0.1", server.port)
            ctrl = admission.configure(client, "apex", seed=4)
            ctrl._mu = 10.0
            ctrl._mu_n = 1
            for i in range(40):
                ctrl._mu = 10.0  # keep every unroll far below the mean
                assert client.put_trajectory(
                    make_unroll(rng, steps=6, scale=0.05))
            snap = ctrl.snapshot()
            assert snap["dropped_unrolls"] > 0
            assert client.stats["unrolls_admission_dropped"] == \
                snap["dropped_unrolls"]
            # ZERO lost mass: what the actor dropped is exactly what the
            # learner folded plus the not-yet-drained ledger.
            learner_folded = fifo.admission_stats()["folded_mass"]
            assert snap["dropped_mass"] == pytest.approx(
                learner_folded + ctrl.pending_folded_mass(), abs=1e-9)
            client.close()
        finally:
            server.stop()
            svc.close()


class TestShmRingPath:
    def test_ring_queue_stamps_and_drainer_fast_accepts(self, td_proxy_env):
        shm = pytest.importorskip(
            "distributed_reinforcement_learning_tpu.runtime.shm_ring")
        ring = shm.ShmRing.create(
            f"drladm-{os.getpid()}-{time.monotonic_ns()}", 1 << 20)
        svc = ShardedReplayService(1, 256, mode="transition",
                                   scorer="td_proxy", seed=0)
        fifo = ReplayIngestFifo(svc, TrajectoryQueue(8))
        drainer = shm.RingDrainer([ring], fifo)
        drainer.start()
        rng = np.random.default_rng(13)
        steps = 10
        try:
            rq = shm.RingQueue(ring, client=None)  # no TCP fallback needed
            ctrl = admission.configure(rq, "apex", seed=5)
            assert ctrl is not None
            for _ in range(4):
                assert rq.put(make_unroll(rng, steps=steps), timeout=2.0)
            deadline = time.monotonic() + 5.0
            while (svc.shards[0].mass_count()[1] < 4 * steps
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert svc.shards[0].mass_count()[1] == 4 * steps
            assert fifo.admission_stats()["stamped_blobs"] == 4
        finally:
            drainer.stop()
            ring.close()
            ring.unlink()
            svc.close()


class TestBackpressure:
    def test_put_reply_pressure_engages_and_releases(self, monkeypatch):
        monkeypatch.setenv("DRL_REPLAY_SCORER", "td_proxy")
        monkeypatch.setenv("DRL_ACTOR_PRIORITY", "1")
        monkeypatch.setenv("DRL_ADMISSION", "1")
        monkeypatch.delenv("DRL_ADMISSION_PRESSURE", raising=False)
        queue = TrajectoryQueue(capacity=10)
        server = TransportServer(queue, WeightStore(), host="127.0.0.1",
                                 port=_free_port()).start()
        rng = np.random.default_rng(14)
        try:
            client = TransportClient("127.0.0.1", server.port)
            ctrl = admission.configure(client, "apex", seed=6)
            # Engage: fill the learner queue to 90% so replies report
            # high pressure; the EWMA must cross the engage threshold.
            for _ in range(8):
                queue.put(make_unroll(rng), timeout=1.0)
            for _ in range(6):
                assert client.put_trajectory(make_unroll(rng))
                while queue.size() > 8:  # hold fill at ~0.9, never full
                    queue.get(timeout=1.0)
            assert ctrl.pressure() >= ctrl.lo
            # Release: drain the queue; low-pressure replies decay the
            # EWMA back below the engage threshold.
            while queue.get(timeout=0.1) is not None:
                pass
            for _ in range(10):
                assert client.put_trajectory(make_unroll(rng))
                queue.get(timeout=1.0)
            assert ctrl.pressure() < ctrl.lo
            client.close()
        finally:
            server.stop()
            queue.close()

    def test_duty_meter_decays_idle(self):
        meter = DutyMeter()
        for _ in range(3):
            meter.note(0.2)
        assert meter.total() == pytest.approx(0.6)
        assert 0.0 <= meter.value() <= 1.0

    def test_ingest_pressure_permille_range(self, td_proxy_env):
        svc = ShardedReplayService(1, 64, mode="transition",
                                   scorer="td_proxy", seed=0)
        fifo = ReplayIngestFifo(svc, TrajectoryQueue(4))
        assert 0 <= fifo.ingest_pressure() <= 1000
        svc.close()


class TestTransforms:
    def test_inverse_transform_is_exact_inverse(self):
        errors = np.asarray([0.0, 0.1, 1.0, 5.0, 123.456], np.float64)
        np.testing.assert_allclose(
            inverse_transform(transform(errors)), errors, atol=1e-12)


"""Codec encode fast path (data/codec.py): schema-cache correctness
(cache-hit blobs byte-identical to cold encodes, mixed schemas
interleaved, dtype/shape-change invalidation), the single-allocation
decode(copy=True) gather, frame-stack dedup round trips (bit-for-bit vs
the undeduped path, stacked and non-stacked schemas, mid-unroll resets),
`unpack_blob`/`blob_ingest` routing for blob-native queues, and the
two-process shm-ring e2e re-run with DRL_OBS_DEDUP=1.
"""

import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.data import codec
from distributed_reinforcement_learning_tpu.data.fifo import (
    TrajectoryQueue,
    blob_ingest,
    put_round,
)

REPO = Path(__file__).resolve().parent.parent
WORKER = REPO / "tests" / "shm_ring_worker.py"

sys.path.insert(0, str(REPO / "tests"))
from shm_ring_worker import make_stacked_trajectories  # noqa: E402
from test_shm_ring import assert_trees_bit_identical  # noqa: E402


@pytest.fixture(autouse=True)
def _cache_on(monkeypatch):
    """Every test here runs with the schema cache forced ON and a clean
    cache, whatever the default."""
    monkeypatch.setenv("DRL_CODEC_CACHE", "1")
    monkeypatch.delenv("DRL_OBS_DEDUP", raising=False)
    codec.clear_caches()
    yield
    codec.clear_caches()


def stacked_obs(T=12, H=16, W=16, S=4, seed=0):
    """[T, H, W, S] uint8 with real newest-last stacking (obs[t,:,:,j]
    == plane[t+j]) — the redundancy the dedup packer targets."""
    rng = np.random.RandomState(seed)
    planes = rng.randint(0, 255, (T + S - 1, H, W)).astype(np.uint8)
    return np.lib.stride_tricks.sliding_window_view(planes, S, axis=0).copy(), planes


def mixed_tree(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "obs": rng.randint(0, 255, (6, 5, 4)).astype(np.uint8),
        "reward": rng.standard_normal(6).astype(np.float32),
        "nested": {"h": rng.standard_normal((2, 8)).astype(np.float32),
                   "step": np.int64(seed)},
        "done": rng.rand(6) < 0.5,
    }


class TestSchemaCache:
    def test_warm_encode_byte_identical_to_cold(self):
        tree = mixed_tree()
        cold = bytes(codec.encode(tree))
        warm = bytes(codec.encode(tree))
        assert cold == warm
        s = codec.cache_stats()
        assert s["encode_misses"] == 1 and s["encode_hits"] == 1

    def test_cache_off_produces_same_bytes(self, monkeypatch):
        tree = mixed_tree()
        cached = bytes(codec.encode(tree))
        monkeypatch.setenv("DRL_CODEC_CACHE", "0")
        assert bytes(codec.encode(tree)) == cached

    def test_mixed_schemas_interleaved(self):
        """Alternating schemas must each hit their own cached plan and
        stay byte-identical to their cold encodes."""
        a, b = mixed_tree(1), {"x": np.arange(10, dtype=np.int32),
                               "y": np.float32(2.5)}
        cold_a, cold_b = bytes(codec.encode(a)), bytes(codec.encode(b))
        for _ in range(3):
            assert bytes(codec.encode(a)) == cold_a
            assert bytes(codec.encode(b)) == cold_b
        out = codec.decode(codec.encode(a), copy=True)
        np.testing.assert_array_equal(out["obs"], a["obs"])

    def test_dtype_change_invalidates(self):
        t1 = {"x": np.arange(8, dtype=np.float32)}
        t2 = {"x": np.arange(8, dtype=np.int32)}
        codec.encode(t1)
        out = codec.decode(codec.encode(t2))
        assert out["x"].dtype == np.int32
        np.testing.assert_array_equal(out["x"], t2["x"])
        assert codec.cache_stats()["encode_misses"] == 2  # distinct plans

    def test_shape_change_invalidates(self):
        t1 = {"x": np.zeros((4, 4), np.uint8)}
        t2 = {"x": np.zeros((4, 5), np.uint8)}
        codec.encode(t1)
        out = codec.decode(codec.encode(t2))
        assert out["x"].shape == (4, 5)
        assert codec.cache_stats()["encode_misses"] == 2

    def test_structure_change_invalidates(self):
        from collections import namedtuple

        NT = namedtuple("Unroll", ["state", "reward"])
        t1 = NT(state=np.ones((2, 3), np.uint8), reward=np.zeros(2, np.float32))
        codec.encode(t1)
        t2 = {"state": np.ones((2, 3), np.uint8), "reward": np.zeros(2, np.float32)}
        out = codec.decode(codec.encode(t2))
        assert isinstance(out, dict)
        out1 = codec.decode(codec.encode(t1))
        assert out1.__class__.__name__ == "Unroll"

    def test_decode_layout_cache_hits(self):
        tree = mixed_tree()
        blob = bytes(codec.encode(tree))
        first = codec.decode(blob, copy=True)
        second = codec.decode(blob, copy=True)
        assert codec.cache_stats()["decode_hits"] >= 1
        assert_trees_bit_identical(first, second)
        assert_trees_bit_identical(first, tree)

    def test_decode_copy_detaches_and_is_writable(self):
        tree = mixed_tree()
        out = codec.decode(codec.encode(tree), copy=True)
        out["obs"][0] = 0  # writable (one owned buffer backs the leaves)
        assert tree["obs"].max() > 0  # and detached from the source

    def test_noncontiguous_and_scalar_leaves(self):
        base = np.arange(24, dtype=np.float32).reshape(4, 6)
        tree = {"t": base.T, "s": 3.5, "i": 7}  # transposed view + scalars
        cold = bytes(codec.encode(tree))
        assert bytes(codec.encode(tree)) == cold
        out = codec.decode(cold)
        np.testing.assert_array_equal(out["t"], base.T)
        assert float(out["s"]) == 3.5 and int(out["i"]) == 7


class TestFrameStackDedup:
    def test_roundtrip_bit_identical_and_smaller(self):
        obs, _ = stacked_obs()
        tree = {"obs": obs, "reward": np.arange(12, dtype=np.float32)}
        plain = bytes(codec.encode(tree))
        packed = bytes(codec.encode(tree, dedup=True))
        assert len(packed) < len(plain) * 0.5
        assert codec.is_packed(packed) and not codec.is_packed(plain)
        # dedup-on decode output == dedup-off decode output, bit for bit.
        assert_trees_bit_identical(codec.decode(packed, copy=True),
                                   codec.decode(plain, copy=True))
        np.testing.assert_array_equal(codec.decode(packed)["obs"], obs)
        s = codec.cache_stats()
        assert s["dedup_blobs"] == 1 and s["dedup_bytes_saved"] > 0
        # Content-keyed dedup plans are accounted separately — they must
        # not drag down the schema-cache hit rate operators read.
        assert s["dedup_plan_misses"] == 1
        packed2 = bytes(codec.encode(tree, dedup=True))
        assert packed2 == packed
        assert codec.cache_stats()["dedup_plan_hits"] == 1

    def test_mid_unroll_reset_reconstructs_exactly(self):
        obs, planes = stacked_obs()
        obs[5] = 0                      # episode reset: stack zeroed,
        obs[5, :, :, -1] = planes[5 + 3]  # only the newest plane is real
        tree = {"obs": obs}
        packed = codec.encode(tree, dedup=True)
        np.testing.assert_array_equal(codec.decode(packed)["obs"], obs)
        # The discontinuity costs one full stack, not the whole leaf.
        assert len(packed) < len(codec.encode(tree)) * 0.6

    def test_non_stacked_passthrough_unchanged(self):
        """Random (non-stacked) uint8 obs and non-4d schemas must encode
        byte-identically with dedup requested — no packing, no growth."""
        rng = np.random.RandomState(3)
        t1 = {"obs": rng.randint(0, 255, (12, 16, 16, 4)).astype(np.uint8)}
        assert bytes(codec.encode(t1, dedup=True)) == bytes(codec.encode(t1))
        t2 = mixed_tree()
        assert bytes(codec.encode(t2, dedup=True)) == bytes(codec.encode(t2))

    def test_interleaved_stacked_and_plain_schemas(self):
        obs, _ = stacked_obs(seed=5)
        stacked = {"obs": obs}
        plain = mixed_tree(5)
        for _ in range(3):
            np.testing.assert_array_equal(
                codec.decode(codec.encode(stacked, dedup=True))["obs"], obs)
            assert_trees_bit_identical(
                codec.decode(codec.encode(plain, dedup=True), copy=True), plain)

    def test_general_stack_width_path(self):
        """S != 4 exercises the elementwise compare fallback (the u32
        word trick only covers S*itemsize == 4)."""
        obs, _ = stacked_obs(S=2)
        packed = codec.encode({"obs": obs}, dedup=True)
        assert codec.is_packed(packed)
        np.testing.assert_array_equal(codec.decode(packed)["obs"], obs)

    def test_unpack_blob_restores_plain_layout(self):
        obs, _ = stacked_obs(seed=7)
        tree = {"obs": obs, "r": np.ones(12, np.float32)}
        plain = bytes(codec.encode(tree))
        packed = codec.encode(tree, dedup=True)
        assert bytes(codec.unpack_blob(packed)) == plain
        unpacked_already = codec.encode(tree)
        assert codec.unpack_blob(unpacked_already) is unpacked_already


class TestBlobIngest:
    def test_pytree_queue_reconstructs_before_queue(self):
        obs, _ = stacked_obs(seed=11)
        tree = {"obs": obs}
        q = TrajectoryQueue(capacity=4)
        prepare, put = blob_ingest(q)
        put(prepare(codec.encode(tree, dedup=True)))
        got = q.get(timeout=1.0)
        np.testing.assert_array_equal(got["obs"], obs)
        got["obs"][0] = 0  # a copy, not a view of the (reusable) blob

    def test_native_queue_gets_plain_blobs(self):
        native = pytest.importorskip(
            "distributed_reinforcement_learning_tpu.data.native")
        if not native.native_available():
            pytest.skip("native library unavailable")
        from distributed_reinforcement_learning_tpu.data.fifo import stack_pytrees

        obs, _ = stacked_obs(seed=13)
        trees = [{"obs": obs, "i": np.int64(k)} for k in range(4)]
        q = native.NativeTrajectoryQueue(8)
        prepare, put = blob_ingest(q)
        for t in trees:
            put(prepare(codec.encode(t, dedup=True)))
        batch = q.get_batch(4)  # the single-header native gather path
        want = stack_pytrees(trees)
        np.testing.assert_array_equal(batch["obs"], want["obs"])
        np.testing.assert_array_equal(batch["i"], want["i"])


class TestPutBatchKnob:
    def test_default_ships_whole_round(self, monkeypatch):
        monkeypatch.delenv("DRL_PUT_BATCH", raising=False)

        calls = []

        class Q:
            def put_many(self, items):
                calls.append(len(items))
                return len(items)

        put_round(Q(), [object()] * 6)
        assert calls == [6]

    def test_put_batch_chunks_round(self, monkeypatch):
        monkeypatch.setenv("DRL_PUT_BATCH", "4")

        calls = []

        class Q:
            def put_many(self, items):
                calls.append(len(items))
                return len(items)

        put_round(Q(), [object()] * 10)
        assert calls == [4, 4, 2]

    def test_invalid_value_is_loud(self, monkeypatch):
        monkeypatch.setenv("DRL_PUT_BATCH", "banana")
        from distributed_reinforcement_learning_tpu.data.fifo import put_batch_size

        with pytest.raises(ValueError, match="DRL_PUT_BATCH"):
            put_batch_size()
        monkeypatch.setenv("DRL_PUT_BATCH", "-3")
        assert put_batch_size() == 0


class TestDedupTwoProcessE2E:
    def test_shm_ring_with_dedup_on_is_bit_identical(self):
        """The shm-ring two-process e2e re-run with DRL_OBS_DEDUP=1: a
        real child process encodes the stacked fixture with dedup and
        ships it over the ring; the drained (reconstructed) trajectories
        must be bit-identical to the locally built set."""
        from distributed_reinforcement_learning_tpu.runtime.shm_ring import (
            RingDrainer, ShmRing)

        seed, count = 21, 6
        name = f"drltest-dedup-{os.getpid()}-{time.monotonic_ns()}"
        ring = ShmRing.create(name, 1 << 20)
        q = TrajectoryQueue(capacity=count + 2)
        drainer = RingDrainer([ring], q).start()
        proc = subprocess.Popen(
            [sys.executable, str(WORKER), name, str(seed), str(count), "stacked"],
            env={**os.environ, "JAX_PLATFORMS": "cpu", "DRL_OBS_DEDUP": "1",
                 "DRL_CODEC_CACHE": "1"},
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        try:
            got = [q.get(timeout=60.0) for _ in range(count)]
            assert proc.wait(timeout=60) == 0, proc.stderr.read()[-800:]
        finally:
            drainer.stop()
        assert all(item is not None for item in got)
        want = make_stacked_trajectories(seed, count)
        for g, w in zip(got, want):
            assert_trees_bit_identical(g, w)

"""C++ data plane: codec round-trips, native queue semantics (incl. threaded
producer/consumer backpressure), native SumTree parity with the Python tree,
and native replay parity with the Python PrioritizedReplay."""

import threading

import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.data import codec
from distributed_reinforcement_learning_tpu.data.replay import (
    NativePrioritizedReplay,
    PrioritizedReplay,
    SumTree,
)

native = pytest.importorskip("distributed_reinforcement_learning_tpu.data.native")
if not native.native_available():
    pytest.skip("native library failed to build", allow_module_level=True)

from distributed_reinforcement_learning_tpu.data.native import (  # noqa: E402
    NativeByteQueue,
    NativeSumTree,
    NativeTrajectoryQueue,
)


def test_makefile_builds_the_sources_native_py_builds():
    """`make` and the first-import build must produce the same library
    (the Makefile once lacked batch_stack.cc), and a build that works
    reports no error."""
    import os
    import re

    makefile = open(os.path.join(native._CPP_DIR, "Makefile")).read()
    srcs = re.search(r"^SRCS := (.*)$", makefile, re.M).group(1).split()
    assert tuple(srcs) == native._SOURCES
    assert native.build_error() is None


class TestCodec:
    def test_roundtrip_dict(self):
        tree = {
            "obs": np.arange(24, dtype=np.uint8).reshape(2, 3, 4),
            "reward": np.float32(1.5) * np.ones(5, np.float32),
            "nested": {"a": np.array([1, 2], np.int64), "b": np.zeros((), np.float64)},
        }
        out = codec.decode(codec.encode(tree))
        assert set(out) == {"obs", "reward", "nested"}
        np.testing.assert_array_equal(out["obs"], tree["obs"])
        np.testing.assert_array_equal(out["nested"]["a"], tree["nested"]["a"])
        assert out["nested"]["b"].shape == ()

    def test_roundtrip_namedtuple(self):
        from collections import namedtuple

        NT = namedtuple("Unroll", ["state", "reward"])
        src = NT(state=np.ones((2, 3), np.uint8), reward=np.zeros(2, np.float32))
        out = codec.decode(codec.encode(src))
        assert out.__class__.__name__ == "Unroll"
        np.testing.assert_array_equal(out.state, src.state)  # attribute access survives
        np.testing.assert_array_equal(out.reward, src.reward)

    def test_roundtrip_sequences(self):
        tree = [np.ones(3), (np.zeros(2, np.int32), np.full(4, 7.0))]
        out = codec.decode(codec.encode(tree))
        assert isinstance(out, list) and isinstance(out[1], tuple)
        np.testing.assert_array_equal(out[1][1], tree[1][1])

    def test_alignment(self):
        blob = codec.encode({"a": np.ones(1, np.uint8), "b": np.ones(7, np.float64)})
        out = codec.decode(blob)
        # decode views must be aligned enough for float64 frombuffer
        assert out["b"].dtype == np.float64

    def test_bad_magic(self):
        with pytest.raises(ValueError, match="magic"):
            codec.decode(b"\x00" * 64)

    def test_copy_detaches(self):
        src = {"x": np.arange(4, dtype=np.int32)}
        out = codec.decode(codec.encode(src), copy=True)
        out["x"][0] = 99
        assert src["x"][0] == 0


class TestNativeByteQueue:
    def test_fifo_order(self):
        q = NativeByteQueue(8)
        for i in range(5):
            assert q.put(bytes([i]) * (i + 1))
        assert q.size() == 5
        for i in range(5):
            assert q.get() == bytes([i]) * (i + 1)

    def test_put_timeout_when_full(self):
        q = NativeByteQueue(2)
        q.put(b"a"), q.put(b"b")
        assert not q.put(b"c", timeout=0.05)

    def test_get_timeout_when_empty(self):
        q = NativeByteQueue(2)
        assert q.get(timeout=0.05) is None

    def test_close_unblocks_and_raises(self):
        q = NativeByteQueue(1)
        q.put(b"x")
        t = threading.Thread(target=q.close)
        t.start()
        t.join()
        assert q.get() == b"x"  # drains before reporting closed
        assert q.get(timeout=0.05) is None
        with pytest.raises(RuntimeError, match="closed"):
            q.put(b"y")

    def test_batch_all_or_nothing(self):
        q = NativeByteQueue(8)
        q.put(b"aa"), q.put(b"bb")
        assert q.get_batch_blobs(3, item_cap=16, timeout=0.05) is None
        assert q.size() == 2  # rollback left both items
        q.put(b"cc")
        blobs = q.get_batch_blobs(3, item_cap=16)
        assert [bytes(b) for b in blobs] == [b"aa", b"bb", b"cc"]

    def test_threaded_producers_consumers(self):
        q = NativeByteQueue(4)  # small: forces backpressure
        n_per, n_prod = 200, 4
        seen = []
        seen_lock = threading.Lock()

        def produce(k):
            for i in range(n_per):
                q.put(int(k * n_per + i).to_bytes(4, "little"))

        def consume():
            while True:
                b = q.get(timeout=2.0)
                if b is None:
                    return
                with seen_lock:
                    seen.append(int.from_bytes(b, "little"))

        prods = [threading.Thread(target=produce, args=(k,)) for k in range(n_prod)]
        cons = [threading.Thread(target=consume) for _ in range(2)]
        for t in prods + cons:
            t.start()
        for t in prods:
            t.join()
        for t in cons:
            t.join()
        assert sorted(seen) == list(range(n_per * n_prod))


class TestNativeTrajectoryQueue:
    def test_pytree_roundtrip_and_batch(self):
        q = NativeTrajectoryQueue(8)
        for i in range(4):
            q.put({"obs": np.full((3, 2), i, np.uint8), "r": np.float32(i)})
        batch = q.get_batch(4)
        assert batch["obs"].shape == (4, 3, 2)
        np.testing.assert_array_equal(batch["r"], np.arange(4, dtype=np.float32))

    def test_interface_matches_python_queue(self):
        q = NativeTrajectoryQueue(2)
        q.put({"x": np.ones(2)})
        assert q.size() == 1
        item = q.get()
        np.testing.assert_array_equal(item["x"], np.ones(2))
        assert q.get(timeout=0.05) is None


class TestNativeSumTree:
    def test_parity_with_python_tree(self):
        rng = np.random.RandomState(0)
        py, nt = SumTree(64), NativeSumTree(64)
        prios = rng.uniform(0.1, 5.0, size=100)  # wraps the ring
        for p in prios:
            py.add(float(p), data="x")
        nt.add_batch(prios)
        assert len(py) == len(nt) == 64
        assert py.total == pytest.approx(nt.total, rel=1e-12)
        values = rng.uniform(0, py.total, size=50)
        got_idx, got_p = nt.get_batch(values)
        for v, i, p in zip(values, got_idx, got_p):
            pi, pp, _ = py.get(float(v))
            assert pi == i and pp == pytest.approx(p, rel=1e-12)

    def test_update_batch(self):
        nt = NativeSumTree(4)
        slots = nt.add_batch(np.array([1.0, 2.0, 3.0]))
        tree_idxs = slots + nt.capacity - 1
        nt.update_batch(tree_idxs, np.array([5.0, 5.0, 5.0]))
        assert nt.total == pytest.approx(15.0)
        assert nt.leaf_priority(int(tree_idxs[0])) == pytest.approx(5.0)


class TestNativeReplayParity:
    def _fill(self, mem, n=50, seed=3):
        rng = np.random.RandomState(seed)
        errs = rng.uniform(0, 4, size=n)
        mem.add_batch(errs, [{"i": i} for i in range(n)])
        return errs

    def test_sample_statistics_match_python(self):
        py, nt = PrioritizedReplay(64), NativePrioritizedReplay(64)
        self._fill(py), self._fill(nt)
        assert py.tree.total == pytest.approx(nt.tree.total, rel=1e-12)
        rng = np.random.RandomState(7)
        items, idxs, w = nt.sample(32, rng)
        assert len(items) == 32 and all(it is not None for it in items)
        assert w.max() == pytest.approx(1.0)
        py.sample(32, np.random.RandomState(7))
        assert nt.beta == pytest.approx(py.beta)  # both anneal by the same increment

    def test_high_priority_sampled_more(self):
        nt = NativePrioritizedReplay(64)
        nt.add_batch(np.array([100.0] + [0.01] * 49), [{"i": i} for i in range(50)])
        rng = np.random.RandomState(0)
        counts = sum(
            sum(1 for it in nt.sample(16, rng)[0] if it["i"] == 0) for _ in range(20)
        )
        assert counts > 100  # the 100x-priority item dominates

    def test_update_changes_sampling(self):
        nt = NativePrioritizedReplay(8)
        tree_idxs = nt.add_batch(np.ones(8), [{"i": i} for i in range(8)])
        nt.update_batch(np.array(tree_idxs), np.array([100.0] + [0.0] * 7))
        rng = np.random.RandomState(0)
        items, _, _ = nt.sample(16, rng)
        assert sum(1 for it in items if it["i"] == 0) >= 12

    def test_single_add_update(self):
        nt = NativePrioritizedReplay(4)
        idx = nt.add(2.0, {"a": 1})
        nt.update(idx, 0.5)
        assert len(nt) == 1


class TestNativeBatchGather:
    """The single-header fast path in NativeTrajectoryQueue.get_batch
    (L native field gathers) must produce exactly what per-blob decode +
    np.stack produces — every dtype, scalar leaves, nested structure."""

    def _tree(self, i):
        return {
            "obs": np.full((4, 3), i, np.uint8),
            "nested": {"h": np.full((2, 5), 0.5 * i, np.float32)},
            "done": np.asarray([i % 2 == 0], bool),
            "step": np.int64(i),  # 0-d leaf
        }

    def test_matches_decode_and_stack(self):
        from distributed_reinforcement_learning_tpu.data.fifo import stack_pytrees

        q = NativeTrajectoryQueue(16)
        trees = [self._tree(i) for i in range(8)]
        for t in trees:
            q.put(t)
        got = q.get_batch(8)
        want = stack_pytrees(trees)
        assert got.keys() == want.keys()
        for k in want:
            np.testing.assert_array_equal(
                np.asarray(got[k] if k != "nested" else got[k]["h"]),
                np.asarray(want[k] if k != "nested" else want[k]["h"]),
            )
        assert got["step"].dtype == np.int64 and got["step"].shape == (8,)
        assert got["done"].dtype == bool

    def test_fresh_wrapper_over_shared_queue(self):
        """The learner-side wrapper (item_cap unknown) still batch-pops
        via the head-peek stride path and assembles correctly."""
        q1 = NativeTrajectoryQueue(16)
        for i in range(4):
            q1.put(self._tree(i))
        # Normal construction, then swap in the shared byte queue — one
        # private touchpoint instead of replicating __init__'s fields.
        q2 = NativeTrajectoryQueue(16)
        q2._q = q1._q
        batch = q2.get_batch(4)
        np.testing.assert_array_equal(batch["step"], np.arange(4))

    def test_single_item_batch(self):
        q = NativeTrajectoryQueue(4)
        q.put(self._tree(7))
        batch = q.get_batch(1)
        assert batch["obs"].shape == (1, 4, 3)
        assert int(batch["step"][0]) == 7

    def test_put_many(self):
        q = NativeTrajectoryQueue(16)
        assert q.put_many([self._tree(i) for i in range(6)]) == 6
        batch = q.get_batch(6)
        np.testing.assert_array_equal(batch["step"], np.arange(6))

    def test_put_many_stops_at_capacity(self):
        q = NativeTrajectoryQueue(4)
        assert q.put_many([self._tree(i) for i in range(6)], timeout=0.2) == 4

    def test_pooled_get_batch_reuses_buffers_and_stays_correct(self):
        """pooled=True must (a) produce byte-identical batches to the
        unpooled path and (b) actually rotate through POOL_SETS reused
        buffer sets (the whole point: no per-dequeue allocation)."""
        q = NativeTrajectoryQueue(32)
        seen_ptrs = []
        for round_i in range(5):
            trees = [self._tree(10 * round_i + j) for j in range(4)]
            for t in trees:
                q.put(t)
            batch = q.get_batch(4, pooled=True)
            from distributed_reinforcement_learning_tpu.data.fifo import stack_pytrees
            want = stack_pytrees(trees)
            np.testing.assert_array_equal(batch["obs"], want["obs"])
            np.testing.assert_array_equal(batch["nested"]["h"], want["nested"]["h"])
            np.testing.assert_array_equal(batch["step"], want["step"])
            seen_ptrs.append(batch["obs"].ctypes.data)
        # Rotation: call k and k+POOL_SETS share the same destination.
        sets = NativeTrajectoryQueue.POOL_SETS
        assert seen_ptrs[0] == seen_ptrs[sets] == seen_ptrs[2 * sets]
        assert len(set(seen_ptrs[:sets])) == sets


class TestConcurrentBatchConsumers:
    """Two threads calling get_batch on ONE wrapper: the scratch
    try-lock must keep every assembled batch internally consistent (the
    loser of the race uses a fresh buffer), with no corruption across
    the shared byte queue."""

    def test_parallel_get_batch_is_consistent(self):
        q = NativeTrajectoryQueue(256)
        n_batches, B = 12, 8

        def tree(i):
            return {"tag": np.full((16,), i, np.int64),
                    "payload": np.full((64,), float(i), np.float32)}

        for i in range(n_batches * B):
            q.put(tree(i))

        results, errors = [], []

        def consume():
            try:
                while True:
                    batch = q.get_batch(B, timeout=0.5)
                    if batch is None:
                        return
                    results.append(batch)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        ts = [threading.Thread(target=consume) for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert not errors, errors
        assert len(results) == n_batches
        seen = []
        for batch in results:
            # Each row must be self-consistent: tag and payload written
            # by the same put (a torn scratch would mix rows).
            for j in range(B):
                tag = int(batch["tag"][j][0])
                assert np.all(batch["tag"][j] == tag)
                np.testing.assert_allclose(batch["payload"][j], float(tag))
                seen.append(tag)
        assert sorted(seen) == list(range(n_batches * B))


class TestThreadSanitizer:
    """Build the C++ stress workload under -fsanitize=thread and run it:
    any data race in the ring queue or SumTree fails the test via
    TSAN's nonzero exit (the reference has no race detection at all —
    SURVEY §5.2)."""

    def test_stress_under_tsan(self):
        import os
        import subprocess

        # Known-FALSE-POSITIVE on this container's toolchain, pinned
        # 2026-08-04 (the anakin_mesh / impala_stale env-skip
        # precedent). Analysis: TSan's FIRST report is "double lock of
        # a mutex" at rq_put's scoped `unique_lock lock(q->mutex)` —
        # impossible in the source (every hold is a scoped RAII lock;
        # an actual std::mutex double lock would deadlock, yet the
        # binary finishes "stress ok: consumed=8000") — and every
        # subsequent "data race" shows the accessing thread ALREADY
        # holding the mutex ("mutexes: write M9"). That is the
        # signature of TSan losing the unlock/relock INSIDE a timed
        # condition wait: ring_queue.cc waits via
        # condition_variable::wait_for -> wait_until<steady_clock>,
        # which libstdc++ lowers to pthread_cond_clockwait on
        # glibc >= 2.30 (this container: glibc 2.31) — and gcc 10's
        # libtsan has NO pthread_cond_clockwait interceptor
        # (`nm -D libtsan.so.0 | grep clockwait` is empty; the
        # interceptor landed in gcc 11). Each missed wait makes the
        # re-acquired mutex look double-locked and every post-wait
        # access look unsynchronized -> 48 phantom warnings, exit 66.
        # The same queue is race-checked for real by this file's
        # two-thread python stress and by scripts/sanitize.sh's
        # instrumented runs; force with DRL_RUN_NATIVE_TSAN=1 on a
        # gcc >= 11 toolchain.
        if os.environ.get("DRL_RUN_NATIVE_TSAN", "") != "1":
            pytest.skip("gcc-10 libtsan lacks the pthread_cond_clockwait "
                        "interceptor; timed condition waits yield phantom "
                        "double-lock/data-race reports on this container "
                        "(DRL_RUN_NATIVE_TSAN=1 forces)")

        cpp = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "distributed_reinforcement_learning_tpu", "cpp")
        build = subprocess.run(["make", "tsan"], cwd=cpp, capture_output=True,
                               text=True, timeout=120)
        if build.returncode != 0:
            pytest.skip(f"tsan build unavailable: {build.stderr[-200:]}")
        run = subprocess.run([os.path.join(cpp, "build", "stress_tsan")],
                             capture_output=True, text=True, timeout=300)
        assert run.returncode == 0, (run.stdout, run.stderr[-2000:])
        assert "ThreadSanitizer" not in run.stderr, run.stderr[-2000:]
        assert "stress ok" in run.stdout

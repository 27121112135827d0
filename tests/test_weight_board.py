"""Shm weight board: seqlock correctness, WeightStore mirroring, TCP
fallback, gating, and the two-process e2e (runtime/weight_board.py).

The board is the learner->actor mirror of the PR-3 shm ring: weights
pulled through it must be BIT-IDENTICAL to TCP pulls — including across
a version flip mid-pull (the seqlock retry) and after a rollback
republish (versions legitimately go backward)."""

import hashlib
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.data import codec
from distributed_reinforcement_learning_tpu.runtime.weight_board import (
    BoardClosed,
    BoardWeights,
    WeightBoard,
    attach_board_weights,
    board_enabled,
    serve_board,
)
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

WORKER = Path(__file__).resolve().parent / "weight_board_worker.py"


def _params(seed: int):
    rng = np.random.RandomState(seed)
    return {
        "conv": {"w": rng.standard_normal((3, 3, 4, 8)).astype(np.float32),
                 "b": rng.standard_normal(8).astype(np.float32)},
        "head": {"w": rng.standard_normal((32, 6)).astype(np.float32)},
        "step": np.int64(seed),
    }


def _board(name_tag: str, slot=1 << 20) -> WeightBoard:
    return WeightBoard.create(f"drltest-wb-{name_tag}-{os.getpid()}", slot)


def assert_trees_bit_identical(a, b):
    la, lb = [], []
    import jax

    jax.tree.map(lambda x: la.append(np.asarray(x)), a)
    jax.tree.map(lambda x: lb.append(np.asarray(x)), b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


class TestBoardBasics:
    def test_round_trip_bit_identical(self):
        board = _board("rt")
        try:
            params = _params(1)
            blob = codec.encode(params, cache=True)
            board.publish_blob(blob, 7)
            got, version = board.read_blob(-1)
            assert version == 7
            assert bytes(got) == bytes(np.asarray(blob))
            assert_trees_bit_identical(codec.decode(got), params)
        finally:
            board.close()
            board.unlink()

    def test_version_identity_not_ordering(self):
        """None ONLY on version equality: a rollback republish's
        backward version must still reach a reader holding a higher
        one (same identity semantics as the TCP server)."""
        board = _board("ident")
        try:
            assert board.version() == -1
            assert board.read_blob(-1) is None  # nothing published yet
            board.publish_blob(codec.encode(_params(1)), 10)
            board.publish_blob(codec.encode(_params(2)), 3)  # rollback
            assert board.version() == 3
            assert board.read_blob(3) is None
            got, version = board.read_blob(10)  # 10 != 3: must transfer
            assert version == 3
            assert_trees_bit_identical(codec.decode(got), _params(2))
        finally:
            board.close()
            board.unlink()

    def test_double_buffer_alternates_slots(self):
        board = _board("slots", slot=8192)
        try:
            for i in range(5):
                board.publish_blob(codec.encode({"x": np.full(8, i)}), i)
                got, version = board.read_blob(-1)
                assert version == i
                np.testing.assert_array_equal(
                    codec.decode(got)["x"], np.full(8, i))
        finally:
            board.close()
            board.unlink()

    def test_oversize_blob_raises(self):
        board = _board("big", slot=4096)
        try:
            with pytest.raises(ValueError, match="cannot fit"):
                board.publish_blob(b"\0" * 8192, 1)
        finally:
            board.close()
            board.unlink()

    def test_attach_validates_header(self):
        from multiprocessing import shared_memory

        seg = shared_memory.SharedMemory(
            name=f"drltest-wb-junk-{os.getpid()}", create=True, size=4096)
        try:
            with pytest.raises(ValueError, match="not an initialized"):
                WeightBoard.attach(seg.name.lstrip("/"))
        finally:
            seg.close()
            seg.unlink()


class _FlipOnCopy(WeightBoard):
    """Test double: injects `flips` publishes between the reader's meta
    read and its slot copy — the exact mid-pull version-flip race the
    seqlock must catch. Two flips re-target the slot the reader chose,
    so the copy it validates must be retried."""

    def arm(self, writer: WeightBoard, blobs, flips: int):
        self._writer = writer
        self._inject = list(blobs)
        self._flips = flips
        self._pub_n = 0
        self.copies = 0

    def _copy_slot(self, slot, n):
        out = super()._copy_slot(slot, n)
        self.copies += 1
        if self._flips and self._inject:
            for _ in range(self._flips):
                self._pub_n += 1
                self._writer.publish_blob(self._inject[0], 100 + self._pub_n)
            self._flips = 0
        return out


class TestSeqlock:
    def test_mid_pull_flip_retries_and_returns_consistent(self):
        """Two publishes landing between a reader's meta read and its
        slot copy rewrite the very slot being copied; the slot seq check
        must reject that copy and the retry must return the LATEST
        consistent (blob, version) pair."""
        writer = _board("flip")
        reader = None
        try:
            first = codec.encode(_params(1))
            second = codec.encode(_params(2))
            writer.publish_blob(first, 1)
            reader = _FlipOnCopy.attach(writer.name)
            reader.arm(writer, [second], flips=2)
            got, version = reader.read_blob(-1)
            assert reader.copies >= 2  # the torn first copy was retried
            assert reader.read_retries >= 1
            assert version == 102  # the retry observed the newest commit
            assert bytes(got) == bytes(np.asarray(second))
        finally:
            if reader is not None:
                reader.close()
            writer.close()
            writer.unlink()

    def test_two_publishes_between_meta_and_slot_seq_read_retry(self):
        """The nastier ordering: TWO publishes complete AFTER the reader's
        meta read but BEFORE it samples the slot seq. The slot seq is
        then stable at its post-rewrite value, so only the meta re-check
        stands between the reader and returning the NEW slot bytes
        labeled with the OLD (version, len)."""
        writer = _board("metarace")
        reader = None
        try:
            first = codec.encode(_params(1))
            second = codec.encode(_params(2))
            writer.publish_blob(first, 1)

            class _RaceBeforeSlotSeq(WeightBoard):
                armed = 1

                def _pre_slot_read(self):
                    if self.armed:
                        self.armed = 0
                        writer.publish_blob(second, 101)  # other slot
                        writer.publish_blob(second, 102)  # OUR slot
            reader = _RaceBeforeSlotSeq.attach(writer.name)
            got, version = reader.read_blob(-1)
            assert version == 102  # never v1 with v102's bytes
            assert bytes(got) == bytes(np.asarray(second))
            assert reader.read_retries >= 1
        finally:
            if reader is not None:
                reader.close()
            writer.close()
            writer.unlink()

    def test_meta_seqlock_odd_times_out_as_board_closed(self):
        """A writer that died mid-publish leaves meta_seq odd forever;
        readers must fail LOUDLY (-> TCP fallback), not hang or decode
        garbage."""
        board = _board("odd")
        try:
            board.publish_blob(codec.encode(_params(1)), 1)
            board._write_u64(64, board._read_u64(64) + 1)  # latch odd
            with pytest.raises(BoardClosed):
                board.read_blob(-1, timeout=0.3)
            with pytest.raises(BoardClosed):
                board.version(timeout=0.3)
        finally:
            board.close()
            board.unlink()

    def test_hammer_concurrent_publish_and_read(self):
        """Free-running writer vs reader on one segment: every read must
        return a (blob, version) pair whose payload matches what that
        version published (content keyed on version), never a torn mix."""
        writer = _board("hammer", slot=1 << 16)
        reader = WeightBoard.attach(writer.name)
        blobs = {v: bytes(np.asarray(codec.encode(
            {"x": np.full(1024, v % 251, np.uint8), "v": np.int64(v)})))
            for v in range(200)}
        errors: list = []
        stop = threading.Event()

        def read_loop():
            have = -1
            while not stop.is_set():
                try:
                    got = reader.read_blob(have, timeout=5.0)
                except BoardClosed as e:
                    errors.append(e)
                    return
                if got is None:
                    continue
                blob, version = got
                if bytes(blob) != blobs[version]:
                    errors.append(f"torn read at version {version}")
                    return
                have = version

        t = threading.Thread(target=read_loop)
        t.start()
        try:
            for v in range(200):
                writer.publish_blob(blobs[v], v)
            time.sleep(0.01)
        finally:
            stop.set()
            t.join(timeout=30.0)
            reader.close()
            writer.close()
            writer.unlink()
        assert not errors, errors[:3]


class TestWeightStoreMirroring:
    def test_store_publishes_land_on_board(self):
        board = _board("store")
        try:
            ws = WeightStore()
            ws.attach_board(board)
            ws.publish(_params(3), 5)
            got, version = board.read_blob(-1)
            assert version == 5
            assert_trees_bit_identical(codec.decode(got), _params(3))
            blob, bv = ws.get_blob()
            assert bytes(got) == bytes(np.asarray(blob)) and bv == 5
        finally:
            board.close()
            board.unlink()

    def test_attach_replays_existing_publication(self):
        ws = WeightStore()
        ws.publish(_params(4), 9)
        board = _board("replay")
        try:
            ws.attach_board(board)
            got, version = board.read_blob(-1)
            assert version == 9
            assert_trees_bit_identical(codec.decode(got), _params(4))
        finally:
            board.close()
            board.unlink()

    def test_rollback_republish_lands_backward_version(self):
        board = _board("rb")
        try:
            ws = WeightStore()
            ws.attach_board(board)
            ws.publish(_params(1), 50)
            ws.publish(_params(2), 12)  # checkpoint-rollback republish
            assert ws.version == 12
            assert board.version() == 12
            got, version = board.read_blob(50)  # reader held the old 50
            assert version == 12
            assert_trees_bit_identical(codec.decode(got), _params(2))
        finally:
            board.close()
            board.unlink()

    def test_oversize_blob_latches_board_off_and_closes_writer(self):
        board = _board("latch", slot=4096)
        ws = WeightStore()
        ws.attach_board(board)
        big = {"w": np.zeros(1 << 16, np.float32)}
        ws.publish(big, 1)  # board write fails; store must still land it
        assert ws.version == 1
        assert board.writer_closed  # actors demote to TCP
        ws.publish(big, 2)  # and later publishes don't touch the board
        assert ws.version == 2
        board.close()
        board.unlink()


class _FakeClient:
    """TCP-side stub recording what fell back to it."""

    def __init__(self):
        self.pulls: list = []

    def get_weights_if_newer(self, have):
        self.pulls.append(have)
        return {"tcp": np.ones(1)}, 999


class TestBoardWeights:
    def test_pull_and_no_syscall_up_to_date_path(self):
        writer = _board("bw")
        try:
            writer.publish_blob(codec.encode(_params(5)), 2)
            client = _FakeClient()
            bw = BoardWeights(WeightBoard.attach(writer.name), client)
            tree, version = bw.get_if_newer(-1)
            assert version == 2
            assert_trees_bit_identical(tree, _params(5))
            assert bw.get_if_newer(2) is None
            assert not client.pulls  # never touched TCP
            s = bw.snapshot_stats()
            assert s["board_pulls"] == 1 and s["board_checks"] == 2
            bw.close()
        finally:
            writer.close()
            writer.unlink()

    def test_writer_closed_demotes_permanently(self):
        writer = _board("demote")
        try:
            writer.publish_blob(codec.encode(_params(1)), 1)
            client = _FakeClient()
            bw = BoardWeights(WeightBoard.attach(writer.name), client)
            assert bw.get_if_newer(-1)[1] == 1
            writer.close_writer()  # learner shut down cleanly
            assert bw.get_if_newer(1)[1] == 999
            assert bw.get_if_newer(1)[1] == 999
            assert client.pulls == [1, 1]  # both served by TCP
            assert bw.snapshot_stats()["tcp_fallbacks"] == 1  # demoted once
        finally:
            writer.close()
            writer.unlink()

    def test_attach_failure_falls_back_to_tcp(self, monkeypatch):
        monkeypatch.setenv("DRL_FLEET", "0")
        assert attach_board_weights("drltest-wb-never-created", _FakeClient(),
                                    deadline_s=0.3) is None

    def test_attach_failure_with_fleet_demotes_at_birth(self, monkeypatch):
        """Fleet plane on: attach failure yields a demoted-at-birth
        BoardWeights (pulls on TCP now, reattach() surface kept) so a
        member that starts during a learner outage can be re-promoted."""
        monkeypatch.setenv("DRL_FLEET", "1")
        client = _FakeClient()
        bw = attach_board_weights("drltest-wb-never-created", client,
                                  deadline_s=0.3)
        assert bw is not None and not bw.attached
        assert bw._name == "drltest-wb-never-created"  # reattach target
        try:
            assert bw.get_if_newer(-1)[1] == 999
            assert client.pulls == [-1]  # rode TCP
        finally:
            bw.close()


class TestGating:
    def test_unset_is_on_only_where_the_seqlock_argument_holds(
            self, monkeypatch):
        import platform

        monkeypatch.delenv("DRL_SHM_WEIGHTS", raising=False)
        monkeypatch.setattr(platform, "machine", lambda: "x86_64")
        assert board_enabled() is True
        monkeypatch.setattr(platform, "machine", lambda: "aarch64")
        assert board_enabled() is False
        monkeypatch.setenv("DRL_SHM_WEIGHTS", "1")  # forced: survivable
        assert board_enabled() is True

    def test_serve_board_failure_returns_none(self, monkeypatch):
        monkeypatch.setenv("DRL_SHM_WEIGHTS_MB", "64")
        board = serve_board(f"drltest-wb-serve-{os.getpid()}")
        assert board is not None
        try:
            # Same name again: create must fail -> None, TCP-only.
            assert serve_board(board.name) is None
        finally:
            board.close()
            board.unlink()


class TestTwoProcessE2E:
    def test_board_matches_tcp_pulls_bit_for_bit(self):
        """A REAL child process attaches the board and pulls every
        version via the deployed BoardWeights surface; the parent
        publishes through a WeightStore serving the SAME store over real
        TCP. Every version the child saw must decode bit-identically to
        the TCP pull of that version (sha1 over canonical re-encode)."""
        from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue
        from distributed_reinforcement_learning_tpu.runtime.transport import (
            TransportClient, TransportServer)

        name = f"drltest-wb-e2e-{os.getpid()}"
        board = WeightBoard.create(name, 1 << 20)
        ws = WeightStore()
        ws.attach_board(board)
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        server = TransportServer(TrajectoryQueue(4), ws, host="127.0.0.1",
                                 port=port).start()
        n_versions = 12
        proc = subprocess.Popen(
            [sys.executable, str(WORKER), name, str(n_versions - 1)],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        tcp_digests = {}
        client = TransportClient("127.0.0.1", port)
        try:
            for v in range(n_versions):
                ws.publish(_params(100 + v), v)
                tree, got_v = client.get_weights_if_newer(-1)
                assert got_v == v
                tcp_digests[v] = hashlib.sha1(
                    bytes(codec.encode(tree, cache=True))).hexdigest()
                time.sleep(0.02)  # let the child observe some versions
            out, err = proc.communicate(timeout=60)
            assert proc.returncode == 0, err[-800:]
        finally:
            client.close()
            server.stop()
            board.close()
            board.unlink()
        line = next(ln for ln in out.splitlines()
                    if ln.startswith("BOARD_WORKER="))
        result = json.loads(line.split("=", 1)[1])
        assert result["versions"], "child saw no versions"
        assert result["versions"][-1] == n_versions - 1
        assert result["stats"]["tcp_fallbacks"] == 0
        for version, digest in zip(result["versions"], result["digests"]):
            assert digest == tcp_digests[version], (
                f"board pull of version {version} != TCP pull")

"""Sharded weight plane: partition rules, per-shard bit-identity,
quant/delta round trips, the segmented board (incl. the per-shard
oversize latch and a two-process e2e), role-scoped pulls, and gates.

The contract under test (ISSUE 8): sharded publication must be
BIT-IDENTICAL to whole-blob for un-quantized pulls — across the store,
the TCP shard op, and the segmented shm board, mid-pull version flips
included — and every failure path demotes (per-shard to TCP, whole
board to TCP, shard op to the whole-blob op) instead of killing roles.
"""

import hashlib
import json
import os
import socket
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_reinforcement_learning_tpu.data import codec
from distributed_reinforcement_learning_tpu.parallel import partition
from distributed_reinforcement_learning_tpu.runtime import weight_shards
from distributed_reinforcement_learning_tpu.runtime.weight_board import (
    BoardClosed,
    BoardWeights,
    ShardedWeightBoard,
    WeightBoard,
    attach_any,
)
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

WORKER = Path(__file__).resolve().parent / "weight_shard_worker.py"


def _small_cnn(seed: int):
    """Reference-parity CNN shapes: every leaf under the partition size
    threshold, so the whole policy lands in the replicated shard."""
    rng = np.random.RandomState(seed)
    return {
        "conv": {"w": rng.standard_normal((3, 3, 4, 8)).astype(np.float32),
                 "b": rng.standard_normal(8).astype(np.float32)},
        "head": {"w": rng.standard_normal((32, 6)).astype(np.float32)},
        "step": np.int64(seed),
    }


def _xformer(seed: int, d: int = 64, layers: int = 3):
    rng = np.random.RandomState(seed)
    blocks = {
        "qkv_kernel": rng.standard_normal((layers, d, 3 * d)).astype(np.float32),
        "proj_kernel": rng.standard_normal((layers, d, d)).astype(np.float32),
        "ln1_scale": np.ones((layers, d), np.float32),
        "ln1_bias": np.zeros((layers, d), np.float32),
    }
    return {
        "blocks_stacked": blocks,
        "head": {"w": rng.standard_normal((d, 128)).astype(np.float32),
                 "b": np.zeros(128, np.float32)},
        "step": np.int64(seed),
    }


def _moe(seed: int, e: int = 8, d: int = 32):
    rng = np.random.RandomState(seed)
    return {
        "moe_gate": rng.standard_normal((d, e)).astype(np.float32),
        "moe_w1": rng.standard_normal((e, d, 4 * d)).astype(np.float32),
        "moe_b1": rng.standard_normal((e, 4 * d)).astype(np.float32),
        "moe_w2": rng.standard_normal((e, 4 * d, d)).astype(np.float32),
        "head": {"w": rng.standard_normal((d, 256)).astype(np.float32)},
        "step": np.int64(seed),
    }


def _leaves(tree):
    import jax

    out = []
    jax.tree.map(lambda x: out.append(np.asarray(x)), tree)
    return out


def assert_trees_bit_identical(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


def _whole(params):
    return codec.decode(codec.encode(params))


@pytest.fixture
def fresh_gates(monkeypatch):
    """Start from all three gates unset."""
    for key in ("DRL_WEIGHTS_SHARDED", "DRL_WEIGHTS_QUANT",
                "DRL_WEIGHTS_DELTA", "DRL_WEIGHTS_KEYS"):
        monkeypatch.delenv(key, raising=False)
    yield monkeypatch


class TestPartitionRules:
    def test_small_cnn_fully_replicated(self):
        plan = partition.shard_plan(_small_cnn(1))
        assert list(plan.shards) == [partition.REPLICATED_KEY]
        assert all(spec == P() for spec in plan.specs)

    def test_xformer_keys(self):
        plan = partition.shard_plan(_xformer(1))
        by_path = dict(zip(plan.paths, plan.specs))
        assert by_path["blocks_stacked/qkv_kernel"] == P("pipe")
        assert by_path["blocks_stacked/proj_kernel"] == P("pipe")
        assert by_path["head/w"] == P(None, "model")
        # LayerNorm rows are under the partition size threshold: pooled
        # into the replicated shard, not micro-sharded.
        assert by_path["blocks_stacked/ln1_scale"] == P()
        assert by_path["step"] == P()  # scalars ALWAYS replicate
        assert set(plan.shards) == {"pipe", "-,model", "rep"}

    def test_moe_keys(self):
        plan = partition.shard_plan(_moe(1))
        by_path = dict(zip(plan.paths, plan.specs))
        assert by_path["moe_w1"] == P("expert")
        assert by_path["moe_w2"] == P("expert")
        assert by_path["moe_gate"] == P()  # router gate is tiny: replicated
        assert by_path["head/w"] == P(None, "model")
        assert "expert" in plan.shards

    def test_scalars_replicate_even_against_greedy_rules(self):
        specs = partition.match_partition_rules(
            ((r".*", P("data")),), {"s": np.float32(1.0),
                                    "one": np.ones(1, np.float32)})
        assert specs["s"] == P() and specs["one"] == P()

    def test_missing_rule_raises(self):
        with pytest.raises(ValueError, match="rule not found"):
            partition.match_partition_rules(
                ((r"never", P()),),
                {"big": np.zeros((128, 128), np.float32)})

    def test_plan_covers_every_leaf_exactly_once(self):
        plan = partition.shard_plan(_moe(2))
        seen = sorted(i for idxs in plan.shards.values() for i in idxs)
        assert seen == list(range(len(plan.paths)))

    def test_spec_key_stability(self):
        assert partition.spec_key(P()) == "rep"
        assert partition.spec_key(P(None)) == "rep"
        assert partition.spec_key(P(None, "model")) == "-,model"
        assert partition.spec_key(P("expert")) == "expert"


class TestBundleBitIdentity:
    @pytest.mark.parametrize("make", [_small_cnn, _xformer, _moe])
    def test_materialize_matches_whole_blob(self, make):
        params = make(3)
        bundle = weight_shards.build_bundle(params)
        manifest = dict(bundle.manifest, version=7)
        tree = weight_shards.materialize(manifest, bundle.blobs)
        assert_trees_bit_identical(tree, _whole(params))

    def test_manifest_json_round_trip(self):
        bundle = weight_shards.build_bundle(_xformer(4))
        manifest = dict(bundle.manifest, version=3)
        parsed = weight_shards.parse_manifest(
            weight_shards.manifest_bytes(manifest))
        tree = weight_shards.materialize(parsed, bundle.blobs)
        assert_trees_bit_identical(tree, _whole(_xformer(4)))

    def test_missing_shard_and_bad_checksum_raise(self):
        bundle = weight_shards.build_bundle(_xformer(5))
        manifest = dict(bundle.manifest, version=1)
        partial = dict(bundle.blobs)
        gone = next(iter(partial))
        del partial[gone]
        with pytest.raises(KeyError):
            weight_shards.materialize(manifest, partial)
        corrupt = {k: np.array(v, copy=True) for k, v in bundle.blobs.items()}
        corrupt[gone][-1] ^= 0xFF
        with pytest.raises(ValueError, match="checksum"):
            weight_shards.materialize(manifest, corrupt)


class TestQuantAndDelta:
    def test_bf16_round_trip_error_bound(self):
        params = _xformer(6)
        bundle = weight_shards.build_bundle(params, quant="bf16")
        tree = weight_shards.materialize(dict(bundle.manifest, version=1),
                                         bundle.blobs)
        for got, want in zip(_leaves(tree), _leaves(_whole(params))):
            assert got.dtype == want.dtype
            if want.dtype == np.float32:
                # bf16 keeps 8 mantissa bits: RNE relative error < 2^-8.
                np.testing.assert_allclose(got, want, rtol=1 / 256, atol=1e-30)
            else:
                assert got.tobytes() == want.tobytes()  # ints untouched
        f32 = sum(len(b) for b in weight_shards.build_bundle(params).blobs.values())
        q = sum(len(b) for b in bundle.blobs.values())
        assert q < 0.6 * f32  # the ~2x broadcast-byte cut

    def test_bf16_specials_survive(self):
        x = np.array([np.nan, np.inf, -np.inf, 0.0, -0.0, 1e-40], np.float32)
        q, meta = weight_shards.quantize_leaves([x], "bf16")
        (back,) = weight_shards.dequantize_leaves(q, meta)
        assert np.isnan(back[0]) and np.isposinf(back[1]) and np.isneginf(back[2])
        assert back[3] == 0.0 and back[4] == 0.0

    def test_int8_round_trip_error_bound(self):
        rng = np.random.RandomState(0)
        x = (rng.standard_normal((64, 64)) * 3).astype(np.float32)
        q, meta = weight_shards.quantize_leaves([x], "int8")
        assert q[0].dtype == np.int8
        (back,) = weight_shards.dequantize_leaves(q, meta)
        scale = meta["scales"][0]
        assert float(np.max(np.abs(back - x))) <= scale / 2 + 1e-7

    def test_delta_round_trip(self):
        rng = np.random.RandomState(1)
        base = rng.randint(0, 256, 1 << 16).astype(np.uint8)
        new = base.copy()
        for off in (0, 777, 40_000, base.size - 3):
            new[off:off + 3] ^= 0xA5
        d = weight_shards.delta_encode(new, base)
        assert d is not None and len(d) < 200
        out = weight_shards.delta_apply(base, d)
        assert out.tobytes() == new.tobytes()

    def test_delta_bails_on_dense_change_and_len_mismatch(self):
        rng = np.random.RandomState(2)
        base = rng.randint(0, 256, 4096).astype(np.uint8)
        assert weight_shards.delta_encode(
            (base + 1).astype(np.uint8), base) is None
        assert weight_shards.delta_encode(base[:-1], base) is None

    def test_empty_delta_is_identity(self):
        base = np.arange(256, dtype=np.uint8)
        d = weight_shards.delta_encode(base.copy(), base)
        assert d is not None and len(d) == 8
        assert weight_shards.delta_apply(base, d).tobytes() == base.tobytes()

    def test_delta_apply_wrong_base_length_raises(self):
        base = np.zeros(64, np.uint8)
        d = weight_shards.delta_encode(base.copy(), base)
        with pytest.raises(ValueError, match="delta base"):
            weight_shards.delta_apply(np.zeros(65, np.uint8), d)


class TestStoreSharded:
    def test_get_sharded_full_and_lazy_whole_blob(self):
        params = _xformer(7)
        ws = WeightStore(sharded=True)
        ws.publish(params, 4)
        got = ws.get_sharded(-1)
        assert got is not None
        version, mbytes, shards = got
        assert version == 4
        assert all(enc == weight_shards.ENC_FULL for _, enc, _, _ in shards)
        tree = weight_shards.materialize(
            weight_shards.parse_manifest(mbytes),
            {k: np.frombuffer(bytes(p), np.uint8) for k, _, _, p in shards})
        assert_trees_bit_identical(tree, _whole(params))
        # Old clients: the whole blob rebuilds lazily, byte-identical
        # to a direct canonical encode.
        blob, bv = ws.get_blob()
        assert bv == 4
        assert bytes(np.asarray(blob)) == bytes(np.asarray(codec.encode(params)))
        assert ws.get_sharded(4) is None  # version identity

    def test_unchanged_elision_and_delta(self, fresh_gates):
        fresh_gates.setenv("DRL_WEIGHTS_DELTA", "1")
        params = _xformer(8)
        ws = WeightStore(sharded=True)
        ws.publish(params, 0)
        params["head"]["w"][0, 0] += 1.0
        ws.publish(params, 1)
        _, _, shards = ws.get_sharded(0, base_version=0, accept_delta=True)
        encs = {k: enc for k, enc, _, _ in shards}
        assert encs["pipe"] == weight_shards.ENC_SKIP
        assert encs["rep"] == weight_shards.ENC_SKIP
        assert encs["-,model"] == weight_shards.ENC_DELTA
        # Without the base, everything ships full.
        _, _, shards = ws.get_sharded(0)
        assert all(enc == weight_shards.ENC_FULL for _, enc, _, _ in shards)
        assert ws.shard_stats()["deltas_encoded"] >= 1

    def test_rollback_republish_backward_version(self):
        ws = WeightStore(sharded=True)
        ws.publish(_xformer(1), 50)
        ws.publish(_xformer(2), 12)  # checkpoint-rollback republish
        assert ws.version == 12
        got = ws.get_sharded(50)  # reader held the old 50: must transfer
        assert got is not None and got[0] == 12

    def test_unencodable_params_fall_back_to_per_leaf(self):
        ws = WeightStore(sharded=True)
        ws.publish({"bad": np.array(["a", "bc"], dtype=object)}, 1)
        assert ws.version == 1
        assert ws.get_sharded(-1) is None  # nothing sharded to serve
        params, v = ws.get()
        assert v == 1 and params["bad"][1] == "bc"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class TestTransportShardOp:
    @pytest.fixture
    def served(self):
        from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue
        from distributed_reinforcement_learning_tpu.runtime.transport import (
            TransportClient, TransportServer)

        params = _xformer(9)
        ws = WeightStore(sharded=True)
        ws.publish(params, 0)
        server = TransportServer(TrajectoryQueue(4), ws, host="127.0.0.1",
                                 port=_free_port()).start()
        client = TransportClient("127.0.0.1", server.port)
        try:
            yield params, ws, server, client
        finally:
            client.close()
            server.stop()

    def test_role_scoped_pull_returns_exactly_requested_shards(self, served):
        _, _, _, client = served
        got = client.get_weights_sharded(-1, keys=["pipe"])
        version, mbytes, shards = got
        assert [k for k, _, _, _ in shards] == ["pipe"]
        # The manifest still describes the WHOLE tree (assembly needs
        # every slot), only the payload is scoped.
        manifest = weight_shards.parse_manifest(mbytes)
        assert {sh["key"] for sh in manifest["shards"]} == {
            "pipe", "-,model", "rep"}

    def test_sharded_client_matches_whole_blob_client(self, served):
        from distributed_reinforcement_learning_tpu.runtime.transport import (
            RemoteWeights, ShardedRemoteWeights)

        params, ws, _, client = served
        srw = ShardedRemoteWeights(client)
        tree, v = srw.get_if_newer(-1)
        whole_tree, wv = RemoteWeights(client).get_if_newer(-1)
        assert v == wv == 0
        assert_trees_bit_identical(tree, whole_tree)
        assert srw.get_if_newer(0) is None
        # A later version flows through the cache path (skip/delta or
        # full — either way bit-identical).
        params["blocks_stacked"]["qkv_kernel"][0, 0, 0] += 1.0
        ws.publish(params, 1)
        tree2, v2 = srw.get_if_newer(0)
        assert v2 == 1
        assert_trees_bit_identical(tree2, _whole(params))
        s = srw.snapshot_stats()
        assert s["shard_pulls"] == 2 and s["whole_fallbacks"] == 0

    def test_role_scoped_pinned_shard_keeps_its_own_quant_meta(self, fresh_gates):
        """Regression: a pinned (un-refreshed) int8 shard must
        dequantize with the scales of the version its CODES came from.
        Using the current manifest's scales would silently drift the
        'frozen' leaves every time the learner's amax moved."""
        from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue
        from distributed_reinforcement_learning_tpu.runtime.transport import (
            ShardedRemoteWeights, TransportClient, TransportServer)

        fresh_gates.setenv("DRL_WEIGHTS_QUANT", "int8")
        params = _xformer(30)
        ws = WeightStore(sharded=True)
        ws.publish(params, 0)
        server = TransportServer(TrajectoryQueue(4), ws, host="127.0.0.1",
                                 port=_free_port()).start()
        client = TransportClient("127.0.0.1", server.port)
        try:
            srw = ShardedRemoteWeights(client, keys=["rep"])
            tree1, v1 = srw.get_if_newer(-1)  # first pull is always full
            assert v1 == 0
            pinned1 = np.asarray(tree1["head"]["w"])  # "-,model" shard
            # New version: the model-shard amax doubles -> its int8
            # scales change; only "rep" is refreshed by this role.
            params["head"]["w"] *= 2.0
            params["step"] = np.int64(1)
            ws.publish(params, 1)
            tree2, v2 = srw.get_if_newer(0)
            assert v2 == 1
            pinned2 = np.asarray(tree2["head"]["w"])
            assert pinned1.tobytes() == pinned2.tobytes(), \
                "pinned shard drifted (decoded with the new scales)"
            # The refreshed shard DID move.
            assert np.asarray(tree2["step"]) == 1
        finally:
            client.close()
            server.stop()

    def test_unsharded_store_demotes_client_permanently(self):
        from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue
        from distributed_reinforcement_learning_tpu.runtime.transport import (
            ShardedRemoteWeights, TransportClient, TransportServer)

        ws = WeightStore(sharded=False)
        ws.publish(_small_cnn(1), 5)
        server = TransportServer(TrajectoryQueue(4), ws, host="127.0.0.1",
                                 port=_free_port()).start()
        client = TransportClient("127.0.0.1", server.port)
        try:
            srw = ShardedRemoteWeights(client)
            tree, v = srw.get_if_newer(-1)
            assert v == 5
            assert_trees_bit_identical(tree, _whole(_small_cnn(1)))
            assert srw._plain  # latched: no second ST_UNAVAILABLE round trip
            assert srw.snapshot_stats()["whole_fallbacks"] == 1
            assert srw.get_if_newer(5) is None
        finally:
            client.close()
            server.stop()


def _sboard(tag: str, arena=1 << 22, **kw) -> ShardedWeightBoard:
    return ShardedWeightBoard.create(
        f"drltest-ws-{tag}-{os.getpid()}", arena, **kw)


class TestShardedBoard:
    def test_round_trip_bit_identical(self):
        board = _sboard("rt")
        try:
            params = _moe(10)
            ws = WeightStore(sharded=True)
            ws.attach_board(board)
            ws.publish(params, 7)
            manifest, blobs, version = board.read_shards(-1)
            assert version == 7
            tree = weight_shards.materialize(manifest, blobs)
            assert_trees_bit_identical(tree, _whole(params))
            assert board.read_shards(7) is None  # version identity
        finally:
            board.close()
            board.unlink()

    def test_publish_memcpys_only_changed_shards(self):
        board = _sboard("delta")
        try:
            params = _xformer(11)
            ws = WeightStore(sharded=True)
            ws.attach_board(board)
            ws.publish(params, 0)
            m1, _, _ = board.read_shards(-1)
            seqs1 = {sh["key"]: (sh["act"],
                                 board._read_u64(sh["seq"]),
                                 board._read_u64(sh["seq"] + 64))
                     for sh in m1["shards"]}
            params["head"]["w"][0, 0] += 1.0  # touches ONLY "-,model"
            ws.publish(params, 1)
            m2, blobs2, v2 = board.read_shards(-1)
            assert v2 == 1
            seqs2 = {sh["key"]: (sh["act"],
                                 board._read_u64(sh["seq"]),
                                 board._read_u64(sh["seq"] + 64))
                     for sh in m2["shards"]}
            assert seqs2["-,model"] != seqs1["-,model"]  # rewritten
            assert seqs2["pipe"] == seqs1["pipe"]        # untouched
            assert seqs2["rep"] == seqs1["rep"]
            assert_trees_bit_identical(
                weight_shards.materialize(m2, blobs2), _whole(params))
        finally:
            board.close()
            board.unlink()

    def test_mid_pull_version_flip_retries_consistent(self):
        writer = _sboard("flip")
        try:
            ws = WeightStore(sharded=True)
            ws.attach_board(writer)
            params = _xformer(12)
            ws.publish(params, 1)
            flip = {"armed": 2}

            class _FlipOnSlotRead(ShardedWeightBoard):
                def _pre_slot_read(self):
                    while flip["armed"]:
                        flip["armed"] -= 1
                        # TWO full publishes re-target the very slots
                        # the reader is about to copy.
                        p = _xformer(20 + flip["armed"])
                        ws.publish(p, 100 + flip["armed"])
                        flip["last"] = p

            reader = _FlipOnSlotRead.attach(writer.name)
            manifest, blobs, version = reader.read_shards(-1)
            assert reader.read_retries >= 1
            assert version in (100, 101)
            want = _xformer(20 + (1 if version == 101 else 0))
            assert_trees_bit_identical(
                weight_shards.materialize(manifest, blobs), _whole(want))
            reader.close()
        finally:
            writer.close()
            writer.unlink()

    def test_oversize_single_shard_latches_only_itself(self):
        # Arena fits the small shards but NOT the big "-,model" kernel.
        board = _sboard("latch", arena=1 << 18)
        try:
            rng = np.random.RandomState(0)
            params = {
                "huge": {"w": rng.standard_normal((256, 512)).astype(np.float32)},
                "blocks_stacked": {"qkv_kernel":
                                   rng.standard_normal((2, 32, 96)).astype(np.float32)},
                "step": np.int64(1),
            }
            ws = WeightStore(sharded=True)
            ws.attach_board(board)
            ws.publish(params, 3)
            assert not board.writer_closed  # the BOARD did not latch
            manifest, blobs, version = board.read_shards(-1)
            assert version == 3
            on_board = {sh["key"]: sh.get("board", True)
                        for sh in manifest["shards"]}
            assert on_board["-,model"] is False  # the oversize shard
            assert on_board["pipe"] is True and on_board["rep"] is True
            assert "-,model" not in blobs and "pipe" in blobs
            # Publishes keep flowing for the surviving shards.
            params["step"] = np.int64(2)
            ws.publish(params, 4)
            assert board.read_shards(3)[2] == 4
        finally:
            board.close()
            board.unlink()

    def test_board_weights_fills_latched_shard_over_tcp(self):
        board = _sboard("fill", arena=1 << 18)
        try:
            rng = np.random.RandomState(1)
            params = {
                "huge": {"w": rng.standard_normal((256, 512)).astype(np.float32)},
                "blocks_stacked": {"qkv_kernel":
                                   rng.standard_normal((2, 32, 96)).astype(np.float32)},
                "step": np.int64(1),
            }
            ws = WeightStore(sharded=True)
            ws.attach_board(board)
            ws.publish(params, 3)

            class _ShardClient:
                def get_weights_sharded(self, have, keys=None,
                                        base_version=-2, accept_delta=False):
                    return ws.get_sharded(have, keys=keys,
                                          base_version=base_version,
                                          accept_delta=accept_delta)

                def get_weights_if_newer(self, have):
                    raise AssertionError("whole pull not expected")

            bw = BoardWeights(attach_any(board.name), _ShardClient())
            tree, version = bw.get_if_newer(-1)
            assert version == 3
            assert_trees_bit_identical(tree, _whole(params))
            s = bw.snapshot_stats()
            assert s["board_shard_fallbacks"] == 1 and s["tcp_fallbacks"] == 0
            bw.close()

            class _WholeOnly:
                def get_weights_if_newer(self, have):
                    return {"tcp": np.ones(1)}, 999

            bw2 = BoardWeights(attach_any(board.name), _WholeOnly())
            got = bw2.get_if_newer(-1)  # no shard op: whole TCP refresh
            assert got[1] == 999
            assert bw2.snapshot_stats()["board_shard_fallbacks"] == 1
            bw2.close()
        finally:
            board.close()
            board.unlink()

    def test_new_shard_key_after_layout_is_board_failure(self):
        board = _sboard("newkey")
        ws = WeightStore(sharded=True)
        ws.attach_board(board)
        ws.publish(_xformer(13), 1)
        ws.publish(_moe(13), 2)  # different schema -> new shard keys
        assert ws.version == 2  # the store itself never fails
        assert board.writer_closed  # board latched off, readers demote
        board.close()
        board.unlink()

    def test_whole_blob_store_latches_sharded_board_off(self):
        board = _sboard("mismatch")
        ws = WeightStore(sharded=False)
        ws.attach_board(board)
        ws.publish(_small_cnn(2), 1)
        assert ws.version == 1 and ws.get_blob()[0] is not None
        assert board.writer_closed
        board.close()
        board.unlink()

    def test_writer_closed_demotes_reader(self):
        board = _sboard("closed")
        try:
            ws = WeightStore(sharded=True)
            ws.attach_board(board)
            ws.publish(_xformer(14), 1)

            class _Fake:
                pulls = 0

                def get_weights_if_newer(self, have):
                    self.pulls += 1
                    return {"tcp": np.ones(1)}, 999

            fake = _Fake()
            bw = BoardWeights(attach_any(board.name), fake)
            assert bw.get_if_newer(-1)[1] == 1
            board.close_writer()
            assert bw.get_if_newer(1)[1] == 999
            assert fake.pulls == 1
            assert bw.snapshot_stats()["tcp_fallbacks"] == 1
            bw.close()
        finally:
            board.close()
            board.unlink()

    def test_attach_any_dispatch_and_magic_validation(self):
        classic = WeightBoard.create(f"drltest-ws-cls-{os.getpid()}", 8192)
        sharded = _sboard("disp")
        try:
            assert isinstance(attach_any(classic.name), WeightBoard)
            assert isinstance(attach_any(sharded.name), ShardedWeightBoard)
            with pytest.raises(ValueError, match="sharded"):
                ShardedWeightBoard.attach(classic.name)
        finally:
            classic.close()
            classic.unlink()
            sharded.close()
            sharded.unlink()

    def test_manifest_overflow_is_board_failure(self):
        board = _sboard("mover", mslot_bytes=64)
        ws = WeightStore(sharded=True)
        ws.attach_board(board)
        ws.publish(_xformer(15), 1)
        assert ws.version == 1
        assert board.writer_closed  # manifest cannot fit: whole-board latch
        board.close()
        board.unlink()

    def test_meta_seqlock_odd_times_out_as_board_closed(self):
        board = _sboard("odd")
        try:
            ws = WeightStore(sharded=True)
            ws.attach_board(board)
            ws.publish(_xformer(16), 1)
            board._write_u64(64, board._read_u64(64) + 1)  # latch odd
            with pytest.raises(BoardClosed):
                board.read_shards(-1, timeout=0.3)
            with pytest.raises(BoardClosed):
                board.version(timeout=0.3)
        finally:
            board.close()
            board.unlink()


class TestGating:
    def test_env_forces_all_three(self, fresh_gates):
        fresh_gates.setenv("DRL_WEIGHTS_SHARDED", "1")
        fresh_gates.setenv("DRL_WEIGHTS_QUANT", "int8")
        fresh_gates.setenv("DRL_WEIGHTS_DELTA", "1")
        assert weight_shards.sharded_enabled() is True
        assert weight_shards.quant_mode() == "int8"
        assert weight_shards.delta_enabled() is True
        fresh_gates.setenv("DRL_WEIGHTS_SHARDED", "0")
        fresh_gates.setenv("DRL_WEIGHTS_QUANT", "0")
        fresh_gates.setenv("DRL_WEIGHTS_DELTA", "0")
        assert weight_shards.sharded_enabled() is False
        assert weight_shards.quant_mode() is None
        assert weight_shards.delta_enabled() is False

    def test_role_keys_parsing(self, fresh_gates):
        assert weight_shards.role_keys() is None
        fresh_gates.setenv("DRL_WEIGHTS_KEYS", "rep, -,model")
        # csv split: "-,model" cannot be spelled in csv -> keys are
        # simple identifiers; commas inside keys split. Pin the simple
        # contract:
        fresh_gates.setenv("DRL_WEIGHTS_KEYS", "rep,expert")
        assert weight_shards.role_keys() == ["rep", "expert"]

    def test_quantized_store_serves_f32_in_process(self, fresh_gates):
        fresh_gates.setenv("DRL_WEIGHTS_QUANT", "bf16")
        params = _xformer(17)
        ws = WeightStore(sharded=True)
        ws.publish(params, 1)
        # In-process snapshot is the f32 master copy, bit-identical.
        tree, v = ws.get()
        assert_trees_bit_identical(tree, _whole(params))
        # The broadcast shards are quantized (u16-carried bf16).
        _, mbytes, shards = ws.get_sharded(-1)
        manifest = weight_shards.parse_manifest(mbytes)
        assert any(sh["quant"] for sh in manifest["shards"])
        pulled = weight_shards.materialize(
            manifest,
            {k: np.frombuffer(bytes(p), np.uint8) for k, _, _, p in shards})
        for got, want in zip(_leaves(pulled), _leaves(_whole(params))):
            if want.dtype == np.float32:
                np.testing.assert_allclose(got, want, rtol=1 / 256, atol=1e-30)


class TestTwoProcessE2E:
    def test_sharded_board_matches_tcp_pulls_bit_for_bit(self):
        """A REAL child process attaches the segmented board through the
        deployed BoardWeights surface; the parent publishes through a
        sharded WeightStore serving the SAME store over real TCP. Every
        version the child saw must re-encode to the sha1 of the parent's
        canonical whole-blob encode of that version."""
        from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue
        from distributed_reinforcement_learning_tpu.runtime.transport import (
            ShardedRemoteWeights, TransportClient, TransportServer)

        name = f"drltest-ws-e2e-{os.getpid()}"
        board = ShardedWeightBoard.create(name, 1 << 22)
        ws = WeightStore(sharded=True)
        ws.attach_board(board)
        server = TransportServer(TrajectoryQueue(4), ws, host="127.0.0.1",
                                 port=_free_port()).start()
        n_versions = 12
        proc = subprocess.Popen(
            [sys.executable, str(WORKER), name, str(n_versions - 1)],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        client = TransportClient("127.0.0.1", server.port)
        srw = ShardedRemoteWeights(client)
        tcp_digests = {}
        try:
            params = _xformer(100)
            for v in range(n_versions):
                params["head"]["w"][0, v] += 1.0  # real per-version drift
                params["step"] = np.int64(v)
                ws.publish(params, v)
                tree, got_v = srw.get_if_newer(-1)
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
                    if ln.startswith("SHARD_WORKER="))
        result = json.loads(line.split("=", 1)[1])
        assert result["versions"], "child saw no versions"
        assert result["versions"][-1] == n_versions - 1
        assert result["stats"]["tcp_fallbacks"] == 0
        assert result["stats"]["board_shard_fallbacks"] == 0
        assert result["stats"]["shard_pulls"] == len(result["versions"])
        for version, digest in zip(result["versions"], result["digests"]):
            assert digest == tcp_digests[version], (
                f"board pull of version {version} != TCP pull")

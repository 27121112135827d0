"""Partition-aware learner collective (ISSUE 19 acceptance pins).

What this suite pins, seat by seat:

- PLAN AGREEMENT: two/three seats building an ExchangePlan from the
  same params schema agree bit-identically on the plan hash; HELLO
  carries the hash both ways and a deliberate mismatch (skewed rules,
  quant, or overlap) is a LOUD refusal — probe answers accepted=False
  and `check_plan_agreement` raises PlanMismatch, never silent
  divergence.
- OWNER-SCOPED EXCHANGE: per sharded spec class (model/expert/pipe)
  the star exchange ends every seat bit-identical, equal to the mean;
  k=2 f32 is EXACT (two-term float add is order-independent), k=3 is
  allclose (reduction-order noise only). An all-replicated plan
  reproduces the plan-less ring BYTE-FOR-BYTE — the partition-off
  equivalence the DRL_COLL_PARTITION=0 gate relies on.
- bf16 TRANSPORT: half the wire bytes exactly, error bounded by
  2^-7 x the mean |contribution| (f32 master accumulation — only
  transported values round, never sums), NaN stays NaN (never rounds
  into Inf), Inf survives, and seats still end bit-identical. The
  codec is single-source: the collective and the weight plane
  (runtime/weight_shards.py) must round IDENTICALLY — byte-identity
  regression against the weight-shard aliases.
- OVERLAPPED ROUNDS: with in-flight depth 1 the exchange really
  overlaps the next step's backward (wall-clock pin vs the serial
  path), the priming step returns the state unchanged, and a worker
  exception (PlanMismatch) re-raises on the learn thread.
"""

from __future__ import annotations

import socket
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.data.bf16 import (
    bf16_u16_to_f32,
    f32_to_bf16_u16,
)
from distributed_reinforcement_learning_tpu.parallel.collective import (
    CollectiveError,
    ExchangePlan,
    HostCollective,
    PlanMismatch,
    class_label,
)
from distributed_reinforcement_learning_tpu.parallel.partition import (
    build_exchange_plan,
)
from distributed_reinforcement_learning_tpu.runtime import learner_tier
from distributed_reinforcement_learning_tpu.runtime.learner_tier import (
    LearnerTier,
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


def _params_tree():
    """A schema hitting every default partition class: a big kernel
    (model), an expert-stacked MoE tensor (expert), a pipe-stacked
    block, and a small bias (replicated)."""
    return {
        "dense": {"kernel": np.ones((64, 128), np.float32),
                  "bias": np.zeros(128, np.float32)},
        "moe_w1": np.ones((4, 32, 64), np.float32),
        "blocks_stacked": {"w": np.ones((8, 32, 32), np.float32)},
    }


# The direct-entry plan the exchange tests drive: one segment per
# class, sizes past MIN_PARTITION_SIZE so the classes are honest.
_ENTRIES = [("rep", 5000), ("-,model", 4096), ("expert", 4096),
            ("pipe", 4096)]
_VEC_LEN = sum(n for _, n in _ENTRIES)


def _seat_vecs(k: int) -> list[np.ndarray]:
    """Per-seat vectors at varied magnitudes (1e-3..1e3) so the bf16
    relative-error bound is exercised across exponents, not just near
    1.0."""
    rng = np.random.RandomState(7)
    scale = np.exp(rng.uniform(np.log(1e-3), np.log(1e3), _VEC_LEN))
    return [(rng.randn(_VEC_LEN) * scale).astype(np.float32)
            for _ in range(k)]


# -------------------------------------------------------- bf16 codec


class TestBf16Codec:
    def test_byte_identity_with_weight_shard_aliases(self):
        """Single-source regression: the weight plane's kernels ARE the
        data/bf16.py functions (aliases, not copies), and their output
        is byte-identical on the adversarial vector — a drifted copy
        would make gradients and published weights round differently."""
        from distributed_reinforcement_learning_tpu.runtime import (
            weight_shards)

        assert weight_shards._f32_to_bf16_u16 is f32_to_bf16_u16
        assert weight_shards._bf16_u16_to_f32 is bf16_u16_to_f32
        x = np.array([0.0, -0.0, 1.0, -1.0, np.pi, 1e-38, 1e38,
                      np.inf, -np.inf, np.nan, -np.nan,
                      1.0039062, 1.0039063,  # straddle the RNE tie
                      65504.0, 3.3895314e38], np.float32)
        a = weight_shards._f32_to_bf16_u16(x)
        b = f32_to_bf16_u16(x)
        assert a.tobytes() == b.tobytes()
        assert (weight_shards._bf16_u16_to_f32(a).tobytes()
                == bf16_u16_to_f32(b).tobytes())

    def test_rne_error_bound_and_idempotency(self):
        rng = np.random.RandomState(3)
        x = (rng.randn(4096) * np.exp(
            rng.uniform(np.log(1e-6), np.log(1e6), 4096))).astype(np.float32)
        rt = bf16_u16_to_f32(f32_to_bf16_u16(x))
        # Half-ulp of the 8-bit bf16 significand: |err| <= 2^-8 |x|.
        assert np.all(np.abs(rt - x) <= np.float32(2.0 ** -8) * np.abs(x))
        # Idempotent: a second roundtrip is the identity — the property
        # that lets the allgather forward quantized words and keep
        # every seat bit-identical.
        rt2 = bf16_u16_to_f32(f32_to_bf16_u16(rt))
        assert rt2.tobytes() == rt.tobytes()

    def test_nan_inf_safety(self):
        x = np.array([np.nan, -np.nan, np.inf, -np.inf,
                      3.39e38, -3.39e38], np.float32)
        rt = bf16_u16_to_f32(f32_to_bf16_u16(x))
        assert np.isnan(rt[0]) and np.isnan(rt[1])  # NaN never -> Inf
        assert rt[2] == np.inf and rt[3] == -np.inf
        # Huge finite values may round to Inf (bf16 shares f32's
        # exponent range, so only past-max values do) but never to NaN.
        assert not np.isnan(rt[4]) and not np.isnan(rt[5])


# ------------------------------------------------------ plan building


class TestExchangePlan:
    def test_segments_merge_and_deterministic_class_walk(self):
        plan = ExchangePlan([("rep", 4), ("rep", 4), ("-,model", 8),
                             ("rep", 2)])
        assert plan.length == 18
        # Adjacent same-class leaves merged; the later rep leaf is a
        # separate segment (the model class sits between).
        assert plan.segments["rep"] == [(0, 8), (16, 18)]
        assert plan.segments["-,model"] == [(8, 16)]
        assert plan.classes == ["rep", "-,model"]  # rep first, then sorted
        vec = np.arange(18, dtype=np.float32)
        rep = plan.gather(vec, "rep")
        assert rep.tolist() == list(range(8)) + [16.0, 17.0]
        out = np.zeros(18, np.float32)
        plan.scatter(out, "rep", rep)
        assert out[:8].tolist() == list(range(8)) and out[16] == 16.0

    def test_plan_hash_agreement_k2_k3(self):
        """Seats never exchange plans — they each BUILD one from the
        same schema and the hashes must land equal (k=2 and k=3 builds,
        fresh trees each time)."""
        hashes = [build_exchange_plan(_params_tree(), tail=1).plan_hash
                  for _ in range(3)]
        assert hashes[0] == hashes[1] == hashes[2]
        plan = build_exchange_plan(_params_tree(), tail=1)
        assert "-,model" in plan.classes and "expert" in plan.classes
        assert "pipe" in plan.classes and "rep" in plan.classes

    def test_quant_and_overlap_fold_into_hash(self):
        base = build_exchange_plan(_params_tree())
        assert build_exchange_plan(_params_tree(),
                                   quant="bf16").plan_hash != base.plan_hash
        assert build_exchange_plan(_params_tree(),
                                   overlap=1).plan_hash != base.plan_hash

    def test_invalid_quant_refused(self):
        with pytest.raises(ValueError, match="f32|bf16"):
            ExchangePlan([("rep", 4)], quant="fp8")

    def test_class_label_vocabulary(self):
        assert class_label("rep") == "rep"
        assert class_label("-,model") == "model"
        assert class_label("expert") == "expert"
        assert class_label("pipe") == "pipe"
        assert class_label("-,weird_axis") == "other"


# -------------------------------------------------- plan negotiation


class TestPlanNegotiation:
    @pytest.mark.parametrize("k", [2, 3])
    def test_hello_pins_agreement(self, k):
        colls = _collectives(k)
        plan = build_exchange_plan(_params_tree(), tail=1)
        try:
            for c in colls:
                c.set_plan(plan)
            for a in range(k):
                for b in range(k):
                    if a != b:
                        assert colls[a].probe_peer(b) is True
            for c in colls:
                c.check_plan_agreement()  # must not raise
        finally:
            for c in colls:
                c.close()

    def test_rule_mismatch_is_loud_refusal(self):
        """Seat 1 launched with skewed partition rules (its model
        kernel classified replicated): probes NAK both directions and
        the partitioned round refuses with PlanMismatch instead of
        merging mismatched segments."""
        colls = _collectives(2)
        good = ExchangePlan(_ENTRIES)
        skewed = ExchangePlan([("rep", 5000 + 4096), ("expert", 4096),
                               ("pipe", 4096)])
        try:
            colls[0].set_plan(good)
            colls[1].set_plan(skewed)
            assert colls[0].probe_peer(1) is False  # hash skew -> NAK
            assert colls[1].probe_peer(0) is False
            with pytest.raises(PlanMismatch):
                colls[0].check_plan_agreement()
            with pytest.raises(PlanMismatch):
                colls[1].check_plan_agreement()
            vec = np.zeros(_VEC_LEN, np.float32)
            with pytest.raises(PlanMismatch):
                colls[0].allreduce_mean(vec, plan=good)
        finally:
            for c in colls:
                c.close()

    def test_quant_mismatch_refused_too(self):
        colls = _collectives(2)
        try:
            colls[0].set_plan(ExchangePlan(_ENTRIES, quant="f32"))
            colls[1].set_plan(ExchangePlan(_ENTRIES, quant="bf16"))
            assert colls[0].probe_peer(1) is False
            with pytest.raises(PlanMismatch):
                colls[0].check_plan_agreement()
        finally:
            for c in colls:
                c.close()

    def test_unnegotiated_peer_is_not_a_mismatch(self):
        """Attach-order race: a peer that has not set a plan yet (None
        hash) must NOT refuse — the check re-runs every round."""
        colls = _collectives(2)
        try:
            colls[0].set_plan(ExchangePlan(_ENTRIES))
            assert colls[0].probe_peer(1) is True
            colls[0].check_plan_agreement()  # peer None: no refusal
        finally:
            for c in colls:
                c.close()


# ------------------------------------------------- partitioned rounds


class TestPartitionedExchange:
    def _round(self, colls, plan, vecs):
        for c in colls:
            c.set_plan(plan)
        return _run_threads(
            [lambda r=r: colls[r].allreduce_mean(vecs[r], plan=plan)
             for r in range(len(colls))])

    def test_owner_scoped_k2_exact_mean_per_class(self):
        """k=2 f32: two-term adds are order-independent, so every seat
        must equal the EXACT (v0+v1)/2 — per class, bit-for-bit."""
        vecs = _seat_vecs(2)
        colls = _collectives(2)
        plan = ExchangePlan(_ENTRIES)
        try:
            out = self._round(colls, plan, vecs)
            expect = (vecs[0] + vecs[1]) / np.float32(2)
            assert out[0].tobytes() == out[1].tobytes()
            for key in plan.classes:
                np.testing.assert_array_equal(
                    plan.gather(out[0], key), plan.gather(expect, key),
                    err_msg=f"class {key}")
        finally:
            for c in colls:
                c.close()

    def test_owner_scoped_k3_bit_identical_and_close(self):
        """k=3: seats bit-identical to EACH OTHER (the hard pin — skew
        here means diverging replicas), allclose to the mean (reduction
        order differs per chunk owner)."""
        vecs = _seat_vecs(3)
        colls = _collectives(3)
        plan = ExchangePlan(_ENTRIES)
        try:
            out = self._round(colls, plan, vecs)
            assert out[0].tobytes() == out[1].tobytes() == out[2].tobytes()
            np.testing.assert_allclose(
                out[0], np.mean(np.stack(vecs), axis=0, dtype=np.float64),
                rtol=1e-5, atol=1e-6)
            # Every sharded class had a distinct owner (3 classes over
            # 3 live ranks): each seat both sent and received star
            # traffic — the per-class byte counters prove the routing.
            for c in colls:
                stats = c.snapshot_stats()
                assert stats["coll_rounds_part"] == 1
                for cls in ("model", "expert", "pipe"):
                    assert stats[f"coll_bytes_{cls}"] > 0, (c.rank, stats)
        finally:
            for c in colls:
                c.close()

    def test_all_replicated_plan_matches_plan_less_ring_bitwise(self):
        """The partition-off equivalence: an all-rep plan must ride the
        exact same ring arithmetic as today's plan-less path — byte for
        byte. (DRL_COLL_PARTITION=0 simply skips building a plan.)"""
        vecs = _seat_vecs(2)
        legacy = _collectives(2)
        try:
            base = self._round(legacy, None, vecs)
        finally:
            for c in legacy:
                c.close()
        part = _collectives(2)
        plan = ExchangePlan([("rep", _VEC_LEN)])
        try:
            out = self._round(part, plan, vecs)
        finally:
            for c in part:
                c.close()
        assert out[0].tobytes() == base[0].tobytes()
        assert out[1].tobytes() == base[1].tobytes()

    def test_bf16_halves_wire_bytes_and_bounds_error(self):
        """bf16 rounds: exactly half the payload bytes of the f32 round
        (u16 vs f32 words, same element counts), seats bit-identical,
        and the absolute error vs the f32 merge bounded by 2^-7 x the
        mean |contribution| — the master-accumulation contract (only
        transported values round, never the f32 sums)."""
        vecs = _seat_vecs(2)
        f32_colls = _collectives(2)
        try:
            f32_out = self._round(f32_colls, ExchangePlan(_ENTRIES), vecs)
            f32_bytes = sum(c.stat("bytes_sent") for c in f32_colls)
        finally:
            for c in f32_colls:
                c.close()
        bf_colls = _collectives(2)
        try:
            bf_out = self._round(bf_colls,
                                 ExchangePlan(_ENTRIES, quant="bf16"), vecs)
            bf_bytes = sum(c.stat("bytes_sent") for c in bf_colls)
            for c in bf_colls:
                assert c.stat("coll_quant_rounds") == 1
        finally:
            for c in bf_colls:
                c.close()
        assert bf_bytes * 2 == f32_bytes
        assert bf_out[0].tobytes() == bf_out[1].tobytes()
        bound = (np.float32(2.0 ** -7)
                 * (np.abs(vecs[0]) + np.abs(vecs[1])) / 2 + 1e-7)
        assert np.all(np.abs(bf_out[0] - f32_out[0]) <= bound)

    def test_bf16_nan_inf_survive_the_round(self):
        """Poisoned gradients must surface AS poison on every seat —
        a NaN that quantized into Inf (or vanished) would corrupt the
        merge silently. One NaN in the ring class, one Inf in a star
        class."""
        vecs = _seat_vecs(2)
        vecs[0][10] = np.nan          # rep segment (ring)
        vecs[1][5000 + 7] = np.inf    # model segment (star)
        colls = _collectives(2)
        try:
            out = self._round(colls, ExchangePlan(_ENTRIES, quant="bf16"),
                              vecs)
            assert out[0].tobytes() == out[1].tobytes()
            assert np.isnan(out[0][10])
            assert np.isinf(out[0][5000 + 7])
        finally:
            for c in colls:
                c.close()

    def test_stale_plan_length_refused(self):
        colls = _collectives(1)  # solo is enough: the check is local
        try:
            with pytest.raises(CollectiveError, match="stale plan"):
                colls[0].allreduce_mean(np.zeros(8, np.float32),
                                        plan=ExchangePlan([("rep", 9)]))
        finally:
            colls[0].close()


# --------------------------------------------------------- env gates


class TestCollGates:
    @pytest.fixture(autouse=True)
    def _fresh_flags(self, monkeypatch):
        for key in ("DRL_COLL_PARTITION", "DRL_COLL_QUANT",
                    "DRL_COLL_OVERLAP"):
            monkeypatch.delenv(key, raising=False)
        yield monkeypatch

    def test_partition_defaults_on_and_env_forces(self, monkeypatch):
        assert learner_tier.coll_partition() is True
        monkeypatch.setenv("DRL_COLL_PARTITION", "0")
        assert learner_tier.coll_partition() is False
        monkeypatch.setenv("DRL_COLL_PARTITION", "1")
        assert learner_tier.coll_partition() is True

    def test_quant_env_forces(self, monkeypatch):
        monkeypatch.setenv("DRL_COLL_QUANT", "bf16")
        assert learner_tier.coll_quant() == "bf16"
        monkeypatch.setenv("DRL_COLL_QUANT", "0")
        assert learner_tier.coll_quant() == "f32"

    def test_overlap_env_caps_depth_at_one(self, monkeypatch):
        monkeypatch.setenv("DRL_COLL_OVERLAP", "3")
        assert learner_tier.coll_overlap() == 1
        monkeypatch.setenv("DRL_COLL_OVERLAP", "0")
        assert learner_tier.coll_overlap() == 0

    def test_overlap_non_integer_is_loud(self, monkeypatch):
        monkeypatch.setenv("DRL_COLL_OVERLAP", "yes")
        with pytest.raises(ValueError, match="DRL_COLL_OVERLAP"):
            learner_tier.coll_overlap()



# --------------------------------------------- backward-overlapped rounds


class _OverlapRig:
    """A solo tier with stubbed backward + exchange latencies: the
    timing pin needs controlled sleeps, not XLA noise. grads_fn IS the
    'backward' (sleep BW), _merged_rounds the exchange (sleep RT)."""

    BW = 0.06
    RT = 0.06

    def __init__(self, overlap: int):
        self.addrs = _addrs(1)
        self.tier = LearnerTier(0, self.addrs, sync="allreduce",
                                probe_interval_s=60.0)
        self.tier.start()
        self.tier._plan = ExchangePlan([("rep", 5)], overlap=overlap)
        self.exchanged = []

        def merged(vec):
            time.sleep(self.RT)
            self.exchanged.append(vec.copy())
            return vec.astype(np.float32, copy=True)

        self.tier._merged_rounds = merged
        if overlap:
            self.tier._coll_worker = threading.Thread(
                target=self.tier._coll_loop, daemon=True, name="t-coll")
            self.tier._coll_worker.start()

        def grads_fn(state, batch, w):
            time.sleep(self.BW)
            return {"g": np.full(4, float(state), np.float32)}, None, 0.5

        def apply_fn(state, grads, loss):
            return state + 1, {"loss": loss, "grad_norm": 1.0}

        self.learn = self.tier._make_allreduce_learn(grads_fn, apply_fn)

    def close(self):
        self.tier.close()


class TestOverlappedRounds:
    def test_overlap_actually_overlaps(self):
        """THE wall-clock pin: 6 steps of (backward BW + exchange RT).
        Serial pays BW+RT per step; overlapped hides the exchange
        behind the NEXT step's backward — ~BW per steady-state step.
        Generous 0.85 bar (expected ratio ~0.55) so a loaded CI host
        cannot flake it, same style as the device-path overlap pin."""
        steps = 6
        serial = _OverlapRig(overlap=0)
        try:
            state, t0 = 0, time.perf_counter()
            for _ in range(steps):
                state, _, _ = serial.learn(state, None, None)
            serial_s = time.perf_counter() - t0
            assert state == steps  # every step applied inline
        finally:
            serial.close()
        rig = _OverlapRig(overlap=1)
        try:
            state, t0 = 0, time.perf_counter()
            for _ in range(steps):
                state, _, _ = rig.learn(state, None, None)
            overlap_s = time.perf_counter() - t0
            # Delayed apply: the priming step applied nothing, so the
            # pipeline is one apply behind.
            assert state == steps - 1
        finally:
            rig.close()
        assert overlap_s < 0.85 * serial_s, (overlap_s, serial_s)

    def test_priming_step_returns_state_unchanged(self):
        rig = _OverlapRig(overlap=1)
        try:
            state, _, metrics = rig.learn(7, None, None)
            assert state == 7  # nothing merged yet: unchanged
            assert set(metrics) == {"loss"}  # local loss only
            state, _, metrics = rig.learn(state, None, None)
            assert state == 8  # previous round's merge applied
            assert "grad_norm" in metrics
            assert rig.tier.snapshot_stats()["overlap_rounds"] == 2
        finally:
            rig.close()

    def test_worker_exception_reraises_on_learn_thread(self):
        """A PlanMismatch inside the worker must refuse the LEARN
        call — training on silently-unmerged gradients is the failure
        mode the forwarding exists to prevent."""
        rig = _OverlapRig(overlap=1)
        try:
            def boom(vec):
                raise PlanMismatch("skewed plans")

            rig.tier._merged_rounds = boom
            rig.learn(0, None, None)  # primes: hands vec to the worker
            with pytest.raises(PlanMismatch, match="skewed"):
                rig.learn(0, None, None)
        finally:
            rig.close()

"""bench.py output contract under failure modes.

The driver takes bench.py's LAST stdout line as the official metric, and
a bench that emitted only at the very end once lost its number to a
timeout. These tests pin the protections by running bench.py as a real
subprocess (BENCH_PLATFORM=cpu — a smoke of the bench's mechanics,
trimmed sections):

- budget gating: with the wall-clock budget effectively exhausted,
  sections are skipped (and recorded) and the final line still parses;
- the watchdog: with the budget set before the process even started
  (negative), the watchdog force-emits a parseable line;
- no failure path exits 0: a run with no measurement, the watchdog, a
  failed section and a missing accelerator all end non-zero, and
  nothing re-runs the bench on another backend.
"""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _load_bench():
    """Import bench.py as a module (repo root is not on sys.path; the
    module top level is import-light — jax only loads inside main)."""
    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

_TRIMMED = {
    "BENCH_PLATFORM": "cpu",
    "BENCH_SWEEP": "8",
    "BENCH_ITERS": "2",
    "BENCH_SCAN": "0", "BENCH_FOLD": "0", "BENCH_RESNET": "0",
    "BENCH_E2E": "0", "BENCH_BUDGET": "0", "BENCH_KERNELS": "0",
    "BENCH_R2D2": "0", "BENCH_APEX": "0", "BENCH_XIMPALA": "0",
    "BENCH_APEX_INGEST": "0", "BENCH_INGEST": "0",
    "BENCH_ANAKIN": "0", "BENCH_ANAKIN_R2D2": "0",
    "BENCH_TRANSPORT": "0", "BENCH_CODEC": "0", "BENCH_WEIGHTS": "0",
    "BENCH_WEIGHTS_SHARD": "0", "BENCH_REPLAY": "0", "BENCH_INFER": "0",
    "BENCH_CHAOS": "0", "BENCH_ACTOR": "0",
    "BENCH_ADMISSION": "0", "BENCH_REPLAY_SPILL": "0",
    "BENCH_LEARNER": "0", "BENCH_SEAT_DRILL": "0",
    "BENCH_DEVICE_PATH": "0", "BENCH_COLLECTIVE": "0",
}


def _run_bench(budget: str, cwd, extra_env=None, timeout: float = 280.0):
    # cwd = a tmp dir: bench.py's _emit rewrites ./bench_artifacts/
    # unconditionally, and running in the repo would clobber the round's
    # real committed artifact.
    env = {**os.environ, **_TRIMMED, "BENCH_TIME_BUDGET": budget,
           "JAX_PLATFORMS": "cpu", **(extra_env or {})}
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py")], env=env, cwd=cwd,
        capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    assert lines, f"no stdout (rc={proc.returncode}): {proc.stderr[-500:]}"
    # The driver reads only the last ~2000 bytes of stdout; r5's enriched
    # final line (~3.6 KB) blew past that and parsed as null. _emit now
    # keeps stdout compact (full detail goes to bench_detail.json) — pin
    # the contract on EVERY final line any mode produces.
    assert len(lines[-1]) <= 2000, (len(lines[-1]), lines[-1][:200])
    return proc, json.loads(lines[-1])


def test_budget_skips_sections_but_final_line_parses(tmp_path):
    proc, last = _run_bench(budget="45", cwd=tmp_path)
    # Every measuring section was gated off: a run with no measurement
    # is a failed run (exit code), whose last line still parses.
    assert proc.returncode != 0
    assert "no learn-step measurement" in last["extra"]["error"]
    assert last["metric"] and "value" in last and "vs_baseline" in last
    assert last["extra"]["device"]["platform"] == "cpu"
    # est 90 s > budget 45 s: the learn sweep section is deterministically
    # gated off — and must be RECORDED, not silently dropped. The compact
    # stdout line carries only the COUNT; the section NAMES live in the
    # full-detail artifact.
    assert last["extra"].get("skipped_sections", 0) > 0, last["extra"]
    detail = json.loads((tmp_path / "bench_artifacts" /
                         "bench_detail.json").read_text())
    skipped = detail["extra"].get("skipped_sections")
    assert skipped and any(s.startswith("learn_step") for s in skipped), skipped


def test_watchdog_force_emits_while_main_thread_is_wedged(tmp_path):
    """budget = -301 puts the watchdog's deadline (budget + 300 s grace)
    in the past at thread start, and BENCH_TEST_STALL_S parks the main
    thread the way a section stuck in a device call does: the WATCHDOG
    (not the normal exit path, which is still asleep) must emit the
    parseable final line — and exit NON-ZERO: the run did not finish."""
    proc, last = _run_bench(budget="-301", cwd=tmp_path,
                            extra_env={"BENCH_TEST_STALL_S": "60"},
                            timeout=90.0)
    assert proc.returncode != 0
    assert last["metric"] and "value" in last
    assert "watchdog" in last["extra"], last["extra"]


class TestDeviceHandling:
    """No probe, no fall-back, no default peak: where bench.py cannot
    say which device a number came from, it fails."""

    def test_no_accelerator_and_no_forced_platform_exits_nonzero(self, tmp_path):
        env = {k: v for k, v in os.environ.items() if k != "BENCH_PLATFORM"}
        proc = subprocess.run(
            [sys.executable, str(REPO / "bench.py")], cwd=tmp_path,
            env={**env, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=120.0)
        assert proc.returncode != 0
        assert proc.stdout.strip() == "", proc.stdout  # no result line
        assert "no accelerator" in proc.stderr

    def test_failed_section_exits_nonzero(self, tmp_path):
        """A section that raises is recorded AND turns the exit code:
        zero on-device envs cannot build the anakin section."""
        proc, last = _run_bench(
            budget="2700", cwd=tmp_path,
            extra_env={"BENCH_ANAKIN": "1", "BENCH_ANAKIN_ENVS": "0"})
        assert proc.returncode != 0
        assert last["extra"]["failed_sections"] == ["anakin"], last["extra"]
        assert last["value"] > 0  # the learn sweep still measured

    @pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
    def test_v5e_peaks_carry_flops_and_hbm(self, monkeypatch, kind):
        import jax

        bench = _load_bench()
        dev = type("D", (), {"device_kind": kind})()
        monkeypatch.setattr(jax, "devices", lambda *a: [dev])
        peaks, source = bench._device_peaks()
        assert peaks == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
        assert kind.lower() in source
        assert bench._peak_flops() == (197e12, source)

    def test_unknown_device_kind_is_an_error_not_a_default(self, monkeypatch):
        import jax

        bench = _load_bench()
        dev = type("D", (), {"device_kind": "TPU v9 imaginary"})()
        monkeypatch.setattr(jax, "devices", lambda *a: [dev])
        with pytest.raises(RuntimeError, match="no published peaks"):
            bench._peak_flops()
        # On the CPU (BENCH_PLATFORM=cpu) no device metric is reported
        # at all, so the table is never asked.
        assert bench._mfu_fields(1e9, 1e-3) == {}

    def test_probe_fallback_and_chunk_gates_are_gone(self):
        bench = _load_bench()
        for name in ("_probe_backend", "_run_cpu_fallback",
                     "check_chunk_gates"):
            assert not hasattr(bench, name), name


class TestTransportCompare:
    """bench_transport_compare: the TCP-vs-shm-ring PUT A/B whose verdict
    gates runtime/shm_ring's auto-enable. Driven directly at a tiny
    config (CPU, host-only) — the committed hardware-adjudication
    numbers live in benchmarks/transport_verdict.json."""

    def test_section_shape_and_verdict(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.setenv("DRL_SHM_RING_MB", "4")  # tiny test segment
        bench = _load_bench()
        from distributed_reinforcement_learning_tpu.agents.impala import ImpalaConfig

        cfg = ImpalaConfig(obs_shape=(8,), num_actions=2, trajectory=8,
                           lstm_size=16)
        r = bench.bench_transport_compare(cfg, n_unrolls=32, reps=1)
        for side in ("tcp", "ring"):
            assert r[side]["frames_per_s"] > 0, r
            assert r[side]["enqueue_wait_ms_p99"] >= r[side]["enqueue_wait_ms_p50"]
        assert r["ring_vs_tcp"] > 0
        assert r["auto_enable"] == (r["ring_vs_tcp"] >= 1.2)
        assert r["verdict"].startswith("ring ") and (
            "auto-on" in r["verdict"] or "opt-in" in r["verdict"])

    def test_committed_verdict_file_consistent(self):
        """The committed adjudication parses, and ring_enabled() follows
        it when DRL_SHM_RING is unset."""
        verdict = json.loads(
            (REPO / "benchmarks" / "transport_verdict.json").read_text())
        assert isinstance(verdict["auto_enable"], bool)
        assert verdict["ratio_runs"] and verdict["bar"] == 1.2
        from distributed_reinforcement_learning_tpu.runtime.shm_ring import (
            ring_auto_enabled)

        assert ring_auto_enabled() is verdict["auto_enable"]


class TestCodecCompare:
    """bench_codec_compare: the old-vs-new encode+PUT A/B whose verdict
    gates the codec schema cache and frame-stack dedup defaults
    (data/codec.py). Driven directly at a tiny stacked config — the
    committed adjudication lives in benchmarks/codec_verdict.json."""

    def test_section_shape_and_verdict(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        # Ambient shell may export the documented knobs; the A/B itself
        # must run the same regardless (the child strips them).
        monkeypatch.delenv("DRL_CODEC_CACHE", raising=False)
        monkeypatch.delenv("DRL_OBS_DEDUP", raising=False)
        bench = _load_bench()
        from distributed_reinforcement_learning_tpu.agents.impala import ImpalaConfig
        from distributed_reinforcement_learning_tpu.data import codec

        cfg = ImpalaConfig(obs_shape=(12, 12, 4), num_actions=2, trajectory=8,
                           lstm_size=16)
        r = bench.bench_codec_compare(cfg, n_unrolls=32, reps=1)
        for side in ("cold", "cached", "dedup"):
            assert r[side]["frames_per_s"] > 0, r
            assert r[side]["put_ms_p99"] >= r[side]["put_ms_p50"]
        # The stacked leaf must actually have packed (dedup saw the
        # redundancy), and the A/B must restore the caller's env.
        assert r["packed_bytes"] < r["unroll_bytes"]
        assert r["cached_vs_cold"] > 0 and r["dedup_vs_cached"] > 0
        assert r["cache_auto_enable"] == (r["cached_vs_cold"] >= 1.2)
        assert r["dedup_auto_enable"] == (r["dedup_vs_cached"] >= 1.2)
        assert r["verdict"].startswith("codec cache ")
        assert os.environ.get("DRL_CODEC_CACHE") is None
        codec.refresh_flags()

    def test_compact_line_carries_codec_verdict_key(self):
        bench = _load_bench()
        assert "codec_verdict" in bench._COMPACT_KEYS

    def test_committed_verdict_file_consistent(self):
        """The committed adjudication parses, and the codec gates follow
        it when the env knobs are unset."""
        verdict = json.loads(
            (REPO / "benchmarks" / "codec_verdict.json").read_text())
        assert isinstance(verdict["cache_auto_enable"], bool)
        assert isinstance(verdict["dedup_auto_enable"], bool)
        assert verdict["cache_ratio_runs"] and verdict["bar"] == 1.2
        from distributed_reinforcement_learning_tpu.data import codec

        old = {k: os.environ.pop(k, None)
               for k in ("DRL_CODEC_CACHE", "DRL_OBS_DEDUP")}
        try:
            codec.refresh_flags()
            assert codec.cache_enabled() is verdict["cache_auto_enable"]
            assert codec.obs_dedup_enabled() is verdict["dedup_auto_enable"]
        finally:
            for k, v in old.items():
                if v is not None:
                    os.environ[k] = v
            codec.refresh_flags()


class TestWeightsCompare:
    """bench_weights_compare: the two-process TCP-vs-shm-board weight
    pull A/B whose verdict gates runtime/weight_board's auto-enable.
    Driven directly at a tiny config (CPU, host-only) — the committed
    adjudication numbers live in benchmarks/weights_verdict.json."""

    def test_section_shape_and_verdict(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        # Small test board — but it must still fit the section's ~4.2 MB
        # params blob per slot (undersized slots are the latch-off test
        # in test_weight_board.py, not this contract).
        monkeypatch.setenv("DRL_SHM_WEIGHTS_MB", "8")
        bench = _load_bench()
        from distributed_reinforcement_learning_tpu.agents.impala import ImpalaConfig

        cfg = ImpalaConfig(obs_shape=(8,), num_actions=2, trajectory=8,
                           lstm_size=16)
        r = bench.bench_weights_compare(cfg, n_actors=1, rounds=16,
                                        publish_period_s=0.005)
        for side in ("tcp", "board"):
            assert r[side]["frames_per_s"] > 0, r
            assert (r[side]["weight_pull_ms_p99"]
                    >= r[side]["weight_pull_ms_p50"])
            # The publish-stage split the section exists to record.
            for stage in ("publish", "publish_handoff", "publish_stall"):
                assert {"p50_ms", "p99_ms", "n"} <= set(r[side][stage])
            assert r[side]["publish"]["n"] > 0
        # The warm pull alone guarantees at least one full board pull
        # even if the timed rounds all raced ahead of the publisher.
        assert r["board"]["board_stats"]["board_pulls"] >= 1
        assert r["board"]["board_stats"]["tcp_fallbacks"] == 0
        assert r["board_vs_tcp"] > 0 and r["pull_p50_speedup"] > 0
        assert r["auto_enable"] == (r["board_vs_tcp"] >= 1.2)
        assert r["verdict"].startswith("board ") and (
            "auto-on" in r["verdict"] or "opt-in" in r["verdict"])

    def test_compact_line_carries_weights_verdict_key(self):
        bench = _load_bench()
        assert "weights_verdict" in bench._COMPACT_KEYS

    def test_committed_verdict_file_consistent(self, monkeypatch):
        """The committed adjudication parses, and board_enabled() follows
        it when DRL_SHM_WEIGHTS is unset."""
        verdict = json.loads(
            (REPO / "benchmarks" / "weights_verdict.json").read_text())
        assert isinstance(verdict["auto_enable"], bool)
        assert verdict["ratio_runs"] and verdict["bar"] == 1.2
        from distributed_reinforcement_learning_tpu.runtime.weight_board import (
            board_auto_enabled)

        assert board_auto_enabled() is verdict["auto_enable"]


class TestWeightsShardCompare:
    """bench_weights_shard_compare: the whole-vs-sharded-vs-bf16 weight
    plane A/B whose verdict gates DRL_WEIGHTS_SHARDED / _QUANT defaults
    (runtime/weight_shards.py). Driven directly at a tiny config and a
    single (cnn) shape — the committed adjudication numbers live in
    benchmarks/weights_shard_verdict.json."""

    def test_section_shape_and_verdict(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        for key in ("DRL_WEIGHTS_SHARDED", "DRL_WEIGHTS_QUANT",
                    "DRL_WEIGHTS_DELTA"):
            monkeypatch.delenv(key, raising=False)
        bench = _load_bench()
        from distributed_reinforcement_learning_tpu.agents.impala import ImpalaConfig

        cfg = ImpalaConfig(obs_shape=(8,), num_actions=2, trajectory=8,
                           lstm_size=16)
        r = bench.bench_weights_shard_compare(
            cfg, n_actors=1, rounds=12, publish_period_s=0.005,
            shapes=("cnn",))
        sec = r["cnn"]
        for side in ("whole", "sharded", "sharded_bf16"):
            assert sec[side]["frames_per_s"] > 0, r
            assert (sec[side]["weight_pull_ms_p99"]
                    >= sec[side]["weight_pull_ms_p50"])
            assert sec[side]["publish"]["n"] > 0
            assert sec[side]["broadcast_bytes_per_version"] > 0
        # The bf16 broadcast must actually halve-ish the bytes...
        assert (sec["sharded_bf16"]["broadcast_bytes_per_version"]
                < 0.6 * sec["whole"]["broadcast_bytes_per_version"])
        # ...and the un-quantized shard variant must NOT change them
        # much (same payload, split differently).
        assert (sec["sharded"]["broadcast_bytes_per_version"]
                <= 1.1 * sec["whole"]["broadcast_bytes_per_version"])
        assert r["policy_equiv"]["action_match"] > 0.9
        assert r["auto_enable"] == (r["sharded_ratio"] >= 1.2)
        assert r["delta_auto_enable"] is False
        assert r["verdict"].startswith("sharded ") and (
            "auto-on" in r["verdict"] or "opt-in" in r["verdict"])

    def test_compact_line_carries_shard_verdict_key(self):
        bench = _load_bench()
        assert "weights_shard_verdict" in bench._COMPACT_KEYS

    def test_committed_verdict_file_consistent(self, monkeypatch):
        """The committed adjudication parses, and the weight_shards
        gates follow it when the env knobs are unset."""
        verdict = json.loads(
            (REPO / "benchmarks" / "weights_shard_verdict.json").read_text())
        assert isinstance(verdict["auto_enable"], bool)
        assert isinstance(verdict["quant_auto_enable"], bool)
        assert isinstance(verdict["delta_auto_enable"], bool)
        assert verdict["bar"] == 1.2
        from distributed_reinforcement_learning_tpu.runtime import weight_shards

        for key in ("DRL_WEIGHTS_SHARDED", "DRL_WEIGHTS_QUANT",
                    "DRL_WEIGHTS_DELTA"):
            monkeypatch.delenv(key, raising=False)
        weight_shards.refresh_flags()
        try:
            assert weight_shards.sharded_enabled() is verdict["auto_enable"]
            assert (weight_shards.quant_mode() is not None) is \
                verdict["quant_auto_enable"]
            assert weight_shards.delta_enabled() is verdict["delta_auto_enable"]
        finally:
            weight_shards.refresh_flags()


class TestReplayCompare:
    """bench_replay_compare: the two-process monolithic-vs-sharded Ape-X
    ingest A/B whose verdict gates data/replay_service's auto-enable.
    Driven directly at a tiny config (CPU, host-only) — the committed
    adjudication numbers live in benchmarks/replay_verdict.json."""

    def test_section_shape_and_verdict(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        bench = _load_bench()
        r = bench.bench_replay_compare(n_unrolls=24, unrolls_per_put=8,
                                       steps=16, obs_dim=16, reps=1)
        for side in ("mono", "sharded"):
            assert r[side]["frames_per_s"] > 0, r
            assert r[side]["sample_ms_p99"] >= r[side]["sample_ms_p50"]
        assert r["sharded"]["shards"] >= 1
        assert sum(r["sharded"]["shard_fill"]) > 0  # shards really filled
        assert r["sharded_vs_mono"] > 0
        assert r["auto_enable"] == (r["sharded_vs_mono"] >= 1.2)
        assert r["verdict"].startswith("replay shards ") and (
            "auto-on" in r["verdict"] or "opt-in" in r["verdict"])

    def test_compact_line_carries_replay_verdict_key(self):
        bench = _load_bench()
        assert "replay_verdict" in bench._COMPACT_KEYS

    def test_committed_verdict_file_consistent(self, monkeypatch):
        """The committed adjudication parses, and shard_count() follows
        it when DRL_REPLAY_SHARDS is unset (env force > committed
        verdict > off)."""
        monkeypatch.delenv("DRL_REPLAY_SHARDS", raising=False)
        verdict = json.loads(
            (REPO / "benchmarks" / "replay_verdict.json").read_text())
        assert isinstance(verdict["auto_enable"], bool)
        assert verdict["ratio_runs"] and verdict["bar"] == 1.2
        from distributed_reinforcement_learning_tpu.runtime.replay_shard import (
            shard_count, shards_auto_enabled)

        assert shards_auto_enabled() is verdict["auto_enable"]
        assert (shard_count() > 0) is verdict["auto_enable"]
        monkeypatch.setenv("DRL_REPLAY_SHARDS", "3")
        assert shard_count() == 3  # env force wins over the verdict
        monkeypatch.setenv("DRL_REPLAY_SHARDS", "0")
        assert shard_count() == 0


class TestReplaySpillCompare:
    """bench_replay_spill_compare: the in-process all-RAM vs hot/cold
    tiered-store A/B whose verdict gates data/replay_spill's
    auto-enable (runtime/replay_shard.spill_auto_enabled). Driven
    directly at a tiny spill-forcing config — the committed
    adjudication numbers live in benchmarks/replay_spill_verdict.json."""

    def test_section_shape_and_verdict(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        bench = _load_bench()
        r = bench.bench_replay_spill_compare(budget_mb=0.25,
                                             capacity_mult=4, obs_dim=32,
                                             seg_items=64, batch=16,
                                             rounds=30, reps=1)
        for side in ("all_ram", "tiered"):
            assert r[side]["stored"] > 0, r
            assert r[side]["transitions_per_gb"] > 0
            assert r[side]["sample_tr_per_s"] > 0
        # The hot budget really forced segments to disk — a spill-free
        # run would adjudicate nothing (the section asserts this too).
        tiered = r["tiered"]
        assert tiered["spilled_segments"] > 0
        assert tiered["disk_mb"] > 0
        assert tiered["stored"] > r["all_ram"]["stored"]  # the point
        # Delivery honesty: no draw was ever padded with a wrong item
        # and no segment was lost to corruption.
        assert tiered["forced_pads"] == 0 and tiered["crc_dropped"] == 0
        assert r["density_ratio"] > 0 and r["sample_parity"] > 0
        assert r["auto_enable"] == (r["density_ratio"] >= 4.0
                                    and r["sample_parity"] >= 0.9)
        assert r["verdict"].startswith("tiered replay ") and (
            "auto-on" in r["verdict"] or "opt-in" in r["verdict"])

    def test_compact_line_carries_spill_verdict_key(self):
        bench = _load_bench()
        assert "replay_spill_verdict" in bench._COMPACT_KEYS
        # The trimmed env the failure-mode subprocess tests run under
        # must gate this (disk-churning, timed) section off.
        assert _TRIMMED["BENCH_REPLAY_SPILL"] == "0"

    def test_committed_verdict_file_consistent(self, monkeypatch):
        """The committed adjudication parses, meets the issue's density
        bar when auto-on, and spill_auto_enabled() follows it when
        DRL_REPLAY_SPILL is unset (env force > committed verdict >
        off)."""
        monkeypatch.delenv("DRL_REPLAY_SPILL", raising=False)
        path = REPO / "benchmarks" / "replay_spill_verdict.json"
        verdict = json.loads(path.read_text())
        assert isinstance(verdict["auto_enable"], bool)
        assert verdict["ratio_runs"] and verdict["bar"] == 4.0
        assert verdict["parity_runs"] and verdict["parity_bar"] == 0.9
        if verdict["auto_enable"]:
            assert verdict["ratio_median"] >= 4.0
            assert verdict["parity_median"] >= 0.9
        from distributed_reinforcement_learning_tpu.runtime.replay_shard import (
            spill_auto_enabled)

        assert spill_auto_enabled(str(path)) is verdict["auto_enable"]
        monkeypatch.setenv("DRL_REPLAY_SPILL", "1")
        assert spill_auto_enabled(str(path))
        monkeypatch.setenv("DRL_REPLAY_SPILL", "0")
        assert not spill_auto_enabled(str(path))


class TestAdmissionCompare:
    """bench_admission_compare: the two-process scored-vs-stamped
    sample-at-source A/B whose verdict gates data/admission's
    auto-enable. Driven directly at a tiny config (CPU, real child over
    loopback TCP) — the committed adjudication numbers live in
    benchmarks/admission_verdict.json."""

    def test_section_shape_and_verdict(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        bench = _load_bench()
        r = bench.bench_admission_compare(n_unrolls=24, unrolls_per_put=8,
                                          steps=16, obs_dim=16, reps=1)
        for leg in ("scored", "stamped", "admitted"):
            assert r[leg]["accepted_transitions"] > 0, r
            assert r[leg]["ingest_cpu_us_per_transition"] > 0
            assert r[leg]["wire_bytes"] > 0
        # Each leg really took its intended ingest path.
        assert r["scored"]["stamped_blobs"] == 0
        assert r["stamped"]["stamped_blobs"] == 24
        assert r["admitted"]["child"]["subsample_dropped"] > 0  # thinned
        # Conservation: the child's dropped mass is the learner's folded
        # mass plus the controller's undrained ledger.
        child = r["admitted"]["child"]
        assert abs(child["dropped_mass"] - (r["admitted"]["folded_mass"]
                                            + child["pending_folded"])) < 1e-9
        assert r["scored_vs_stamped_cpu"] > 0
        assert r["auto_enable"] == (r["scored_vs_stamped_cpu"] >= 1.2)
        assert r["admission_auto_enable"] is False  # opt-in by design
        assert r["verdict"].startswith("actor stamps ") and (
            "auto-on" in r["verdict"] or "opt-in" in r["verdict"])

    def test_compact_line_carries_admission_verdict_key(self):
        bench = _load_bench()
        assert "admission_verdict" in bench._COMPACT_KEYS

    def test_committed_verdict_file_consistent(self, monkeypatch):
        """The committed adjudication parses, and the gates follow it
        when the env knobs are unset (env force > committed verdict >
        off)."""
        monkeypatch.delenv("DRL_ACTOR_PRIORITY", raising=False)
        monkeypatch.delenv("DRL_ADMISSION", raising=False)
        verdict = json.loads(
            (REPO / "benchmarks" / "admission_verdict.json").read_text())
        assert isinstance(verdict["actor_priority_auto_enable"], bool)
        assert isinstance(verdict["admission_auto_enable"], bool)
        assert verdict["ratio_runs"] and verdict["bar"] == 1.2
        # The sequence-mode (R2D2) re-adjudication the original
        # verdict's honest-negative note called for is recorded.
        rerun = verdict["rerun_sequence_mode"]
        assert isinstance(rerun["auto_enable"], bool)
        assert rerun["ratio_runs"] and rerun["bar"] == 1.2
        from distributed_reinforcement_learning_tpu.data import admission

        admission.refresh_flags()
        try:
            assert (admission.actor_priority_enabled()
                    is verdict["actor_priority_auto_enable"])
            assert (admission.admission_enabled()
                    is verdict["admission_auto_enable"])
            monkeypatch.setenv("DRL_ACTOR_PRIORITY", "1")
            monkeypatch.setenv("DRL_ADMISSION", "1")
            admission.refresh_flags()
            assert admission.actor_priority_enabled()  # env force wins
            assert admission.admission_enabled()
        finally:
            monkeypatch.undo()
            admission.refresh_flags()


class TestDevicePathCompare:
    """bench_device_path_compare: the host-vs-fused sample-path A/B
    whose verdict gates data/device_path's auto-enable. Driven directly
    at a tiny config (CPU, real feeder child over loopback TCP, real
    sharded service both sides) — the committed adjudication numbers
    live in benchmarks/device_path_verdict.json."""

    def test_section_shape_and_verdict(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        bench = _load_bench()
        r = bench.bench_device_path_compare(window_s=1.5, steps=16,
                                            obs_dim=16, k=2, batch_size=16,
                                            reps=1)
        for side in ("host", "device"):
            assert r[side]["train_frames_per_s"] > 0, r
            assert r[side]["train_steps_in_window"] > 0
            assert r[side]["ingested_unrolls_in_window"] > 0  # under load
            assert (r[side]["train_call_ms_p99"]
                    >= r[side]["train_call_ms_p50"])
        # The device variant really trained through the fused path.
        dp = r["device"]["devpath"]
        assert dp["entries_out"] > 0 and dp["h2d_bytes"] > 0
        assert dp["k"] == 2 and dp["dead_reason"] is None
        assert r["device_vs_host"] > 0
        assert r["auto_enable"] == (r["device_vs_host"] >= 1.2)
        assert r["verdict"].startswith("device sample path ") and (
            "auto-on" in r["verdict"] or "opt-in" in r["verdict"])

    def test_compact_line_carries_device_path_verdict_key(self):
        bench = _load_bench()
        assert "device_path_verdict" in bench._COMPACT_KEYS

    def test_trimmed_env_disables_section(self):
        assert _TRIMMED["BENCH_DEVICE_PATH"] == "0"

    def test_committed_verdict_file_consistent(self, monkeypatch):
        """The committed adjudication parses, and the gate follows it
        when DRL_DEVICE_PATH is unset (env force > verdict > off)."""
        monkeypatch.delenv("DRL_DEVICE_PATH", raising=False)
        path = REPO / "benchmarks" / "device_path_verdict.json"
        verdict = json.loads(path.read_text())
        assert isinstance(verdict["auto_enable"], bool)
        assert verdict["ratio_runs"] and verdict["bar"] == 1.2
        from distributed_reinforcement_learning_tpu.data.device_path import (
            device_path_enabled)

        assert device_path_enabled(str(path)) is verdict["auto_enable"]
        monkeypatch.setenv("DRL_DEVICE_PATH", "1")
        assert device_path_enabled(str(path))
        monkeypatch.setenv("DRL_DEVICE_PATH", "0")
        assert not device_path_enabled(str(path))


class TestLearnerCompare:
    """bench_learner_compare: the one-seat vs N-seat learner-tier A/B
    whose verdict gates runtime/learner_tier's auto-enable. Driven
    directly at a tiny config (CPU, real seat child processes + real
    collective rounds) — the committed adjudication numbers live in
    benchmarks/learner_verdict.json."""

    def test_section_shape_and_verdict(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        bench = _load_bench()
        r = bench.bench_learner_compare(seats=2, sync="allreduce",
                                        window_s=4.0, unrolls_per_put=4,
                                        steps=8, obs_dim=12, reps=1)
        for side in ("solo", "tier"):
            assert r[side]["frames_per_s"] > 0, r
            assert r[side]["train_steps_in_window"] > 0, r
        assert r["solo"]["seats"] == 1 and r["tier"]["seats"] == 2
        assert len(r["tier"]["per_seat_frames_per_s"]) == 2
        # The tier variant really exchanged gradients (the section
        # fails itself otherwise — two independent learners would be a
        # mislabeled ratio).
        assert r["tier"]["rounds_ok"] > 0
        assert r["tier_vs_solo"] > 0
        assert r["auto_enable"] == (r["tier_vs_solo"] >= 1.2)
        assert r["verdict"].startswith("learner tier ") and (
            "auto-on" in r["verdict"] or "opt-in" in r["verdict"])

    def test_compact_line_carries_learner_verdict_key(self):
        bench = _load_bench()
        assert "learner_verdict" in bench._COMPACT_KEYS
        # The trimmed env the failure-mode subprocess tests run under
        # must gate this (multi-process) section off — and the seat
        # drill with it.
        assert _TRIMMED["BENCH_LEARNER"] == "0"
        assert _TRIMMED["BENCH_SEAT_DRILL"] == "0"

    def test_committed_verdict_file_consistent(self, monkeypatch):
        """The committed adjudication parses, and seat_count() follows
        it when DRL_LEARNER_SEATS is unset (env force > committed
        verdict > off)."""
        monkeypatch.delenv("DRL_LEARNER_SEATS", raising=False)
        verdict = json.loads(
            (REPO / "benchmarks" / "learner_verdict.json").read_text())
        assert isinstance(verdict["auto_enable"], bool)
        assert verdict["ratio_runs"] and verdict["bar"] == 1.2
        assert verdict["sync"] in ("allreduce", "async")
        from distributed_reinforcement_learning_tpu.runtime.learner_tier import (
            seat_count, tier_auto_enabled)

        assert tier_auto_enabled() is verdict["auto_enable"]
        assert (seat_count() > 0) is verdict["auto_enable"]
        monkeypatch.setenv("DRL_LEARNER_SEATS", "3")
        assert seat_count() == 3  # env force wins over the verdict
        monkeypatch.setenv("DRL_LEARNER_SEATS", "0")
        assert seat_count() == 0


class TestCollectiveCompare:
    """bench_collective_compare: the ring-vs-partitioned-vs-bf16
    gradient-exchange A/B whose verdict gates the DRL_COLL_QUANT /
    DRL_COLL_OVERLAP defaults (runtime/learner_tier.py). Driven
    directly at the small cnn shape — the committed xformer-scale
    adjudication lives in benchmarks/collective_verdict.json."""

    def test_section_shape_and_verdict(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        bench = _load_bench()
        r = bench.bench_collective_compare(shape="cnn", rounds=3, warmup=1)
        for side in ("ring_f32", "part_f32", "part_bf16"):
            assert r[side]["round_ms_p50"] > 0, r
            assert r[side]["round_ms_max"] >= r[side]["round_ms_p50"]
            assert r[side]["bytes_per_round"] > 0
        # The partitioned variants really routed by class; the plan-less
        # ring has no class counters to report.
        assert r["ring_f32"]["bytes_by_class"] == {}
        assert r["part_f32"]["bytes_by_class"], r
        # bf16 must halve the wire bytes exactly (u16 vs f32 words).
        assert (r["part_bf16"]["bytes_per_round"] * 2
                == r["part_f32"]["bytes_per_round"])
        assert r["byte_cut"] >= 0.45
        assert r["quant_auto_enable"] == (r["quant_ratio"] >= 1.2)
        assert r["overlap_auto_enable"] == (r["overlap_ratio"] >= 1.2)
        assert r["verdict"].startswith("partitioned collective ")

    def test_compact_line_carries_collective_verdict_key(self):
        bench = _load_bench()
        assert "collective_verdict" in bench._COMPACT_KEYS
        # The trimmed env the failure-mode subprocess tests run under
        # must gate this (multi-collective, timed) section off.
        assert _TRIMMED["BENCH_COLLECTIVE"] == "0"

    def test_committed_verdict_file_consistent(self, monkeypatch):
        """The committed adjudication parses, meets the byte-cut
        acceptance bar, and the learner-tier gates follow it when the
        env knobs are unset (env force > committed verdict > off)."""
        verdict = json.loads(
            (REPO / "benchmarks" / "collective_verdict.json").read_text())
        assert isinstance(verdict["quant_auto_enable"], bool)
        assert isinstance(verdict["overlap_auto_enable"], bool)
        assert verdict["bar"] == 1.2
        assert verdict["byte_cut"] >= 0.45  # the acceptance criterion
        assert verdict["quant_ratio_runs"] and verdict["overlap_ratio_runs"]
        from distributed_reinforcement_learning_tpu.runtime import (
            learner_tier)

        for key in ("DRL_COLL_PARTITION", "DRL_COLL_QUANT",
                    "DRL_COLL_OVERLAP"):
            monkeypatch.delenv(key, raising=False)
        learner_tier.refresh_coll_flags()
        try:
            assert learner_tier.coll_partition() is True  # default ON
            assert (learner_tier.coll_quant() == "bf16") \
                is verdict["quant_auto_enable"]
            assert (learner_tier.coll_overlap() == 1) \
                is verdict["overlap_auto_enable"]
        finally:
            learner_tier.refresh_coll_flags()


class TestInferenceCompare:
    """bench_inference_compare: the learner-hosted vs replica-tier act
    client-swarm A/B whose verdict gates runtime/serving's replica
    default. Driven directly at a tiny config (CPU, host-only) — the
    committed adjudication lives in benchmarks/inference_verdict.json."""

    def test_section_shape_and_verdict(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        bench = _load_bench()
        from distributed_reinforcement_learning_tpu.agents.impala import ImpalaConfig

        cfg = ImpalaConfig(obs_shape=(8,), num_actions=2, trajectory=8,
                           lstm_size=16)
        r = bench.bench_inference_compare(cfg, n_clients=1, requests=10,
                                          rows=4, replicas=1, max_batch=8)
        for side in ("learner_hosted", "replica_tier"):
            assert r[side]["actions_per_s"] > 0, r
            assert r[side]["act_ms_p99"] >= r[side]["act_ms_p50"]
        # Variant labeling honesty: the learner-hosted swarm acts only
        # through the fallback, the replica swarm never leaks off-tier.
        assert r["learner_hosted"]["client_stats"]["fallback_acts"] > 0
        assert r["replica_tier"]["client_stats"]["fallback_acts"] == 0
        assert r["replica_tier"]["client_stats"]["replica_demotes"] == 0
        assert r["replicas_vs_learner"] > 0 and r["act_p50_speedup"] > 0
        assert r["auto_enable"] == (r["replicas_vs_learner"] >= 1.2)
        assert r["verdict"].startswith("inference replicas ") and (
            "auto-on" in r["verdict"] or "opt-in" in r["verdict"])

    def test_compact_line_carries_inference_verdict_key(self):
        bench = _load_bench()
        assert "inference_verdict" in bench._COMPACT_KEYS
        # The trimmed env the failure-mode subprocess tests run under
        # must gate this (multi-process) section off.
        assert _TRIMMED["BENCH_INFER"] == "0"

    def test_committed_verdict_file_consistent(self, monkeypatch):
        """The committed adjudication parses, and replica_count()
        follows it when DRL_INFER_REPLICAS is unset (env force >
        committed verdict > off)."""
        monkeypatch.delenv("DRL_INFER_REPLICAS", raising=False)
        verdict = json.loads(
            (REPO / "benchmarks" / "inference_verdict.json").read_text())
        assert isinstance(verdict["auto_enable"], bool)
        assert verdict["ratio_runs"] and verdict["bar"] == 1.2
        from distributed_reinforcement_learning_tpu.runtime.serving import (
            replica_count, replicas_auto_enabled)

        assert replicas_auto_enabled() is verdict["auto_enable"]
        assert (replica_count() > 0) is verdict["auto_enable"]
        monkeypatch.setenv("DRL_INFER_REPLICAS", "3")
        assert replica_count() == 3  # env force wins over the verdict
        monkeypatch.setenv("DRL_INFER_REPLICAS", "0")
        assert replica_count() == 0


class TestActorCompare:
    """bench_actor_compare: the sequential-vs-pipelined actor A/B whose
    verdict gates runtime/actor_pipeline's default. Driven directly at a
    tiny config (CartPole flat obs — the child resolves envs by registry
    name, so the tiny cfg rides an env whose shape the registry can
    produce); the committed adjudication lives in
    benchmarks/actor_pipeline_verdict.json."""

    def test_section_shape_and_verdict(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        bench = _load_bench()
        from distributed_reinforcement_learning_tpu.agents.impala import ImpalaConfig

        cfg = ImpalaConfig(obs_shape=(4,), num_actions=2, trajectory=8,
                           lstm_size=16)
        r = bench.bench_actor_compare(cfg=cfg, num_envs=4, rounds=4,
                                      warmup=1, env_name="CartPole-v0",
                                      available_action=0)
        for side in ("seq", "pipe"):
            assert r[side]["frames_per_s"] > 0, r
            assert r[side]["round_ms_p99"] >= r[side]["round_ms_p50"]
        # Equal work per variant: same rounds x envs x trajectory.
        assert r["seq"]["frames"] == r["pipe"]["frames"]
        # Variant labeling honesty: the pipelined child reports the
        # overlap it actually measured (act-wait/env-step per round
        # interleave, put-wait per publisher submit), the sequential
        # child the blocking PUT it actually paid.
        overlap = r["pipe"]["overlap"]
        for stage in ("act_wait_ms", "env_step_ms", "put_wait_ms"):
            assert overlap[stage]["n"] > 0, overlap
        assert r["seq"]["put_ms_p99"] >= r["seq"]["put_ms_p50"] > 0
        assert r["pipe_vs_seq"] > 0
        assert r["auto_enable"] == (r["pipe_vs_seq"] >= 1.2)
        assert r["verdict"].startswith("actor pipeline ") and (
            "auto-on" in r["verdict"] or "opt-in" in r["verdict"])

    def test_compact_line_carries_actor_pipeline_verdict_key(self):
        bench = _load_bench()
        assert "actor_pipeline_verdict" in bench._COMPACT_KEYS
        # The trimmed env the failure-mode subprocess tests run under
        # must gate this (multi-process) section off.
        assert _TRIMMED["BENCH_ACTOR"] == "0"

    def test_committed_verdict_file_consistent(self, monkeypatch):
        """The committed adjudication parses, and pipeline_enabled()
        follows it when DRL_ACTOR_PIPE is unset (env force > committed
        verdict > off)."""
        monkeypatch.delenv("DRL_ACTOR_PIPE", raising=False)
        verdict = json.loads(
            (REPO / "benchmarks" / "actor_pipeline_verdict.json").read_text())
        assert isinstance(verdict["auto_enable"], bool)
        assert verdict["ratio_runs"] and verdict["bar"] == 1.2
        from distributed_reinforcement_learning_tpu.runtime.actor_pipeline import (
            pipeline_auto_enabled, pipeline_enabled)

        assert pipeline_auto_enabled() is verdict["auto_enable"]
        assert pipeline_enabled() is verdict["auto_enable"]
        monkeypatch.setenv("DRL_ACTOR_PIPE", "1")
        assert pipeline_enabled() is True  # env force wins over the verdict
        monkeypatch.setenv("DRL_ACTOR_PIPE", "0")
        assert pipeline_enabled() is False


class TestChaosCompare:
    """bench_chaos_compare: the kill/respawn drill adjudicating the
    elastic fleet (runtime/fleet.py) — baseline vs learner-SIGKILL
    window over the REAL ring+board+heartbeat topology. Driven directly
    at a tiny config; the committed adjudication lives in
    benchmarks/chaos_verdict.json."""

    def test_section_shape_and_verdict(self, monkeypatch):
        bench = _load_bench()
        # The learner-seat drill has its own test below — running it
        # here too would double the (multi-process) cost.
        monkeypatch.setenv("BENCH_SEAT_DRILL", "0")
        # Window sized for a loaded 2-core host: the kill is gated on
        # observed verified traffic (so a slow-starting actor child
        # cannot make the drill vacuous) and lands kill_at seconds
        # after, leaving the respawned incarnation a multi-second
        # re-promote runway inside the actor's window.
        r = bench.bench_chaos_compare(n_actors=1, secs=10.0, kill_at=1.5,
                                      steps=4, obs_dim=8,
                                      repromote_deadline_s=10.0)
        for side in ("baseline", "chaos"):
            assert r[side]["unrolls_verified"] > 0, r
            assert r[side]["unrolls_corrupt"] == 0, r
        # The chaos window really crossed a learner restart: two
        # incarnations tallied, and the surviving actor's ring AND
        # board ladders each re-promoted at least once.
        assert r["chaos"]["incarnations"] == 2, r
        assert r["chaos"]["ring_reattaches"] >= 1, r
        assert r["chaos"]["board_reattaches"] >= 1, r
        assert r["zero_corruption"] is True
        assert r["dip_ratio"] > 0
        assert r["chaos_pass"] == (
            r["zero_corruption"] and r["dip_ratio"] >= r["dip_bound"]
            and r["repromoted_in_deadline"])
        assert r["verdict"].startswith("chaos ") and (
            "PASS" in r["verdict"] or "FAIL" in r["verdict"])

    def test_compact_line_carries_chaos_verdict_key(self):
        bench = _load_bench()
        assert "chaos_verdict" in bench._COMPACT_KEYS
        # The trimmed env the failure-mode subprocess tests run under
        # must gate this (multi-process) section off.
        assert _TRIMMED["BENCH_CHAOS"] == "0"

    def test_seat_drill_kill_one_of_two_learners(self):
        """The kill-ONE-OF-N-learners drill (runtime/learner_tier.py):
        SIGKILL the publisher seat of a real 2-seat tier mid-run — the
        survivor re-forms the collective solo, takes over publication
        (board re-created under the same name; its actor observes
        post-kill versions through the reattached board), and every
        landed trajectory still crc-verifies."""
        bench = _load_bench()
        r = bench._chaos_seat_drill(secs=16.0, steps=4, obs_dim=8,
                                    repromote_deadline_s=12.0)
        assert r["corrupt"] == 0 and r["verified"] > 0, r
        assert r["survivor_solo"] and r["survivor_publisher"], r
        assert r["reelected_s"] is not None \
            and r["reelected_s"] <= r["repromote_deadline_s"], r
        assert r["post_kill_versions_observed"] >= 1, r
        assert r["survivor_board_reattaches"] >= 1, r
        assert r["pass"] is True

    def test_committed_verdict_file_consistent(self):
        """The committed chaos adjudication parses and is internally
        consistent (pass flag == its measured sub-verdicts, the
        learner-seat drill included)."""
        verdict = json.loads(
            (REPO / "benchmarks" / "chaos_verdict.json").read_text())
        assert isinstance(verdict["chaos_pass"], bool)
        assert verdict["chaos_pass"] == (
            verdict["zero_corruption"]
            and verdict["dip_ratio"] >= verdict["dip_bound"]
            and verdict["repromoted_in_deadline"]
            and verdict.get("seat_drill_pass", True))
        assert verdict["chaos"]["incarnations"] == 2
        assert verdict["repromote_deadline_s"] > 0
        # The committed verdict must carry the kill-one-of-N drill.
        assert verdict["seat_drill_pass"] is True
        drill = verdict["seat_drill"]
        assert drill["corrupt"] == 0
        assert drill["survivor_publisher"] and drill["survivor_solo"]

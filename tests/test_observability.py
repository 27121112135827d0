"""Run-wide telemetry (observability/): span emitter, gauge shards,
scripted weight-staleness over real transport, and the merged report CLI.

All CPU-only, tier-1 safe. The global TELEMETRY singleton is configured
and closed per-test (close() re-disables it), so nothing leaks into the
rest of the suite — and the disabled-path test pins exactly what every
hot path relies on: telemetry off means one attribute read, no state,
no files.
"""

import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue
from distributed_reinforcement_learning_tpu.observability import (
    TELEMETRY,
    Telemetry,
    TraceEmitter,
    load_trace,
    maybe_configure,
)
from distributed_reinforcement_learning_tpu.observability.metrics import _NULL_SPAN
from distributed_reinforcement_learning_tpu.runtime.transport import (
    TransportClient,
    TransportServer,
)
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore
from distributed_reinforcement_learning_tpu.utils.profiling import StageTimer

REPO = Path(__file__).resolve().parent.parent


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _read_jsonl(path) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _gauges(records: list[dict], name: str) -> list[dict]:
    return [r for r in records if r.get("kind") == "gauge" and r["name"] == name]


# -- trace.py ---------------------------------------------------------------


class TestTraceEmitter:
    def test_valid_chrome_trace_json(self, tmp_path):
        path = str(tmp_path / "trace-learner-0.json")
        tr = TraceEmitter(path, label="learner-0", pid=7)
        with tr.span("learn"):
            pass
        tr.emit("publish", wall_start_s=100.0, duration_s=0.25,
                args={"version": 3})
        tr.close()
        with open(path) as f:
            events = json.load(f)  # strict: a clean close is valid JSON
        meta = [e for e in events if e["ph"] == "M"]
        assert meta and meta[0]["args"]["name"] == "learner-0"
        spans = [e for e in events if e["ph"] == "X"]
        assert sorted(e["name"] for e in spans) == ["learn", "publish"]
        pub = next(e for e in spans if e["name"] == "publish")
        # Trace Event Format: ts/dur in microseconds, wall-clock epoch ts.
        assert pub["ts"] == pytest.approx(100.0 * 1e6)
        assert pub["dur"] == pytest.approx(0.25 * 1e6)
        assert pub["pid"] == 7 and pub["args"] == {"version": 3}

    def test_load_trace_tolerates_crashed_stream(self, tmp_path):
        path = str(tmp_path / "trace-actor-1.json")
        tr = TraceEmitter(path, label="actor-1")
        tr.emit("actor_round", wall_start_s=1.0, duration_s=0.1)
        tr.flush()  # on disk as an unterminated array: a killed process
        events = load_trace(path)
        assert any(e.get("name") == "actor_round" for e in events)

    def test_load_trace_tolerates_torn_final_event(self, tmp_path):
        """SIGTERM mid-flush (launch_local_cluster tears actors down with
        terminate()) can cut the final event at an arbitrary byte: every
        complete event must still load, the torn tail dropped."""
        path = str(tmp_path / "trace-actor-0.json")
        tr = TraceEmitter(path, label="actor-0")
        tr.emit("a", wall_start_s=1.0, duration_s=0.1)
        tr.emit("b", wall_start_s=2.0, duration_s=0.1)
        tr.flush()
        with open(path) as f:
            text = f.read()
        cut = text.rindex('{"name": "b"')  # keep "b"'s line, torn mid-object
        with open(path, "w") as f:
            f.write(text[: cut + 20])
        events = load_trace(path)
        assert any(e.get("name") == "a" for e in events)
        assert all(e.get("name") != "b" for e in events)  # torn tail dropped

    def test_max_events_cap_drops_not_grows(self, tmp_path):
        path = str(tmp_path / "trace-learner-0.json")
        tr = TraceEmitter(path, label="learner-0", max_events=3)
        for i in range(10):
            tr.emit(f"s{i}", wall_start_s=float(i), duration_s=0.01)
        tr.close()
        events = load_trace(path)
        assert sum(1 for e in events if e.get("ph") == "X") == 3
        dropped = next(e for e in events
                       if e.get("name") == "trace_dropped_events")
        assert dropped["args"]["dropped"] == 7


# -- metrics.py -------------------------------------------------------------


class TestTelemetryShards:
    def test_counters_gauges_and_providers_flush_to_shard(self, tmp_path):
        t = Telemetry()
        t.configure(str(tmp_path), "learner", rank=0, flush_interval=0)
        try:
            t.count("learner/train_steps", 4)
            t.count("learner/train_steps", 2)
            for v in (1.0, 5.0, 3.0):
                t.gauge("publish/latency_ms", v)
            t.sample("transport/queue_depth", lambda: 11)
            t.flush()
        finally:
            t.close()
        records = _read_jsonl(tmp_path / "learner-0.jsonl")
        assert records[0]["kind"] == "meta"
        assert records[0]["role"] == "learner" and records[0]["rank"] == 0
        counter = next(r for r in records if r.get("kind") == "counter")
        assert counter["name"] == "learner/train_steps"
        assert counter["value"] == 6  # cumulative, not per-flush
        lat = _gauges(records, "publish/latency_ms")[0]
        assert lat["n"] == 3 and lat["min"] == 1.0 and lat["max"] == 5.0
        assert lat["mean"] == pytest.approx(3.0) and lat["last"] == 3.0
        depth = _gauges(records, "transport/queue_depth")[0]
        assert depth["last"] == 11.0  # provider polled at flush time

    def test_counter_provider_and_weighted_gauge(self, tmp_path):
        """kind="counter" providers surface an existing cumulative stats
        dict as throughput; gauge(weight=K) lets one batched observation
        stand for K (a batched PUT's staleness covers K unrolls)."""
        t = Telemetry()
        t.configure(str(tmp_path), "learner", rank=0, flush_interval=0)
        try:
            stats = {"unrolls_accepted": 0}
            t.sample("transport/unrolls_accepted",
                     lambda: stats["unrolls_accepted"], kind="counter")
            t.gauge("learner/weight_staleness", 2.0, weight=16)
            t.gauge("learner/weight_staleness", 4.0, weight=4)
            t.gauge("learner/weight_staleness", 9.0, weight=0)  # dropped
            stats["unrolls_accepted"] = 37
            t.flush()
        finally:
            t.close()
        records = _read_jsonl(tmp_path / "learner-0.jsonl")
        counter = next(r for r in records if r.get("kind") == "counter")
        assert counter["name"] == "transport/unrolls_accepted"
        assert counter["value"] == 37
        w = _gauges(records, "learner/weight_staleness")[0]
        assert w["n"] == 20 and w["max"] == 4.0 and w["last"] == 4.0
        assert w["mean"] == pytest.approx((2.0 * 16 + 4.0 * 4) / 20)

    def test_gauge_windows_reset_between_flushes(self, tmp_path):
        t = Telemetry()
        t.configure(str(tmp_path), "learner", rank=0, flush_interval=0)
        try:
            t.gauge("learner/batch_wait_ms", 10.0)
            t.flush()
            t.gauge("learner/batch_wait_ms", 30.0)
            t.flush()
        finally:
            t.close()
        windows = _gauges(_read_jsonl(tmp_path / "learner-0.jsonl"),
                          "learner/batch_wait_ms")
        assert [w["mean"] for w in windows] == [10.0, 30.0]
        assert all(w["n"] == 1 for w in windows)

    def test_thread_safety_of_hot_instruments(self, tmp_path):
        t = Telemetry()
        t.configure(str(tmp_path), "learner", rank=0, flush_interval=0)
        try:
            def hammer():
                for _ in range(1000):
                    t.count("c")
                    t.gauge("g", 1.0)
            threads = [threading.Thread(target=hammer) for _ in range(4)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            t.flush()
        finally:
            t.close()
        records = _read_jsonl(tmp_path / "learner-0.jsonl")
        assert next(r for r in records
                    if r.get("kind") == "counter")["value"] == 4000
        assert _gauges(records, "g")[0]["n"] == 4000

    def test_maybe_configure_env_gated(self, tmp_path, monkeypatch):
        out = tmp_path / "telemetry"
        monkeypatch.setenv("DRL_TELEMETRY_DIR", str(out))
        try:
            assert maybe_configure("learner", 0) is True
            TELEMETRY.count("x")
            TELEMETRY.flush()
        finally:
            TELEMETRY.close()
        assert (out / "learner-0.jsonl").exists()
        assert (out / "trace-learner-0.json").exists()
        # And without either env var, the singleton stays disabled.
        monkeypatch.delenv("DRL_TELEMETRY_DIR")
        monkeypatch.delenv("DRL_TELEMETRY", raising=False)
        assert maybe_configure("learner", 0, run_dir=str(tmp_path)) is False
        assert TELEMETRY.enabled is False


class TestDisabledPath:
    """Telemetry OFF (the default) must cost one attribute read and
    allocate nothing — every per-train-step hot path relies on this."""

    def test_disabled_instruments_keep_no_state_and_touch_no_files(
            self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)  # any stray write would land here
        assert TELEMETRY.enabled is False
        TELEMETRY.count("learner/train_steps", 5)
        TELEMETRY.gauge("publish/latency_ms", 1.0)
        TELEMETRY.sample("transport/queue_depth", lambda: 1)
        TELEMETRY.flush()
        assert TELEMETRY._counters == {}
        assert TELEMETRY._gauges == {}
        assert TELEMETRY._providers == {}
        assert os.listdir(tmp_path) == []

    def test_disabled_span_is_the_shared_noop_singleton(self):
        assert TELEMETRY.enabled is False
        span_a = TELEMETRY.span("learn")
        span_b = TELEMETRY.span("publish")
        assert span_a is span_b is _NULL_SPAN  # zero allocations per call
        with span_a:
            pass

    def test_stage_timer_emits_no_trace_while_disabled(self):
        assert TELEMETRY.trace is None
        timer = StageTimer(logger=None, log_every=1)
        with timer.stage("learn"):
            pass
        timer.step_done(1)  # must not raise nor touch telemetry


# -- staleness over real transport -----------------------------------------


class TestStalenessScripted:
    def test_staleness_gauge_matches_publish_consume_script(self, tmp_path):
        """Scripted sequence: actor pulls v3, PUTs (staleness 0), learner
        publishes v5, actor PUTs again without re-pulling (staleness 2).
        The gauge is attributed per-connection on the server side — no
        wire-format change — and lands in the learner's shard."""
        queue = TrajectoryQueue(capacity=8)
        weights = WeightStore()
        port = _free_port()
        server = TransportServer(queue, weights, host="127.0.0.1",
                                 port=port).start()
        TELEMETRY.configure(str(tmp_path), "learner", rank=0,
                            flush_interval=0)
        client = TransportClient("127.0.0.1", port)
        traj = {"obs": np.zeros((4, 3), np.uint8)}
        try:
            weights.publish({"w": np.ones(2, np.float32)}, version=3)
            params, version = client.get_weights_if_newer(-1)
            assert version == 3
            client.put_trajectory(traj)
            TELEMETRY.flush()

            weights.publish({"w": np.zeros(2, np.float32)}, version=5)
            client.put_trajectory(traj)
            TELEMETRY.flush()
        finally:
            client.close()
            server.stop()
            TELEMETRY.close()
        records = _read_jsonl(tmp_path / "learner-0.jsonl")
        staleness = _gauges(records, "learner/weight_staleness")
        assert [w["last"] for w in staleness] == [0.0, 2.0]
        # Exact observation-time histogram counters (cumulative).
        buckets = {r["name"]: r["value"] for r in records
                   if r.get("kind") == "counter"
                   and r["name"].startswith("staleness_bucket/")}
        assert buckets == {"staleness_bucket/0": 1, "staleness_bucket/2": 1}
        # The actor-side pull gauges landed too (same shard: one process
        # hosts both ends in this test).
        pulls = _gauges(records, "actor/weight_version")
        assert pulls and pulls[0]["last"] == 3.0
        waits = _gauges(records, "transport/enqueue_wait_ms")
        assert len(waits) == 2  # one window per flushed PUT

    def test_put_before_any_pull_records_no_staleness(self, tmp_path):
        """A connection that never pulled weights (remote_act actors) has
        undefined staleness: the gauge must stay absent, not read 'very
        stale'."""
        queue = TrajectoryQueue(capacity=8)
        weights = WeightStore()
        weights.publish({"w": np.ones(1, np.float32)}, version=9)
        port = _free_port()
        server = TransportServer(queue, weights, host="127.0.0.1",
                                 port=port).start()
        TELEMETRY.configure(str(tmp_path), "learner", rank=0,
                            flush_interval=0)
        client = TransportClient("127.0.0.1", port)
        try:
            client.put_trajectory({"obs": np.zeros(3, np.uint8)})
            TELEMETRY.flush()
        finally:
            client.close()
            server.stop()
            TELEMETRY.close()
        records = _read_jsonl(tmp_path / "learner-0.jsonl")
        assert _gauges(records, "learner/weight_staleness") == []
        assert _gauges(records, "transport/enqueue_wait_ms")  # PUT observed


# -- scripts/obs_report.py --------------------------------------------------


def _synthetic_run_dir(tmp_path) -> Path:
    """Two-role run dir: a learner and an actor shard + trace each,
    written through the real Telemetry/TraceEmitter write path."""
    tdir = tmp_path / "telemetry"
    learner = Telemetry()
    learner.configure(str(tdir), "learner", rank=0, flush_interval=0)
    learner.count("learner/train_steps", 50)
    for depth in (2.0, 8.0, 16.0):
        learner.gauge("transport/queue_depth", depth)
        learner.gauge("publish/latency_ms", depth / 2)
        learner.gauge("learner/weight_staleness", depth / 8)
        learner.flush()
    learner.gauge("learner/weight_version", 50)
    with learner.trace.span("learn"):
        time.sleep(0.002)
    learner.close()

    actor = Telemetry()
    actor.configure(str(tdir), "actor", rank=0, flush_interval=0)
    actor.count("actor/env_frames", 4096)
    actor.gauge("actor/weight_pull_ms", 1.5)
    actor.gauge("actor/weight_version", 48)
    with actor.trace.span("actor_round"):
        time.sleep(0.002)
    actor.close()
    return tmp_path


def test_obs_report_merges_two_role_run_dir(tmp_path):
    run_dir = _synthetic_run_dir(tmp_path)
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "obs_report.py"),
         str(run_dir)],
        capture_output=True, text=True, timeout=60,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-800:]
    report = proc.stdout
    # Both processes, all four report dimensions.
    assert "learner-0" in report and "actor-0" in report
    assert "learner/train_steps" in report and "actor/env_frames" in report
    assert "Queue depth" in report and "mean 8.7" in report  # (2+8+16)/3
    assert "publish latency" in report
    assert "staleness" in report.lower()
    assert "weight pull" in report
    # Stage latencies from the traces of more than one process.
    assert "learn" in report and "actor_round" in report
    # The merged trace: every process on its own labeled track.
    merged = json.loads((run_dir / "telemetry" /
                         "trace-merged.json").read_text())
    events = merged["traceEvents"]
    labels = {e["args"]["name"] for e in events
              if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert {"learner-0", "actor-0"} <= labels
    spans = [e for e in events if e.get("ph") == "X"]
    assert len({e["pid"] for e in spans}) == 2


def test_obs_report_tiered_replay_section(tmp_path):
    """The 'Tiered replay' section renders the spill tier's gauges and
    counters (hot/cold fill, ram/disk footprint, spill/promote traffic,
    promote-wait percentiles) and keeps the raw replay_spill/ counters
    out of the generic Throughput section."""
    tdir = tmp_path / "telemetry"
    learner = Telemetry()
    learner.configure(str(tdir), "learner", rank=0, flush_interval=0)
    for wait in (1.0, 2.0, 40.0):
        learner.gauge("replay_spill/0/hot_items", 1000.0)
        learner.gauge("replay_spill/0/cold_items", 7000.0)
        learner.gauge("replay_spill/0/ram_bytes", 2.0 * 2**20)
        learner.gauge("replay_spill/0/disk_bytes", 3.0 * 2**30)
        learner.gauge("replay_spill/0/queue_depth", 2.0)
        learner.gauge("replay_spill/0/promote_wait_ms", wait)
        learner.flush()
    learner.count("replay_spill/0/spilled_segments_total", 83)
    learner.count("replay_spill/0/promoted_segments_total", 28)
    learner.count("replay_spill/0/spilled_bytes", 21 * 2**20)
    learner.count("replay_spill/0/promoted_bytes", 7 * 2**20)
    learner.count("replay_spill/0/crc_dropped_total", 0)
    learner.count("replay_spill/0/forced_pads_total", 0)
    learner.close()
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "obs_report.py"),
         str(tmp_path), "--no-merge"],
        capture_output=True, text=True, timeout=60,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-800:]
    report = proc.stdout
    assert "Tiered replay (hot/cold spill)" in report
    assert "hot 1000 / cold 7000 items (12% resident)" in report
    assert "ram 2.0 MB" in report and "disk 3.00 GB" in report
    assert "spilled 83 segments (21.0 MB" in report
    assert "promoted 28 (7.0 MB" in report
    assert "promote wait p50 2.00ms" in report  # series percentiles
    assert "p99 " in report and "max 40.00ms" in report
    # Raw counter names stay out of the generic Throughput section.
    assert "replay_spill/0/spilled_bytes" not in report


def test_obs_report_no_merge_flag(tmp_path):
    run_dir = _synthetic_run_dir(tmp_path)
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "obs_report.py"),
         str(run_dir), "--no-merge"],
        capture_output=True, text=True, timeout=60,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-800:]
    assert not (run_dir / "telemetry" / "trace-merged.json").exists()

"""Inference serving tier (runtime/serving.py + transport adapters).

Covers the pieces the replicated act service is built from, and the two
acceptance pins of the tier itself:

- the CONTINUOUS batcher: correct results, coalescing while a batch is
  in flight, equivalence with the classic run-at-max_batch server under
  identical params + rng;
- ADMISSION control: a full pending budget raises InferenceBusy
  in-process and ST_BUSY over the wire (InferenceBusyError on the
  client), and the service keeps serving afterwards;
- the two-process EQUIVALENCE pin: a replica process serving over real
  TCP produces identical action rows to the learner-hosted service for
  identical params + rng;
- CHAOS: killing a replica mid-hammer demotes it permanently and every
  request still completes on the survivor — no lost or corrupted
  requests.
"""

import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent, ImpalaConfig
from distributed_reinforcement_learning_tpu.data import codec
from distributed_reinforcement_learning_tpu.runtime.inference import (
    InferenceBusy,
    InferenceServer,
)
from distributed_reinforcement_learning_tpu.runtime.serving import (
    ContinuousInferenceServer,
    replica_count,
)
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

REPO = Path(__file__).resolve().parent.parent


def _tiny_agent():
    cfg = ImpalaConfig(obs_shape=(4,), num_actions=2, trajectory=8,
                       lstm_size=32, start_learning_rate=1e-3,
                       learning_frame=10**6)
    return ImpalaAgent(cfg), cfg


def _impala_request(cfg, n, seed=0):
    return {
        "obs": np.random.default_rng(seed).random((n, 4), np.float32),
        "prev_action": np.zeros(n, np.int32),
        "h": np.zeros((n, cfg.lstm_size), np.float32),
        "c": np.zeros((n, cfg.lstm_size), np.float32),
    }


def _published_store(agent):
    weights = WeightStore()
    weights.publish(agent.init_state(jax.random.PRNGKey(0)).params, 0)
    return weights


def _free_port():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class _GatedActFn:
    """act_fn whose Nth call blocks on an event — the deterministic way
    to hold a batch in flight while more submits pile up."""

    def __init__(self, inner, block_call=1):
        self.inner = inner
        self.block_call = block_call
        self.calls = 0
        self.entered = threading.Event()
        self.release = threading.Event()
        self.batch_rows = []
        self.expected_keys = getattr(inner, "expected_keys", None)

    def __call__(self, params, rows, rng):
        self.calls += 1
        self.batch_rows.append(next(iter(rows.values())).shape[0])
        if self.calls == self.block_call:
            self.entered.set()
            assert self.release.wait(timeout=30.0)
        return self.inner(params, rows, rng)


class TestContinuousBatcher:
    def test_matches_classic_server_and_local_act(self):
        """Same params + same seed + one request -> the continuous
        server's first batch must be IDENTICAL to the classic server's
        (same adapter, same PRNG split discipline, same bucket)."""
        agent, cfg = _tiny_agent()
        weights = _published_store(agent)
        req = _impala_request(cfg, 5, seed=3)
        classic = InferenceServer.for_agent("impala", agent, weights,
                                            max_batch=64, seed=11)
        cont = ContinuousInferenceServer.for_agent("impala", agent, weights,
                                                   max_batch=64, seed=11)
        try:
            a = classic.submit(dict(req))
            b = cont.submit(dict(req))
            np.testing.assert_array_equal(a["action"], b["action"])
            np.testing.assert_allclose(a["policy"], b["policy"], rtol=1e-6)
            np.testing.assert_allclose(a["h"], b["h"], rtol=1e-6)
        finally:
            classic.stop()
            cont.stop()

    def test_next_batch_assembles_while_previous_in_flight(self):
        """The continuous contract: submits arriving while a batch is
        in flight coalesce into ONE next batch (no run-at-max_batch
        barrier, no per-batch wait window)."""
        agent, cfg = _tiny_agent()
        weights = _published_store(agent)
        from distributed_reinforcement_learning_tpu.runtime.inference import (
            make_act_adapter)

        gate = _GatedActFn(make_act_adapter("impala", agent), block_call=2)
        server = ContinuousInferenceServer(gate, weights, max_batch=64, seed=0)
        results = [None] * 7

        def one(i, n):
            results[i] = server.submit(_impala_request(cfg, n))

        try:
            one(0, 4)  # call 1: unblocked (warms jit, primes the gate)
            t0 = threading.Thread(target=one, args=(1, 4))
            t0.start()
            assert gate.entered.wait(timeout=10.0)  # call 2 now in flight
            rest = [threading.Thread(target=one, args=(i, 4))
                    for i in range(2, 7)]
            for t in rest:
                t.start()
            # All 5 late submits are pending while the gate holds.
            deadline = time.monotonic() + 10.0
            while server._pending_rows < 20:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            gate.release.set()
            for t in [t0, *rest]:
                t.join(timeout=30.0)
            assert all(r is not None and r["action"].shape == (4,)
                       for r in results)
            # Call 1 + gated call 2 + ONE coalesced batch of the 5
            # waiters (20 rows <= max_batch).
            assert gate.calls == 3, gate.batch_rows
            assert gate.batch_rows[2] == 32  # 20 rows padded to pow2
            assert server.rows_served == 7 * 4
        finally:
            gate.release.set()
            server.stop()

    def test_oversized_submit_is_chunked(self):
        """Inherited oversubscription contract: a 70-row submit against
        max_batch=16 must never compile past the bucket range."""
        agent, cfg = _tiny_agent()
        weights = _published_store(agent)
        server = ContinuousInferenceServer.for_agent(
            "impala", agent, weights, max_batch=16, seed=0)
        sizes = []
        inner = server.act_fn

        def recording(params, rows, rng):
            sizes.append(rows["obs"].shape[0])
            return inner(params, rows, rng)

        recording.expected_keys = inner.expected_keys
        server.act_fn = recording
        try:
            req = _impala_request(cfg, 70, seed=1)
            out = server.submit(req)
            assert out["action"].shape == (70,)
            assert out["policy"].shape == (70, cfg.num_actions)
            assert sizes and max(sizes) <= 16, sizes
            # Policy is rng-independent: chunked serving must agree with
            # the direct 70-row forward.
            local = agent.act(weights.get()[0], req["obs"],
                              req["prev_action"], req["h"], req["c"],
                              jax.random.PRNGKey(9))
            np.testing.assert_allclose(out["policy"], np.asarray(local.policy),
                                       rtol=1e-5)
        finally:
            server.stop()

    def test_stop_races_submit_without_hanging(self):
        agent, cfg = _tiny_agent()
        weights = _published_store(agent)
        server = ContinuousInferenceServer.for_agent(
            "impala", agent, weights, max_batch=8, seed=0)
        server.submit(_impala_request(cfg, 2))  # warm
        outcomes = []

        def spam():
            for _ in range(50):
                try:
                    server.submit(_impala_request(cfg, 2))
                except RuntimeError:
                    outcomes.append("raised")
                    return
            outcomes.append("done")

        threads = [threading.Thread(target=spam) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.05)
        server.stop()
        for t in threads:
            t.join(timeout=10.0)
            assert not t.is_alive(), "submit hung across stop()"
        assert len(outcomes) == 4


class TestAdmissionControl:
    def test_budget_rejects_and_recovers_in_process(self):
        agent, cfg = _tiny_agent()
        weights = _published_store(agent)
        from distributed_reinforcement_learning_tpu.runtime.inference import (
            make_act_adapter)

        gate = _GatedActFn(make_act_adapter("impala", agent), block_call=2)
        server = ContinuousInferenceServer(gate, weights, max_batch=64,
                                           admission_rows=4, seed=0)
        try:
            server.submit(_impala_request(cfg, 2))  # warm + prime gate
            t = threading.Thread(
                target=server.submit, args=(_impala_request(cfg, 2),))
            t.start()
            assert gate.entered.wait(timeout=10.0)  # batch 2 held in flight
            t2 = threading.Thread(
                target=server.submit, args=(_impala_request(cfg, 3),))
            t2.start()  # 3 pending rows behind the held batch
            deadline = time.monotonic() + 10.0
            while server._pending_rows < 3:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            with pytest.raises(InferenceBusy, match="admission budget full"):
                server.submit(_impala_request(cfg, 2))  # 3 + 2 > 4
            assert server.admission_reject_count() == 1
            gate.release.set()
            t.join(timeout=10.0)
            t2.join(timeout=10.0)
            # Budget freed: the service serves again.
            out = server.submit(_impala_request(cfg, 2))
            assert out["action"].shape == (2,)
        finally:
            gate.release.set()
            server.stop()

    def test_busy_maps_to_st_busy_over_the_wire(self):
        """ST_BUSY end-to-end: raw client raises InferenceBusyError with
        busy_retry=False, and the default jittered-retry path absorbs
        the busy window and completes."""
        from distributed_reinforcement_learning_tpu.runtime.transport import (
            InferenceBusyError, TransportClient, TransportServer)

        agent, cfg = _tiny_agent()
        weights = _published_store(agent)
        from distributed_reinforcement_learning_tpu.runtime.inference import (
            make_act_adapter)

        gate = _GatedActFn(make_act_adapter("impala", agent), block_call=2)
        server_infer = ContinuousInferenceServer(gate, weights, max_batch=64,
                                                 admission_rows=4, seed=0)
        port = _free_port()
        server = TransportServer(None, weights, host="127.0.0.1", port=port,
                                 inference=server_infer).start()
        client = TransportClient("127.0.0.1", port)
        retry_client = TransportClient("127.0.0.1", port)
        try:
            client.remote_act(_impala_request(cfg, 2))  # warm + prime gate
            t = threading.Thread(
                target=server_infer.submit, args=(_impala_request(cfg, 2),))
            t.start()
            assert gate.entered.wait(timeout=10.0)
            t2 = threading.Thread(
                target=server_infer.submit, args=(_impala_request(cfg, 3),))
            t2.start()
            deadline = time.monotonic() + 10.0
            while server_infer._pending_rows < 3:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            with pytest.raises(InferenceBusyError):
                client.remote_act(_impala_request(cfg, 2), busy_retry=False)
            assert client.stat("act_busy_waits") == 1
            assert server.stat("act_busy_replies") >= 1

            # The retrying client parks in jittered backoff until the
            # gate opens, then completes — bounded queueing, not an
            # error, for single-endpoint callers.
            got = []
            t3 = threading.Thread(target=lambda: got.append(
                retry_client.remote_act(_impala_request(cfg, 2))))
            t3.start()
            time.sleep(0.1)
            gate.release.set()
            t3.join(timeout=30.0)
            t.join(timeout=10.0)
            t2.join(timeout=10.0)
            assert got and got[0]["action"].shape == (2,)
        finally:
            gate.release.set()
            server.stop()
            server_infer.stop()
            client.close()
            retry_client.close()


def _spawn_replica(port, params_file, seed, tmp_env):
    proc = subprocess.Popen(
        [sys.executable, str(REPO / "tests" / "inference_replica_worker.py"),
         str(port), str(params_file), str(seed), "4", "2", "32"],
        env=tmp_env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    line = proc.stdout.readline()
    if "READY" not in line:
        err = proc.stderr.read() if proc.poll() is not None else "(no stderr)"
        raise RuntimeError(f"replica worker failed to start: {err[-500:]}")
    return proc


def _worker_env():
    import os

    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_replica_acts_equal_learner_hosted_acts(tmp_path):
    """THE equivalence pin (acceptance): identical params + rng ->
    identical action rows from a real replica process over real TCP and
    from the learner-hosted classic server. Both services see the
    request as their FIRST batch, so both consume the first split of
    PRNGKey(seed)."""
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        TransportClient)

    agent, cfg = _tiny_agent()
    params = agent.init_state(jax.random.PRNGKey(0)).params
    params_file = tmp_path / "params.bin"
    params_file.write_bytes(bytes(codec.encode(params)))

    port = _free_port()
    proc = _spawn_replica(port, params_file, 77, _worker_env())
    weights = WeightStore()
    weights.publish(params, 0)
    local = InferenceServer.for_agent("impala", agent, weights,
                                      max_batch=64, seed=77)
    client = TransportClient("127.0.0.1", port)
    try:
        req = _impala_request(cfg, 5, seed=42)
        mine = local.submit(dict(req))
        theirs = client.remote_act(dict(req))
        np.testing.assert_array_equal(mine["action"], theirs["action"])
        np.testing.assert_allclose(mine["policy"], theirs["policy"], rtol=1e-6)
        np.testing.assert_allclose(mine["h"], theirs["h"], rtol=1e-6)
        np.testing.assert_allclose(mine["c"], theirs["c"], rtol=1e-6)
    finally:
        client.close()
        local.stop()
        try:
            proc.stdin.close()
            proc.wait(timeout=10)
        except (OSError, subprocess.TimeoutExpired):
            proc.kill()


def test_replica_kill_demotes_to_survivor_without_losing_requests(tmp_path):
    """THE chaos pin (acceptance): kill one of two replicas mid-hammer.
    Every request must complete with correctly-shaped, uncorrupted rows
    (remote acts are resend-safe, so failover re-acts the in-flight
    request on a survivor), the dead replica must demote PERMANENTLY,
    and the survivor serves the rest."""
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        RemoteActService)

    agent, cfg = _tiny_agent()
    params = agent.init_state(jax.random.PRNGKey(0)).params
    params_file = tmp_path / "params.bin"
    params_file.write_bytes(bytes(codec.encode(params)))

    env = _worker_env()
    ports = [_free_port(), _free_port()]
    procs = [_spawn_replica(ports[0], params_file, 1, env),
             _spawn_replica(ports[1], params_file, 2, env)]
    svc = RemoteActService.from_addrs(
        [f"127.0.0.1:{p}" for p in ports], connect_retries=2)
    served = []
    errors = []
    lock = threading.Lock()
    n_threads, per_thread = 3, 20

    def hammer(tid):
        for k in range(per_thread):
            req = _impala_request(cfg, 4, seed=tid * 1000 + k)
            try:
                out = svc(req)
            except Exception as e:  # noqa: BLE001 — the test's assertion
                with lock:
                    errors.append(e)
                return
            with lock:
                served.append((out["action"].shape, out["policy"].shape))

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(n_threads)]
    try:
        for t in threads:
            t.start()
        deadline = time.monotonic() + 30.0
        while True:  # kill replica 0 mid-hammer, with work still queued
            with lock:
                done = len(served)
            if done >= 6:
                break
            assert time.monotonic() < deadline, "hammer never progressed"
            time.sleep(0.005)
        procs[0].kill()
        for t in threads:
            t.join(timeout=60.0)
            assert not t.is_alive(), "hammer thread hung after replica kill"
        assert errors == []
        assert len(served) == n_threads * per_thread
        assert all(a == (4,) and p == (4, cfg.num_actions)
                   for a, p in served)
        assert svc.live_endpoints() == 1
        assert svc.snapshot_stats()["replica_demotes"] == 1
    finally:
        svc.close()
        for proc in procs:
            try:
                proc.stdin.close()
            except OSError:
                pass
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def test_busy_replica_fails_over_to_idle_sibling():
    """A busy-rejected request must land on an idle sibling IMMEDIATELY
    (no backoff sleep while a live replica has not rejected this
    round), and the saturated replica must stay live."""
    from distributed_reinforcement_learning_tpu.runtime.inference import (
        make_act_adapter)
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        RemoteActService, TransportServer)

    agent, cfg = _tiny_agent()
    weights = _published_store(agent)
    # Replica A: admission budget held full by a gated in-flight batch.
    gate = _GatedActFn(make_act_adapter("impala", agent), block_call=2)
    busy_infer = ContinuousInferenceServer(gate, weights, max_batch=64,
                                           admission_rows=4, seed=0)
    # Replica B: healthy.
    idle_infer = ContinuousInferenceServer.for_agent("impala", agent,
                                                     weights, seed=1)
    ports = [_free_port(), _free_port()]
    servers = [
        TransportServer(None, weights, host="127.0.0.1", port=ports[0],
                        inference=busy_infer).start(),
        TransportServer(None, weights, host="127.0.0.1", port=ports[1],
                        inference=idle_infer).start(),
    ]
    svc = RemoteActService.from_addrs([f"127.0.0.1:{p}" for p in ports],
                                      connect_retries=2)
    try:
        busy_infer.submit(_impala_request(cfg, 2))  # warm + prime gate
        t = threading.Thread(
            target=busy_infer.submit, args=(_impala_request(cfg, 2),))
        t.start()
        assert gate.entered.wait(timeout=10.0)  # A's batch held in flight
        t2 = threading.Thread(
            target=busy_infer.submit, args=(_impala_request(cfg, 3),))
        t2.start()  # 3 pending rows: A's budget now rejects 2-row acts
        deadline = time.monotonic() + 10.0
        while busy_infer._pending_rows < 3:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        # Round-robin tries A first (index 0, equal pending), gets
        # ST_BUSY, and must serve from idle B in the same call.
        out = svc(_impala_request(cfg, 2))
        assert out["action"].shape == (2,)
        stats = svc.snapshot_stats()
        assert stats["busy_failovers"] >= 1
        assert stats["replica_demotes"] == 0
        assert svc.live_endpoints() == 2  # saturated != dead
    finally:
        gate.release.set()
        svc.close()
        for s in servers:
            s.stop()
        busy_infer.stop()
        idle_infer.stop()


def test_replica_app_error_does_not_demote():
    """ST_ERROR is an APPLICATION failure from an alive replica (a
    poisoned co-batched request, weights not yet published) — it must
    propagate to the caller like the single-endpoint path always has,
    WITHOUT demoting the replica: one bad request latching healthy
    replicas dead would let a single actor take the whole tier down."""
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        RemoteActFailed, RemoteActService, TransportServer)

    agent, cfg = _tiny_agent()
    empty = WeightStore()  # never published -> every act answers ST_ERROR
    inference = ContinuousInferenceServer.for_agent("impala", agent, empty,
                                                    seed=0)
    port = _free_port()
    server = TransportServer(None, empty, host="127.0.0.1", port=port,
                             inference=inference).start()
    svc = RemoteActService.from_addrs([f"127.0.0.1:{port}"],
                                      connect_retries=2)
    try:
        for _ in range(3):  # deterministic app errors, repeatedly
            with pytest.raises(RemoteActFailed):
                svc(_impala_request(cfg, 2))
        assert svc.live_endpoints() == 1  # the alive replica survived
        assert svc.snapshot_stats()["replica_demotes"] == 0
    finally:
        svc.close()
        server.stop()
        inference.stop()


class TestReplicaGate:
    """replica_count: the launcher asks it (scripts/launch_local_cluster.py)."""

    def test_unset_is_learner_hosted_and_malformed_is_loud(self, monkeypatch):
        monkeypatch.delenv("DRL_INFER_REPLICAS", raising=False)
        assert replica_count() == 0
        monkeypatch.setenv("DRL_INFER_REPLICAS", "-1")
        assert replica_count() == 0
        monkeypatch.setenv("DRL_INFER_REPLICAS", "two")
        with pytest.raises(ValueError, match="DRL_INFER_REPLICAS"):
            replica_count()

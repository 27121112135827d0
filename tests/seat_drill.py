"""The kill-one-of-two-learner-seats fault drill.

Two real learner seats (a `LearnerTier` collective, a `FleetSupervisor`
and crc verification of every landed trajectory) and one actor per seat
(crc-stamped PUTs, weight-board pulls with the heartbeat-driven
reattach ladder) run as child processes; the drill SIGKILLs the
publisher seat and reports what the survivor did.
tests/test_learner_tier.py asserts on the report.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _read_stats(stats_path: str) -> dict:
    """Per-pid last stats line of each learner incarnation (the file is
    append-only so a SIGKILL can lose at most a torn final line)."""
    per_pid: dict = {}
    try:
        with open(stats_path) as f:
            for raw in f:
                try:
                    rec = json.loads(raw)
                except ValueError:
                    continue  # torn final line of a SIGKILLed incarnation
                per_pid[rec["pid"]] = rec
    except FileNotFoundError:
        pass
    return per_pid


_SEAT_DRILL_LEARNER_CHILD = r"""
import json, os, signal, sys, threading, time, zlib

import numpy as np

(host, port, rank, seats, peers, board_name, stats_path, window_s,
 steps, obs_dim) = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
    sys.argv[5], sys.argv[6], sys.argv[7], float(sys.argv[8]),
    int(sys.argv[9]), int(sys.argv[10]))
from distributed_reinforcement_learning_tpu.runtime.learner_tier import (
    LearnerTier)

# Both seats join the collective together: the parent releases them
# once each has paid its imports, so on a loaded host neither can
# declare the other dead (DRL_FLEET_DEAD_S) while it is still loading.
print("SEAT_IMPORTED", flush=True)
sys.stdin.readline()
tier = LearnerTier(rank, peers.split(","), sync="allreduce").start()

import jax

from distributed_reinforcement_learning_tpu.agents.apex import (
    ApexAgent, ApexBatch, ApexConfig)
from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue
from distributed_reinforcement_learning_tpu.runtime import (
    apex_runner, fleet, weight_board)
from distributed_reinforcement_learning_tpu.runtime.transport import (
    TransportServer)
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

agent = ApexAgent(ApexConfig(obs_shape=(obs_dim,), num_actions=2))
wire_q = TrajectoryQueue(256)     # crc-verified, then forwarded
learner_q = TrajectoryQueue(256)  # what the learner ingests
weights = WeightStore()
learner = apex_runner.ApexLearner(
    agent, learner_q, weights, batch_size=16, replay_capacity=4096,
    train_start_unrolls=2, rng=jax.random.PRNGKey(rank))
tier.attach(learner)

board = None

def make_board():
    # Publisher-only: create (or RECLAIM, creator-pid) the tier's
    # shared board and replay the current snapshot into it.
    global board
    b = weight_board.WeightBoard.create(board_name, 4 << 20)
    weights.attach_board(b)
    board = b

if tier.is_publisher():
    make_board()
tier.set_promote_cb(make_board)
sup = fleet.FleetSupervisor(board_pid_fn=tier.publisher_pid).start()
server = TransportServer(wire_q, weights, host=host, port=port,
                         fleet=sup).start()

stop = threading.Event()
signal.signal(signal.SIGTERM, lambda *a: stop.set())
verified = corrupt = 0
vlock = threading.Lock()

def verify_loop():
    global verified, corrupt
    while not stop.is_set():
        item = wire_q.get(timeout=0.2)
        if item is None:
            continue
        try:
            state = np.ascontiguousarray(item["batch"].state)
            ok = int(item["crc"]) == (zlib.crc32(state.tobytes())
                                      & 0xFFFFFFFF)
        except Exception:
            ok = False
        with vlock:
            if ok:
                verified += 1
            else:
                corrupt += 1
        if ok:
            learner_q.put(item["batch"], timeout=0.5)

vt = threading.Thread(target=verify_loop, daemon=True)
vt.start()

# Warm/compile outside the drill: local prefill + one collective round
# (both seats reach this barrier together).
# Warm unrolls use the SAME unroll length as the drill actor's PUTs
# (a mixed-length queue would fail the stacked dequeue) and round-trip
# the CODEC so the replay store is seeded with the reconstructed
# namedtuple class the wire path yields (replay_compare's precedent —
# the SoA store's tree map is namedtuple-TYPE-strict).
from distributed_reinforcement_learning_tpu.data import codec

rng = np.random.RandomState(rank)
for _ in range(4):
    learner_q.put(codec.decode(codec.encode(ApexBatch(
        state=rng.rand(steps, obs_dim).astype(np.float32),
        next_state=rng.rand(steps, obs_dim).astype(np.float32),
        previous_action=rng.randint(0, 2, steps).astype(np.int32),
        action=rng.randint(0, 2, steps).astype(np.int32),
        reward=rng.randn(steps).astype(np.float32),
        done=(rng.rand(steps) < 0.1))), copy=True))
while learner.ingest_many(timeout=0.0):
    pass
assert tier.await_peers(120.0), "tier startup barrier failed"
assert learner.train() is not None
print("SEAT_READY", os.getpid(), flush=True)

deadline = time.monotonic() + window_s
next_stats = 0.0
while not stop.is_set() and time.monotonic() < deadline:
    # BOUNDED drain: allreduce couples the seats' TRAIN cadences, so an
    # unbounded ingest drain under a fast producer would starve this
    # seat's rounds and stall the peer mid-round (the BSP livelock the
    # tier docs call out) — cap unrolls per train call instead.
    drained = False
    for _ in range(8):
        if not learner.ingest_many(timeout=0.005):
            break
        drained = True
    if learner.train() is None and not drained:
        time.sleep(0.01)
    if time.monotonic() >= next_stats:
        next_stats = time.monotonic() + 0.2
        with vlock:
            line = {"pid": os.getpid(), "rank": rank, "verified": verified,
                    "corrupt": corrupt, "train_steps": learner.train_steps,
                    "version": weights.version,
                    "publisher": tier.is_publisher(),
                    "solo": tier.collective.membership.solo,
                    "wire_q": wire_q.size(), "learner_q": learner_q.size(),
                    "rounds_ok": tier.collective.stat("rounds_ok")}
        with open(stats_path, "a") as f:
            f.write(json.dumps(line) + "\n")
stop.set()
vt.join(timeout=2.0)
learner.close()
server.stop()
sup.stop()
tier.close()
if board is not None:
    board.close_writer()
    board.close()
    board.unlink()
"""

_SEAT_DRILL_ACTOR_CHILD = r"""
import json, sys, time, zlib

import numpy as np

from distributed_reinforcement_learning_tpu.runtime import fleet, weight_board
from distributed_reinforcement_learning_tpu.runtime.transport import (
    RemoteQueue, TransportClient)

(host, port, rank, board_name, steps, obs_dim, secs) = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4],
    int(sys.argv[5]), int(sys.argv[6]), float(sys.argv[7]))
ApexBatch = __import__("collections").namedtuple(
    "ApexBatch", ["state", "next_state", "previous_action", "action",
                  "reward", "done"])
client = TransportClient(host, port)
queue = RemoteQueue(client)
bw = weight_board.attach_board_weights(board_name, client)
hb = fleet.HeartbeatLoop(host, port, "actor", rank)
hb.watch(bw)
hb.start()
client.connect_retries = 3
rng = np.random.RandomState(rank)
sent = i = 0
version = -1
version_changes = []  # (monotonic t, version) on every observed change
deadline = time.monotonic() + secs
while time.monotonic() < deadline:
    state = rng.rand(steps, obs_dim).astype(np.float32)
    tree = {"batch": ApexBatch(
        state=state,
        next_state=rng.rand(steps, obs_dim).astype(np.float32),
        previous_action=rng.randint(0, 2, steps).astype(np.int32),
        action=rng.randint(0, 2, steps).astype(np.int32),
        reward=rng.randn(steps).astype(np.float32),
        done=(rng.rand(steps) < 0.1)),
        "crc": np.uint32(zlib.crc32(np.ascontiguousarray(state).tobytes())
                         & 0xFFFFFFFF)}
    try:
        sent += bool(queue.put(tree))
    except (ConnectionError, OSError):
        time.sleep(0.2)  # seat outage: ride it out
    i += 1
    if i % 8 == 0 and bw is not None:
        try:
            got = bw.get_if_newer(version)
            if got is not None:
                version = got[1]
                version_changes.append([round(time.monotonic(), 3), version])
        except (ConnectionError, OSError):
            pass
    time.sleep(0.002)
hb.stop()
out = {"sent": sent, "version_changes": version_changes,
       "board_stats": bw.snapshot_stats() if bw is not None else None,
       "hb_stats": hb.snapshot_stats()}
if bw is not None:
    bw.close()
client.close()
print("DRILL_ACTOR=" + json.dumps(out), flush=True)
"""


def seat_drill(secs: float = 22.0, steps: int = 8, obs_dim: int = 16,
               repromote_deadline_s: float = 15.0) -> dict:
    """Kill ONE of N=2 learner seats mid-run (the PUBLISHER, seat 0 —
    the hardest case) and measure, not assume:

    - the SURVIVOR re-forms the collective solo and keeps training
      (stats lines show solo=true + train_steps advancing);
    - the survivor takes over PUBLICATION: promoted to publisher,
      re-creates the shared board under the same name (creator-pid
      reclaim), and the surviving seat's actor observes post-kill
      version changes THROUGH its reattached board (version-identity
      semantics — the ladder validates the new creator via the
      heartbeat reply's board_pid);
    - ZERO corrupted trajectories: every unroll that landed on either
      seat crc32-verifies, across the kill.
    """
    import shutil
    import tempfile

    from distributed_reinforcement_learning_tpu.runtime.shm_ring import (
        _attach_shm)

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    # Probe pacing scaled to the drill window; ladder/collective shapes
    # are the production ones.
    env.setdefault("DRL_FLEET_HB_S", "0.25")
    env.setdefault("DRL_REATTACH_BASE_S", "0.25")
    env.setdefault("DRL_REATTACH_MAX_S", "1.0")
    env.setdefault("DRL_LEARNER_WAIT_S", "2.0")
    env.setdefault("DRL_FLEET_DEAD_S", "1.5")

    tag = f"drlseat-{os.getpid()}-{os.urandom(3).hex()}"
    board_name = f"{tag}-b"
    tmp = tempfile.mkdtemp(prefix="seatdrill_")
    stats_paths = [os.path.join(tmp, f"seat{r}.jsonl") for r in range(2)]
    ports = [_free_port() for _ in range(2)]
    peers = ",".join(f"127.0.0.1:{_free_port()}" for _ in range(2))
    seats: list = []
    actors: list = []
    stderr_tails: dict = {}
    watchers: list = []

    def watch_stderr(name, proc):
        tail = stderr_tails.setdefault(name, [])
        for line in proc.stderr:
            tail.append(line)
            del tail[:-60]

    def last_stats(r: int) -> dict:
        per = _read_stats(stats_paths[r])
        # newest line per pid; one pid per seat here (no respawn)
        return per.popitem()[1] if per else {}

    try:
        for r in range(2):
            proc = subprocess.Popen(
                [sys.executable, "-c", _SEAT_DRILL_LEARNER_CHILD,
                 "127.0.0.1", str(ports[r]), str(r), "2", peers, board_name,
                 stats_paths[r], str(secs), str(steps), str(obs_dim)],
                env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True)
            seats.append(proc)
            t = threading.Thread(target=watch_stderr, args=(f"seat{r}", proc),
                                 daemon=True)
            t.start()
            watchers.append(t)
        if not all("SEAT_IMPORTED" in proc.stdout.readline()
                   for proc in seats):
            raise RuntimeError(
                "a drill seat failed to import: "
                + "".join(stderr_tails.get("seat0", [])
                          + stderr_tails.get("seat1", []))[-800:])
        for proc in seats:
            proc.stdin.write("go\n")
            proc.stdin.flush()
        for r, proc in enumerate(seats):
            line = proc.stdout.readline()
            if "SEAT_READY" not in line:
                raise RuntimeError(
                    f"drill seat {r} failed to start: "
                    f"{''.join(stderr_tails.get(f'seat{r}', []))[-800:]}")
        for r in range(2):
            proc = subprocess.Popen(
                [sys.executable, "-c", _SEAT_DRILL_ACTOR_CHILD, "127.0.0.1",
                 str(ports[r]), str(r), board_name, str(steps), str(obs_dim),
                 str(secs)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True)
            actors.append(proc)
            t = threading.Thread(target=watch_stderr,
                                 args=(f"actor{r}", proc), daemon=True)
            t.start()
            watchers.append(t)
        # Kill only after OBSERVED verified traffic on BOTH seats (a
        # vacuous early kill would prove nothing).
        t_gate = time.monotonic() + 90.0
        while time.monotonic() < t_gate:
            if all(last_stats(r).get("verified", 0) >= 10 for r in range(2)):
                break
            if any(p.poll() is not None for p in seats):
                raise RuntimeError(
                    "a drill seat died before the kill: "
                    + "".join(stderr_tails.get("seat0", [])
                              + stderr_tails.get("seat1", []))[-800:])
            time.sleep(0.1)
        else:
            raise RuntimeError("seat drill: no verified traffic within 90s")
        pre_kill = last_stats(1)
        t_kill = time.monotonic()
        seats[0].kill()  # SIGKILL the PUBLISHER seat
        seats[0].wait()
        # Survivor must go solo + publisher + keep training, inside the
        # re-promotion deadline.
        reelected_s = None
        while time.monotonic() - t_kill < repromote_deadline_s:
            s = last_stats(1)
            if (s.get("solo") and s.get("publisher")
                    and s.get("train_steps", 0)
                    > pre_kill.get("train_steps", 0)):
                reelected_s = round(time.monotonic() - t_kill, 2)
                break
            time.sleep(0.1)
        results = []
        for r, proc in enumerate(actors):
            proc.wait(timeout=secs + 120)
            out_s = proc.stdout.read()
            line = next((ln for ln in out_s.splitlines()
                         if ln.startswith("DRILL_ACTOR=")), None)
            results.append(json.loads(line.split("=", 1)[1])
                           if line else None)
        seats[1].wait(timeout=secs + 120)
        final = last_stats(1)
        dead_final = last_stats(0)
        corrupt = (final.get("corrupt", 0) or 0) + \
            (dead_final.get("corrupt", 0) or 0)
        verified = (final.get("verified", 0) or 0) + \
            (dead_final.get("verified", 0) or 0)
        surv_actor = results[1] or {}
        post_kill_versions = [
            v for t, v in surv_actor.get("version_changes", ())
            if t >= t_kill]
        board_reattaches = (surv_actor.get("board_stats") or {}).get(
            "reattaches", 0)
        ok = bool(corrupt == 0 and verified > 0
                  and reelected_s is not None
                  and post_kill_versions
                  and board_reattaches >= 1)
        return {
            "verified": verified, "corrupt": corrupt,
            "reelected_s": reelected_s,
            "repromote_deadline_s": repromote_deadline_s,
            "survivor_solo": bool(final.get("solo")),
            "survivor_publisher": bool(final.get("publisher")),
            "survivor_train_steps": final.get("train_steps", 0),
            "post_kill_versions_observed": len(post_kill_versions),
            "survivor_board_reattaches": board_reattaches,
            "actor_stats": results,
            "pass": ok,
        }
    finally:
        for proc in seats + actors:
            if proc.poll() is None:
                proc.kill()
        for proc in seats + actors:
            try:
                proc.wait(timeout=10)
            except (subprocess.TimeoutExpired, OSError):
                pass
        for t in watchers:
            t.join(timeout=3.0)
        try:
            seg = _attach_shm(board_name)
            seg.unlink()
            seg.close()
        except (FileNotFoundError, OSError):
            pass
        shutil.rmtree(tmp, ignore_errors=True)

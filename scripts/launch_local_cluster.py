"""Launch the reference topology (1+ learners + N actors) in one command.

The reference README has the operator run N+1 shell commands by hand
(`/root/reference/README.md:26-55`, one per `--job_name/--task`). This
helper spawns the same topology as subprocesses of one command, prefixes
their output, and tears everything down on Ctrl-C or learner exit.

    python scripts/launch_local_cluster.py --section impala_cartpole \
        --actors 2 --updates 500 [--learners 2] [--serve_inference ...]

With --learners K > 1 the learner processes join one jax.distributed
runtime (coordinator on localhost) and jointly pjit the learn step over
the global mesh; actors are partitioned round-robin across the learners'
data planes via DRL_LEARNER_INDEX. This is exactly the topology
tests/test_multihost.py::test_socket_topology_two_learners_with_restart
exercises.

ELASTIC FLEET (runtime/fleet.py): `--respawn on-exit` re-spawns any
role process that dies mid-run with the SAME command and environment —
a respawned learner re-creates its shm segments under the same names
(stale segments are reclaimed by creator-pid, runtime/shm_ring.py),
restores from `--checkpoint_dir` when given, and the surviving actors'
heartbeat-driven reattach ladders re-promote them off their TCP
demotions. `--chaos` additionally KILLS roles mid-run on an escalation
schedule (actor, then inference replica, then learner, every
`--chaos_interval` seconds); it implies `--respawn chaos` (same
respawn behavior as on-exit, plus the kill schedule).
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# The package owns every default this launcher plans by. Importing it
# loads JAX but opens no device, so the chip stays free for the learner.
from distributed_reinforcement_learning_tpu.runtime import (  # noqa: E402
    learner_tier,
    serving,
    shm_ring,
    weight_board,
)


def _reap_segments(names, why: str) -> None:
    """Unlink the named shm segments whose OWNING pid is dead — keyed by
    the header's creator-pid word, never just the name: a respawned
    learner re-creating segments under the same names must not lose
    them to a sweep aimed at the dead incarnation's leftovers."""
    from multiprocessing import shared_memory

    for name in names:
        if shm_ring.pid_alive(shm_ring.segment_owner_pid(name)):
            continue  # a live (respawned) owner: not ours to reap
        try:
            seg = shared_memory.SharedMemory(name=name)
            seg.close()
            seg.unlink()
            print(f"[cluster] reaped leaked shm segment {name} ({why})",
                  file=sys.stderr)
        except FileNotFoundError:
            pass  # the owner cleaned up, as it should
        except OSError:
            pass

ALGO_LAUNCHER = {
    "impala": "train_impala.py", "apex": "train_apex.py", "r2d2": "train_r2d2.py",
    "xformer": "train_xformer.py", "ximpala": "train_ximpala.py",
}


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _pump(prefix: str, proc: subprocess.Popen) -> None:
    for line in proc.stdout:  # type: ignore[union-attr]
        sys.stdout.write(f"[{prefix}] {line}")
        sys.stdout.flush()


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--config", default=os.path.join(REPO, "config.json"))
    p.add_argument("--section", default="impala_cartpole")
    p.add_argument("--algo", default=None,
                   help="algorithm (default: section-name prefix)")
    p.add_argument("--actors", type=int, default=2)
    p.add_argument("--learners", type=int, default=1,
                   help=">1: multiple learner processes — a SHARDED "
                        "LEARNER TIER (independent seats exchanging "
                        "gradients over the host collective, "
                        "runtime/learner_tier.py) when seat mode "
                        "resolves on (--learner_sync / DRL_LEARNER_SEATS), "
                        "else the jax.distributed multihost learners "
                        "over one global mesh")
    p.add_argument("--learner_sync", choices=("allreduce", "async",
                                              "multihost"), default=None,
                   help="with --learners N>1: force the learner-tier "
                        "seat mode with this collective sync "
                        "(DRL_LEARNER_SYNC — allreduce: lockstep ring "
                        "gradient exchange; async: bounded-staleness "
                        "parameter merging) or force the old multihost "
                        "pjit group. Unset defers to DRL_LEARNER_SEATS, "
                        "then multihost; see docs/performance.md "
                        "'Learner tier'")
    p.add_argument("--updates", type=int, default=500)
    p.add_argument("--run_dir", default=None,
                   help="run directory: the learner's metrics.jsonl plus "
                        "run-wide telemetry shards from EVERY process "
                        "(<run_dir>/telemetry/<role>-<rank>.jsonl + Chrome "
                        "traces; merge with scripts/obs_report.py)")
    p.add_argument("--checkpoint_dir", default=None)
    p.add_argument("--platform", default=None,
                   help="force a jax platform for the LEARNER (actors are cpu)")
    p.add_argument("--serve_inference", action="store_true")
    p.add_argument("--remote_act", action="store_true")
    p.add_argument("--inference_replicas", type=int, default=None,
                   help="remote-act topologies: N dedicated act-serving "
                        "replica processes (runtime/serving.py) between "
                        "the actors and the learner — each attaches to "
                        "the learner's weight plane (shm board / TCP "
                        "fallback) and serves OP_ACT on its own port "
                        "with continuous batching + admission control "
                        "(DRL_INFER_REPLICAS; 0, the default, keeps "
                        "acts learner-hosted); see docs/performance.md "
                        "'Inference serving'")
    p.add_argument("--replay_shards", type=int, default=None,
                   help="prioritized-replay learners (apex/r2d2/xformer): "
                        "N>=1 shards replay across the learner's ingest "
                        "threads with ingest-time prioritization "
                        "(DRL_REPLAY_SHARDS; 0 forces the monolithic "
                        "path; 2 by default); see docs/performance.md "
                        "'Replay shards'")
    p.add_argument("--weights_sharded", type=int, default=None,
                   choices=(0, 1),
                   help="force per-shard weight publication on (1) or "
                        "off (0) for every role (DRL_WEIGHTS_SHARDED — "
                        "partition-keyed shard blobs + manifest on the "
                        "board and the shard-scoped TCP pull; pair with "
                        "DRL_WEIGHTS_QUANT=bf16|int8 / DRL_WEIGHTS_DELTA "
                        "for the quantized/delta broadcast). Off by "
                        "default; see docs/performance.md 'Sharded "
                        "weight plane'")
    p.add_argument("--respawn", choices=("off", "on-exit", "chaos"),
                   default=None,
                   help="elastic-fleet respawn policy: on-exit re-spawns "
                        "any role process that dies mid-run with the same "
                        "command/env (a respawned learner re-creates its "
                        "shm segments under the same names and restores "
                        "from --checkpoint_dir); chaos = on-exit plus the "
                        "--chaos kill schedule. Default off (unless "
                        "--chaos, which implies chaos)")
    p.add_argument("--chaos", action="store_true",
                   help="kill roles mid-run on an escalation schedule "
                        "(actor, inference replica, learner — one each, "
                        "--chaos_interval apart) and respawn them; the "
                        "fleet supervisor + reattach ladders must carry "
                        "the topology through")
    p.add_argument("--chaos_interval", type=float, default=20.0,
                   help="seconds between chaos kills (default 20)")
    p.add_argument("--max_respawns", type=int, default=5,
                   help="per-role respawn budget (default 5); an "
                        "exhausted role stays down")
    p.add_argument("--staleness_budget", type=int, default=None,
                   help="bound the weight staleness actors can be observed "
                        "at (in train steps, the unit of the "
                        "learner/weight_staleness telemetry) by deriving "
                        "publish_interval from it instead of the config "
                        "section's fixed default; see docs/performance.md "
                        "'Staleness budget'")
    args = p.parse_args()

    algo = args.algo or args.section.split("_")[0]
    if algo not in ALGO_LAUNCHER:
        p.error(f"unknown algorithm {algo!r} (from --section/--algo); "
                f"one of {sorted(ALGO_LAUNCHER)}")
    if args.remote_act and not args.serve_inference:
        # Actors would fail fast with InferenceUnavailableError while the
        # learner idles on an empty queue forever.
        p.error("--remote_act needs the learner to serve inference; "
                "pass --serve_inference too")
    respawn = args.respawn or ("chaos" if args.chaos else "off")
    if args.chaos and respawn == "off":
        p.error("--chaos needs a respawn policy; drop --respawn off")

    def ask(resolve):
        """What the package resolves a knob to; a malformed value is a
        usage error of this command."""
        try:
            return resolve()
        except ValueError as e:
            p.error(str(e))

    # Learner-tier seat mode (runtime/learner_tier.py): with
    # --learners N>1, decide between N cooperating SEATS over the host
    # collective and the old jax.distributed multihost pjit group.
    def learner_tier_sync() -> str | None:
        if args.learners <= 1 or args.learner_sync == "multihost":
            return None
        if args.learner_sync in ("allreduce", "async"):
            return args.learner_sync
        return (ask(learner_tier.sync_mode)
                if ask(learner_tier.seat_count) >= 2 else None)

    tier_sync = learner_tier_sync()
    if tier_sync == "allreduce" and algo != "apex":
        # tier.attach would reject this anyway — but only after every
        # seat paid seconds of jit/agent init. The algo and the sync
        # are both known right here.
        p.error(f"learner-tier allreduce needs the apex family's split "
                f"learn step (agent.grads/apply_grads); use "
                f"--learner_sync async for {algo!r}")
    if respawn != "off" and args.learners > 1:
        # jax.distributed offers no single-process rejoin of a pjit
        # group, and tier SEATS cannot rejoin a live collective either
        # (dead ranks stay dead — params diverged; see
        # parallel/collective.py): a respawned ex-publisher would
        # elect itself publisher against the promoted survivor and
        # race it for the shared board name. Either way the learner
        # set restarts WHOLESALE, which this per-role loop cannot
        # express (ROADMAP lists live seat re-admission as the
        # follow-on).
        p.error("--respawn needs --learners 1 (a pjit group or a "
                "learner tier can only restart wholesale)")
    learner_platform = (args.platform or os.environ.get("JAX_PLATFORMS", "")
                        ).split(",")[0].strip().lower()
    if args.learners > 1 and learner_platform not in ("", "cpu"):
        # A chip belongs to ONE process, and this launcher does not
        # divide a host's chips between learner processes: seat 1 would
        # ask for the chips seat 0 holds and fail (or sit in the runtime
        # until utils/device.open_devices gives up on it).
        p.error(f"--learners {args.learners} starts {args.learners} JAX "
                f"processes on this host and platform "
                f"{learner_platform!r} gives its chips to one process: "
                f"pass --platform cpu")
    launcher = os.path.join(REPO, ALGO_LAUNCHER[algo])

    class Role:
        """One respawnable seat of the topology: the command + env it
        was (re)launched with, its live process, and — for learners —
        the shm segment names it owns (the respawn loop reaps a dead
        incarnation's leftovers by creator-pid before re-spawning)."""

        def __init__(self, name: str, cmd: list[str], env: dict,
                     kind: str, segments: tuple = ()):
            self.name, self.cmd, self.env, self.kind = name, cmd, env, kind
            self.segments = list(segments)
            self.proc: subprocess.Popen | None = None
            self.respawns = 0
            self.done = False  # finished normally / budget exhausted

    roles: list[Role] = []
    pumps: list[threading.Thread] = []

    def spawn_proc(role: Role) -> subprocess.Popen:
        role.proc = subprocess.Popen(
            role.cmd, cwd=REPO, env=role.env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        t = threading.Thread(target=_pump, args=(role.name, role.proc),
                             daemon=True)
        t.start()
        pumps.append(t)
        return role.proc

    def spawn(name: str, cmd: list[str], env: dict, kind: str,
              segments: tuple = ()) -> subprocess.Popen:
        role = Role(name, cmd, env, kind, segments)
        roles.append(role)
        return spawn_proc(role)

    base = [sys.executable, launcher, "--config", args.config,
            "--section", args.section]
    learner_cmd = base + ["--mode", "learner", "--updates", str(args.updates)]
    if args.run_dir:
        learner_cmd += ["--run_dir", args.run_dir]
    if args.checkpoint_dir:
        learner_cmd += ["--checkpoint_dir", args.checkpoint_dir]
    if args.platform:
        learner_cmd += ["--platform", args.platform]
    if args.serve_inference:
        learner_cmd += ["--serve_inference"]

    env = dict(os.environ)
    if args.run_dir:
        # Enable run-wide telemetry in every child (actors included):
        # each process writes its own shard + Chrome trace under here.
        # Explicit --run_dir WINS over an inherited DRL_TELEMETRY_DIR —
        # a stale export must not silently divert this run's shards.
        env["DRL_TELEMETRY_DIR"] = os.path.join(
            os.path.abspath(args.run_dir), "telemetry")
    if args.replay_shards is not None:
        # Learner-side gate (runtime/replay_shard.shard_count is the
        # canonical resolution; this just forces it for the topology).
        env["DRL_REPLAY_SHARDS"] = str(max(0, args.replay_shards))
        print(f"[cluster] replay shards: "
              f"{'off (monolithic)' if args.replay_shards <= 0 else args.replay_shards}",
              file=sys.stderr)
    if args.weights_sharded is not None:
        # Every role reads the same gate (learner decides what it
        # publishes/creates, actors follow the board magic / demote on
        # the TCP op) — exporting it cluster-wide keeps them agreeing.
        env["DRL_WEIGHTS_SHARDED"] = str(args.weights_sharded)
        print(f"[cluster] sharded weight publication "
              f"{'on' if args.weights_sharded else 'off (whole-blob)'}",
              file=sys.stderr)
    if args.staleness_budget is not None:
        # Derivation from the learner/weight_staleness semantics (the
        # histogram measures learner version minus the version each
        # actor's connection last pulled, at queue ingest): cadence
        # quantization contributes up to `publish_interval` steps, and
        # the async-publish bounded-staleness flush
        # (runtime/publishing.py) admits a worker lag of up to
        # 3*publish_interval more — so the observable bound is
        # ~4*publish_interval, and a budget of N steps buys interval
        # N//4. See docs/performance.md "Staleness budget".
        interval = max(1, args.staleness_budget // 4)
        env["DRL_PUBLISH_INTERVAL"] = str(interval)
        print(f"[cluster] staleness_budget {args.staleness_budget} -> "
              f"publish_interval {interval} (cadence + 3x async-lag bound)",
              file=sys.stderr)
    # Everything this launcher spawns shares one host, so every
    # actor/learner pair is co-hosted: wire one shm ring per actor
    # (runtime/shm_ring.py) when rings are enabled.
    ring_names: dict[int, str] = {}
    board_names: dict[int, str] = {}
    tag = f"{os.getpid()}-{os.urandom(4).hex()}"
    if ask(shm_ring.ring_enabled):
        ring_names = {task: f"drlring-{tag}-{task}"
                      for task in range(args.actors)}
        print(f"[cluster] shm rings enabled for {args.actors} co-hosted "
              f"actor(s)", file=sys.stderr)
    # The weight plane's mirror: ONE board per learner, shared by every
    # actor partitioned to it (runtime/weight_board.py) — publish is one
    # memcpy + flip regardless of actor count, pulls are shared-memory
    # reads.
    if ask(weight_board.board_enabled):
        if tier_sync is not None:
            # Seat mode: ONE shared board name for the whole tier —
            # only the elected publisher seat creates/writes it
            # (run_role gates on tier.is_publisher(); a takeover
            # re-creates the same name via creator-pid reclaim), and
            # every actor attaches the same segment regardless of
            # which seat's data plane it feeds.
            shared = f"drlwboard-{tag}-tier"
            board_names = {pid: shared for pid in range(args.learners)}
        else:
            board_names = {pid: f"drlwboard-{tag}-{pid}"
                           for pid in range(args.learners)}
        print(f"[cluster] shm weight board(s) enabled for {args.actors} "
              f"co-hosted actor(s)", file=sys.stderr)

    # Inference tier sizing: --inference_replicas forces, else
    # DRL_INFER_REPLICAS decides. Replicas only make sense for
    # remote-act actors.
    def infer_replicas() -> int:
        if args.inference_replicas is not None:
            return max(0, args.inference_replicas)
        if not args.remote_act:
            return 0
        return ask(serving.replica_count)

    n_infer = infer_replicas()
    if n_infer and not args.remote_act:
        p.error("--inference_replicas needs remote-act actors; "
                "pass --remote_act too")
    learners = []
    if args.learners > 1 and tier_sync is None:
        env["DRL_COORDINATOR"] = f"localhost:{_free_port()}"
        env["DRL_NUM_PROCESSES"] = str(args.learners)
    coll_peers = ""
    if tier_sync is not None:
        # One collective endpoint per seat; the roster (index = rank)
        # is exported to every seat so the ring and the probes agree.
        coll_peers = ",".join(f"127.0.0.1:{_free_port()}"
                              for _ in range(args.learners))
        print(f"[cluster] learner tier: {args.learners} seat(s), "
              f"sync={tier_sync}", file=sys.stderr)
    for pid in range(args.learners):
        lenv = {**env}
        if args.learners > 1 and tier_sync is None:
            lenv["DRL_PROCESS_ID"] = str(pid)
        if tier_sync is not None:
            lenv["DRL_LEARNER_SEATS"] = str(args.learners)
            lenv["DRL_LEARNER_RANK"] = str(pid)
            lenv["DRL_LEARNER_PEERS"] = coll_peers
            lenv["DRL_LEARNER_SYNC"] = tier_sync
        mine = [ring_names[t] for t in sorted(ring_names)
                if t % args.learners == pid]
        if mine:
            lenv["DRL_SHM_RING_CREATE"] = ",".join(mine)
        if pid in board_names:
            lenv["DRL_SHM_WEIGHTS_CREATE"] = board_names[pid]
        learners.append(spawn(
            f"learner{pid}" if args.learners > 1 else "learner",
            learner_cmd, lenv, kind="learner",
            segments=(*mine, *((board_names[pid],)
                               if pid in board_names else ()))))

    # Inference replicas sit between the learners and the actors: each
    # serves OP_ACT on its own port, pulling weights from learner
    # (k % learners) — over that learner's shm board when boards are on
    # (read-only attach; the board is multi-reader by construction).
    infer_addrs: list[str] = []
    for k in range(n_infer):
        iport = _free_port()
        infer_cmd = base + ["--mode", "inference", "--task", str(k)]
        if args.run_dir:
            infer_cmd += ["--run_dir", args.run_dir]
        ienv = {**env, "DRL_INFER_PORT": str(iport),
                "DRL_LEARNER_INDEX": str(k % args.learners)}
        if k % args.learners in board_names:
            ienv["DRL_SHM_WEIGHTS_NAME"] = board_names[k % args.learners]
        spawn(f"infer{k}", infer_cmd, ienv, kind="infer")
        infer_addrs.append(f"127.0.0.1:{iport}")
    if infer_addrs:
        env["DRL_INFER_ADDRS"] = ",".join(infer_addrs)
        print(f"[cluster] inference tier: {n_infer} act-serving "
              f"replica(s)", file=sys.stderr)

    actor_procs = []
    for task in range(args.actors):
        actor_cmd = base + ["--mode", "actor", "--task", str(task)]
        if args.remote_act:
            actor_cmd += ["--remote_act"]
        aenv = {**env, "DRL_LEARNER_INDEX": str(task % args.learners)}
        if task in ring_names:
            aenv["DRL_SHM_RING_NAME"] = ring_names[task]
        if task % args.learners in board_names:
            aenv["DRL_SHM_WEIGHTS_NAME"] = board_names[task % args.learners]
        actor_procs.append(spawn(f"actor{task}", actor_cmd, aenv,
                                 kind="actor"))

    stop_evt = threading.Event()

    def shutdown(*_):
        stop_evt.set()
        for role in roles:
            if role.proc is not None and role.proc.poll() is None:
                role.proc.terminate()

    signal.signal(signal.SIGINT, shutdown)
    signal.signal(signal.SIGTERM, shutdown)

    learner_roles = [r for r in roles if r.kind == "learner"]
    actor_roles = [r for r in roles if r.kind == "actor"]
    infer_roles = [r for r in roles if r.kind == "infer"]
    respawn_tally = {"learner": 0, "actor": 0, "infer": 0}

    # Chaos schedule: one kill per role kind, escalating actor ->
    # inference replica -> learner, --chaos_interval apart. SIGKILL on
    # purpose — the drill is preemption, not polite shutdown: no atexit
    # runs, shm segments leak until the pid-keyed reap, and the fleet
    # supervisor must detect the death by missed heartbeats alone.
    if args.chaos:
        def chaos_loop() -> None:
            seq = [r for r in (actor_roles[:1] + infer_roles[:1]
                               + learner_roles[:1])]
            for role in seq:
                if stop_evt.wait(args.chaos_interval):
                    return
                if role.proc is not None and role.proc.poll() is None:
                    print(f"[cluster] chaos: SIGKILL {role.name} "
                          f"(pid {role.proc.pid})", file=sys.stderr)
                    role.proc.kill()

        threading.Thread(target=chaos_loop, daemon=True,
                         name="chaos").start()

    rc = 0
    # Wait on the whole topology: learners finishing (exit 0) is the
    # normal end; with respawn on, any other death re-spawns the seat
    # (same cmd/env) until its budget runs out. A learner respawn first
    # reaps the dead incarnation's shm segments BY CREATOR-PID — the
    # new learner re-creates the same names, and a name-keyed sweep
    # here would race it and unlink the live segments.
    while not stop_evt.is_set():
        for role in roles:
            code = role.proc.poll() if role.proc is not None else None
            if code is None or role.done:
                continue
            if code == 0:
                # Clean exit is completion for EVERY role, not a death:
                # a learner trained out, an actor ended its grace window
                # — respawning either would churn processes and inflate
                # the respawn tally until the budget exhausted.
                role.done = True
                continue
            if (respawn != "off" and role.respawns < args.max_respawns
                    and not all(r.done for r in learner_roles)):
                # The learner-completion re-check keeps a role that died
                # in the SAME poll pass the (earlier-listed) learner
                # finished in from being respawned just to be SIGTERMed
                # by the shutdown below.
                role.respawns += 1
                respawn_tally[role.kind] += 1
                if role.kind == "learner":
                    _reap_segments(role.segments, "pre-respawn")
                print(f"[cluster] respawning {role.name} "
                      f"(exit {code}, attempt {role.respawns}/"
                      f"{args.max_respawns})", file=sys.stderr)
                spawn_proc(role)
            else:
                role.done = True
                if role.kind == "learner":
                    # A signal-killed learner (negative returncode) is a
                    # failure, not exit 0: the shell's 128+sig convention.
                    rc = max(rc, 128 - code if code < 0 else code)
        if all(r.done for r in learner_roles):
            break
        # The liveness check watches the ACTORS, not the inference
        # replicas: replicas are a serving tier, and a topology whose
        # actors all died for good (respawn off, or budget exhausted —
        # either way the loop above marked them done) must come down
        # rather than hang while the learner idles.
        if actor_roles and all(r.done for r in actor_roles):
            print("[cluster] all actors exited; shutting down",
                  file=sys.stderr)
            rc = 1
            break
        try:
            signal.sigtimedwait([signal.SIGCHLD], 1.0)
        except (AttributeError, InterruptedError):
            time.sleep(1.0)
    shutdown()  # bring everything down
    for role in roles:
        if role.proc is None:
            continue
        try:
            role.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            role.proc.kill()
            # Reap the SIGKILLed child: a zombie still passes the shm
            # sweep's pid_alive check below, which would skip every
            # segment the dead learner owned.
            try:
                role.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
    # An interrupted run (operator SIGINT/SIGTERM -> stop_evt) must not
    # exit 0: map a learner seat whose FINAL incarnation did not finish
    # cleanly to the shell's 128+sig convention, exactly like the
    # in-loop budget-exhausted branch. (Chaos-mode mid-run SIGKILLs are
    # consumed by the respawn branch and never reach here — the final
    # incarnation trains to completion and reports 0.)
    for role in learner_roles:
        code = role.proc.poll() if role.proc is not None else None
        if code is not None and code != 0:
            rc = max(rc, 128 - code if code < 0 else code)
    for t in pumps:
        # Drain the relay threads: without the join, the children's final
        # lines (e.g. the learner's "done: N updates") race sys.exit.
        t.join(timeout=5.0)
    if sum(respawn_tally.values()):
        print(f"[cluster] respawn tally: {respawn_tally}", file=sys.stderr)
    # Shm reaper: the learner unlinks its segments (rings AND weight
    # boards) on a clean stop, but a SIGKILLed/crashed learner leaves
    # them in /dev/shm — sweep every name this launch created, KEYED BY
    # OWNING PID (never just the name prefix), best-effort, after the
    # children are dead.
    _reap_segments([*ring_names.values(), *board_names.values()],
                   "final sweep")
    sys.exit(rc)


if __name__ == "__main__":
    main()

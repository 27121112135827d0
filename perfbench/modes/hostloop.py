"""Mode `hostloop`: actors feeding a learner, as
`scripts/launch_local_cluster.py --section <s>` starts them.

One learner process on the chip (the program's `run_role` learner
through `hostloop_learner.py`), and the configuration's `num_actors`
actor processes on the CPU — the program's own `train_<algo>.py --mode
actor --task k` — over the native queue behind the TCP transport and
the shared-memory weight board. This parent never imports JAX: a chip
belongs to one process. It wires the topology the way the launcher
does (a weight-board name per run), stamps every actor line with its
arrival time, and stops everything it started. What belongs to the
algorithm (launcher script, loss tag) comes from `families/<algorithm>.py`.
"""

from __future__ import annotations

import ast
import json
import math
import os
import re
import signal
import socket
import statistics
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
TIMEOUT_S = 1100.0  # a run that has not ended by then has failed
_STATS = re.compile(r"\[actor (\d+)\] stats (\{.*\})")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class _Actor:
    """One actor process; a thread copies its output to a log, stamping
    each `[actor k] stats {...}` line with the wall clock on arrival."""

    def __init__(self, task: int, cmd: list[str], env: dict, cwd: str,
                 log_path: str):
        self.task = task
        self.samples: list[tuple[float, int, int]] = []  # (t, frames, version)
        self.proc = subprocess.Popen(
            cmd, cwd=cwd, env=env, text=True, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, start_new_session=True)
        self._log = open(log_path, "w")
        self._thread = threading.Thread(target=self._pump, daemon=True)
        self._thread.start()

    def _pump(self) -> None:
        for line in self.proc.stdout:
            now = time.time()
            self._log.write(f"{now:.4f} {line}")
            found = _STATS.search(line)
            if found:
                try:
                    stats = ast.literal_eval(found.group(2))
                    self.samples.append((now, int(stats["frames"]),
                                         int(stats["weight_version"] or 0)))
                except (ValueError, SyntaxError, KeyError, TypeError):
                    pass
        self._log.flush()

    def alive(self) -> bool:
        return self.proc.poll() is None

    def stop(self) -> None:
        _stop_group(self.proc)
        self._thread.join(timeout=5.0)
        self._log.close()


def _stop_group(proc: subprocess.Popen, grace: float = 5.0) -> None:
    """End `proc` and whatever it started, and wait until it is gone."""
    for sig in (signal.SIGTERM, signal.SIGKILL):
        try:
            os.killpg(proc.pid, sig)
        except ProcessLookupError:
            break
        try:
            proc.wait(timeout=grace)
            break
        except subprocess.TimeoutExpired:
            continue
    proc.wait()


def _unlink_shm(name: str) -> None:
    try:
        os.unlink(os.path.join("/dev/shm", name))
    except OSError:
        pass  # the learner unlinked it on its clean stop, as it should


def _p95(values: list[float]) -> float:
    return statistics.quantiles(values, n=100, method="inclusive")[94]


def run(ctx: dict) -> dict:
    import discover
    import parentlib

    cfg, traffic, args = ctx["config"], ctx["traffic"], ctx["args"]
    section_name = cfg["section"]
    section = dict(cfg[section_name])
    algo, family = discover.family(ctx["data_dir"], section_name, section)
    out = ctx["out_dir"]
    port = _free_port()
    section["server_port"] = port
    section["server_ip"] = "127.0.0.1"
    run_cfg = os.path.join(out, "config.json")
    with open(run_cfg, "w") as f:
        json.dump({section_name: section}, f)
    seed = parentlib.program_seed(args.seed)

    tag = f"{os.getpid()}-{os.urandom(3).hex()}"
    board = f"drlwboard-{tag}-0"
    env = parentlib.child_env(ctx)
    if args.trace:
        env["DRL_TELEMETRY_DIR"] = os.path.join(out, "telemetry")
    lenv = {**env, "DRL_SHM_WEIGHTS_CREATE": board}

    learner_log = open(os.path.join(out, "learner.log"), "w")
    learner = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "hostloop_learner.py"),
         *parentlib.child_args(ctx, run_cfg, section_name)],
        cwd=ctx["root"], env=lenv, stdout=learner_log,
        stderr=subprocess.STDOUT, start_new_session=True)
    actors: list[_Actor] = []
    try:
        # Actors start while the learner compiles; their client retries
        # the connection until the learner's server is up.
        aenv = {**env, "JAX_PLATFORMS": "cpu",
                "DRL_TRANSPORT_STATS_S": str(traffic["stats_s"]),
                "DRL_LEARNER_INDEX": "0", "DRL_SHM_WEIGHTS_NAME": board}
        for k in range(section["num_actors"]):
            actors.append(_Actor(
                k, [sys.executable, os.path.join(ctx["root"], family.LAUNCHER),
                    "--config", run_cfg, "--section", section_name,
                    "--mode", "actor", "--task", str(k), "--seed", str(seed),
                    "--platform", "cpu"],
                aenv, ctx["root"], os.path.join(out, f"actor{k}.log")))
        deadline = time.time() + TIMEOUT_S
        while learner.poll() is None and time.time() < deadline:
            if not any(a.alive() for a in actors):
                break  # nothing feeds the learner any more
            time.sleep(0.2)
        timed_out = learner.poll() is None
        alive_at_end = [a.alive() for a in actors]
    finally:
        if learner.poll() is None:
            _stop_group(learner)
        for a in actors:
            a.stop()
        learner_log.close()
        _unlink_shm(board)
    rc = learner.returncode
    if rc == 3:
        raise ctx["NoDevice"](f"JAX found no {args.expect_platform} device")
    result_path = os.path.join(out, "learner_result.json")
    if timed_out or rc != 0 or not os.path.exists(result_path):
        raise ctx["RunFailed"](
            f"learner ended with code {rc} (timed out: {timed_out}); "
            f"see {os.path.join(out, 'learner.log')}")
    with open(result_path) as f:
        res = json.load(f)

    t0, t1 = res["t0"], res["t1"]
    window = t1 - t0
    updates = res["step1"] - res["step0"]
    times = res["update_times"]
    notes = []

    # -- correctness ------------------------------------------------------
    problems = parentlib.common_problems(res, cfg, updates)
    losses, grads = [], []
    try:
        with open(os.path.join(out, "metrics.jsonl")) as f:
            for line in f:
                rec = json.loads(line)
                if res["step0"] < rec["step"] <= res["step1"]:
                    if rec["tag"] == family.LOSS_TAG:
                        losses.append(rec["value"])
                    elif rec["tag"] == "learner/grad_norm":
                        grads.append(rec["value"])
    except OSError:
        pass
    bad_losses = sum(1 for v in losses if not math.isfinite(v))
    if not losses:
        problems.append("the learner logged no loss inside the window")
    if bad_losses:
        problems.append(f"{bad_losses} non-finite losses")
    if not grads or not all(math.isfinite(g) and g > 0 for g in grads):
        problems.append("gradient norm not positive throughout")
    dead = alive_at_end.count(False)
    if dead:
        problems.append(f"{dead} actor process(es) died")

    # -- actors: frames and the weights they act on -------------------------
    collected = 0.0
    lags = []
    for a in actors:
        inside = [s for s in a.samples if t0 <= s[0] <= t1]
        if len(inside) < 2 or inside[-1][0] <= inside[0][0]:
            problems.append(f"actor {a.task}: {len(inside)} stats samples "
                            f"inside the window")
            collected = math.nan
            continue
        collected += ((inside[-1][1] - inside[0][1])
                      / (inside[-1][0] - inside[0][0]))
        if not inside[-1][2] > inside[0][2]:
            problems.append(f"actor {a.task}: weight version stayed at "
                            f"{inside[0][2]} through the window")
        if inside[-1][2] < res["step0"]:
            problems.append(f"actor {a.task}: ends on weights {inside[-1][2]}"
                            f", older than the window's start {res['step0']}")
        for t, _, version in inside:
            step_then = res["step0"] + sum(1 for u in times if u <= t)
            lags.append(step_then - version)
    if lags:
        notes.append(f"weight lag in updates (learner step minus the version "
                     f"an actor holds, {len(lags)} samples): median "
                     f"{statistics.median(lags)}, largest {max(lags)}")

    gaps = [b - a for a, b in zip(times, times[1:])]
    e2e = {
        "frames_learned_per_s": updates * cfg["frames_per_update"] / window,
        "frames_collected_per_s": collected,
        "setup_s": t0 - ctx["t_start"],
    }
    if len(gaps) >= 2:
        # Printed, not a metric: a host loop that completes some tens of
        # updates in a window cannot carry a 95th percentile (PERF.md).
        notes.append(f"update gaps: {len(gaps)} samples, median "
                     f"{1e3 * statistics.median(gaps):.3f} ms, p95 "
                     f"{1e3 * _p95(gaps):.3f} ms, largest "
                     f"{1e3 * max(gaps):.3f} ms")
    notes.append(f"window {window:.3f} s, {updates} updates, "
                 f"{len(losses)} logged losses, machine {res['machine']}, "
                 f"replay {res.get('replay')}, reference {res['reference']}")
    for p in problems:
        notes.append(f"NOT CORRECT: {p}")

    facts = {**res, "window_s": window, "updates": updates, "run_dir": out,
             "telemetry_dir": os.path.join(out, "telemetry"),
             "algorithm": algo, "section": section, "chips": ctx["chips"]}
    if "trace" in res:
        tr = res["trace"]
        in_trace = sum(1 for u in times
                       if tr["start_wall"] <= u <= tr["stop_wall"])
        facts["trace_updates"] = in_trace
    return {"device": {**res["device"],
                       "memory_peak_bytes": res["memory_peak_bytes"]},
            "correct": not problems, "attempted": updates,
            "failed": bad_losses + dead, "e2e": e2e, "facts": facts,
            "notes": notes}

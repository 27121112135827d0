#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip: the main path, once
    python chip_smoke.py --chips 4   # one four-chip host: the mesh learner only

With no arguments it drives actors feeding a learner through the entry
points a user calls, at the full width of `config.json`'s `impala`
section (Breakout simulator, 84x84x4 uint8 frames, Nature-CNN +
LSTM-256, unroll 20, batch 32; weights random from the seed), a few
updates each:

- local:      `train_impala.py --section impala --mode local`
              (`runtime.launch.train_local`), plus the assertion that
              the compiled learn step holds both V-trace passes as
              Mosaic kernels (no `reference` fall-back, no interpret
              mode) and that the committed `cpp/*.cc` build;
- cluster:    `scripts/launch_local_cluster.py --section impala
              --actors 2` — learner process on the chip, actor processes
              on the CPU, socket data plane, shm weight board, async
              publication;
- anakin:     `train_impala.py --mode anakin` (`train_anakin`), the
              fused on-device collect+learn loop;
- apex, r2d2_pixel: the other two families' learn paths through
              `train_local` (replay warm-up included).

With `--chips 4` it runs only what exists across chips: the
`ShardedLearner` step over a 4-device `(data,)` mesh against the
single-device step on the same seeded batches, then the learner-mode
topology whose learner builds that mesh by itself.

A chip belongs to one process at a time, so this parent never imports
JAX: every phase is a child process, one after the other, and the
device in the last line is what the children reported. Each phase
prints one JSON line (seconds, set-up = the wall seconds of JAX's trace,
lower and compile events, as the program's own record has them, apart
from run seconds, loss, kernels in the compiled learn step); the
full output of each phase goes to `chiprun_out/chip_smoke/<phase>.log`.
The LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code is 0 only if every phase passed on the expected
platform. Anything else — no accelerator, a failed phase, a directory
without the repo — exits 1 with `"ok": false`.

`--config` and `--expect-platform` exist for tests/test_chip_smoke.py,
which steers the same phases onto CartPole-sized sections on the CPU;
they are arguments of this script, not options of the program.
"""

from __future__ import annotations

import argparse
import ast
import json
import math
import os
import re
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

# Learner updates per phase: a smoke, not a benchmark. The replay
# families fill their warm-up gate first (apex 10 unrolls, r2d2_pixel
# 2 x batch sequences), whatever the count here.
UPDATES = {"local": 4, "cluster": 8, "anakin": 6, "apex": 2,
           "r2d2_pixel": 2, "sharded": 3, "mesh_cluster": 8}
ANAKIN_CHUNK = 2  # updates per compiled chunk -> three chunks
PHASES = {1: ("local", "cluster", "anakin", "apex", "r2d2_pixel"),
          4: ("sharded", "mesh_cluster")}
# The contract allows 1200 s, compilation included.
DEADLINE_S = 1100
PHASE_TIMEOUT_S = 420
EXIT_WRONG_PLATFORM = 3
# Sharded vs single-device step, float32, after UPDATES["sharded"]
# RMSProp steps: only the order of the batch reductions differs.
LOSS_RTOL = 1e-3
PARAM_ATOL = 1e-4


class PhaseFailed(Exception):
    pass


class NoDevice(Exception):
    """JAX found no device of the expected platform: nothing is run."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


# ---------------------------------------------------------------- children


def _compiled_text(jitted, *args, static: tuple = ()) -> str:
    """HLO text of `jitted` compiled for `args`' shapes and shardings
    (`static`: trailing static arguments)."""
    import jax

    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=getattr(x, "sharding", None)), args)
    return jitted.lower(*shapes, *static).compile().as_text()


def _kernels(hlo_text: str) -> list[str]:
    """The Mosaic kernels (`tpu_custom_call`) in a compiled program, by
    the jit that wraps each `pallas_call`. An interpret-mode kernel
    lowers to plain HLO and is not listed."""
    names = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            op = re.search(r'op_name="([^"]*)"', line)
            inner = re.findall(r"jit\((\w+)\)", op.group(1)) if op else []
            names.append(inner[-1] if inner else "?")
    return names


def _learn_args(algo: str, cfg, rt):
    """Seeded learn-step arguments of the section's shapes."""
    from distributed_reinforcement_learning_tpu.utils import synthetic

    if algo == "impala":
        return (synthetic.synthetic_impala_batch(
            rt.batch_size, cfg.trajectory, cfg.obs_shape, cfg.num_actions,
            cfg.lstm_size),)
    pixels = len(cfg.obs_shape) == 3  # frames travel as uint8
    if algo == "apex":
        return synthetic.synthetic_apex_batch(
            rt.batch_size, cfg.obs_shape, cfg.num_actions,
            obs_dtype="uint8" if pixels else "float32")
    batch, is_weight = synthetic.synthetic_r2d2_batch(
        rt.batch_size, cfg.seq_len, cfg.obs_shape, cfg.num_actions,
        cfg.lstm_size)
    if pixels:
        batch = batch._replace(state=batch.state.astype("uint8"))
    return batch, is_weight


def _learn_kernels(config: str, section: str) -> list[str]:
    """Kernels in the section's compiled learn step: the same agent
    construction `build_local` uses, so `auto` kernel selection sees
    what the run saw (the compile itself is a cache hit)."""
    import jax

    from distributed_reinforcement_learning_tpu.runtime import launch
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    cfg, rt = load_config(config, section)
    agent = launch.make_agent(rt.algorithm, cfg, rt)
    state = jax.eval_shape(agent.init_state, jax.random.PRNGKey(0))
    return _kernels(_compiled_text(
        agent.learn, state, *_learn_args(rt.algorithm, cfg, rt)))


def _sync_loop_frames(updates: int, rt, unroll: int) -> int:
    """Frames `impala_runner.run_sync` collects for `updates` updates:
    whole actor rounds until a batch is queued, leftovers carried."""
    per_round = rt.num_actors * rt.envs_per_actor
    queued = rounds = 0
    for _ in range(updates):
        while queued < rt.batch_size:
            queued += per_round
            rounds += 1
        queued -= rt.batch_size
    return rounds * per_round * unroll


def _train_local_phase(args, section: str, loss_key: str) -> dict:
    from distributed_reinforcement_learning_tpu.runtime.launch import train_local

    n = UPDATES[args.phase]
    result = train_local(args.config, section, n)
    m = result["last_metrics"]
    _check(math.isfinite(m.get(loss_key, math.nan)),
           f"{section}: loss not finite: {m}")
    _check(math.isfinite(m["grad_norm"]) and m["grad_norm"] > 0,
           f"{section}: grad_norm not positive: {m}")
    return {"section": section, "updates": n, "frames": result["frames"],
            "loss": m[loss_key], "grad_norm": m["grad_norm"],
            "kernels": _learn_kernels(args.config, section)}


def _phase_local(args) -> dict:
    from distributed_reinforcement_learning_tpu.data import native
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    # Callers of the native plane fall back to Python queues without a
    # word; here a checkout whose cpp/*.cc do not build is a failure.
    _check(native.native_available(),
           f"native data plane did not build: {native.build_error()}")
    out = _train_local_phase(args, "impala", "total_loss")
    cfg, rt = load_config(args.config, "impala")
    expected = _sync_loop_frames(out["updates"], rt, cfg.trajectory)
    _check(out["frames"] == expected
           >= out["updates"] * rt.batch_size * cfg.trajectory,
           f"local: collected {out['frames']} frames, expected {expected}")
    if args.expect_platform == "tpu":
        _check(out["kernels"].count("vtrace_pallas") == 2,
               f"compiled IMPALA learn step lacks the V-trace kernel: "
               f"{out['kernels']}")
    return {**out, "data_plane": "native"}


def _phase_anakin(args) -> dict:
    import jax

    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent
    from distributed_reinforcement_learning_tpu.runtime import launch
    from distributed_reinforcement_learning_tpu.runtime.anakin import AnakinImpala
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    n = UPDATES["anakin"]
    result = launch.train_anakin(args.config, "impala", n, chunk=ANAKIN_CHUNK)
    cfg, rt = load_config(args.config, "impala")
    num_envs = rt.num_actors * rt.envs_per_actor
    _check(result["frames"] == n * num_envs * cfg.trajectory,
           f"anakin: frames {result['frames']}")
    _check(len(result["chunk_mean_returns"]) == n // ANAKIN_CHUNK,
           f"anakin: chunks {result['chunk_mean_returns']}")
    _check(math.isfinite(result["last_loss"]), f"anakin: loss {result}")
    env_mod, _ = launch._jittable_env_for(cfg, rt)
    anakin = AnakinImpala(ImpalaAgent(cfg), num_envs, env=env_mod)
    state = jax.eval_shape(anakin.init, jax.random.PRNGKey(0))
    kernels = _kernels(_compiled_text(anakin.train_chunk, state,
                                      static=(ANAKIN_CHUNK,)))
    return {"section": "impala", "updates": n, "frames": result["frames"],
            "loss": result["last_loss"], "kernels": kernels}


def _phase_sharded(args) -> dict:
    """`ShardedLearner` over every local device vs the single-device
    step, same initial state, same seeded batches."""
    import jax
    import numpy as np

    from distributed_reinforcement_learning_tpu.parallel import ShardedLearner, make_mesh
    from distributed_reinforcement_learning_tpu.runtime import launch
    from distributed_reinforcement_learning_tpu.utils import synthetic
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    cfg, rt = load_config(args.config, "impala")
    agent = launch.make_agent("impala", cfg, rt)
    devices = jax.local_devices()
    mesh = make_mesh(devices=devices)  # what run_role builds for a learner
    n = mesh.shape["data"]
    _check(n == args.chips and rt.batch_size % n == 0,
           f"mesh {dict(mesh.shape)} for batch {rt.batch_size}")
    learner = ShardedLearner(agent, mesh)
    init = jax.device_get(agent.init_state(jax.random.PRNGKey(args.seed)))
    single = jax.device_put(init, devices[0])
    sharded = learner.place_state(init)
    losses = []
    for k in range(UPDATES["sharded"]):
        batch = synthetic.synthetic_impala_batch(
            rt.batch_size, cfg.trajectory, cfg.obs_shape, cfg.num_actions,
            cfg.lstm_size, seed=args.seed + k, uniform_behavior=False)
        placed = learner.shard_batch(batch)
        for leaf in jax.tree.leaves(placed):
            _check(len(leaf.sharding.device_set) == n
                   and leaf.addressable_shards[0].data.shape[0]
                   == rt.batch_size // n,
                   f"batch leaf not split over {n} devices: {leaf.sharding}")
        for leaf in jax.tree.leaves(sharded):
            _check(len(leaf.sharding.device_set) == n,
                   f"state leaf not on {n} devices: {leaf.sharding}")
        if k == 0:
            text = _compiled_text(learner.learn, sharded, placed)
            kernels = _kernels(text)
        sharded, m_sharded = learner.learn(sharded, placed)
        single, m_single = agent.learn(single, jax.device_put(batch, devices[0]))
        losses.append((float(m_sharded["total_loss"]),
                       float(m_single["total_loss"])))
    _check("all-reduce" in text, "compiled sharded step has no all-reduce")
    if args.expect_platform == "tpu":
        _check(kernels.count("vtrace_pallas") == 2,
               f"sharded learn step lacks the V-trace kernel: {kernels}")

    def max_abs_diff(a, b) -> float:
        return max(jax.tree.leaves(jax.tree.map(
            lambda x, y: float(np.max(np.abs(np.asarray(x) - np.asarray(y)))),
            jax.device_get(a), jax.device_get(b))))

    param_diff = max_abs_diff(sharded.params, single.params)
    param_update = max_abs_diff(single.params, init.params)
    loss_rel = max(abs(a - b) / max(1.0, abs(b)) for a, b in losses)
    _check(all(math.isfinite(a) for a, _ in losses), f"sharded losses {losses}")
    _check(loss_rel <= LOSS_RTOL, f"losses disagree: {losses}")
    _check(param_diff <= PARAM_ATOL,
           f"params disagree by {param_diff} (largest update {param_update})")
    return {"section": "impala", "updates": len(losses),
            "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
            "losses_sharded_vs_single": losses, "loss_rel_diff": loss_rel,
            "param_max_abs_diff": param_diff,
            "param_max_abs_update": param_update,
            "tolerance": {"loss_rtol": LOSS_RTOL, "param_atol": PARAM_ATOL},
            "loss": losses[-1][0], "all_reduce": True, "kernels": kernels}


_CHILD_PHASES = {
    "local": _phase_local, "anakin": _phase_anakin,
    "apex": lambda args: _train_local_phase(args, "apex", "loss"),
    "r2d2_pixel": lambda args: _train_local_phase(args, "r2d2_pixel", "loss"),
    "sharded": _phase_sharded}


def _child_main(args) -> int:
    from distributed_reinforcement_learning_tpu.observability.trace import (
        HOST_RECORD)
    from distributed_reinforcement_learning_tpu.utils.device import (
        enable_compile_cache, open_devices)

    cache_dir = enable_compile_cache()
    device = open_devices("chip_smoke")  # the program's record listens from here
    if device["platform"] != args.expect_platform:
        print(f"[chip_smoke] JAX found {device}, not a "
              f"{args.expect_platform} device: nothing is run",
              file=sys.stderr)
        return EXIT_WRONG_PLATFORM
    t0 = time.perf_counter()
    try:
        out = _CHILD_PHASES[args.phase](args)
    except PhaseFailed as e:
        print(f"[chip_smoke] {args.phase} FAILED: {e}", file=sys.stderr)
        return 1
    seconds = time.perf_counter() - t0
    # Wall seconds JAX spent tracing, lowering and compiling (or reading
    # the persistent cache) in this process: the union of its events, from
    # the program's own record (observability/trace.py).
    setup = HOST_RECORD.seconds()["any"]
    print(json.dumps({
        "phase": args.phase, "ok": True, **out, "device": device,
        "setup_s": round(setup, 2), "run_s": round(seconds - setup, 2),
        "cache_hits": HOST_RECORD.cache["hits"],
        "cache_misses": HOST_RECORD.cache["misses"],
        "cache_dir": cache_dir}), flush=True)
    return 0


# ------------------------------------------------------------------ parent


def _run(cmd: list[str], log_path: str, timeout: float,
         env: dict | None = None) -> int | None:
    """Run `cmd` in its own process group, all output to `log_path`;
    -> exit code, None on timeout. Nothing of the group outlives it."""
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            return proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            return None
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass  # the whole group already exited, as it should
            proc.wait()


def _shm_segments() -> set[str]:
    try:
        return {n for n in os.listdir("/dev/shm")
                if n.startswith(("drlring-", "drlwboard-"))}
    except OSError:
        return set()


def _cluster_phase(args, phase: str, log_path: str, timeout: float) -> dict:
    """`launch_local_cluster.py --section impala --actors 2`, checked
    from what its processes logged."""
    n, actors = UPDATES[phase], 2
    run_dir = tempfile.mkdtemp(prefix="chip_smoke_run_")
    before = _shm_segments()
    env = {**os.environ,
           "JAX_LOG_COMPILES": "1",         # learner's compile seconds
           "DRL_TRANSPORT_STATS_S": "0.2"}  # actors' weight versions
    rc = _run([sys.executable, os.path.join(REPO, "scripts", "launch_local_cluster.py"),
               "--config", args.config, "--section", "impala",
               "--actors", str(actors), "--updates", str(n),
               "--run_dir", run_dir], log_path, timeout, env)
    _check(rc is not None, f"{phase}: no end after {timeout:.0f}s")
    _check(rc == 0, f"{phase}: launcher exit code {rc}")
    with open(log_path, errors="replace") as f:
        log = f.read()
    learner = "\n".join(ln for ln in log.splitlines()
                        if ln.startswith("[learner]"))
    found = re.search(r"\[learner\] device: (\{.*\})", learner)
    _check(found is not None, f"{phase}: learner printed no device line")
    device = ast.literal_eval(found.group(1))
    _check(device["platform"] == args.expect_platform,
           f"{phase}: learner ran on {device}")
    _check(f"[learner] done: {n} updates" in learner,
           f"{phase}: learner did not finish {n} updates")
    _check("data plane: NativeTrajectoryQueue" in learner,
           f"{phase}: learner is not on the native data plane")
    _check("shm weight board serving" in learner,
           f"{phase}: learner serves no shm weight board")
    out: dict = {"section": "impala", "updates": n, "actors": actors,
                 "data_plane": "native", "weight_plane": "shm board"}
    if phase == "mesh_cluster":
        found = re.search(r"\[learner\] mesh: (\{.*\})", learner)
        _check(found is not None, "mesh_cluster: learner built no mesh")
        mesh = ast.literal_eval(found.group(1))
        _check(mesh.get("data") == args.chips,
               f"mesh_cluster: learner mesh {mesh}")
        out["mesh"] = {k: v for k, v in mesh.items() if v > 1}
    versions = {}
    for task, stats in re.findall(r"\[actor (\d+)\] stats (\{.*\})", log):
        v = ast.literal_eval(stats).get("weight_version")
        versions[int(task)] = max(versions.get(int(task), 0), v or 0)
    _check(len(versions) == actors and min(versions.values()) >= 1,
           f"{phase}: actors' newest weight versions {versions}")
    for task in range(actors):
        _check(f"[actor {task}] shm weight board attached" in log,
               f"{phase}: actor {task} did not attach the weight board")
    _check("[weights] WARNING" not in log and "[publish] WARNING" not in log,
           f"{phase}: weight publication warned")
    leaked = _shm_segments() - before
    _check(not leaked, f"{phase}: /dev/shm segments left behind: {leaked}")
    loss = math.nan
    with open(os.path.join(run_dir, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if rec["tag"] == "learner/total_loss":
                loss = rec["value"]
    _check(math.isfinite(loss), f"{phase}: learner logged no finite loss")
    compile_s = sum(float(s) for s in re.findall(
        r"Finished (?:tracing \+ transforming|jaxpr to MLIR module conversion"
        r"|XLA compilation of) .* in ([0-9.]+) sec", learner))
    return {**out, "loss": loss, "actor_weight_versions": versions,
            "device": device, "setup_s": round(compile_s, 2)}


def _in_process_phase(args, phase: str, log_path: str, timeout: float) -> dict:
    rc = _run([sys.executable, os.path.abspath(__file__), "--phase", phase,
               "--chips", str(args.chips), "--config", args.config,
               "--expect-platform", args.expect_platform,
               "--seed", str(args.seed)], log_path, timeout)
    if rc == EXIT_WRONG_PLATFORM:
        raise NoDevice(f"JAX found no {args.expect_platform} device")
    _check(rc is not None, f"{phase}: no end after {timeout:.0f}s")
    _check(rc == 0, f"{phase}: exit code {rc}")
    with open(log_path, errors="replace") as f:
        lines = [ln for ln in f.read().splitlines()
                 if ln.startswith('{"phase"')]
    _check(bool(lines), f"{phase}: printed no result")
    return json.loads(lines[-1])


def _finish(ok: bool, device: dict | None, error: str | None = None) -> int:
    last = {"ok": ok, "device": device}
    if error:
        last["error"] = error
    print(json.dumps(last), flush=True)
    return 0 if ok else 1


def _parent_main(args) -> int:
    os.makedirs(LOG_DIR, exist_ok=True)
    t_start = time.monotonic()
    device, failed = None, []
    for phase in PHASES[args.chips]:
        log_path = os.path.join(LOG_DIR, f"{phase}.log")
        timeout = min(PHASE_TIMEOUT_S,
                      DEADLINE_S - (time.monotonic() - t_start))
        t0 = time.monotonic()
        try:
            _check(timeout > 0, f"{phase}: no time left")
            run = (_cluster_phase if phase.endswith("cluster")
                   else _in_process_phase)
            out = run(args, phase, log_path, timeout)
            seconds = time.monotonic() - t0
            out.setdefault("run_s", round(seconds - out["setup_s"], 2))
            _check(device in (None, out["device"]),
                   f"{phase}: ran on {out['device']}, earlier phases on {device}")
            device = out["device"]
            print(json.dumps({"phase": phase, "ok": True,
                              "seconds": round(seconds, 2),
                              **{k: v for k, v in out.items()
                                 if k not in ("phase", "ok")}}), flush=True)
        except PhaseFailed as e:
            failed.append(phase)
            print(json.dumps({"phase": phase, "ok": False,
                              "seconds": round(time.monotonic() - t0, 2),
                              "error": str(e), "log": log_path}), flush=True)
            with open(log_path, errors="replace") as f:
                # JAX_LOG_COMPILES lines carry whole argument lists.
                tail = [ln[:300].rstrip("\n") for ln in f
                        if "jax._src" not in ln][-40:]
            print(f"---- last lines of {log_path}", *tail, sep="\n",
                  file=sys.stderr)
    if failed:
        return _finish(False, device, f"failed: {', '.join(failed)}")
    if device["count"] != args.chips:
        return _finish(False, device, f"ran on {device['count']} device(s), "
                                      f"not {args.chips}")
    return _finish(True, device)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the mesh learner and what it is compared "
                        "with, on one four-chip host")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--config", default=os.path.join(REPO, "config.json"),
                   help="(tests) a config whose sections are cut to size")
    p.add_argument("--expect-platform", default="tpu",
                   help="(tests) the platform every phase must find")
    p.add_argument("--phase", choices=sorted(_CHILD_PHASES),
                   help="(internal) run one phase in this process")
    args = p.parse_args()
    if args.phase:
        return _child_main(args)
    try:
        return _parent_main(args)
    except NoDevice as e:
        return _finish(False, None, str(e))


if __name__ == "__main__":
    sys.exit(main())

"""Mode `anakin`: the fused on-device collect+learn loop
(`runtime/anakin.py`), built and driven by the program's own
`runtime/launch.train_anakin`, with the benchmark watching.

The parent (`run`) never imports JAX; the child owns the chip. The
child calls `train_anakin` once and lets it run: the loop, its chunked
dispatch and the device read that ends each chunk are the program's. The
benchmark only hangs an observer on `AnakinImpala.train_chunk`, the one
call that loop makes per chunk of `chunk_updates` updates: the time from
one entry to the next is a whole chunk (dispatch, the program's read of
its metrics, its log line), whatever the loop overlaps. The window is a
whole number of chunks: it opens at the entry after the warm chunks,
the first entry after `--seconds` closes it (the observer then raises
`_WindowClosed` through the program's loop), and the rate is taken over
all of them and all the time they took.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
TIMEOUT_S = 1100.0  # a run that has not ended by then has failed
WARM_CHUNKS = 2  # the second shows the steady state's host-side reads compiled
TRACE_AFTER_CHUNKS = 1  # chunks of the window before the profiler starts
TRACE_CHUNKS = 2  # chunks it covers


def run(ctx: dict) -> dict:
    import parentlib

    cfg = ctx["config"]
    out = ctx["out_dir"]
    section_name = cfg["section"]
    run_cfg = os.path.join(out, "config.json")
    with open(run_cfg, "w") as f:
        json.dump({section_name: cfg[section_name]}, f)
    log_path = os.path.join(out, "anakin.log")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             *parentlib.child_args(ctx, run_cfg, section_name)],
            cwd=ctx["root"], env=parentlib.child_env(ctx), stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True)
        try:
            rc = proc.wait(timeout=TIMEOUT_S)
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            if proc.poll() is None:
                proc.kill()
            proc.wait()
    if rc == 3:
        raise ctx["NoDevice"]("JAX found no device of the expected platform")
    result_path = os.path.join(out, "anakin_result.json")
    if rc != 0 or not os.path.exists(result_path):
        raise ctx["RunFailed"](f"anakin child ended with code {rc}; see {log_path}")
    with open(result_path) as f:
        res = json.load(f)

    window = res["t1"] - res["t0"]
    updates = res["updates"]
    problems = parentlib.common_problems(res, cfg, updates)
    if res["bad_updates"]:
        problems.append(f"{res['bad_updates']} updates with a non-finite loss "
                        f"or a gradient norm that is not positive")
    frames_per_update = res["num_envs"] * cfg[section_name].get("trajectory", 20)
    notes = [f"window {window:.3f} s, {updates} updates in "
             f"{len(res['chunk_seconds'])} chunks of "
             f"{res['chunk_updates']} (chunk seconds: "
             f"{[round(s, 4) for s in res['chunk_seconds']]}), "
             f"{res['num_envs']} envs, machine {res['machine']}, "
             f"device memory {res['memory_stats']}, "
             f"reference {res['reference']}"]
    notes += [f"NOT CORRECT: {p}" for p in problems]
    e2e = {"frames_learned_per_s": updates * frames_per_update / window,
           "setup_s": res["t0"] - ctx["t_start"]}
    facts = {**res, "window_s": window, "run_dir": out,
             "section": cfg[section_name], "chips": ctx["chips"],
             "learn_batch": res["num_envs"],
             "frames_per_update": frames_per_update}
    return {"device": {**res["device"],
                       "memory_peak_bytes": res["memory_peak_bytes"]},
            "correct": not problems, "attempted": updates,
            "failed": res["bad_updates"], "e2e": e2e, "facts": facts,
            "notes": notes}


# ------------------------------------------------------------------ child


class _WindowClosed(Exception):
    """Raised by the observer through the program's loop to end it."""


class _ChunkWatch:
    """Observer of `AnakinImpala.train_chunk` inside `train_anakin`."""

    def __init__(self, family, section: dict, seed: int, seconds: float,
                 tracer, clock, out: dict):
        import childlib

        self.childlib = childlib
        self.family, self.section, self.seed = family, section, seed
        self.seconds, self.tracer, self.clock, self.out = (
            seconds, tracer, clock, out)
        self.entries = 0
        self.last_entry = None
        self.chunk_seconds: list[float] = []
        self.chunk_updates: list[int] = []
        self.metrics: list = []  # each window chunk's, read after the window
        self.spans: list[tuple[str, float, float]] = []
        self.traced_updates = 0
        self.t_dispatched = None

    def attach(self, anakin) -> None:
        jitted = anakin.train_chunk

        def observed(state, updates):
            self._entry(anakin, jitted, state, updates)
            t_a = time.time()
            result = jitted(state, updates)
            self.t_dispatched = time.time()
            self.spans.append(("chunk_dispatch", t_a, self.t_dispatched))
            if self.entries <= WARM_CHUNKS:  # set-up: a chunk is in flight
                self.childlib.memory_peak_bytes()
            else:
                self.metrics.append(result[1])
                self.chunk_updates.append(updates)
                if self.tracer is not None and self.tracer.active:
                    self.traced_updates += updates
            return result

        anakin.train_chunk = observed

    def _entry(self, anakin, jitted, state, updates: int) -> None:
        """Before each dispatch; every earlier chunk has been read by
        the program's loop, so the device is at rest here."""
        childlib, out = self.childlib, self.out
        n, self.entries = self.entries, self.entries + 1
        if n == 0:  # set-up checks, on the state the program built
            out["num_envs"] = anakin.num_envs
            out["chunk_updates"] = updates
            out["reference"] = self.family.reference_check(
                anakin.agent, state.train, self.section, self.seed)
            print(f"[perfbench] reference check: {out['reference']}",
                  flush=True)
            out["kernels"] = childlib.kernels_in_lowered(
                jitted, state, static=(updates,))
        if n < WARM_CHUNKS:
            return
        if n == WARM_CHUNKS:  # the window opens
            self.before = childlib.param_fingerprint(state.train.params)
            self.setup = self.clock.snapshot()
            self.t0 = self.last_entry = time.time()
            return
        now = time.time()
        self.spans.append(("chunk_read_and_log", self.t_dispatched, now))
        self.chunk_seconds.append(now - self.last_entry)
        self.last_entry = now
        tracer = self.tracer
        if tracer is not None and tracer.active \
                and self.traced_updates >= TRACE_CHUNKS * updates:
            tracer.stop()
        if now - self.t0 >= self.seconds:
            self._close(state, now)
            raise _WindowClosed
        if tracer is not None and tracer.start_wall is None \
                and len(self.chunk_seconds) >= TRACE_AFTER_CHUNKS:
            tracer.start()

    def _close(self, state, t1: float) -> None:
        import numpy as np

        if self.tracer is not None and self.tracer.active:
            self.tracer.stop()
        in_window = self.clock.since(self.setup)
        after = self.childlib.param_fingerprint(state.train.params)
        bad = 0
        for m in self.metrics:
            loss = np.asarray(m["total_loss"])
            grad = np.asarray(m["grad_norm"])
            bad += int(np.sum(~np.isfinite(loss) | ~(grad > 0)))
        self.out.update({
            "t0": self.t0, "t1": t1, "updates": sum(self.chunk_updates),
            "chunk_seconds": self.chunk_seconds, "bad_updates": bad,
            "setup_monitoring": self.setup, "window_monitoring": in_window,
            "params_changed": bool(after != self.before
                                   and math.isfinite(after)),
            "memory_peak_bytes": self.childlib.memory_peak_bytes(),
            "memory_stats": self.childlib.memory_stats(),
            "trace_updates": self.traced_updates,
        })


def _child() -> int:
    import childlib
    import discover

    args = childlib.child_parser().parse_args()
    params = json.loads(args.params)
    if args.expect_platform == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")

    from distributed_reinforcement_learning_tpu.runtime import anakin, launch
    from distributed_reinforcement_learning_tpu.utils.device import (
        enable_compile_cache)

    enable_compile_cache()
    clock = childlib.CompileClock()
    device = childlib.open_chip("perfbench", args.expect_platform, args.chips)
    with open(args.config) as f:
        section = json.load(f)[args.section]
    algo, family = discover.family(args.data_dir, args.section, section)
    out: dict = {"device": device, "machine": childlib.machine_facts(),
                 "algorithm": algo}
    tracer = (childlib.TraceWindow(os.path.join(args.out, "profile"))
              if args.trace else None)
    watch = _ChunkWatch(family, section, args.seed, args.seconds, tracer,
                        clock, out)
    built = anakin.AnakinImpala.__init__

    def build_and_watch(self, *a, **kw):
        built(self, *a, **kw)
        watch.attach(self)

    anakin.AnakinImpala.__init__ = build_and_watch
    try:
        launch.train_anakin(args.config, args.section, num_updates=10 ** 9,
                            chunk=int(params["chunk_updates"]), seed=args.seed,
                            num_envs=int(params["num_envs"]))
    except _WindowClosed:
        pass
    finally:
        anakin.AnakinImpala.__init__ = built
    if "t1" not in out:
        print("[perfbench] train_anakin ended before the window closed",
              file=sys.stderr)
        return 1
    if tracer is not None:
        out["trace"] = tracer.reduce(device["platform"], args.chips,
                                     watch.spans, args.out)
        if out["trace"] is None:
            return 4
    childlib.write_result(args.out, "anakin_result.json", out)
    return 0


if __name__ == "__main__":
    sys.exit(_child())

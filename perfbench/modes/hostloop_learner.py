"""The learner process of a host-loop cell: the program's own
`run_role(mode="learner")` and its own `transport._learner_loop`, run
to the end of the window, with the benchmark watching.

`run_role` builds the learner exactly as the launchers do (queue,
weight store and board, replay service, transport server, telemetry)
and then calls `transport._learner_loop`. This wrapper stands between
the two, in this process only: `_bench_loop` does the set-up checks,
hangs an observer on the ONE method of the learner that the program's
loop calls per update (the family file names it), and then calls the
ORIGINAL loop once, with the caller's own `num_updates`. The loop is the
program's: whatever it overlaps, batches or reorders shows in the
stamps. The observer only looks — after each return of that method it
reads `learner.train_steps` and the clock, walks warm-up -> window, and
starts and stops the profiler between updates — and when the window
has closed it raises `_WindowClosed`, which `_bench_loop` catches, so
that `run_role`'s own `finally` block stops the prefetcher, closes the
queue, server and board and flushes telemetry, as at the end of any
training run. No file of the program is edited.

Writes `<out>/learner_result.json`; the parent (`modes/hostloop.py`)
turns it into metrics. Exit code 3: JAX found no device of the
expected platform (nothing is run, nothing is written).
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

# PYTHONPATH (set by modes/hostloop.py) holds the repo's root and the
# benchmark's directory: the program and childlib are imported from there.

# After the warm updates the window waits for the queue's backlog to
# drain and then for SETTLE_UPDATES more: the first of those takes the
# batch the prefetcher already holds, the next ones wait for the actors,
# as every update does in the steady state. (Opened earlier, the window
# drains ~1.5 batches that the actors queued while the learner compiled
# and reads learned 4-10 % above collected: my chip run, PR 23.)
SETTLE_UPDATES = 2


class _WindowClosed(Exception):
    """Raised by the observer through the program's loop to end it."""


class _Observer:
    """Called after every return of the learner's per-update method.
    Phases: `warm` (the traffic file's `warm_updates`, less one) ->
    `drain` (until no whole batch is queued, at most the queue's
    capacity in batches) -> `settle` (SETTLE_UPDATES, then the update
    whose end OPENS the window) -> `window` (closes at the end of the
    first update that ends after `--seconds`): a whole number of
    updates over exactly the time they took, so that a host loop that
    completes an update every two seconds is not read to the nearest
    update."""

    def __init__(self, learner, rt, params: dict, seconds: float, tracer,
                 clock, out: dict):
        import childlib

        self.childlib = childlib
        self.learner, self.rt, self.out = learner, rt, out
        self.seconds, self.tracer, self.clock = seconds, tracer, clock
        self.warm = int(params["warm_updates"])
        self.trace_at = float(params["trace_start_s"])
        self.trace_for = float(params["trace_seconds"])
        self.phase = "warm"
        self.seen = learner.train_steps
        self.t_begin = time.time()
        self.drained = 0
        self.settle_left = SETTLE_UPDATES + 1
        self.update_times: list[float] = []

    def _backlog(self) -> bool:
        size = getattr(getattr(self.learner, "queue", None), "size", None)
        return (callable(size) and size() >= self.rt.batch_size
                and self.drained < self.rt.queue_size // self.rt.batch_size + 2)

    def after_call(self) -> None:
        import jax

        learner = self.learner
        if learner.train_steps == self.seen:
            return  # the call completed no update (timeout, replay not warm)
        self.seen = learner.train_steps
        now = time.time()
        if self.phase == "warm":
            if self.seen < self.warm - 1:
                return
            self.phase = "drain"
        if self.phase == "drain":
            if self._backlog():
                self.drained += 1
                return
            self.phase = "settle"
            return
        if self.phase == "settle":
            self.settle_left -= 1
            if self.settle_left == 1:  # before the last warm update
                jax.block_until_ready(learner.state.params)
                self.before = self.childlib.param_fingerprint(
                    learner.state.params)
            elif self.settle_left == 0:  # its end opens the window
                self.t0 = now
                self.step0 = self.seen
                self.setup = self.clock.snapshot()
                self.phase = "window"
                print(f"[perfbench] {self.seen} warm updates in "
                      f"{now - self.t_begin:.1f}s", flush=True)
            return
        # -- the measured window ---------------------------------------
        self.update_times.append(now)
        tracer = self.tracer
        if now >= self.t0 + self.seconds:
            self._close()
            raise _WindowClosed
        if tracer is not None:
            if tracer.start_wall is None and now - self.t0 >= self.trace_at:
                tracer.start()
            elif tracer.active and now - tracer.start_wall >= self.trace_for:
                jax.block_until_ready(learner.state.params)
                tracer.stop()

    def _close(self) -> None:
        import jax

        learner = self.learner
        jax.block_until_ready(learner.state.params)
        t1 = time.time()
        if self.tracer is not None and self.tracer.active:
            self.tracer.stop()
        after = self.childlib.param_fingerprint(learner.state.params)
        self.out.update({
            "t0": self.t0, "t1": t1, "step0": self.step0, "step1": self.seen,
            "update_times": self.update_times,
            "setup_monitoring": self.setup,
            "window_monitoring": self.clock.since(self.setup),
            "params_changed": bool(after != self.before
                                   and math.isfinite(after)),
            "memory_peak_bytes": self.childlib.memory_peak_bytes(),
            "publish_interval": getattr(learner, "publish_interval", None),
            "replay": type(learner._active_replay()).__name__
            if hasattr(learner, "_active_replay") else None,
        })


def main() -> int:
    import childlib
    import discover

    args = childlib.child_parser().parse_args()
    params = json.loads(args.params)

    if args.expect_platform == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")

    from distributed_reinforcement_learning_tpu.runtime import transport
    from distributed_reinforcement_learning_tpu.utils.config import load_config
    from distributed_reinforcement_learning_tpu.utils.device import (
        enable_compile_cache)

    enable_compile_cache()
    clock = childlib.CompileClock()
    device = childlib.open_chip("perfbench", args.expect_platform, args.chips)
    _, rt = load_config(args.config, args.section)
    with open(args.config) as f:
        section = json.load(f)[args.section]
    algo, family = discover.family(args.data_dir, args.section, section)
    original_loop = transport._learner_loop
    out: dict = {"device": device, "algorithm": algo,
                 "machine": childlib.machine_facts()}
    kept: dict = {}

    def _bench_loop(algo_, learner, *loop_args, **loop_kw) -> None:
        kept["learner"] = learner
        # -- set-up checks, outside the window -------------------------
        out["reference"] = family.reference_check(
            learner.agent, learner.state, section, args.seed)
        print(f"[perfbench] reference check: {out['reference']}", flush=True)
        out["kernels"] = family.learn_step_kernels(
            learner.agent, learner.state, section)
        tracer = (childlib.TraceWindow(os.path.join(args.out, "profile"))
                  if args.trace else None)
        kept["tracer"] = tracer
        observer = _Observer(learner, rt, params, args.seconds, tracer,
                             clock, out)
        update = getattr(learner, family.UPDATE_METHOD)

        def observed(*a, **kw):
            result = update(*a, **kw)
            observer.after_call()
            return result

        setattr(learner, family.UPDATE_METHOD, observed)
        try:
            original_loop(algo_, learner, *loop_args, **loop_kw)
        except _WindowClosed:
            pass  # return: run_role's finally block is the learner's exit path
        finally:
            delattr(learner, family.UPDATE_METHOD)

    transport._learner_loop = _bench_loop
    os.makedirs(args.out, exist_ok=True)
    transport.run_role(algo, args.config, args.section, "learner", 0,
                       num_updates=10 ** 9, run_dir=args.out, seed=args.seed)
    if "t1" not in out:
        print("[perfbench] the program's loop ended before the window closed",
              file=sys.stderr)
        return 1
    kept["learner"].logger.flush()  # metrics.jsonl is block-buffered

    tracer = kept["tracer"]
    if tracer is not None:
        import telemetry_read

        out["trace"] = tracer.reduce(
            device["platform"], args.chips, telemetry_read.host_spans(
                os.path.join(args.out, "telemetry", "trace-learner-0.json")),
            args.out)
        if out["trace"] is None:
            return 4
    childlib.write_result(args.out, "learner_result.json", out)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Profiling: per-stage learner timing + JAX device-trace capture.

The reference's only performance signal is one wall-clock delta per train
step logged as `data/time` (`train_impala.py:99,113` — SURVEY §5.1). Here
profiling is first-class:

- `StageTimer`: named host-side stages (dequeue / learn / publish / ...)
  accumulated per train step and emitted through the MetricsLogger as
  `profile/<stage>_ms` means every `log_every` steps. This splits "the
  step took 40ms" into queue-wait vs device-compute vs weight-publication
  — the split that tells you whether the data plane or the chip is the
  bottleneck (SURVEY §7 hard part (a)). Every stage invocation is also
  a `chip_span`: an event beside the device ops in any live
  `jax.profiler` trace and, when the run-wide telemetry is enabled
  (observability/), a span on the process's Chrome-trace timeline — the
  TIMELINE the means cannot show (one 400 ms publish stall vs "publish
  averaged 3 ms").
- `ProfilerSession`: captures a real `jax.profiler` device trace (XLA op
  timeline with the scope names of observability/scopes.py, viewable in
  TensorBoard/Perfetto) for a configured window of train steps — of the
  host-loop learners and of the fused loops (one `on_step` per chunk).
  Enabled via env vars so any launcher/run picks it up:
      DRL_PROFILE_DIR=/tmp/trace DRL_PROFILE_START=50 DRL_PROFILE_STEPS=5
  `close()` then writes `scope_ledger.json` beside the profile and prints
  it: the device's self time per scope, the ops the compiler made
  resolved to the scope they serve (observability/attribution.py).
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Iterator

from distributed_reinforcement_learning_tpu.observability import TELEMETRY as _OBS
from distributed_reinforcement_learning_tpu.observability import chip_span
from distributed_reinforcement_learning_tpu.utils.environ import env_int
from distributed_reinforcement_learning_tpu.utils.logger import MetricsLogger


class StageTimer:
    """Accumulates wall-clock per named stage; logs means periodically.

    Usage in a learner loop:
        with timer.stage("dequeue"): batch = queue.get_batch(...)
        with timer.stage("learn"):   state, m = agent.learn(...)
        timer.step_done(train_steps)
    """

    def __init__(
        self,
        logger: MetricsLogger | None = None,
        prefix: str = "profile/",
        log_every: int = 100,
    ):
        self.logger = logger
        self.prefix = prefix
        self.log_every = log_every
        self._sums: dict[str, float] = {}
        self._counts: dict[str, int] = {}
        self._steps = 0
        self.last_means_ms: dict[str, float] = {}

    def reset(self) -> None:
        """Drop accumulated sums (e.g. to exclude a warm-up/compile step)."""
        self._sums.clear()
        self._counts.clear()
        self._steps = 0

    @contextlib.contextmanager
    def stage(self, name: str) -> Iterator[None]:
        # Trace handle read once: with telemetry off the span is only the
        # profiler's annotation (inert unless a profile is being taken).
        t0 = time.perf_counter()
        try:
            with chip_span(name, _OBS.trace):
                yield
        finally:
            dt = time.perf_counter() - t0
            self._sums[name] = self._sums.get(name, 0.0) + dt
            self._counts[name] = self._counts.get(name, 0) + 1

    def step_done(self, step: int) -> None:
        """Mark one train step; every `log_every` steps emit + reset means.

        Means are per stage INVOCATION, not per train step: replay-path
        learners run many ingest stages before their first train step
        (warm-up gate), and a per-step divisor would smear that warm-up
        into a wildly inflated first flush.
        """
        self._steps += 1
        if self._steps < self.log_every:
            return
        self.last_means_ms = {
            name: 1e3 * total / self._counts[name] for name, total in self._sums.items()
        }
        if self.logger is not None:
            self.logger.add_scalars(
                {f"{self.prefix}{n}_ms": ms for n, ms in self.last_means_ms.items()},
                step,
            )
        self._sums.clear()
        self._counts.clear()
        self._steps = 0


class ProfilerSession:
    """Window-triggered `jax.profiler` trace around train steps.

    `on_step(step)` is called once per train step; the trace starts when
    `step` reaches `start_step` and stops `num_steps` later (or at
    `close()`, whichever comes first). Inactive (no-op) unless `out_dir`
    is set, so learners can call it unconditionally.
    """

    def __init__(self, out_dir: str | None, start_step: int = 10, num_steps: int = 5):
        self.out_dir = out_dir
        self.start_step = start_step
        self.num_steps = num_steps
        self._active = False
        self._done = out_dir is None
        self._traced = False

    @classmethod
    def from_env(cls) -> "ProfilerSession":
        """DRL_PROFILE_DIR / DRL_PROFILE_START / DRL_PROFILE_STEPS."""
        return cls(
            os.environ.get("DRL_PROFILE_DIR") or None,
            start_step=env_int("DRL_PROFILE_START", 10),
            num_steps=env_int("DRL_PROFILE_STEPS", 5),
        )

    def on_step(self, step: int) -> None:
        if self._done:
            return
        if not self._active and step >= self.start_step:
            import jax

            # No Python tracer: it slows the loop the trace is there to
            # observe and makes stop_trace hold the thread for seconds.
            # Device planes, chip_span annotations and the runtime's own
            # host events need only the host tracer.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.out_dir, profiler_options=options)
            self._active = True
            self._stop_at = step + self.num_steps
        elif self._active and step >= self._stop_at:
            self._stop()

    def _stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self._active = False
        self._done = True
        self._traced = True
        print(f"[profiler] device trace written to {self.out_dir}", flush=True)

    def close(self) -> None:
        """Stop a trace still running, then (once, after the loop: it
        takes seconds) write the profile's scope ledger beside it."""
        if self._active:
            self._stop()
        if self._traced:
            self._traced = False
            self._write_ledger()

    def _write_ledger(self) -> None:
        # imported here: a run without DRL_PROFILE_DIR loads neither the
        # resolver nor xprof
        from distributed_reinforcement_learning_tpu.observability import attribution

        try:
            led = attribution.ledger(self.out_dir, attribution.program_vocabulary())
        except Exception as e:  # noqa: BLE001 - a report must not end a run
            print(f"[profiler] no scope ledger: {type(e).__name__}: {e}", flush=True)
            return
        if led is None:  # no device op line (a CPU run)
            print("[profiler] no scope ledger: the profile holds no device op",
                  flush=True)
            return
        path = attribution.write(led, self.out_dir)
        print(f"[profiler] scope ledger written to {path}\n"
              f"{attribution.table(led)}", flush=True)

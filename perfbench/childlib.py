"""What the process that owns the chip needs, whichever mode it runs:
the set-up clock, the device line, the traced interval, and the
tolerance of the comparison with the plain reference.

Imported only by the children that `modes/*.py` start — never by
`run.py`'s parent process, which must stay off JAX (a chip belongs to
one process at a time).
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import trace_reduce  # noqa: E402

EXIT_WRONG_PLATFORM = 3

# Program-vs-reference tolerance, relative (see `close`); each family's
# `reference_check` gives the reason for the magnitude it is held to.
LOSS_RTOL = 1e-4


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or reading the
    persistent cache) in this process, and how many such events there
    were, from JAX's own monitoring events. Copied from `chip_smoke.py`
    (PR 21, proven on the chip); `events` was added to count
    compilations inside the measured window."""

    DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self._lock = threading.Lock()  # compiles happen on worker threads too
        self.seconds = {k: 0.0 for k in self.DURATIONS}
        self.events = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if event in self.seconds:
            with self._lock:
                self.seconds[event] += seconds
                self.events += 1

    def _on_event(self, event: str, **_) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"seconds": dict(self.seconds), "events": self.events,
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses}

    def since(self, earlier: dict) -> dict:
        """Events and cache misses since the snapshot `earlier`."""
        now = self.snapshot()
        return {k: now[k] - earlier[k] for k in ("events", "cache_misses")}


def child_parser() -> argparse.ArgumentParser:
    """The arguments `parentlib.child_args` passes to a mode's child."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--section", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--params", required=True, help="JSON: the traffic file")
    ap.add_argument("--expect-platform", default="tpu")
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--data-dir", required=True)
    return ap


def open_chip(role: str, expect_platform: str, chips: int) -> dict:
    """Open the backend through the program's own `open_devices`; exit
    with `EXIT_WRONG_PLATFORM` (and no result) unless JAX found
    `chips` devices of `expect_platform`."""
    from distributed_reinforcement_learning_tpu.utils.device import open_devices

    device = open_devices(role)
    if device["platform"] != expect_platform or device["count"] < chips:
        print(f"[perfbench] JAX found {device}; the cell needs {chips} "
              f"{expect_platform} device(s): nothing is run", file=sys.stderr)
        sys.exit(EXIT_WRONG_PLATFORM)
    return device


_MEMORY_PEAK = 0


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device, as far as the
    backend tells (0 where it reports nothing, as XLA:CPU does). The
    TPU's `peak_bytes_in_use` counts arrays only: the scratch memory of
    the loaded programs stands apart under `bytes_reserved` (seen on the
    chip: a program with 1,610,677,248 B of temporaries moved
    `bytes_reserved` by 1,610,645,504 and `peak_bytes_in_use` by
    nothing). So each call reads arrays + scratch as they are held at
    that moment, and the largest reading of the process is returned:
    call it once while a step of the window's shape is in flight and
    once when the window closes."""
    global _MEMORY_PEAK
    import jax

    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        held = (int(stats.get("bytes_in_use", 0))
                + int(stats.get("bytes_reserved", 0)))
        _MEMORY_PEAK = max(_MEMORY_PEAK, held,
                           int(stats.get("peak_bytes_in_use", 0)))
    return _MEMORY_PEAK


def memory_stats() -> dict:
    """The first device's memory counters as the backend gives them,
    for an earlier line of the run."""
    import jax

    return dict(jax.local_devices()[0].memory_stats() or {})


def machine_facts() -> dict:
    """The host as this run saw it: CPU actors pace two of the cells."""
    ram = None
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    ram = int(line.split()[1]) * 1024
    except OSError:
        pass
    affinity = (len(os.sched_getaffinity(0))
                if hasattr(os, "sched_getaffinity") else None)
    return {"cpu_count": os.cpu_count(), "cpu_affinity": affinity,
            "ram_bytes": ram}


class TraceWindow:
    """One `jax.profiler` trace over part of the measured window, with
    the harness's mark dropped at its start so that the trace's clock
    can be tied to the wall clock of the host spans."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.start_wall = self.stop_wall = self.mark_wall = None
        self.active = False

    def start(self) -> None:
        import jax

        # No Python tracer: it slows the host loop that the trace is
        # there to observe, and stop_trace then takes seconds. The
        # harness's mark and the device planes need only the host tracer.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        self.active = True
        self.start_wall = time.time()
        self.mark_wall = time.time()
        with jax.profiler.TraceAnnotation(trace_reduce.MARK):
            pass

    def stop(self) -> None:
        import jax

        self.stop_wall = time.time()
        jax.profiler.stop_trace()
        self.active = False

    @property
    def window_s(self) -> float:
        return self.stop_wall - self.start_wall

    def reduce(self, platform: str, chips: int, host_spans: list,
               out_dir: str) -> dict | None:
        """-> {"busy_s", "window_s", "breakdown", "op_totals", "details",
        "inventory", "start_wall", "stop_wall"} and the first events in
        `<out_dir>/trace_events.json` (what a test fixture is cut from);
        None, with the reason on stderr, where the trace cannot give a
        sound number — the run must then fail, not print."""
        try:
            paths = glob.glob(os.path.join(self.out_dir, "plugins", "profile",
                                           "*", "*.xplane.pb"))
            if not paths:
                raise trace_reduce.TraceError(
                    f"the profiler wrote no .xplane.pb under {self.out_dir}")
            trace = trace_reduce.read_xplane(sorted(paths)[-1], platform)
            busy = trace_reduce.device_busy(trace, self.window_s, chips)
            totals = trace_reduce.op_totals(trace)
        except trace_reduce.TraceError as e:
            print(f"[perfbench] TRACE REDUCTION FAILED: {e}", file=sys.stderr)
            return None
        trace_reduce.save(trace_reduce.trim_for_fixture(trace, 20_000),
                          os.path.join(out_dir, "trace_events.json"))
        return {**busy, "op_totals": totals, "details": trace.details,
                "breakdown": {
                    "device_ops": trace_reduce.top_ops(totals),
                    "idle_gaps": trace_reduce.idle_gaps(trace, host_spans,
                                                        self.mark_wall)},
                "inventory": trace_reduce.lines_inventory(trace),
                "start_wall": self.start_wall, "stop_wall": self.stop_wall}


def write_result(out_dir: str, name: str, result: dict) -> None:
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(result, f)


def kernels_in_lowered(jitted, *args, static: tuple = ()) -> int:
    """`tpu_custom_call`s (Mosaic kernels) in `jitted` lowered for the
    shapes of `args`. An interpret-mode or reference fall-back lowers
    to plain HLO and is not counted."""
    import jax

    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
    text = jitted.lower(*shapes, *static).as_text()
    return len(re.findall(r"tpu_custom_call", text))


def close(got: float, want: float, magnitude: float = 0.0) -> bool:
    """`got` within LOSS_RTOL of `want`, relative to max(1, |want|,
    `magnitude`): a family's `reference_check` names the magnitude where
    the quantity is a sum of terms that cancel."""
    return (math.isfinite(got) and abs(got - want)
            <= LOSS_RTOL * max(1.0, abs(want), magnitude))


_FINGERPRINT = None


def param_fingerprint(params) -> float:
    """A number that moves when any parameter does (sum of |x| over all
    leaves, one jitted call): read before and after the window."""
    global _FINGERPRINT
    import jax
    import jax.numpy as jnp

    if _FINGERPRINT is None:
        _FINGERPRINT = jax.jit(lambda p: sum(
            jnp.sum(jnp.abs(x.astype(jnp.float32)))
            for x in jax.tree.leaves(p)))
    return float(_FINGERPRINT(params))

"""Process start-up on a device: where compiled programs are kept, and
which device this process opened.

Two calls, both made once per process by every entry point (the five
`train_*.py` launchers, `chip_smoke.py`, the profiling
scripts):

- `enable_compile_cache()` before the first jit. A cold IMPALA learn
  step takes 15-18 s to compile for a v5e (ahead-of-time compile, PR 21;
  no chip time involved), a cluster run compiles act / learn / the
  weight snapshot in several processes, and a machine that is thrown
  away after every command starts cold — so the cache directory has to
  be placeable from OUTSIDE the program (`JAX_COMPILATION_CACHE_DIR`)
  and otherwise sit at one fixed path: the path is part of JAX's cache
  key, so a directory that moves (tempfile, pid, time) never hits.
- `open_devices(role)` as the process's FIRST touch of the backend: it
  opens the devices under a deadline and prints one line saying where
  this process runs, so every log names its platform and a quiet
  fall-back to another backend cannot pass for a chip run. It is also
  where the process's record of its start (`observability.trace
  .HOST_RECORD`) starts listening, and the span `start/backend`.
"""

from __future__ import annotations

import faulthandler
import os
import sys
from pathlib import Path

import jax

from distributed_reinforcement_learning_tpu.observability import (
    TELEMETRY,
    chip_span,
    scopes,
)
from distributed_reinforcement_learning_tpu.observability.trace import HOST_RECORD

# <repo>/.jax_cache — in .gitignore and .chiprunignore.
_DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# A chip belongs to one process at a time. A second process that asks
# for a held chip either gets JAX's own "Unable to initialize backend"
# error or blocks inside the runtime, where no Python exception can
# reach it. Backend start-up on a free chip takes about 15 s.
_OPEN_DEADLINE_S = 180


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; -> its directory.

    `JAX_COMPILATION_CACHE_DIR` set: JAX reads it by itself and nothing
    is configured here. Unset: the cache lives at `<repo>/.jax_cache`.
    Learner, actors and replicas of one run call this with the same
    environment, so they share one directory either way.
    `JAX_ENABLE_COMPILATION_CACHE=0` (tests/conftest.py) keeps the
    cache off wherever it points.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_CACHE_DIR))
    return str(_DEFAULT_CACHE_DIR)


def open_devices(role: str) -> dict:
    """Open this process's backend, print `[<role>] device: {...}` and
    return that `{"platform", "kind", "count"}`, as JAX reports them —
    the `device` object of chip_smoke.py's and the benchmark's lines.

    Call it where the process would first touch JAX (after
    `jax.distributed.initialize` in a multi-host learner). If the
    backend cannot be opened within `_OPEN_DEADLINE_S` — another
    process on this host holds the chip — the process dumps its stacks
    and exits 1 instead of hanging its launcher forever."""
    # sys.__stderr__: the watchdog writes from C to a file descriptor,
    # which a replaced sys.stderr (a test's capture, a logger) lacks.
    HOST_RECORD.begin()  # before anything can compile
    faulthandler.dump_traceback_later(_OPEN_DEADLINE_S, exit=True,
                                      file=sys.__stderr__)
    try:
        with chip_span(scopes.START_BACKEND, TELEMETRY.trace):
            devices = jax.devices()
    finally:
        faulthandler.cancel_dump_traceback_later()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    print(f"[{role}] device: {info}", file=sys.stderr, flush=True)
    return info

"""Chrome-trace/Perfetto span emitter: a host-side timeline per process.

The learner's `StageTimer` already measures dequeue/learn/publish, but it
reduces everything to windowed means — "publish averaged 3 ms" cannot
show the one 400 ms stall that starved the chip. A `TraceEmitter`
records every stage invocation as a complete-duration event (`ph: "X"`)
in the Trace Event Format, so `trace-<role>-<rank>.json` opens directly
in Perfetto (ui.perfetto.dev) or chrome://tracing. A process that owns
a chip opens its spans through `chip_span`, which also puts them on the
host plane of any live `jax.profiler` trace (`ProfilerSession`, the
benchmark's traced run): host and device on one clock, in one file.

Timestamps are wall-clock epoch microseconds (not perf_counter): spans
from different PROCESSES of one run then align on a shared axis, which
is what makes the merged cross-role trace of `scripts/obs_report.py`
meaningful (actor enqueue stalls visibly overlapping learner queue
waits). Durations come from `perf_counter` deltas, so they stay
monotonic even if the wall clock steps.

The file is streamed: events append as a JSON array that `close()`
terminates, so a crashed process still leaves a loadable trace
(`load_trace` tolerates the missing `]`; a clean close writes strictly
valid JSON). A bounded event cap (`DRL_TRACE_MAX_EVENTS`) keeps a
long run from growing the trace without limit — past it, new events are
counted as dropped, not stored.

`HostRecord` (the process's `HOST_RECORD`) is the same spans kept in
memory, always: what `chip_span` opens, JAX's own trace / lower /
compile events as wall-clock intervals, the persistent cache's counts
and the collector's passes, from the process's first instruction. The
fused launchers print it in their log (runtime/launch.py: one line when
the start closes, one line a chunk), so a run with no telemetry and no
profile still says where its start's seconds and a held chunk's went.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import gc
import json
import os
import threading
import time
from typing import Iterator

from distributed_reinforcement_learning_tpu.observability import scopes
from distributed_reinforcement_learning_tpu.utils.environ import env_int

DEFAULT_MAX_EVENTS = 100_000


class TraceEmitter:
    """Buffered Chrome-trace writer for one process's host spans."""

    # Concurrency map (tools/drlint lock-discipline): every span
    # emitter shares the buffer with the telemetry flush thread; all
    # five fields only move under `_lock` (emit/flush/close).
    _GUARDED_BY = {
        "dropped": "_lock",
        "_pending": "_lock",
        "_written": "_lock",
        "_file": "_lock",
        "_closed": "_lock",
    }

    def __init__(
        self,
        path: str,
        label: str,
        pid: int | None = None,
        max_events: int | None = None,
    ):
        self.path = path
        self.label = label
        self.pid = os.getpid() if pid is None else pid
        if max_events is None:
            max_events = env_int("DRL_TRACE_MAX_EVENTS", DEFAULT_MAX_EVENTS)
        self.max_events = max_events
        self.dropped = 0
        self._lock = threading.Lock()
        self._pending: list[dict] = []
        self._written = 0
        self._file = None
        self._closed = False

    def emit(self, name: str, wall_start_s: float, duration_s: float,
             tid: int | None = None, args: dict | None = None) -> None:
        """Record one complete span (start wall-clock seconds + duration)."""
        event = {
            "name": name,
            "ph": "X",
            "ts": round(wall_start_s * 1e6, 1),
            "dur": round(duration_s * 1e6, 1),
            "pid": self.pid,
            "tid": tid if tid is not None else threading.get_ident(),
            "cat": "host",
        }
        if args:
            event["args"] = args
        with self._lock:
            if self._closed or self._written + len(self._pending) >= self.max_events:
                self.dropped += 1
                return
            self._pending.append(event)

    @contextlib.contextmanager
    def span(self, name: str, args: dict | None = None) -> Iterator[None]:
        wall = time.time()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.emit(name, wall, time.perf_counter() - t0, args=args)

    def _open(self):
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        f = open(self.path, "w")
        f.write("[\n")
        # Process metadata so viewers label the track by role, not pid.
        f.write(json.dumps({"ph": "M", "name": "process_name", "pid": self.pid,
                            "tid": 0, "args": {"name": self.label}}))
        return f

    def flush(self) -> None:
        """Append pending events to the on-disk (still-open) JSON array."""
        with self._lock:
            if self._closed or not self._pending:
                return
            if self._file is None:
                self._file = self._open()
            for event in self._pending:
                self._file.write(",\n" + json.dumps(event))
            self._written += len(self._pending)
            self._pending.clear()
            self._file.flush()

    def close(self) -> None:
        """Terminate the array: the file becomes strictly valid JSON."""
        self.flush()
        with self._lock:
            if self._closed:
                return
            self._closed = True
            if self._file is None:
                self._file = self._open()
            if self.dropped:
                self._file.write(",\n" + json.dumps(
                    {"ph": "M", "name": "trace_dropped_events", "pid": self.pid,
                     "tid": 0, "args": {"dropped": self.dropped}}))
            self._file.write("\n]\n")
            self._file.close()
            self._file = None


def process_start_wall() -> float:
    """The wall-clock second at which the kernel started this process:
    its `starttime` (`/proc/self/stat`, ticks of 10 ms since boot) against
    `CLOCK_BOOTTIME`. Now, where the kernel does not say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return time.time()
    return time.time() - age


def merge_intervals(intervals) -> list[tuple]:
    """`(start, end, ...)` tuples -> their union, sorted and disjoint; a
    merged interval keeps the rest of the tuple that starts it (of equal
    starts, the longest): the outermost event's."""
    out: list[list] = []
    for iv in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        if out and iv[0] <= out[-1][1]:
            out[-1][1] = max(out[-1][1], iv[1])
        else:
            out.append(list(iv))
    return [tuple(iv) for iv in out]


def split_by_first(a: float, b: float, layers: list[tuple]) -> dict:
    """Seconds of `[a, b]` by the FIRST of `layers` (`(name, [(start,
    end, ...), ...])`, in order of precedence) that covers each instant;
    `other` is what none covers. The values sum to `b - a`."""
    merged = [(name, merge_intervals(iv)) for name, iv in layers]
    edges = sorted({a, b, *(min(max(t, a), b) for _, ivs in merged
                            for iv in ivs for t in iv[:2])})
    out = {name: 0.0 for name, _ in merged}
    out["other"] = 0.0
    for lo, hi in zip(edges, edges[1:]):
        mid = (lo + hi) / 2
        for name, ivs in merged:
            i = bisect.bisect_right(ivs, (mid, float("inf"))) - 1
            if i >= 0 and ivs[i][1] > mid:
                out[name] += hi - lo
                break
        else:
            out["other"] += hi - lo
    return out


class HostRecord:
    """One process's host spans, compile events and collector passes, in
    memory, on the wall clock, from the process's start. Always on.

    - spans: `(name, parent, wall_start_s, duration_s)`, start from
      `time.time()`, duration from `perf_counter` (as `TraceEmitter.span`),
      fed by `chip_span`. All spans of the START are kept, then a ring of
      the newest chunks (`end_chunk`, one call a chunk of a fused loop).
    - JAX's trace / lower / compile events as INTERVALS on the same
      clock (`jax.monitoring`'s time-span listener hands each event's
      start and end on `time.time()` and the `fun_name` it was for). The
      events nest (one `jaxpr_trace_duration` a traced function), so a
      kind's seconds are the union of its intervals, not their sum.
    - the persistent cache's hits, misses and retrieval seconds.
    - the collector's passes and seconds by generation, through
      `gc.callbacks`, until the start closes.

    The start CLOSES at the end of the first chunk during which no
    compile event arrived. The listeners stay after that (they are called
    only when something compiles, which a steady loop never does), so a
    chunk that recompiled says so in its own line; the collector's
    callback goes. A process that runs no fused loop never closes its
    start: every list here is bounded for it too.
    """

    KINDS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
             "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
             "/jax/core/compile/backend_compile_duration": "compile"}
    CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                    "/jax/compilation_cache/cache_misses": "misses"}
    START_SPANS_MAX = 512
    CHUNK_RING = 64
    INTERVALS_MAX = 4096  # merged intervals kept a kind
    COMPACT_EVERY = 1024  # raw events between two merges
    GC_SECONDS_MAX = 4096  # the collector's callback goes after that
    TOP_FUNS = 5

    # Spans and compile events arrive from any thread (a learner's stage
    # threads, JAX's compile workers).
    _GUARDED_BY = {
        "spans": "_lock", "chunks": "_lock", "closed_at": "_lock",
        "cache": "_lock", "events": "_lock", "_pending": "_lock",
        "_intervals": "_lock", "_raw": "_lock", "_in_chunk": "_lock",
        "_chunk_n": "_lock", "_listening": "_lock",
    }
    # The collector's callback takes no lock: a pass can start inside a
    # locked region of the same thread. Passes do not overlap.
    _NOT_GUARDED = {
        "gc_passes": "written by the collector's callback alone",
        "gc_seconds": "written by the collector's callback alone",
        "gc_by_second": "written by the collector's callback alone",
        "_gc_t0": "written by the collector's callback alone",
    }

    def __init__(self, process_start: float | None = None):
        self.pid = os.getpid()
        self.process_start = (process_start_wall() if process_start is None
                              else process_start)
        self._lock = threading.Lock()
        self._open = threading.local()  # .stack: names of this thread's open spans
        self.spans: list[tuple] = []  # the start's
        self.chunks: collections.deque = collections.deque(maxlen=self.CHUNK_RING)
        self.closed_at: float | None = None
        self.cache = {"hits": 0, "misses": 0, "retrieval_s": 0.0}
        self.events = {k: 0 for k in self.KINDS.values()}
        self._pending: collections.deque = collections.deque(maxlen=self.CHUNK_RING)
        self._intervals: dict[str, list] = {k: [] for k in self.KINDS.values()}
        self._raw = 0
        self._in_chunk: dict[str, list] = {}  # kind -> [events, longest s, its fun_name]
        self._chunk_n = 0
        self._listening = False
        self.gc_passes = [0, 0, 0]
        self.gc_seconds = [0.0, 0.0, 0.0]
        self.gc_by_second: list[float] = []  # index: whole seconds since process_start
        self._gc_t0 = 0.0

    # -- spans ----------------------------------------------------------------

    def enter(self, name: str) -> str | None:
        """A span opens on this thread; -> its parent's name."""
        stack = self._open.__dict__.setdefault("stack", [])
        parent = stack[-1] if stack else None
        stack.append(name)
        return parent

    def leave(self, name: str, parent: str | None, wall_start_s: float,
              duration_s: float) -> None:
        self._open.stack.pop()
        self.add(name, parent, wall_start_s, duration_s)

    def add(self, name: str, parent: str | None, wall_start_s: float,
            duration_s: float) -> None:
        """Record one complete span."""
        span = (name, parent, wall_start_s, duration_s)
        with self._lock:
            if self.closed_at is None and len(self.spans) < self.START_SPANS_MAX:
                self.spans.append(span)
            self._pending.append(span)

    # -- JAX's events and the collector ---------------------------------------

    def begin(self) -> None:
        """Start listening to JAX's compile events and to the collector:
        `utils/device.open_devices` calls this before the backend opens,
        so no compile precedes it. A second call does nothing."""
        with self._lock:
            if self._listening:
                return
            self._listening = True
        from jax import monitoring

        monitoring.register_event_time_span_listener(self._on_time_span)
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        gc.callbacks.append(self._on_gc)

    def _on_time_span(self, event: str, start_time: float, end_time: float,
                      fun_name: str = "", **_) -> None:
        kind = self.KINDS.get(event)
        if kind is None:
            return
        seconds = end_time - start_time
        with self._lock:
            self.events[kind] += 1
            # nested events end before the one that holds them: a chunk's
            # longest is its outermost, and names what compiled again
            tally = self._in_chunk.setdefault(kind, [0, 0.0, ""])
            tally[0] += 1
            if seconds >= tally[1]:
                tally[1:] = seconds, str(fun_name)
            kept = self._intervals[kind]
            kept.append((start_time, end_time, str(fun_name)))
            self._raw += 1
            if self._raw >= self.COMPACT_EVERY:
                self._compact_locked()

    def _compact_locked(self) -> None:
        self._raw = 0
        for kind, kept in self._intervals.items():
            self._intervals[kind] = merge_intervals(kept)[-self.INTERVALS_MAX:]

    def _on_event(self, event: str, **_) -> None:
        counted = self.CACHE_EVENTS.get(event)
        if counted is not None:
            with self._lock:
                self.cache[counted] += 1

    def _on_duration(self, event: str, duration_secs: float, **_) -> None:
        if event == "/jax/compilation_cache/cache_retrieval_time_sec":
            with self._lock:
                self.cache["retrieval_s"] += duration_secs

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
            return
        seconds = time.perf_counter() - self._gc_t0
        self.gc_passes[info["generation"]] += 1
        self.gc_seconds[info["generation"]] += seconds
        second = int(time.time() - self.process_start)
        if second >= self.GC_SECONDS_MAX:
            self._drop_gc_callback()
            return
        if second >= len(self.gc_by_second):
            self.gc_by_second.extend([0.0] * (second + 1 - len(self.gc_by_second)))
        self.gc_by_second[second] += seconds

    def _drop_gc_callback(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def intervals(self) -> dict[str, list[tuple]]:
        """{kind: the union of its events so far, `(start, end, fun_name
        of the outermost event)`}."""
        with self._lock:
            self._compact_locked()
            return {kind: list(kept) for kind, kept in self._intervals.items()}

    def seconds(self) -> dict[str, float]:
        """{kind: WALL seconds of its events so far}, each the union of its
        intervals; `any`: the union of all three kinds."""
        by_kind = self.intervals()
        by_kind["any"] = merge_intervals(
            iv for ivs in by_kind.values() for iv in ivs)
        return {kind: sum(iv[1] - iv[0] for iv in ivs)
                for kind, ivs in by_kind.items()}

    # -- what the fused launchers print ---------------------------------------

    def end_chunk(self, label: str) -> list[str]:
        """A fused loop's chunk has ended: -> the line of its spans and,
        when this chunk closes the start, the start's line after it."""
        with self._lock:
            self._chunk_n += 1
            spans = [s for s in self._pending if s[0] in scopes.CHUNK_SPANS]
            self._pending.clear()
            in_chunk, self._in_chunk = self._in_chunk, {}
            chunk = {"chunk": self._chunk_n,
                     "spans": [[n, round(w, 6), round(d, 6)] for n, _, w, d in spans]}
            if in_chunk:
                chunk["compiled"] = {
                    kind: {"events": n, "longest_s": round(s, 4), "fun_name": fun}
                    for kind, (n, s, fun) in in_chunk.items()}
            self.chunks.append(chunk)
            closing = self.closed_at is None and not in_chunk
        total = sum(d for *_, d in spans)
        parts = " + ".join(f"{n.rsplit('/', 1)[-1]} {d:.4f}" for n, _, _, d in spans)
        recompiled = "".join(
            f", {kind} x{v['events']} longest {v['longest_s']:.2f} s ({v['fun_name']})"
            for kind, v in chunk.get("compiled", {}).items())
        lines = [f"[{label}] chunk {chunk['chunk']}: {total:.4f} s = {parts}"
                 f"{recompiled} {json.dumps(chunk)}"]
        if closing:
            lines.append(self.close_start(label))
        return lines

    def close_start(self, label: str) -> str | None:
        """Close the start (once: None after that) -> its line: the
        seconds from the process's start by where they went, first rule
        that applies (a start's span; else a trace, lower or compile
        interval; else a chunk's wait), and the record as JSON. A launcher
        whose loop ends before any chunk closed the start calls this
        itself, so a short or a failed run still prints it."""
        with self._lock:
            if self.closed_at is not None:
                return None
            self.closed_at = now = time.time()
            spans, events = list(self.spans), dict(self.events)
            cache = {**self.cache,
                     "retrieval_s": round(self.cache["retrieval_s"], 4)}
        self._drop_gc_callback()
        intervals = self.intervals()

        def of(*names):
            return [(w, w + d) for n, _, w, d in spans if n in names]

        split = split_by_first(self.process_start, now, [
            ("import", of(scopes.START_IMPORT)),
            ("backend", of(scopes.START_BACKEND)),
            ("init", of(scopes.START_BUILD, scopes.START_INIT,
                        scopes.START_RESTORE, scopes.START_WARM_COLLECT)),
            *((kind, intervals[kind]) for kind in ("trace", "lower", "compile")),
            ("wait", of(scopes.WAIT))])
        top = {}
        for kind, ivs in intervals.items():
            by_fun: dict[str, float] = {}
            for start, end, fun in ivs:
                by_fun[fun] = by_fun.get(fun, 0.0) + end - start
            top[kind] = [[fun, round(s, 4)] for fun, s in sorted(
                by_fun.items(), key=lambda kv: -kv[1])[:self.TOP_FUNS]]
        record = {
            "pid": self.pid, "process_start": round(self.process_start, 6),
            "closed_at": round(now, 6),
            "spans": [[n, p, round(w, 6), round(d, 6)] for n, p, w, d in spans],
            # seconds since process_start, to keep the line short
            "intervals": {kind: [[round(s - self.process_start, 4),
                                  round(e - self.process_start, 4)]
                                 for s, e, _ in ivs]
                          for kind, ivs in intervals.items()},
            "events": events, "cache": cache, "top": top,
            "gc": {"passes": list(self.gc_passes),
                   "seconds": [round(s, 4) for s in self.gc_seconds],
                   "by_second": [round(s, 4) for s in self.gc_by_second]}}
        parts = " + ".join(f"{name} {s:.2f}" for name, s in split.items())
        return (f"[{label}] start: {now - self.process_start:.2f} s = {parts} "
                f"{json.dumps(record)}")


# The process's record. `chip_span` feeds it from every process that owns
# a chip; only the fused launchers (runtime/launch.py) print it.
HOST_RECORD = HostRecord()


@contextlib.contextmanager
def chip_span(name: str, emitter: "TraceEmitter | None" = None) -> Iterator[None]:
    """A host span of a process that owns a chip, on the profiler's clock
    and on the wall clock.

    One call site, three sinks. A `jax.profiler.TraceAnnotation`: a no-op
    unless a profiler session is live, and then an event on the host
    plane of the SAME `.xplane.pb` as the device ops — one clock, nothing
    to align. `HOST_RECORD`, always: the span with its wall start, in
    memory, for the launcher's log. With an `emitter` (the process's
    `TELEMETRY.trace`, None while telemetry is off) the same span also
    goes to the wall-clock Chrome trace that `scripts/obs_report.py`
    merges across processes. Actor processes (no device, no profiler)
    keep `TELEMETRY.span()`."""
    from jax.profiler import TraceAnnotation

    wall = time.time()
    t0 = time.perf_counter()
    parent = HOST_RECORD.enter(name)
    with TraceAnnotation(name):
        try:
            yield
        finally:
            duration = time.perf_counter() - t0
            HOST_RECORD.leave(name, parent, wall, duration)
            if emitter is not None:
                emitter.emit(name, wall, duration)


def load_trace(path: str) -> list[dict]:
    """Load a trace written by `TraceEmitter` (or any Chrome-trace JSON).

    Tolerates the streaming form a crashed process leaves behind (open
    array, no terminator) and the `{"traceEvents": [...]}` wrapper some
    tools produce.
    """
    with open(path) as f:
        text = f.read().strip()
    try:
        data = json.loads(text)
    except json.JSONDecodeError:
        try:
            data = json.loads(text.rstrip().rstrip(",") + "\n]")
        except json.JSONDecodeError:
            # A SIGTERM mid-flush can cut the final event at an arbitrary
            # byte. Events are one-per-line on disk, so recover every
            # complete line and drop the torn tail — one mangled shard
            # must not abort the whole run's report.
            data = []
            for line in text.splitlines():
                line = line.strip().rstrip(",")
                if not line or line in ("[", "]"):
                    continue
                try:
                    data.append(json.loads(line))
                except json.JSONDecodeError:
                    continue
    if isinstance(data, dict):
        data = data.get("traceEvents", [])
    return data

"""From a profiler trace to device busy time, kernel time and idle gaps.

The yardstick's own reduction: later PRs cannot change it. Two halves:

- `read_xplane` turns the `.xplane.pb` that `jax.profiler` writes into a
  plain `Trace` (a list of `(plane, line, name, start_ns, dur_ns)` rows
  plus one detail string per distinct name). It is the only function
  here that imports JAX, and it runs in the process that owned the chip.
- everything else is arithmetic on that list, checked by
  `tests/test_trace_reduce.py` on a recorded fixture.

Busy time is a UNION of intervals on ONE line of a device's plane: on a
TPU plane the "XLA Ops" line holds the operations, and the "XLA
Modules" and "Steps" lines nest the very same time, so adding lines up
counts the time twice or three times (PR 22's traced run overshot its
window that way). `device_busy` never adds: per device it takes the
union over the op line, and over several devices it averages.
"""

from __future__ import annotations

import dataclasses
import json
import re

# Which plane is a device and which of its lines holds the operations,
# by platform. "cpu" exists for the rehearsal of the harness on a
# machine without the chip (tests): XLA:CPU runs its ops on client
# threads of the host plane. Its numbers are never published.
DEVICE_LINES = {
    "tpu": {"plane": r"^/device:TPU:\d+$", "line": r"^XLA Ops$"},
    "cpu": {"plane": r"^/host:CPU$", "line": r"^tf_XLAPjRtCpuClient/"},
}
MARK = "perfbench_mark"  # TraceAnnotation the harness drops at trace start
_NOT_OPS = re.compile(r"^(ThreadpoolListener::|end: )")


class TraceError(RuntimeError):
    """The trace cannot give a sound number: the run must fail."""


@dataclasses.dataclass
class Trace:
    platform: str
    # rows: [plane, line, name, start_ns, dur_ns]
    events: list
    details: dict  # name -> long name / HLO text of its first event
    mark_ns: float | None = None  # start of the MARK annotation, trace clock

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(d["platform"], [list(e) for e in d["events"]],
                   dict(d.get("details", {})), d.get("mark_ns"))


def read_xplane(path: str, platform: str) -> Trace:
    """Every event of the device planes (all their lines, so that the
    nesting can be seen and tested) and the harness's mark."""
    from jax.profiler import ProfileData

    spec = DEVICE_LINES.get(platform)
    if spec is None:
        raise TraceError(f"no device-line rule for platform {platform!r}")
    plane_re = re.compile(spec["plane"])
    line_re = re.compile(spec["line"])
    events, details, mark_ns = [], {}, None
    for plane in ProfileData.from_file(path).planes:
        is_device = bool(plane_re.match(plane.name))
        host_only = platform == "cpu"
        for line in plane.lines:
            keep_line = is_device and (not host_only or line_re.match(line.name))
            for ev in line.events:
                # A TPU op's name is its whole HLO instruction,
                # "%fusion.1 = f32[...] fusion(...)": the name proper
                # goes into the rows, the rest into `details`.
                full = ev.name
                name = full.split(" = ", 1)[0].lstrip("%")
                if name == MARK and mark_ns is None:
                    mark_ns = float(ev.start_ns)
                if not keep_line or _NOT_OPS.match(name):
                    continue
                events.append([plane.name, line.name, name,
                               float(ev.start_ns), float(ev.duration_ns)])
                if name not in details:
                    details[name] = (full[:600] + " " + _detail(ev)).strip()
    return Trace(platform, events, details, mark_ns)


def _detail(ev) -> str:
    parts = []
    try:
        for key, value in ev.stats:
            if key in ("long_name", "tf_op", "hlo_op", "name", "hlo_module",
                       "kernel_details", "source"):
                parts.append(f"{key}={value}")
    except Exception:  # noqa: BLE001 — stats are optional decoration
        pass
    return " ".join(parts)[:400]


# ----------------------------------------------------------- arithmetic


def union(intervals, lo: float | None = None,
          hi: float | None = None) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping `(start, end)` intervals, each
    clipped to `[lo, hi]` where given. Empty ones vanish."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_seconds(intervals, lo=None, hi=None) -> float:
    """Seconds covered by nanosecond `intervals`."""
    return sum(e - s for s, e in union(intervals, lo, hi)) / 1e9


def op_events(trace: Trace) -> dict[str, list]:
    """device plane -> the rows of its ONE op line (never two lines
    added: on a TPU exactly one line may match)."""
    spec = DEVICE_LINES[trace.platform]
    plane_re, line_re = re.compile(spec["plane"]), re.compile(spec["line"])
    by_plane: dict[str, dict[str, list]] = {}
    for row in trace.events:
        if plane_re.match(row[0]) and line_re.match(row[1]):
            by_plane.setdefault(row[0], {}).setdefault(row[1], []).append(row)
    out = {}
    for plane, lines in by_plane.items():
        if trace.platform != "cpu" and len(lines) != 1:
            raise TraceError(
                f"{plane}: {len(lines)} lines match the op line "
                f"{spec['line']!r}: {sorted(lines)}")
        # cpu rehearsal: the client threads together stand for the device
        out[plane] = [r for rows in lines.values() for r in rows]
    return out


def lines_inventory(trace: Trace) -> list[dict]:
    """What each line of each device plane holds: for the log, so that a
    changed trace layout is seen at once."""
    acc: dict[tuple, list] = {}
    for plane, line, _name, start, dur in trace.events:
        acc.setdefault((plane, line), []).append((start, start + dur))
    return [{"plane": p, "line": ln, "events": len(iv),
             "sum_s": sum(e - s for s, e in iv) / 1e9,
             "union_s": union_seconds(iv)}
            for (p, ln), iv in sorted(acc.items())]


def device_busy(trace: Trace, window_s: float, chips: int) -> dict:
    """-> {"busy_s", "window_s", "span_s", "per_device"}: seconds in
    which an operation ran, as the union over each device's op line,
    averaged over the chips. Raises where the contract would break: no
    device event, fewer devices than chips, or more busy than window."""
    per_plane = op_events(trace)
    if not per_plane or not any(per_plane.values()):
        raise TraceError("the trace holds no device operation")
    if len(per_plane) != chips:
        raise TraceError(
            f"the trace holds {len(per_plane)} device plane(s) with "
            f"operations, the cell asks for {chips}")
    lo = min(r[3] for rows in per_plane.values() for r in rows)
    hi = max(r[3] + r[4] for rows in per_plane.values() for r in rows)
    span_s = (hi - lo) / 1e9
    if span_s > window_s * 1.02 + 0.05:
        raise TraceError(
            f"device events span {span_s:.4f} s, the traced interval "
            f"was {window_s:.4f} s: the clocks or the window are wrong")
    per_device = {plane: union_seconds([(r[3], r[3] + r[4]) for r in rows])
                  for plane, rows in per_plane.items()}
    busy_s = sum(per_device.values()) / len(per_device)
    if not 0 < busy_s <= window_s:
        raise TraceError(
            f"busy_s {busy_s} not in (0, window_s {window_s}]")
    return {"busy_s": busy_s, "window_s": window_s, "span_s": span_s,
            "per_device": per_device}


def event_seconds(trace: Trace, pattern: str) -> tuple[float, int]:
    """(summed device seconds, count) of the op-line events whose name
    or detail matches `pattern`, averaged over the devices."""
    return match_totals(op_totals(trace), trace.details, pattern)


def _self_ns(rows: list) -> list[float]:
    """Per row, its duration minus the time of the rows nested directly
    inside it on the same line (a `while` holds its body's ops)."""
    order = sorted(range(len(rows)), key=lambda i: (rows[i][3], -rows[i][4]))
    self_ns = [r[4] for r in rows]
    stack: list[int] = []  # open rows, outermost first
    for i in order:
        start, end = rows[i][3], rows[i][3] + rows[i][4]
        while stack and rows[stack[-1]][3] + rows[stack[-1]][4] <= start:
            stack.pop()
        if stack and end <= rows[stack[-1]][3] + rows[stack[-1]][4]:
            self_ns[stack[-1]] -= rows[i][4]
        stack.append(i)
    return self_ns


def op_totals(trace: Trace) -> dict[str, list]:
    """name -> [seconds, events, self seconds] on the op line, averaged
    over the devices. Self seconds leave out nested operations."""
    per_plane = op_events(trace)
    n = max(1, len(per_plane))
    acc: dict[str, list] = {}
    for rows in per_plane.values():
        for row, own in zip(rows, _self_ns(rows)):
            tot = acc.setdefault(row[2], [0.0, 0, 0.0])
            tot[0] += row[4] / 1e9 / n
            tot[1] += 1
            tot[2] += max(own, 0.0) / 1e9 / n
    return {name: [s, c // n, own] for name, (s, c, own) in acc.items()}


def top_ops(totals: dict, k: int = 10) -> list[list]:
    """[[name, seconds], ...] from `op_totals`: the operations that took
    most time of their own (nested operations not counted twice)."""
    ranked = sorted(totals.items(), key=lambda kv: -kv[1][2])[:k]
    return [[name, tot[2]] for name, tot in ranked]


_OPERAND = re.compile(r"%[\w.\-]+")


def match_totals(totals: dict, details: dict, pattern: str) -> tuple[float, int]:
    """(seconds, events) of the names in `totals` (from `op_totals`)
    whose name matches `pattern`, or whose detail does once the operands
    it names are struck out: a fusion that CONSUMES a kernel's output
    names the kernel among its operands and is not the kernel (on the
    recorded trace it made three "V-trace" events an update where the
    step holds two kernels)."""
    rx = re.compile(pattern)
    seconds, count = 0.0, 0
    for name, (s, c, *_own) in totals.items():
        _, _, definition = details.get(name, "").partition(" = ")
        if rx.search(name) or rx.search(_OPERAND.sub("", definition)):
            seconds += s
            count += c
    return seconds, count


def idle_gaps(trace: Trace, host_spans: list, trace_start_wall_s: float,
              k: int = 10) -> list[list]:
    """[[label, seconds], ...]: the device's idle time inside the trace
    by what the host was doing then. `host_spans` are `(name, start_s,
    end_s)` on the wall clock; the trace's clock is tied to it through
    the harness's mark (dropped at `trace_start_wall_s`). The innermost
    span that covers a gap's midpoint names it; gaps no span covers
    are `unattributed`. First device only: labels, not a metric."""
    per_plane = op_events(trace)
    if not per_plane:
        return []
    rows = per_plane[sorted(per_plane)[0]]
    busy = union([(r[3], r[3] + r[4]) for r in rows])
    if len(busy) < 2:
        return []
    mark = trace.mark_ns if trace.mark_ns is not None else busy[0][0]
    to_wall = lambda ns: trace_start_wall_s + (ns - mark) / 1e9
    acc: dict[str, float] = {}
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        mid = to_wall((e0 + s1) / 2)
        label, width = "unattributed", None
        for name, s, e in host_spans:
            if s <= mid <= e and (width is None or e - s < width):
                label, width = name, e - s
        acc[label] = acc.get(label, 0.0) + (s1 - e0) / 1e9
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, seconds] for name, seconds in ranked]


def trim_for_fixture(trace: Trace, max_events: int = 6000) -> Trace:
    """The first `max_events` rows in time order (every line kept in
    proportion), small enough to commit beside the test."""
    rows = sorted(trace.events, key=lambda r: r[3])[:max_events]
    names = {r[2] for r in rows}
    return Trace(trace.platform, rows,
                 {n: d for n, d in trace.details.items() if n in names},
                 trace.mark_ns)


def save(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)


def load(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))

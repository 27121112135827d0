"""Readers of what the program's telemetry wrote in a traced run:
per-role JSONL shards (`<role>-<rank>.jsonl`: counters, gauges) and the
Chrome-trace host spans (`trace-<role>-<rank>.json`). Plain JSON, no
JAX, tolerant of a torn last line (a process stopped mid-flush).

The shard-merging idea (one list per role, cumulative counters turned
into rates by the reader) follows `scripts/obs_report.py`; nothing is
imported from it.
"""

from __future__ import annotations

import glob
import json
import os


def _json_lines(path: str) -> list[dict]:
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip().rstrip(",")
                if not line or line in ("[", "]"):
                    continue
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    continue  # a torn tail
    except OSError:
        pass
    return out


def host_spans(trace_path: str) -> list[tuple[str, float, float]]:
    """`(name, start_s, end_s)` of every complete span, wall clock."""
    return [(e["name"], e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6)
            for e in _json_lines(trace_path)
            if e.get("ph") == "X" and "ts" in e and "dur" in e]


def shards(telemetry_dir: str, role: str) -> dict[str, list[dict]]:
    """shard file name -> its records, for every `<role>-<rank>.jsonl`."""
    return {os.path.basename(p): _json_lines(p)
            for p in sorted(glob.glob(
                os.path.join(telemetry_dir, f"{role}-*.jsonl")))}


def gauge_window_mean(records: list[dict], name: str, t0: float,
                      t1: float) -> float | None:
    """Observation-weighted mean of gauge `name` over the flushes that
    landed inside `[t0, t1]`; None where there were none."""
    n = total = 0.0
    for r in records:
        if r.get("kind") == "gauge" and r.get("name") == name \
                and t0 <= r.get("t", 0) <= t1:
            n += r["n"]
            total += r["mean"] * r["n"]
    return total / n if n else None


def counter_rate(records: list[dict], name: str, t0: float,
                 t1: float) -> float | None:
    """Per-second rate of cumulative counter `name` between its first
    and last flush inside `[t0, t1]`; None with fewer than two."""
    pts = [(r["t"], r["value"]) for r in records
           if r.get("kind") == "counter" and r.get("name") == name
           and t0 <= r.get("t", 0) <= t1]
    if len(pts) < 2 or pts[-1][0] <= pts[0][0]:
        return None
    return (pts[-1][1] - pts[0][1]) / (pts[-1][0] - pts[0][0])

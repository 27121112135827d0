#!/usr/bin/env python
"""Merge one run's telemetry shards into a human-readable report + trace.

Every process of a topology writes its own shard
(`telemetry/<role>-<rank>.jsonl`) and host-span timeline
(`telemetry/trace-<role>-<rank>.json`) — see
`distributed_reinforcement_learning_tpu/observability/`. This CLI is the
read side: point it at the run directory (or the telemetry directory
itself) and it prints

- per-role throughput (counter deltas over the shard's time span),
- per-stage host latencies (p50/p99 over the trace spans),
- the queue-depth timeline (min/mean/max + an ASCII strip),
- publish latency and weight-version staleness statistics,

and writes `trace-merged.json`: all roles' spans on one wall-clock axis
(processes get distinct track labels), loadable in Perfetto
(ui.perfetto.dev) or chrome://tracing.

Shards are STREAMED line-by-line with incremental aggregation (counters
keep first/last points, gauges fold into running {n, total, min, max,
last} windows, staleness buckets accumulate as they pass) — an
hours-long run's multi-GB shard costs this report one line of memory,
not the whole file. Only the handful of gauges that render as timelines
(the queue/ring depth sparklines) retain their per-flush means, which
grow with flush count, not record count.

    python scripts/obs_report.py /tmp/run
    python scripts/obs_report.py /tmp/run --no-merge

`--profile <dir>` reads a `jax.profiler` trace instead (what
`DRL_PROFILE_DIR` wrote, or any directory above an `.xplane.pb`): the
device's self time per scope of `observability/scopes.py`, the ops the
compiler made resolved to the scope they serve
(`observability/attribution.py`), and the fused loop's `anakin/*` host
spans on the same clock.

    python scripts/obs_report.py --profile /tmp/trace
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_reinforcement_learning_tpu.observability.metrics import (
    STALENESS_BUCKET_NAMES,
    STALENESS_BUCKETS,
)
from distributed_reinforcement_learning_tpu.observability.trace import load_trace

_SPARK = " .:-=+*#%@"

# Gauges whose per-flush mean SERIES the report renders (sparklines or
# percentiles); every other gauge folds into a constant-size running
# aggregate. Suffixes cover per-shard-id names (replay_spill/<sid>/...).
_SERIES_GAUGES = ("transport/queue_depth", "ring/depth",
                  "tier/coll_round_ms")
_SERIES_SUFFIXES = ("/promote_wait_ms",)
# Gauges needing the fallback per-window histogram (pre-exact-counter
# shards): per-record (mean, n) folds straight into bucket counts.
_STALE_GAUGE = "learner/weight_staleness"


class GaugeAgg:
    """One gauge's running aggregate across flush windows — the same
    arithmetic (sequential sum of mean*n) the old whole-file
    `gauge_stats` performed, so reports are byte-identical."""

    __slots__ = ("n", "total", "lo", "hi", "last")

    def __init__(self):
        self.n = 0
        self.total = 0.0
        self.lo = float("inf")
        self.hi = float("-inf")
        self.last = 0.0

    def add(self, record: dict) -> None:
        self.n += record["n"]
        self.total += record["mean"] * record["n"]
        self.lo = min(self.lo, record["min"])
        self.hi = max(self.hi, record["max"])
        self.last = record["last"]

    def stats(self) -> dict | None:
        if not self.n:
            return None
        return {"n": self.n, "mean": self.total / self.n,
                "min": self.lo, "max": self.hi, "last": self.last}


class ShardAgg:
    """Streaming aggregate of one `<role>-<rank>.jsonl` shard."""

    def __init__(self, path: str):
        self.path = path
        m = re.match(r"(.+)-(\d+)\.jsonl$", os.path.basename(path))
        self.role = m.group(1) if m else "proc"
        self.rank = int(m.group(2)) if m else 0
        self.n_records = 0
        self._meta_seen = False  # first meta wins: a process has one identity
        self.t_min: float | None = None
        self.t_max: float | None = None
        # counter name -> [t_first, v_first, t_last, v_last]
        self.counters: dict[str, list] = {}
        self.gauges: dict[str, GaugeAgg] = {}
        self.series: dict[str, list[float]] = {}  # sparkline means only
        # Fallback staleness histogram, bucketed AS records stream by.
        self._stale_edges = list(STALENESS_BUCKETS) + [(float("inf"), ">16")]
        self._stale_counts = [0] * len(self._stale_edges)

    def consume(self, record: dict) -> None:
        self.n_records += 1
        t = record.get("t")
        if t is not None:
            self.t_min = t if self.t_min is None else min(self.t_min, t)
            self.t_max = t if self.t_max is None else max(self.t_max, t)
        kind = record.get("kind")
        if kind == "meta":
            if not self._meta_seen:
                self._meta_seen = True
                self.role = record.get("role") or self.role
                self.rank = record.get("rank", self.rank)
        elif kind == "counter":
            entry = self.counters.get(record["name"])
            if entry is None:
                self.counters[record["name"]] = [t, record["value"],
                                                 t, record["value"]]
            else:
                entry[2], entry[3] = t, record["value"]
        elif kind == "gauge":
            name = record["name"]
            agg = self.gauges.get(name)
            if agg is None:
                agg = self.gauges[name] = GaugeAgg()
            agg.add(record)
            if name in _SERIES_GAUGES or name.endswith(_SERIES_SUFFIXES):
                self.series.setdefault(name, []).append(record["mean"])
            if name == _STALE_GAUGE:
                value = record["mean"]
                for i, (edge, _) in enumerate(self._stale_edges):
                    if value <= edge:
                        self._stale_counts[i] += record["n"]
                        break

    def counter_rates(self) -> dict[str, dict]:
        """Per counter: total (last cumulative value) and rate over the
        counter's own first->last flush window."""
        out = {}
        for name, (t0, v0, t1, v1) in self.counters.items():
            out[name] = {
                "total": v1,
                "rate": (v1 - v0) / (t1 - t0) if t1 > t0 else 0.0,
            }
        return out

    def gauge_stats(self, name: str) -> dict | None:
        agg = self.gauges.get(name)
        return agg.stats() if agg is not None else None

    def stale_fallback_hist(self) -> list[tuple[str, int]]:
        return [(name, c) for (_, name), c
                in zip(self._stale_edges, self._stale_counts) if c]


def shard_paths(tdir: str) -> list[str]:
    """Only `<role>-<rank>.jsonl` files: a run_dir's metrics.jsonl (the
    MetricsLogger stream) must not be misread as a telemetry shard."""
    return sorted(p for p in glob.glob(os.path.join(tdir, "*.jsonl"))
                  if re.match(r".+-\d+\.jsonl$", os.path.basename(p)))


def find_telemetry_dir(run_dir: str) -> str:
    for cand in (os.path.join(run_dir, "telemetry"), run_dir):
        if shard_paths(cand):
            return cand
    raise SystemExit(f"no telemetry shards (<role>-<rank>.jsonl) under "
                     f"{run_dir} — was the run launched with telemetry "
                     f"enabled (--run_dir / DRL_TELEMETRY_DIR)?")


def read_shard(path: str) -> ShardAgg:
    """Stream one shard into a ShardAgg — one line in memory at a time."""
    agg = ShardAgg(path)
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                record = json.loads(line)
            except json.JSONDecodeError:
                continue  # torn final line of a killed process
            agg.consume(record)
    return agg


def shard_label(shard: ShardAgg) -> str:
    return f"{shard.role}-{shard.rank}"


def sparkline(values: list[float], width: int = 60) -> str:
    """ASCII strip of a gauge timeline (bucketed means, scaled to max)."""
    if not values:
        return ""
    if len(values) > width:
        per = len(values) / width
        values = [
            sum(values[int(i * per):max(int((i + 1) * per), int(i * per) + 1)])
            / max(len(values[int(i * per):max(int((i + 1) * per), int(i * per) + 1)]), 1)
            for i in range(width)
        ]
    hi = max(values) or 1.0
    return "".join(_SPARK[min(int(v / hi * (len(_SPARK) - 1) + 0.5),
                              len(_SPARK) - 1)] for v in values)


def percentile(sorted_values: list[float], q: float) -> float:
    if not sorted_values:
        return 0.0
    idx = min(int(q * (len(sorted_values) - 1) + 0.5), len(sorted_values) - 1)
    return sorted_values[idx]


def stage_latencies(tdir: str) -> list[dict]:
    """Per (process, span-name) p50/p99 from every trace shard."""
    rows = []
    for path in sorted(glob.glob(os.path.join(tdir, "trace-*.json"))):
        if os.path.basename(path) == "trace-merged.json":
            continue
        label = re.sub(r"^trace-|\.json$", "", os.path.basename(path))
        spans: dict[str, list[float]] = {}
        for event in load_trace(path):
            if event.get("ph") != "X":
                continue
            spans.setdefault(event["name"], []).append(event.get("dur", 0.0) / 1e3)
        for name, durs in sorted(spans.items()):
            durs.sort()
            rows.append({
                "proc": label, "stage": name, "count": len(durs),
                "p50_ms": percentile(durs, 0.50),
                "p99_ms": percentile(durs, 0.99),
                "total_s": sum(durs) / 1e3,
            })
    return rows


def merge_traces(tdir: str, out_path: str) -> int:
    """One Chrome trace with every process on its own labeled track."""
    events: list[dict] = []
    for pid, path in enumerate(sorted(glob.glob(os.path.join(tdir, "trace-*.json")))):
        if os.path.basename(path) == "trace-merged.json":
            continue
        label = re.sub(r"^trace-|\.json$", "", os.path.basename(path))
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0, "args": {"name": label}})
        for event in load_trace(path):
            if event.get("ph") == "M" and event.get("name") == "process_name":
                continue  # replaced by the merged labels above
            event = dict(event)
            event["pid"] = pid
            events.append(event)
    with open(out_path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return sum(1 for e in events if e.get("ph") == "X")


def staleness_buckets_exact(shard: ShardAgg) -> list[tuple[str, int]]:
    """Exact histogram from the observation-time `staleness_bucket/*`
    counters the transport server maintains (preferred: per-window gauge
    means would average a rare stall into the window's bulk and hide the
    tail). Edges shared with the write side via observability.metrics."""
    rates = shard.counter_rates()
    return [(name, int(rates[f"staleness_bucket/{name}"]["total"]))
            for name in STALENESS_BUCKET_NAMES
            if rates.get(f"staleness_bucket/{name}", {}).get("total")]


def build_report(tdir: str, merge: bool = True) -> str:
    shards = [read_shard(p) for p in shard_paths(tdir)]
    shards = [s for s in shards if s.n_records]
    if not shards:
        raise SystemExit(f"no readable telemetry records under {tdir}")
    lines: list[str] = []
    out = lines.append
    t_mins = [s.t_min for s in shards if s.t_min is not None]
    t_maxs = [s.t_max for s in shards if s.t_max is not None]
    out("== Telemetry report ==")
    out(f"run: {tdir}")
    out(f"processes: {', '.join(shard_label(s) for s in shards)}")
    if t_mins:
        out(f"span: {max(t_maxs) - min(t_mins):.1f}s of telemetry")

    out("")
    out("-- Throughput (counters) --")
    any_counter = False
    for shard in shards:
        for name, stats in sorted(shard.counter_rates().items()):
            if name.startswith(("staleness_bucket/", "codec/", "board/",
                                "replay_shard/", "replay_spill/",
                                "inference/", "remote_act/", "wshard/",
                                "weights/", "fleet/", "pipe/", "devpath/",
                                "admission/")):
                continue  # rendered as their own sections below
            any_counter = True
            out(f"  {shard_label(shard):<14} {name:<28} "
                f"total {stats['total']:>12.0f}   {stats['rate']:>10.1f}/s")
    if not any_counter:
        out("  (no counters recorded)")

    out("")
    out("-- Host stage latencies (trace spans) --")
    rows = stage_latencies(tdir)
    if rows:
        out(f"  {'process':<14} {'stage':<20} {'count':>7} "
            f"{'p50_ms':>9} {'p99_ms':>9} {'total_s':>9}")
        for r in rows:
            out(f"  {r['proc']:<14} {r['stage']:<20} {r['count']:>7} "
                f"{r['p50_ms']:>9.2f} {r['p99_ms']:>9.2f} {r['total_s']:>9.2f}")
    else:
        out("  (no trace spans recorded)")

    out("")
    out("-- Queue depth (learner transport) --")
    any_depth = False
    for shard in shards:
        stats = shard.gauge_stats("transport/queue_depth")
        if stats is None:
            continue
        any_depth = True
        out(f"  {shard_label(shard)}: min {stats['min']:.0f}  "
            f"mean {stats['mean']:.1f}  max {stats['max']:.0f}  "
            f"last {stats['last']:.0f}")
        out(f"    [{sparkline(shard.series.get('transport/queue_depth', []))}]")
    if not any_depth:
        out("  (no queue-depth samples)")

    # Shm-ring data plane (runtime/shm_ring.py), next to the TCP stats:
    # in-flight bytes per flush window, rendered like the queue depth.
    # Section only appears when a run actually used rings.
    ring_lines: list[str] = []
    for shard in shards:
        stats = shard.gauge_stats("ring/depth")
        if stats is None:
            continue
        ring_lines.append(
            f"  {shard_label(shard)}: min {stats['min']:.0f}B  "
            f"mean {stats['mean']:.0f}B  max {stats['max']:.0f}B  "
            f"last {stats['last']:.0f}B")
        ring_lines.append(
            f"    [{sparkline(shard.series.get('ring/depth', []))}]")
    for shard in shards:
        stats = shard.gauge_stats("ring/full_wait_ms")
        if stats is not None:
            ring_lines.append(
                f"  {shard_label(shard)}: ring full-wait mean "
                f"{stats['mean']:.2f}ms  max {stats['max']:.2f}ms  "
                f"({stats['n']} stalls)")
    if ring_lines:
        out("")
        out("-- Shm ring (co-hosted data plane) --")
        lines.extend(ring_lines)

    # Actor pipeline (runtime/actor_pipeline.py): double-buffered
    # sampling + async publication. Per actor shard: the step share
    # (env-step span time over env-step + act-wait — 1.0 means the act
    # worker's XLA/RPC latency is fully hidden behind host stepping),
    # publisher depth/full-wait backpressure, per-slice frame counters
    # and the demote/re-promote tallies. Section only appears when a
    # run ran pipelined actors.
    pipe_lines: list[str] = []
    span_totals: dict[str, dict[str, float]] = {}
    for r in rows:
        if r["stage"] in ("pipe_act_wait", "pipe_env_step"):
            span_totals.setdefault(r["proc"], {})[r["stage"]] = r["total_s"]
    for shard in shards:
        rates = shard.counter_rates()
        if not any(k.startswith("pipe/") for k in rates):
            continue

        def total(key, rates=rates):
            return rates.get(key, {}).get("total", 0)

        spans = span_totals.get(shard_label(shard), {})
        wait, step = spans.get("pipe_act_wait", 0.0), spans.get("pipe_env_step", 0.0)
        share = f"step share {step / (wait + step):.0%}  " if wait + step else ""
        pipe_lines.append(
            f"  {shard_label(shard)}: {share}"
            f"published {total('pipe/published_rounds'):.0f} rounds "
            f"({total('pipe/published_unrolls'):.0f} unrolls), "
            f"{total('pipe/demotions'):.0f} demotions, "
            f"{total('pipe/repromotions'):.0f} re-promotions")
        depth = shard.gauge_stats("pipe/publisher_depth")
        if depth is not None:
            fw = shard.gauge_stats("pipe/publisher_full_wait_ms")
            fw_part = (f"  full-waits {total('pipe/publisher_full_waits'):.0f}"
                       f" (mean {fw['mean']:.2f}ms, max {fw['max']:.2f}ms)"
                       if fw is not None else "")
            pipe_lines.append(
                f"    publisher depth mean {depth['mean']:.1f}  "
                f"max {depth['max']:.0f}{fw_part}")
        per_slice = sorted(k for k in rates if k.startswith("pipe/slice")
                           and k.endswith("_frames"))
        if per_slice:
            pipe_lines.append("    slice frames: " + "  ".join(
                f"{k.removeprefix('pipe/').removesuffix('_frames')} "
                f"{rates[k]['total']:.0f} ({rates[k]['rate']:.0f}/s)"
                for k in per_slice))
    if pipe_lines:
        out("")
        out("-- Actor pipeline (double-buffered sampling) --")
        lines.extend(pipe_lines)

    # Codec fast path (data/codec.py): schema-cache hit rates and the
    # dedup wire-byte cut. Section only appears when a run recorded the
    # codec counters (telemetry on + codec providers registered).
    codec_lines: list[str] = []
    for shard in shards:
        rates = shard.counter_rates()
        if not any(k.startswith("codec/") for k in rates):
            continue

        def total(key, rates=rates):
            return rates.get(key, {}).get("total", 0)

        for side, label in (("encode", "encode schema-cache"),
                            ("decode", "decode schema-cache"),
                            ("dedup_plan", "dedup plan-cache")):
            hits, misses = total(f"codec/{side}_hits"), total(f"codec/{side}_misses")
            if hits + misses > 0:
                codec_lines.append(
                    f"  {shard_label(shard)}: {label} "
                    f"{100 * hits / (hits + misses):.1f}% hit "
                    f"({hits:.0f}/{hits + misses:.0f})")
        blobs, saved = total("codec/dedup_blobs"), total("codec/dedup_bytes_saved")
        if blobs > 0:
            codec_lines.append(
                f"  {shard_label(shard)}: dedup packed {blobs:.0f} blobs, "
                f"saved {saved / 1e6:.1f} MB on the wire "
                f"({saved / blobs / 1e3:.0f} KB/blob)")
    if codec_lines:
        out("")
        out("-- Codec fast path (schema cache + frame-stack dedup) --")
        lines.extend(codec_lines)

    # Sharded replay (data/replay_service.py): per-shard fill + priority
    # mass, ingest/update throughput, gather-sample latency. Section only
    # appears when a run actually ran with DRL_REPLAY_SHARDS ingest.
    shard_lines: list[str] = []
    for shard in shards:
        per = sorted(
            n.split("/")[1] for n in shard.gauges
            if n.startswith("replay_shard/") and n.endswith("/fill"))
        rates = shard.counter_rates()
        for sid in per:
            fill = shard.gauge_stats(f"replay_shard/{sid}/fill")
            mass = shard.gauge_stats(f"replay_shard/{sid}/priority_mass")
            if fill is None:
                continue
            ing = rates.get(f"replay_shard/{sid}/ingested_items", {})
            upd = rates.get(f"replay_shard/{sid}/updates_applied", {})
            mass_part = f"mass {mass['last']:.1f}  " if mass is not None else ""
            shard_lines.append(
                f"  {shard_label(shard)} shard {sid}: fill "
                f"{100 * fill['last']:.1f}% (peak {100 * fill['max']:.1f}%)  "
                f"{mass_part}"
                f"ingested {ing.get('total', 0):.0f} items "
                f"({ing.get('rate', 0):.0f}/s)  "
                f"updates {upd.get('total', 0):.0f}")
        stats = shard.gauge_stats("replay_shard/sample_ms")
        if stats is not None:
            shard_lines.append(
                f"  {shard_label(shard)}: gather-sample mean "
                f"{stats['mean']:.2f}ms  max {stats['max']:.2f}ms  "
                f"({stats['n']} samples)")
    if shard_lines:
        out("")
        out("-- Replay shards (ingest-time prioritization) --")
        lines.extend(shard_lines)

    # Tiered replay spill (data/replay_spill.py): per-shard hot/cold
    # fill, RAM vs on-disk footprint, spill/promote traffic, and the
    # promote-wait latency parked cold draws paid before the pump
    # delivered their segment. Section only appears when a run had the
    # spill tier on (DRL_REPLAY_SPILL, on by default).
    spill_lines: list[str] = []
    for shard in shards:
        per = sorted(
            n.split("/")[1] for n in shard.gauges
            if n.startswith("replay_spill/") and n.endswith("/hot_items"))
        rates = shard.counter_rates()
        for sid in per:

            def last(key, sid=sid, shard=shard):
                stats = shard.gauge_stats(f"replay_spill/{sid}/{key}")
                return stats["last"] if stats is not None else 0.0

            def total(key, sid=sid, rates=rates):
                # The sampled cumulative tally (`*_total`, survives a
                # flush-thread gap) wins; the event-driven counter of
                # the same stem is the pre-sampling fallback.
                entry = (rates.get(f"replay_spill/{sid}/{key}_total")
                         or rates.get(f"replay_spill/{sid}/{key}") or {})
                return entry.get("total", 0)

            hot, cold = last("hot_items"), last("cold_items")
            spill_lines.append(
                f"  {shard_label(shard)} shard {sid}: hot {hot:.0f} / "
                f"cold {cold:.0f} items "
                f"({100 * hot / max(hot + cold, 1):.0f}% resident)  "
                f"ram {last('ram_bytes') / 2**20:.1f} MB  "
                f"disk {last('disk_bytes') / 2**30:.2f} GB  "
                f"tier queue {last('queue_depth'):.0f}")
            sp = rates.get(f"replay_spill/{sid}/spilled_bytes", {})
            pr = rates.get(f"replay_spill/{sid}/promoted_bytes", {})
            spill_lines.append(
                f"    spilled {total('spilled_segments'):.0f} segments "
                f"({sp.get('total', 0) / 2**20:.1f} MB, "
                f"{sp.get('rate', 0) / 2**20:.2f} MB/s)  "
                f"promoted {total('promoted_segments'):.0f} "
                f"({pr.get('total', 0) / 2**20:.1f} MB, "
                f"{pr.get('rate', 0) / 2**20:.2f} MB/s)  "
                f"crc-dropped {total('crc_dropped'):.0f}  "
                f"forced pads {total('forced_pads'):.0f}")
            series = shard.series.get(
                f"replay_spill/{sid}/promote_wait_ms", [])
            wait = shard.gauge_stats(f"replay_spill/{sid}/promote_wait_ms")
            if wait is not None:
                pct = ""
                if series:
                    import numpy as _np

                    pct = (f"p50 {_np.percentile(series, 50):.2f}ms  "
                           f"p99 {_np.percentile(series, 99):.2f}ms  ")
                spill_lines.append(
                    f"    promote wait {pct}max {wait['max']:.2f}ms  "
                    f"({wait['n']} promotes)")
    if spill_lines:
        out("")
        out("-- Tiered replay (hot/cold spill) --")
        lines.extend(spill_lines)

    # Sample-at-source admission (data/admission.py): actor-side stamp/
    # subsample/drop ladder + the learner-side fast-accept split. Bytes
    # saved is the actors' estimate of wire traffic the ladder avoided
    # (subsample: payload-proportional; whole drops: full-unroll EWMA).
    # Section only appears when a run stamped or fast-accepted blobs.
    adm_lines: list[str] = []
    for shard in shards:
        rates = shard.counter_rates()

        def total(name: str) -> float:
            return rates.get(name, {}).get("total", 0)

        stamped = total("admission/stamped_puts")
        if stamped > 0:  # actor side
            dropped_u = total("admission/dropped_unrolls")
            sub_puts = total("admission/subsampled_puts")
            sub_t = total("admission/subsample_dropped_transitions")
            mass = total("admission/dropped_mass")
            sent_b = total("admission/wire_bytes_sent")
            saved_b = total("admission/wire_bytes_saved")
            press = shard.gauge_stats("admission/pressure")
            press_part = (f"pressure {press['last']:.2f} "
                          f"(peak {press['max']:.2f})  "
                          if press is not None else "")
            adm_lines.append(
                f"  {shard_label(shard)}: stamped {stamped:.0f} puts "
                f"({sub_puts:.0f} subsampled, -{sub_t:.0f} transitions; "
                f"{dropped_u:.0f} unrolls dropped whole, "
                f"mass {mass:.1f} folded)  {press_part}")
            if sent_b > 0 or saved_b > 0:
                pct = (100 * saved_b / (sent_b + saved_b)
                       if sent_b + saved_b > 0 else 0.0)
                adm_lines.append(
                    f"  {shard_label(shard)}: wire {sent_b / 1e6:.1f} MB sent, "
                    f"~{saved_b / 1e6:.1f} MB saved at source ({pct:.0f}%)")
        fast = total("admission/ingest_stamped")
        plain = total("admission/ingest_scored")
        if fast + plain > 0:  # learner side
            folded = total("admission/folded_mass")
            adm_lines.append(
                f"  {shard_label(shard)}: ingest fast-accepted {fast:.0f} "
                f"stamped blobs, scored {plain:.0f} plain "
                f"({100 * fast / (fast + plain):.0f}% skipped scoring; "
                f"folded mass {folded:.1f} drained)")
    if adm_lines:
        out("")
        out("-- Ingest admission (sample-at-source) --")
        lines.extend(adm_lines)

    # Device sample path (data/device_path.py): the fused gather ->
    # H2D -> scanned-learn pipeline on the learner shard. Depth gauge
    # (device-resident sampled calls waiting), H2D bytes + per-entry
    # copy time, the overlap ratio (how much of the gather+copy the
    # learn scan hid: 1.0 = the learn thread never waited), scan-K
    # utilization, and the single-D2H priority readback latency.
    # Section only appears when a run trained through the fused path.
    devpath_lines: list[str] = []
    for shard in shards:
        rates = shard.counter_rates()
        entries = rates.get("devpath/entries")
        if entries is None:
            continue
        gather = shard.gauge_stats("devpath/gather_ms")
        h2d = shard.gauge_stats("devpath/h2d_ms")
        bytes_total = rates.get("devpath/h2d_bytes", {}).get("total", 0)
        h2d_part = (f"h2d {h2d['mean']:.2f}ms/entry "
                    f"({bytes_total / 1e6:.1f} MB total)  "
                    if h2d is not None else "")
        # Overlap: the sample stage on the learn thread is pure entry
        # WAIT under the fused path — time the background pipeline
        # failed to hide. 1.0 means gather+copy were fully hidden.
        wait_rows = [r for r in rows if r["stage"] == "replay_sample"
                     and r["proc"] == shard_label(shard)]
        overlap_part = ""
        if wait_rows and gather is not None and h2d is not None:
            hidden = gather["mean"] + h2d["mean"]
            waited = wait_rows[0]["p50_ms"]
            if hidden > 0:
                ratio = max(0.0, min(1.0, 1.0 - waited / hidden))
                overlap_part = (f"overlap {ratio:.0%} "
                                f"(entry wait p50 {waited:.2f}ms)  ")
        devpath_lines.append(
            f"  {shard_label(shard)}: {entries['total']:.0f} entries "
            f"({entries['rate']:.1f}/s)  {h2d_part}{overlap_part}"
            f"dropped {rates.get('devpath/dropped_entries', {}).get('total', 0):.0f}")
        depth = shard.gauge_stats("devpath/depth")
        scan_k = shard.gauge_stats("devpath/scan_k")
        d2h = shard.gauge_stats("devpath/d2h_ms")
        parts = []
        if depth is not None:
            parts.append(f"prefetch depth mean {depth['mean']:.1f} "
                         f"(max {depth['max']:.0f})")
        if scan_k is not None:
            parts.append(f"scan-K mean {scan_k['mean']:.1f} "
                         f"(last {scan_k['last']:.0f})")
        if d2h is not None:
            parts.append(f"priority D2H mean {d2h['mean']:.2f}ms "
                         f"max {d2h['max']:.2f}ms ({d2h['n']} calls)")
        if parts:
            devpath_lines.append("    " + "  ".join(parts))
    if devpath_lines:
        out("")
        out("-- Device sample path (fused gather/H2D/scan) --")
        lines.extend(devpath_lines)

    # Fleet health (runtime/fleet.py): the learner shard carries the
    # roster gauges (alive/suspect/dead over time) + the supervisor's
    # join/rejoin/death/respawn event counters; member shards carry
    # heartbeat counters and per-surface demote -> re-promote tallies.
    # The heartbeat latency p50/p99 comes from the `heartbeat` trace
    # span each member's loop records. Section only appears when a run
    # had the fleet plane on.
    fleet_lines: list[str] = []
    for shard in shards:
        rates = shard.counter_rates()
        alive = shard.gauge_stats("fleet/alive")
        if alive is not None:  # the supervisor (learner) side

            def total(key, rates=rates):
                return rates.get(key, {}).get("total", 0)

            suspect = shard.gauge_stats("fleet/suspect")
            dead = shard.gauge_stats("fleet/dead")
            fleet_lines.append(
                f"  {shard_label(shard)}: roster last {alive['last']:.0f} "
                f"alive / {suspect['last'] if suspect else 0:.0f} suspect "
                f"/ {dead['last'] if dead else 0:.0f} dead  (peak "
                f"{alive['max']:.0f} alive)")
            fleet_lines.append(
                f"    [{sparkline(shard.series.get('fleet/alive', []))}]")
            fleet_lines.append(
                f"    events: {total('fleet/joins'):.0f} joins, "
                f"{total('fleet/rejoins'):.0f} rejoins, "
                f"{total('fleet/suspects'):.0f} suspects, "
                f"{total('fleet/deaths'):.0f} deaths, "
                f"{total('fleet/respawns'):.0f} respawns, "
                f"{total('fleet/heartbeats'):.0f} heartbeats served")
    hb_rows = [r for r in rows if r["stage"] == "heartbeat"]
    for r in hb_rows:
        fleet_lines.append(
            f"  {r['proc']}: heartbeat p50 {r['p50_ms']:.2f}ms  "
            f"p99 {r['p99_ms']:.2f}ms  ({r['count']} beats)")
    for shard in shards:
        rates = shard.counter_rates()
        beats = rates.get("fleet/heartbeats")
        if beats is None or shard.gauge_stats("fleet/alive") is not None:
            continue  # supervisor shard: fleet/heartbeats is the SERVED
            # tally, already rendered on the events line above — the
            # member-counter row would misread it as member beats.

        def total(key, rates=rates):
            return rates.get(key, {}).get("total", 0)

        fleet_lines.append(
            f"  {shard_label(shard)}: {beats['total']:.0f} heartbeats, "
            f"{total('fleet/heartbeat_failures'):.0f} failures, "
            f"{total('fleet/registrations'):.0f} registrations, "
            f"{total('fleet/learner_restarts'):.0f} learner restarts seen")
        # Demote -> re-promote per surface: the demote counters live in
        # each surface's own stats (tcp_fallbacks / whole_fallbacks /
        # replica_demotes), re-promotions in the new `reattaches` /
        # `replica_repromotes` counters registered under the same
        # prefixes.
        pairs = (("ring", "ring/tcp_fallbacks", "ring/reattaches"),
                 ("board", "board/tcp_fallbacks", "board/reattaches"),
                 ("wshard", "wshard/whole_fallbacks", "wshard/reattaches"),
                 ("remote_act", "remote_act/replica_demotes",
                  "remote_act/replica_repromotes"))
        surf = [f"{label} {total(dem):.0f}->{total(rep):.0f}"
                for label, dem, rep in pairs
                if total(dem) or total(rep)]
        if surf:
            fleet_lines.append(
                f"    demote->re-promote: {'  '.join(surf)}")
    if fleet_lines:
        out("")
        out("-- Fleet health (supervisor + heartbeats) --")
        lines.extend(fleet_lines)

    # Learner tier (runtime/learner_tier.py): per-seat train rate +
    # collective round latency, membership/publisher timeline, merge
    # accounting. Section only appears when seats ran with the tier.
    tier_lines: list[str] = []
    for shard in shards:
        pub = shard.gauge_stats("tier/publisher")
        if pub is None:
            continue
        rates = shard.counter_rates()

        def total(key, rates=rates):
            return rates.get(key, {}).get("total", 0)

        live = shard.gauge_stats("tier/live_seats")
        trained = rates.get("learner/train_steps", {})
        tier_lines.append(
            f"  {shard_label(shard)}: publisher "
            f"{'YES' if pub['last'] else 'no'} (was "
            f"{'ever' if pub['max'] else 'never'})  live seats "
            f"{live['last'] if live else 0:.0f} (min "
            f"{live['min'] if live else 0:.0f})  train "
            f"{trained.get('total', 0):.0f} steps "
            f"({trained.get('rate', 0):.1f}/s)")
        tier_lines.append(
            f"    publisher timeline "
            f"[{sparkline(shard.series.get('tier/publisher', []))}]")
        rms = shard.gauge_stats("tier/round_ms")
        if rms is not None:
            tier_lines.append(
                f"    collective round mean {rms['mean']:.2f}ms  max "
                f"{rms['max']:.2f}ms  ({rms['n']} samples)")
        tier_lines.append(
            f"    rounds {total('tier/rounds_ok'):.0f} ok / "
            f"{total('tier/round_retries'):.0f} retried / "
            f"{total('tier/round_giveups'):.0f} solo-fallback  "
            f"peer deaths {total('tier/peer_deaths'):.0f}  "
            f"promotions {total('tier/promotions'):.0f}")
        merges = total("tier/merges_applied")
        if merges or total("tier/merge_rounds"):
            tier_lines.append(
                f"    async merges {merges:.0f} applied / "
                f"{total('tier/merges_skipped_stale'):.0f} dropped stale "
                f"({total('tier/merge_rounds'):.0f} rounds)")

        # Partition-aware collective (parallel/collective.py plan
        # rounds): bytes/round by spec class, round latency p50/p99
        # over the per-flush means, and the overlap ratio (share of
        # exchange time hidden behind the backward — 1 when the learn
        # thread never waited on the in-flight round).
        part = total("tier/coll_rounds_part")
        if part:
            by_class = []
            for cls in ("rep", "model", "expert", "pipe", "other"):
                b = total(f"tier/coll_bytes_{cls}")
                if b:
                    by_class.append(f"{cls} {b / part / 1024:.1f}KB")
            tier_lines.append(
                f"    partitioned rounds {part:.0f} "
                f"({total('tier/coll_quant_rounds'):.0f} bf16)  "
                f"bytes/round: {'  '.join(by_class) or 'n/a'}")
            series = shard.series.get("tier/coll_round_ms", [])
            if series:
                import numpy as _np

                tier_lines.append(
                    f"    coll round p50 {_np.percentile(series, 50):.2f}ms"
                    f"  p99 {_np.percentile(series, 99):.2f}ms "
                    f"({len(series)} windows)")
            wait = shard.gauge_stats("tier/coll_wait_ms")
            rnd = shard.gauge_stats("tier/coll_round_ms")
            if wait is not None and rnd is not None and rnd["mean"] > 0:
                hidden = max(0.0, 1.0 - wait["mean"] / rnd["mean"])
                tier_lines.append(
                    f"    overlap: {total('tier/overlap_rounds'):.0f} "
                    f"pipelined steps  wait mean {wait['mean']:.2f}ms  "
                    f"ratio {hidden:.0%} of exchange hidden")
    if tier_lines:
        out("")
        out("-- Learner tier (seats + collective) --")
        lines.extend(tier_lines)

    # Inference serving (runtime/inference.py + runtime/serving.py):
    # per-service act throughput, batch occupancy, admission rejects and
    # queue wait; per-actor replica-selection counters. Section only
    # appears when a run served acts (learner-hosted or replica tier).
    infer_lines: list[str] = []
    for shard in shards:
        rates = shard.counter_rates()
        served = rates.get("inference/rows_served")
        if served is not None:
            batches = rates.get("inference/batches_run", {})
            rejects = rates.get("inference/admission_rejects", {})
            per_batch = served["total"] / max(batches.get("total", 0), 1)
            infer_lines.append(
                f"  {shard_label(shard)}: {served['total']:.0f} rows acted "
                f"({served['rate']:.0f}/s) in {batches.get('total', 0):.0f} "
                f"batches ({per_batch:.1f} rows/batch), "
                f"{rejects.get('total', 0):.0f} admission rejects")
            occ = shard.gauge_stats("inference/batch_occupancy")
            wait = shard.gauge_stats("inference/queue_wait_ms")
            if occ is not None or wait is not None:
                parts = []
                if occ is not None:
                    parts.append(f"bucket occupancy mean "
                                 f"{100 * occ['mean']:.0f}%")
                if wait is not None:
                    parts.append(f"queue wait mean {wait['mean']:.2f}ms "
                                 f"max {wait['max']:.2f}ms")
                infer_lines.append("    " + "  ".join(parts))
    for shard in shards:
        rates = shard.counter_rates()
        acts = rates.get("remote_act/acts")
        if acts is None:
            continue
        infer_lines.append(
            f"  {shard_label(shard)}: {acts['total']:.0f} remote acts, "
            f"{rates.get('remote_act/busy_failovers', {}).get('total', 0):.0f}"
            f" busy failovers, "
            f"{rates.get('remote_act/replica_demotes', {}).get('total', 0):.0f}"
            f" replica demotes, "
            f"{rates.get('remote_act/fallback_acts', {}).get('total', 0):.0f}"
            f" fallback acts")
    if infer_lines:
        out("")
        out("-- Inference serving (act path) --")
        lines.extend(infer_lines)

    out("")
    out("-- Weight publication --")
    any_pub = False
    for shard in shards:
        stats = shard.gauge_stats("publish/latency_ms")
        if stats is None:
            continue
        any_pub = True
        out(f"  {shard_label(shard)}: publish latency mean "
            f"{stats['mean']:.2f}ms  max {stats['max']:.2f}ms  "
            f"({stats['n']} publishes)")
    # The publish p99 SPLIT from the trace spans (runtime/publishing.py
    # sub-stages): handoff = the device-side copy dispatch on the learn
    # thread, stall = the bounded-staleness flush — the attribution the
    # fat `publish` mean can't give.
    pub_rows = {(r["proc"], r["stage"]): r for r in rows
                if r["stage"] in ("publish", "publish_handoff",
                                  "publish_stall")}
    for proc in sorted({p for p, _ in pub_rows}):
        parts = []
        for stage in ("publish", "publish_handoff", "publish_stall"):
            r = pub_rows.get((proc, stage))
            if r is not None:
                parts.append(f"{stage} p99 {r['p99_ms']:.2f}ms "
                             f"(n={r['count']})")
        if parts:
            any_pub = True
            out(f"  {proc}: " + "  ".join(parts))
    # Per-rank pull latency (both transports gauge the same name, so a
    # board run and a TCP run read identically here).
    for shard in shards:
        stats = shard.gauge_stats("actor/weight_pull_ms")
        if stats is not None:
            any_pub = True
            out(f"  {shard_label(shard)}: weight pull mean "
                f"{stats['mean']:.2f}ms  max {stats['max']:.2f}ms  "
                f"({stats['n']} pulls)")
    # Shm weight board (runtime/weight_board.py): pull/check/fallback
    # counters per actor rank; lines only appear when a run used the
    # board.
    for shard in shards:
        rates = shard.counter_rates()

        def total(key, rates=rates):
            return rates.get(key, {}).get("total", 0)

        if not total("board/board_checks"):
            continue  # learner shards carry only the publish counters
        any_pub = True
        out(f"  {shard_label(shard)}: board pulls {total('board/board_pulls'):.0f} "
            f"of {total('board/board_checks'):.0f} checks, "
            f"{total('board/seqlock_retries'):.0f} seqlock retries, "
            f"{total('board/tcp_fallbacks'):.0f} tcp fallbacks")
    for shard in shards:
        rates = shard.counter_rates()
        pubs = rates.get("board/publishes", {}).get("total", 0)
        if pubs:
            nbytes = rates.get("board/published_bytes", {}).get("total", 0)
            out(f"  {shard_label(shard)}: board published {pubs:.0f} "
                f"versions ({nbytes / 1e6:.1f} MB total)")
    # Sharded weight plane (runtime/weight_shards.py): learner-side
    # per-shard publish/quant/delta counters plus per-role shard-pull
    # counters (TCP shard op "wshard/", board pulls fold into the board
    # lines above). Lines appear only when a run published per shard.
    wshard_lines: list[str] = []
    for shard in shards:
        rates = shard.counter_rates()

        def total(key, rates=rates):
            return rates.get(key, {}).get("total", 0)

        pubs = total("weights/shard_publishes")
        if pubs:
            per_ver = total("weights/broadcast_bytes") / pubs
            line = (f"  {shard_label(shard)}: {pubs:.0f} sharded publishes, "
                    f"{total('weights/shards_changed') / pubs:.1f} shards/"
                    f"publish, {per_ver / 1e6:.2f} MB broadcast/version")
            if total("weights/quant_bytes_saved"):
                line += (f", quant saved "
                         f"{total('weights/quant_bytes_saved') / 1e6:.1f} MB")
            if total("weights/deltas_encoded"):
                line += (f", {total('weights/deltas_encoded'):.0f} deltas "
                         f"({total('weights/delta_bytes') / 1e6:.2f} MB)")
            wshard_lines.append(line)
        sends = total("transport/shard_sends")
        if sends:
            # Hit rate over SHARDS served (full+delta+skip), not over
            # replies — a 3-shard manifest sends 3 shard units per pull.
            served = (total("transport/shard_full_sends")
                      + total("transport/shard_delta_sends")
                      + total("transport/shard_skip_sends"))
            wshard_lines.append(
                f"  {shard_label(shard)}: served {sends:.0f} shard pulls "
                f"({total('transport/shard_bytes_sent') / 1e6:.1f} MB, "
                f"{total('transport/shard_delta_sends'):.0f} deltas, "
                f"{total('transport/shard_skip_sends'):.0f} unchanged "
                f"elisions — delta hit rate "
                f"{(total('transport/shard_delta_sends') + total('transport/shard_skip_sends')) / max(served, 1):.0%})")
        pulls = total("wshard/shard_pulls")
        if pulls:
            wshard_lines.append(
                f"  {shard_label(shard)}: {pulls:.0f} shard pulls "
                f"({total('wshard/bytes_received') / 1e6:.1f} MB: "
                f"{total('wshard/shards_full'):.0f} full, "
                f"{total('wshard/shards_delta'):.0f} delta, "
                f"{total('wshard/shards_skipped'):.0f} skipped; "
                f"{total('wshard/repair_pulls'):.0f} repairs, "
                f"{total('wshard/whole_fallbacks'):.0f} whole fallbacks)")
        bpulls = total("board/shard_pulls")
        if bpulls:
            wshard_lines.append(
                f"  {shard_label(shard)}: {bpulls:.0f} board shard pulls, "
                f"{total('board/board_shard_fallbacks'):.0f} latched-shard "
                f"tcp fills")
    if wshard_lines:
        any_pub = True
        out("  -- Weight sharding --")
        lines.extend(wshard_lines)
    if not any_pub:
        out("  (no publish/pull gauges)")

    out("")
    out("-- Weight staleness (learner version - actor version at queue "
        "ingest; lower bound on staleness at train time) --")
    any_stale = False
    for shard in shards:
        stats = shard.gauge_stats(_STALE_GAUGE)
        if stats is None:
            continue
        any_stale = True
        out(f"  {shard_label(shard)}: mean {stats['mean']:.2f}  "
            f"max {stats['max']:.0f}  ({stats['n']} ingested unrolls)")
        hist = staleness_buckets_exact(shard) or shard.stale_fallback_hist()
        width = max((c for _, c in hist), default=1)
        for bucket, count in hist:
            bar = "#" * max(1, int(30 * count / width))
            out(f"    {bucket:>6}: {count:>8} {bar}")
    for shard in shards:
        stats = shard.gauge_stats("actor/weight_version")
        if stats is not None:
            any_stale = True
            out(f"  {shard_label(shard)}: last pulled version {stats['last']:.0f}")
    for shard in shards:
        stats = shard.gauge_stats("learner/weight_version")
        if stats is not None:
            out(f"  {shard_label(shard)}: last published version {stats['last']:.0f}")
    if not any_stale:
        out("  (no staleness gauges — actors may not have pulled weights)")

    # Runtime sanitizer (tools/drlint/rt): a chaos run executed
    # under DRL_SANITIZE=1 leaves a sanitize*.jsonl artifact next to
    # the telemetry; render findings-by-rule and the hottest hold-time
    # sites so a sanitized run reads with the same tooling as a plain
    # one. Section only appears when an artifact exists.
    san_lines = sanitizer_section(tdir)
    if san_lines:
        out("")
        out("-- Sanitizer (drlint-rt) --")
        lines.extend(san_lines)

    if merge:
        out("")
        merged = os.path.join(tdir, "trace-merged.json")
        n = merge_traces(tdir, merged)
        out(f"merged trace: {merged} ({n} spans; open in ui.perfetto.dev)")
    return "\n".join(lines)


def sanitizer_artifacts(tdir: str) -> list[str]:
    """sanitize*.jsonl next to the telemetry: in the telemetry dir
    itself or the run dir above it."""
    dirs = [tdir, os.path.dirname(os.path.abspath(tdir))]
    out: list[str] = []
    for d in dirs:
        out.extend(sorted(glob.glob(os.path.join(d, "sanitize*.jsonl"))))
    return sorted(set(out))


def sanitizer_section(tdir: str, top: int = 5) -> list[str]:
    paths = sanitizer_artifacts(tdir)
    if not paths:
        return []
    from tools.drlint.rt.reconcile import Artifact

    art = Artifact.load_many(paths)
    lines: list[str] = []
    lines.append(f"  artifact{'s' if len(paths) > 1 else ''}: "
                 f"{', '.join(paths)} ({len(art.pids)} sanitized "
                 f"process(es))")
    by_rule: dict[str, int] = {}
    for r in art.findings:
        by_rule[r.get("rule", "?")] = by_rule.get(r.get("rule", "?"), 0) + 1
    if by_rule:
        for rule, n in sorted(by_rule.items()):
            lines.append(f"  findings [{rule}]: {n}")
    else:
        lines.append("  findings: 0")
    lines.append(f"  observed: {len(art.edges)} lock edges, "
                 f"{len(art.accesses)} guarded attrs exercised")
    # Leak census (kind: "lifecycle"): per-resource acquire/release
    # tallies, rolled up by owner class so a run report answers "whose
    # threads / segments / sockets, and did they all end" at a glance.
    if art.lifecycle:
        per_res: dict[str, dict[str, int]] = {}
        owners: dict[str, set[str]] = {}
        for rec in art.lifecycle:
            res = rec.get("res", "?")
            a = per_res.setdefault(res, {"n": 0, "ended": 0})
            a["n"] += rec.get("n", 0)
            a["ended"] += rec.get("ended", 0)
            owners.setdefault(res, set()).add(rec.get("owner", "<module>"))
        noun = {"thread": "threads", "shm": "shm segments",
                "socket": "sockets"}
        for res in sorted(per_res):
            a = per_res[res]
            leaked = a["n"] - a["ended"]
            own = ", ".join(sorted(owners[res]))
            lines.append(
                f"  census [{noun.get(res, res)}]: {a['n']} acquired, "
                f"{a['ended']} released"
                + (f", {leaked} LEAKED" if leaked else "")
                + f"  (owners: {own})")
    holds = sorted(art.holds.items(),
                   key=lambda kv: kv[1]["max_ms"], reverse=True)[:top]
    if holds:
        lines.append(f"  top hold-time sites (by max):")
        for site, h in holds:
            mean = h["total_ms"] / max(h["count"], 1)
            lines.append(f"    {site:<58} {h['count']:>7}x  "
                         f"mean {mean:>8.2f}ms  max {h['max_ms']:>9.1f}ms")
    lines.append("  reconcile: python -m tools.drlint --reconcile "
                 f"{paths[0]}")
    return lines


def profile_report(profile_dir: str) -> str:
    """The scope ledger of the newest profile under `profile_dir` and the
    host spans of the same file."""
    from distributed_reinforcement_learning_tpu.observability import attribution

    led = attribution.ledger(profile_dir, attribution.program_vocabulary())
    if led is None:
        return (f"no device op in a profile under {profile_dir} "
                f"(none written, or a CPU run)")
    lines = [f"== Scope ledger: {attribution.xplane_path(profile_dir)} ==",
             attribution.table(led), "", "-- Host spans (same clock) --"]
    for name, (count, seconds) in sorted(
            attribution.host_spans(profile_dir).items()):
        lines.append(f"  {name:24s} {count:5d} x {1e3 * seconds / count:10.3f} ms")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("run_dir", nargs="?",
                   help="run directory (or the telemetry dir itself)")
    p.add_argument("--no-merge", action="store_true",
                   help="skip writing trace-merged.json")
    p.add_argument("--profile", metavar="DIR",
                   help="print the scope ledger of a jax.profiler trace instead")
    args = p.parse_args(argv)
    if args.profile:
        print(profile_report(args.profile))
        return 0
    if not args.run_dir:
        p.error("a run directory, or --profile DIR")
    print(build_report(find_telemetry_dir(args.run_dir), merge=not args.no_merge))
    return 0


if __name__ == "__main__":
    sys.exit(main())

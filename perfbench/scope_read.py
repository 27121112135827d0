"""A traced run's own `.xplane.pb`, read by the names of the program's
vocabulary (`distributed_reinforcement_learning_tpu/observability/
scopes.py`): device self time per named scope, and the fused loop's host
spans. No JAX here: the reducers run in `run.py`'s parent process.

Scope names do not reach the events that `jax.profiler.ProfileData`
yields (`trace_reduce._detail` asks for `tf_op` and gets nothing), so
`facts["trace"]` cannot tell a scope. The profiler's own converter can:
`hlo_stats` gives one row per HLO op with `tf_op_name` (the `op_name`
metadata, scopes included) and `total_self_time` (a `while` does not
count its body twice); `trace_viewer` gives the host plane, on which a
`jax.profiler.TraceAnnotation` of the program is an event on the device
ops' own clock. Both are converted once per run and kept in `facts`.

A fusion belongs to the scope its own metadata names: where XLA fused
across a boundary, one side gets all of it. An op's scope is the name of
the vocabulary that ends deepest in its path; the vocabulary is every
name the files under `layer_metrics/` list as `scopes` or `less`, so a
later metric on a deeper scope is a file, and its parent still counts
the time (a scope holds its children).

A test hands a recording instead: `facts["scope_recording"] =
{"hlo_stats": [[hlo_op_name, tf_op_name, self_us], ...],
 "host_spans": [[name, start_us, dur_us], ...]}`.
"""

from __future__ import annotations

import glob
import json
import os
import re

HOST_PLANE = "/host:CPU"


def xplane_path(facts: dict) -> str | None:
    """The newest `.xplane.pb` of the run's traced interval."""
    paths = glob.glob(os.path.join(
        facts.get("run_dir", ""), "profile", "plugins", "profile", "*",
        "*.xplane.pb"))
    return sorted(paths)[-1] if paths else None


def _convert(facts: dict, tool: str):
    from xprof.convert import raw_to_tool_data  # no JAX behind it

    path = xplane_path(facts)
    if path is None:
        return None
    data, _ = raw_to_tool_data.xspace_to_tool_data([path], tool, {})
    return json.loads(data)


def _cached(facts: dict, key: str, make):
    cache = facts.setdefault("_scope_read", {})
    if key not in cache:
        recording = facts.get("scope_recording")
        cache[key] = recording.get(key) if recording else make()
    return cache[key]


def hlo_stats(facts: dict) -> list | None:
    """[[hlo_op_name, tf_op_name, self_us], ...] of the traced interval,
    one row per HLO op of each program; None without a profile."""

    def make():
        table = _convert(facts, "hlo_stats")
        if not table:
            return None
        col = {c["id"]: i for i, c in enumerate(table["cols"])}
        return [[row["c"][col["hlo_op_name"]]["v"],
                 row["c"][col["tf_op_name"]]["v"],
                 float(row["c"][col["total_self_time"]]["v"])]
                for row in table["rows"]]

    return _cached(facts, "hlo_stats", make)


def host_spans(facts: dict) -> list | None:
    """[[name, start_us, dur_us], ...]: the complete events of the host
    plane of the same file; None without a profile."""

    def make():
        trace = _convert(facts, "trace_viewer")
        if not trace:
            return None
        events = trace["traceEvents"]
        pids = {e["pid"] for e in events
                if e.get("ph") == "M" and e.get("name") == "process_name"
                and e.get("args", {}).get("name") == HOST_PLANE}
        return [[e["name"], float(e["ts"]), float(e["dur"])] for e in events
                if e.get("ph") == "X" and e.get("pid") in pids]

    return _cached(facts, "host_spans", make)


def vocabulary(data_dir: str) -> list[str]:
    """Every scope name a `layer_metrics/*.json` lists."""
    names = set()
    for path in glob.glob(os.path.join(data_dir, "layer_metrics", "*.json")):
        with open(path) as f:
            detail = json.load(f).get("source_detail", {})
        names.update(detail.get("scopes", []), detail.get("less", []))
    return sorted(names)


def scope_of(op_path: str, names: list[str]) -> str | None:
    """The name that ends deepest in `op_path` as whole path elements
    (`/`-separated; a transformation wraps them in parentheses, as in
    `transpose(jvp(learn/loss))`); the longer name on a tie."""
    best, best_end = None, -1
    for name in names:
        for m in re.finditer(rf"(?:^|(?<=[/(])){re.escape(name)}(?=[/)]|$)",
                             op_path):
            if (m.end(), len(name)) > (best_end, len(best or "")):
                best, best_end = name, m.end()
    return best


def _under(scope: str | None, roots: list[str]) -> bool:
    return scope is not None and any(
        scope == r or scope.startswith(r + "/") for r in roots)


def scope_seconds(facts: dict, scopes: list[str],
                  less: tuple | list = ()) -> float | None:
    """Device self seconds of the ops under `scopes` (each with its
    children) and not under `less`; None where no op of the trace is
    under any name of the vocabulary: a program without scopes, or an
    executable that another commit compiled (scopes.py, CACHE_TAG)."""
    rows = hlo_stats(facts)
    if not rows:
        return None
    names = vocabulary(facts["data_dir"])
    cache = facts.setdefault("_scope_read", {}).setdefault("scope_of", {})
    total, any_scoped = 0.0, False
    for _hlo, op_path, self_us in rows:
        if op_path not in cache:
            cache[op_path] = scope_of(op_path, names)
        scope = cache[op_path]
        any_scoped = any_scoped or scope is not None
        if _under(scope, scopes) and not _under(scope, list(less)):
            total += self_us
    return total / 1e6 if any_scoped else None

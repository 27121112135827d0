"""The benchmark's last line, checked before it is printed.

Every run of `run.py`, traced or not, builds its last line and hands it
to `check_line` first. A line that breaks the contract is never printed:
the run fails loudly on stderr and exits non-zero instead (PR 22 was
refused over one traced line whose `busy_s` overshot `window_s`).

No JAX here: the parent process of a run imports this module.
"""

from __future__ import annotations

import math
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
BREAKDOWN_KEYS = ("device_ops", "idle_gaps")
BREAKDOWN_MAX = 10


class ContractError(ValueError):
    """The line (or `BENCHMARK.json`) does not meet the contract."""


def _number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def cell_metrics(bench: dict, cell: str, traced: bool) -> dict[str, dict]:
    """The metrics `cell` reports: its `end_to_end` metrics with
    `--trace 0`, its `per_layer` metrics with `--trace 1`. A metric
    without a `workloads` key belongs to every cell."""
    if cell not in {w["name"] for w in bench["workloads"]}:
        raise ContractError(f"BENCHMARK.json has no workload {cell!r}")
    group = bench["per_layer" if traced else "end_to_end"]
    return {m["name"]: m for m in group
            if "workloads" not in m or cell in m["workloads"]}


def check_line(line: dict, bench: dict, cell: str, traced: bool,
               chips: int | None = None) -> dict:
    """-> `line` if it is the contract's object for `cell`; raises
    `ContractError` naming the first fault otherwise."""
    if not isinstance(line, dict):
        raise ContractError("the last line is not a JSON object")
    for key in LINE_KEYS:
        if key not in line:
            raise ContractError(f"key {key!r} is missing")
    extra = set(line) - set(LINE_KEYS) - {"breakdown"}
    if extra:
        raise ContractError(f"unexpected keys {sorted(extra)}")
    if not isinstance(line["correct"], bool):
        raise ContractError("`correct` is not true or false")
    for key in ("attempted", "failed"):
        if not isinstance(line[key], int) or isinstance(line[key], bool) \
                or line[key] < 0:
            raise ContractError(f"`{key}` is not a count: {line[key]!r}")
    if line["failed"] > line["attempted"] + 64:
        raise ContractError("`failed` far exceeds `attempted`")

    want = cell_metrics(bench, cell, traced)
    got = line["metrics"]
    if not isinstance(got, dict):
        raise ContractError("`metrics` is not an object")
    for name, spec in want.items():
        if name not in got:
            raise ContractError(f"metric {name!r} of {cell!r} is missing")
        entry = got[name]
        if not isinstance(entry, dict) or set(entry) != {"value", "unit"}:
            raise ContractError(f"metric {name!r} is not {{value, unit}}")
        if not _number(entry["value"]):
            raise ContractError(
                f"metric {name!r} has no finite value: {entry['value']!r}")
        if entry["unit"] != spec["unit"]:
            raise ContractError(
                f"metric {name!r} has unit {entry['unit']!r}, "
                f"BENCHMARK.json says {spec['unit']!r}")
        if ("roofline" in name or "mfu" in name) and entry["value"] > 105.0:
            raise ContractError(
                f"{name} reads {entry['value']}% of a peak: the operations "
                f"or bytes are counted too high, or the time leaves out work")
    for name, entry in got.items():
        if name not in want:
            raise ContractError(f"metric {name!r} is not one of {cell!r}'s")
        if not NAME_RE.match(name):
            raise ContractError(f"metric name {name!r} has a bad character")
        if not UNIT_RE.match(str(entry["unit"])):
            raise ContractError(
                f"unit {entry['unit']!r} of {name!r}: 1 to 16 of "
                f"letters, digits, _ / % . -")
    if "setup_s" in want and got["setup_s"]["value"] <= 0:
        raise ContractError("setup_s is not above 0")

    dev = line["device"]
    if not isinstance(dev, dict):
        raise ContractError("`device` is not an object")
    for key in DEVICE_KEYS:
        if key not in dev:
            raise ContractError(f"device.{key} is missing")
    if not isinstance(dev["platform"], str) or not isinstance(dev["kind"], str):
        raise ContractError("device.platform / device.kind are not strings")
    if not isinstance(dev["count"], int) or dev["count"] < 1:
        raise ContractError(f"device.count {dev['count']!r}")
    if chips is not None and dev["count"] != chips:
        raise ContractError(
            f"the cell asks for {chips} chip(s), the run saw {dev['count']}")
    if not _number(dev["memory_peak_bytes"]) or dev["memory_peak_bytes"] < 0:
        raise ContractError(
            f"device.memory_peak_bytes {dev['memory_peak_bytes']!r}")
    if traced:
        for key in ("busy_s", "window_s"):
            if key not in dev or not _number(dev[key]):
                raise ContractError(f"traced run without device.{key}")
        if not 0 < dev["busy_s"] <= dev["window_s"]:
            raise ContractError(
                f"busy_s {dev['busy_s']} is not in (0, window_s "
                f"{dev['window_s']}]: the device was never busy, or "
                f"nested trace lines were added up")
    if "breakdown" in line:
        bd = line["breakdown"]
        if not traced:
            raise ContractError("`breakdown` on an untraced run")
        if not isinstance(bd, dict) or set(bd) - set(BREAKDOWN_KEYS):
            raise ContractError("`breakdown` has keys other than "
                                "device_ops and idle_gaps")
        for key, rows in bd.items():
            if not isinstance(rows, list) or len(rows) > BREAKDOWN_MAX:
                raise ContractError(f"breakdown.{key}: at most 10 rows")
            for row in rows:
                if (not isinstance(row, list) or len(row) != 2
                        or not isinstance(row[0], str) or not _number(row[1])):
                    raise ContractError(
                        f"breakdown.{key} row {row!r} is not [name, seconds]")
    return line

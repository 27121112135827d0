#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run: loads, warms up (set-up), measures for
`--seconds`, prints earlier lines of detail and, LAST, one JSON object
`{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"]}`
that `contract.check_line` has passed — the cell's end-to-end metrics
with `--trace 0`, its per-layer metrics with `--trace 1`. Exit code 0
only then. No accelerator (or fewer chips than the cell asks for), a
directory without the program, a failed child, a trace that cannot be
reduced soundly: a message on stderr, a non-zero exit code and NO
result line.

Everything that belongs to one cell, configuration, traffic mix,
per-layer metric, reducer or mode is a file found by its name:

    BENCHMARK.json                       which cells and metrics exist
    perfbench/workloads/<cell>.json      configuration + traffic of a cell
    perfbench/configs/<config>.json      the section the program loads
    perfbench/traffic/<traffic>.json     mode and its parameters
    perfbench/layer_metrics/<name>.json  reducer + source of a metric
    perfbench/reducers/<reducer>.py      reduce(facts, spec) -> number
    perfbench/modes/<mode>.py            run(ctx) -> result
    perfbench/families/<algorithm>.py    launcher, reference check, FLOPs
    perfbench/torsos/<torso>.py          multiply-adds of a torso

This parent process never imports JAX: the chip belongs to the child
that a mode starts. `--expect-platform`, `--data-dir` and `--benchmark`
exist for the tests (the CPU rehearsal and the discovery test); they
are arguments of this script, not options of the program.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

T_START = time.time()  # set-up counts from the start of the process
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import contract  # noqa: E402
import discover  # noqa: E402

OUT_DIR = os.path.join(ROOT, "perfbench_out")  # in .gitignore


class NoDevice(Exception):
    """JAX found no device of the expected platform: nothing is run."""


class RunFailed(Exception):
    """A child failed, or what it left cannot be turned into a result."""


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(bench: dict, data_dir: str, cell: str) -> dict:
    """Everything the cell names, found by name under `data_dir`."""
    entry = next((w for w in bench["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise RunFailed(f"BENCHMARK.json has no workload {cell!r}")
    workload = discover.data(data_dir, "workloads", cell)
    for key in ("config", "traffic"):
        if workload[key] != entry[key]:
            raise RunFailed(f"{cell}: {key} {workload[key]!r} in its file, "
                            f"{entry[key]!r} in BENCHMARK.json")
    config_path = os.path.join(data_dir, "configs", f"{entry['config']}.json")
    traffic = discover.data(data_dir, "traffic", entry["traffic"])
    traffic.update(workload.get("overrides", {}))
    return {"entry": entry, "workload": workload, "traffic": traffic,
            "config": _load_json(config_path), "config_path": config_path}


def layer_metrics(bench: dict, data_dir: str, cell: str, facts: dict,
                  notes: list) -> dict:
    """Each per-layer metric of the cell through its own reader; a
    reader that finds nothing to read returns None and is left out (the
    contract check then names what is missing). A reader that raises
    (a family or torso whose count no file brings, a device without
    published peaks) fails the run, with the metric's name."""
    out = {}
    for name, meta in contract.cell_metrics(bench, cell, traced=True).items():
        spec = discover.data(data_dir, "layer_metrics", name)
        try:
            value = discover.module(data_dir, "reducers",
                                    spec["reducer"]).reduce(facts, spec)
        except Exception as e:  # noqa: BLE001 - any reader, any fault
            raise RunFailed(f"per-layer metric {name}: "
                            f"{type(e).__name__}: {e}") from e
        if value is None:
            notes.append(f"per-layer metric {name}: nothing to read")
            continue
        out[name] = {"value": value, "unit": meta["unit"]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--expect-platform", default="tpu",
                    help="(tests) the platform the run must find")
    ap.add_argument("--data-dir", default=HERE,
                    help="(tests) where workloads/, configs/, traffic/, "
                         "layer_metrics/, reducers/, modes/, families/ and "
                         "torsos/ are looked up")
    ap.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="(tests) another BENCHMARK.json")
    args = ap.parse_args()
    args.data_dir = os.path.abspath(args.data_dir)

    try:
        bench = _load_json(args.benchmark)
        cell = load_cell(bench, args.data_dir, args.workload)
        if not os.path.isdir(os.path.join(
                ROOT, "distributed_reinforcement_learning_tpu")):
            raise RunFailed("the program is not in this directory")
        mode = discover.module(args.data_dir, "modes", cell["traffic"]["mode"])
        out_dir = os.path.join(OUT_DIR, args.workload,
                               f"seed{args.seed}-trace{args.trace}")
        shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(out_dir)
        result = mode.run({
            "root": ROOT, "bench_dir": HERE, "data_dir": args.data_dir,
            "out_dir": out_dir, "args": args,
            "config": cell["config"], "traffic": cell["traffic"],
            "chips": cell["entry"]["chips"], "t_start": T_START,
            "NoDevice": NoDevice, "RunFailed": RunFailed})
        notes = result["notes"]
        facts = result.get("facts", {})
        facts.update({"notes": notes, "data_dir": args.data_dir})
        device = dict(result["device"])
        line = {"correct": bool(result["correct"]),
                "attempted": int(result["attempted"]),
                "failed": int(result["failed"]), "device": device}
        if args.trace:
            trace = facts.get("trace")
            if not trace:
                raise RunFailed("the traced run left no reduced trace")
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            line["breakdown"] = trace["breakdown"]
            notes.append(f"trace lines: {json.dumps(trace['inventory'])}")
            line["metrics"] = layer_metrics(bench, args.data_dir,
                                            args.workload, facts, notes)
        else:
            wanted = contract.cell_metrics(bench, args.workload, traced=False)
            line["metrics"] = {
                name: {"value": result["e2e"][name], "unit": meta["unit"]}
                for name, meta in wanted.items() if name in result["e2e"]}
        for note in notes:
            print(f"[perfbench] {note}", flush=True)
        contract.check_line(line, bench, args.workload, bool(args.trace),
                            chips=cell["entry"]["chips"])
    except NoDevice as e:
        print(f"[perfbench] NO DEVICE: {e}", file=sys.stderr)
        return 3
    except (RunFailed, contract.ContractError, OSError, KeyError,
            json.JSONDecodeError) as e:
        print(f"[perfbench] FAILED ({type(e).__name__}): {e}", file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

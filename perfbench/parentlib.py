"""What the parent side of every mode shares: the children's
environment and command line, and the checks on a child's result that
do not depend on the mode. No JAX here: the parent never holds the chip.
"""

from __future__ import annotations

import json
import os


def child_env(ctx: dict) -> dict:
    """The environment of every process a mode starts: the traffic
    file's `env` over the caller's, unbuffered output, the program and
    the benchmark's own modules importable, and no `BENCH_RUN` (the
    driver's own variable, of which the benchmark takes no notice)."""
    env = {**os.environ, "PYTHONUNBUFFERED": "1",
           **{k: str(v) for k, v in ctx["traffic"].get("env", {}).items()}}
    env.pop("BENCH_RUN", None)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (ctx["root"], ctx["bench_dir"], env.get("PYTHONPATH")) if p)
    return env


def program_seed(seed: int) -> int:
    """The program seeds `PRNGKey(seed)` and `RandomState(seed + 1 +
    task)`: fold the driver's large seeds into what both accept."""
    return seed % (2 ** 31 - 1024)


def child_args(ctx: dict, run_cfg: str, section_name: str) -> list[str]:
    """The arguments `childlib.child_parser` reads."""
    args = ctx["args"]
    return ["--config", run_cfg, "--section", section_name,
            "--seed", str(program_seed(args.seed)),
            "--seconds", str(args.seconds), "--trace", str(args.trace),
            "--out", ctx["out_dir"], "--params", json.dumps(ctx["traffic"]),
            "--expect-platform", args.expect_platform,
            "--chips", str(ctx["chips"]), "--data-dir", ctx["data_dir"]]


def common_problems(res: dict, cfg: dict, updates: int) -> list[str]:
    """Why a run is not `correct`, as far as every mode's child reports
    it the same way: the reference comparison, the Mosaic kernels the
    configuration names, compilation inside the window, parameters that
    did not move, no update at all."""
    problems = []
    if not res["reference"]["ok"]:
        problems.append(f"loss differs from the reference: {res['reference']}")
    want = cfg.get("kernels", {}).get("tpu_custom_call")
    if (want is not None and res["device"]["platform"] == "tpu"
            and res.get("kernels") != want):
        problems.append(f"the learn step holds {res.get('kernels')} Mosaic "
                        f"kernels, the configuration names {want}")
    if res["window_monitoring"]["events"]:
        problems.append(f"{res['window_monitoring']['events']} trace/lower/"
                        f"compile events inside the window")
    if not res["params_changed"]:
        problems.append("parameters did not change over the window")
    if updates <= 0:
        problems.append("no update completed in the window")
    return problems

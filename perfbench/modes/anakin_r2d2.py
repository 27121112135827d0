"""Mode `anakin_r2d2`: the fused on-device replay loop
(`runtime/anakin_r2d2.py`: collect, score, ring write, K x (prioritized
sample, learn, priority write-back)), built and driven by the program's
own `runtime/launch.train_anakin_r2d2`, with the benchmark watching.

As in `modes/anakin.py`, whose observer this one extends: the child
calls `train_anakin_r2d2` once and lets it run (its warm-up fills the
ring through `collect_chunk`, then its loop dispatches `train_chunk`),
and the benchmark only stamps the entries of `AnakinR2D2.train_chunk`.
The window is a whole number of chunks, opened at the entry after the
warm chunks and closed at the first entry after `--seconds`.

A program that cannot run the configuration (no `n_step`, no
`dueling_hidden`: every commit before PR 26) is told apart BEFORE the
chip is opened or anything is built: the child exits with
`EXIT_UNSUPPORTED` and one line, the run fails with no result line.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
TIMEOUT_S = 1100.0  # a run that has not ended by then has failed
ALGORITHM = "r2d2_atari"  # families/r2d2_atari.py, whatever the section says
EXIT_UNSUPPORTED = 5
SECTION_KEYS = ("updates_per_call", "train_start_factor")  # the traffic's


def run(ctx: dict) -> dict:
    import parentlib

    cfg, traffic = ctx["config"], ctx["traffic"]
    out = ctx["out_dir"]
    section_name = cfg["section"]
    # The program reads K and its warm-up from the section: the traffic
    # mix's values lie over the configuration's.
    section = {**cfg[section_name], **{k: traffic[k] for k in SECTION_KEYS}}
    run_cfg = os.path.join(out, "config.json")
    with open(run_cfg, "w") as f:
        json.dump({section_name: section}, f)
    log_path = os.path.join(out, "anakin_r2d2.log")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             *parentlib.child_args(ctx, run_cfg, section_name)],
            cwd=ctx["root"], env=parentlib.child_env(ctx), stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True)
        try:
            rc = proc.wait(timeout=TIMEOUT_S)
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            if proc.poll() is None:
                proc.kill()
            proc.wait()
    if rc == 3:
        raise ctx["NoDevice"]("JAX found no device of the expected platform")
    if rc == EXIT_UNSUPPORTED:
        with open(log_path) as f:
            said = [line.strip() for line in f if "[perfbench]" in line]
        raise ctx["RunFailed"](said[-1] if said else "unsupported configuration")
    result_path = os.path.join(out, "anakin_r2d2_result.json")
    if rc != 0 or not os.path.exists(result_path):
        raise ctx["RunFailed"](
            f"anakin_r2d2 child ended with code {rc}; see {log_path}")
    with open(result_path) as f:
        res = json.load(f)

    window = res["t1"] - res["t0"]
    updates, k = res["updates"], section["updates_per_call"]
    problems = parentlib.common_problems(res, cfg, updates)
    if res["bad_updates"]:
        problems.append(f"{res['bad_updates']} updates with a non-finite loss "
                        f"or a gradient norm that is not positive")
    if not res["sampler"]["ok"]:
        problems.append(f"the sampler differs from the numpy sampler: "
                        f"{res['sampler']}")
    if res["replay_size_at_open"] != res["capacity"] \
            or res["replay_size_min"] != res["capacity"]:
        problems.append(
            f"the ring of {res['capacity']} sequences held "
            f"{res['replay_size_at_open']} when the window opened and at "
            f"least {res['replay_size_min']} inside it")
    frames_per_update = k * section["batch_size"] * section["seq_len"]
    notes = [f"window {window:.3f} s, {updates} updates ({updates * k} "
             f"optimizer steps) in {len(res['chunk_seconds'])} chunks of "
             f"{res['chunk_updates']} (chunk seconds: "
             f"{[round(s, 4) for s in res['chunk_seconds']]}), "
             f"{res['num_envs']} envs, ring {res['capacity']} sequences, "
             f"counters {res['counters']}, machine {res['machine']}, "
             f"device memory {res['memory_stats']}, "
             f"reference {res['reference']}, sampler {res['sampler']}"]
    notes += [f"NOT CORRECT: {p}" for p in problems]
    e2e = {"frames_learned_per_s": updates * frames_per_update / window,
           "setup_s": res["t0"] - ctx["t_start"]}
    facts = {**res, "window_s": window, "run_dir": out, "section": section,
             "chips": ctx["chips"], "learn_batch": section["batch_size"],
             "frames_per_update": frames_per_update}
    return {"device": {**res["device"],
                       "memory_peak_bytes": res["memory_peak_bytes"]},
            "correct": not problems, "attempted": updates,
            "failed": res["bad_updates"], "e2e": e2e, "facts": facts,
            "notes": notes}


# ------------------------------------------------------------------ child


def _unsupported(config_path: str, section_name: str, section: dict):
    """Why this program cannot run the section, or None: the loaded
    agent configuration must carry the section's `n_step` and
    `dueling_hidden` (a `load_config` that ignores them would build
    another network and train it on 1-step targets)."""
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    agent_cfg, _ = load_config(config_path, section_name)
    for key in ("n_step", "dueling_hidden"):
        if key in section and getattr(agent_cfg, key, None) != section[key]:
            return (f"this program's load_config does not read `{key}` "
                    f"(section {section_name!r} says {section[key]!r}, the "
                    f"agent configuration {getattr(agent_cfg, key, None)!r}): "
                    f"it cannot run this configuration")
    return None


def _watch_class(base):
    """`modes/anakin.py`'s observer, for a chunk whose state carries a
    ring and whose metrics are the replay family's."""
    import numpy as np

    class ReplayWatch(base._ChunkWatch):
        def _entry(self, anakin, jitted, state, updates):
            if self.entries == 0:  # set-up, on the full ring the program built
                self.out["capacity"] = anakin.capacity
                self.out["sampler"] = self.family.sampler_check(
                    anakin, state.replay, self.seed)
                print(f"[perfbench] sampler check: {self.out['sampler']}",
                      flush=True)
            if self.entries == base.WARM_CHUNKS:  # the window opens
                self.out["replay_size_at_open"] = int(state.replay.size)
            super()._entry(anakin, jitted, state, updates)

        def _close(self, state, t1):
            # the base class reads IMPALA's name for the loss
            self.metrics = [{**m, "total_loss": m["loss"]}
                            for m in self.metrics]
            super()._close(state, t1)
            every = lambda key: np.concatenate(
                [np.asarray(m[key]).reshape(-1) for m in self.metrics])
            self.out["replay_size_min"] = int(every("replay_size").min())
            self.out["counters"] = {
                "priority_mean": float(every("priority_mean").mean()),
                "priority_max": float(every("priority_max").max()),
                "is_weight_min": float(every("is_weight_min").min()),
                "target_syncs": int(every("target_syncs").sum()),
                "train_step": int(state.train.step)}

    return ReplayWatch


def _child() -> int:
    import childlib
    import discover

    args = childlib.child_parser().parse_args()
    params = json.loads(args.params)
    if args.expect_platform == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
    with open(args.config) as f:
        section = json.load(f)[args.section]
    why_not = _unsupported(args.config, args.section, section)
    if why_not:  # before the chip is opened or anything is built
        print(f"[perfbench] UNSUPPORTED: {why_not}", file=sys.stderr)
        return EXIT_UNSUPPORTED

    from distributed_reinforcement_learning_tpu.runtime import (
        anakin_r2d2, launch)
    from distributed_reinforcement_learning_tpu.utils.device import (
        enable_compile_cache)

    enable_compile_cache()
    clock = childlib.CompileClock()
    device = childlib.open_chip("perfbench", args.expect_platform, args.chips)
    base = discover.module(os.path.dirname(HERE), "modes", "anakin")
    family = discover.module(args.data_dir, "families", ALGORITHM)
    out: dict = {"device": device, "machine": childlib.machine_facts(),
                 "algorithm": ALGORITHM}
    tracer = (childlib.TraceWindow(os.path.join(args.out, "profile"))
              if args.trace else None)
    watch = _watch_class(base)(family, section, args.seed, args.seconds,
                               tracer, clock, out)
    built = anakin_r2d2.AnakinR2D2.__init__

    def build_and_watch(self, *a, **kw):
        built(self, *a, **kw)
        watch.attach(self)

    anakin_r2d2.AnakinR2D2.__init__ = build_and_watch
    try:
        launch.train_anakin_r2d2(
            args.config, args.section, num_updates=10 ** 9,
            chunk=int(params["chunk_updates"]), seed=args.seed,
            num_envs=int(params["num_envs"]), capacity=int(params["capacity"]))
    except base._WindowClosed:
        pass
    finally:
        anakin_r2d2.AnakinR2D2.__init__ = built
    if "t1" not in out:
        print("[perfbench] train_anakin_r2d2 ended before the window closed",
              file=sys.stderr)
        return 1
    if tracer is not None:
        out["trace"] = tracer.reduce(device["platform"], args.chips,
                                     watch.spans, args.out)
        if out["trace"] is None:
            return 4
    childlib.write_result(args.out, "anakin_r2d2_result.json", out)
    return 0


if __name__ == "__main__":
    sys.exit(_child())

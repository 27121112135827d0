"""Mode `anakin_tokens`: the fused on-device token loop
(`runtime/anakin_tokens.py`: N token envs each play one episode by
decode through a per-pass key/value cache, then one V-trace learn step
over the N x T rollout), built and driven by the program's own
`runtime/launch.train_anakin_tokens`, with the benchmark watching.

As in `modes/anakin.py`, whose observer this one extends: the child
calls `train_anakin_tokens` once and lets it run, and the benchmark only
stamps the entries of `AnakinTokens.train_chunk`. The window is a whole
number of chunks, opened at the entry after the warm chunks and closed at
the first entry after `--seconds`.

Two comparisons with the plain reference decide `correct`
(`families/looplm.py`): (a) a seeded batch at the stated precision and at
`highest`; (b) the reference's replay of the FIRST WARM CHUNK, the
compiled program that the window drives, at the timed sizes: each
update's log mu(a_t), loss terms and gradient norm, and the parameters
the chunk ended with. The plain reference is yardstick, not program: its
eager float32 gradients hold more of the chip than the chunk does and
take longer than the chunk's compile. So the child that is timed only
RECORDS (a strided sample of the parameters before and after the first
warm chunk, that chunk's rollouts and logged metrics: 3 MB), and a
SECOND PROCESS makes both comparisons after the window, when the first
has left the chip: `setup_s` and `memory_peak_bytes` are the program's.
It builds the program's initial state the way the program does, by
letting `train_anakin_tokens` run up to its first chunk, and holds that
state's sample against the recorded one before it compares anything.

A program that cannot run the configuration (no `looplm` family in its
`load_config`, no `train_anakin_tokens`: every commit before PR 30) is
told apart BEFORE the chip is opened or anything is built: the child
exits with `EXIT_UNSUPPORTED` and one line, the run fails with no result
line.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
TIMEOUT_S = 1100.0  # a run that has not ended by then has failed
CHECK_TIMEOUT_S = 900.0
EXIT_UNSUPPORTED = 5
RECORD = "first_chunk.npz"
COUNTERS = ("exit_cdf_pass1", "exit_cdf_pass2", "exit_cdf_pass3",
            "exit_entropy", "rho_clipped_share", "behaviour_logp_mean")


def _child_process(ctx: dict, argv: list, log_path: str, timeout: float):
    """Run this file as a child to its end (or `timeout`) -> exit code,
    None if it had to be killed."""
    import parentlib

    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), *argv],
            cwd=ctx["root"], env=parentlib.child_env(ctx), stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True)
        try:
            return proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            return None
        finally:
            if proc.poll() is None:
                proc.kill()
            proc.wait()


def run(ctx: dict) -> dict:
    import parentlib

    cfg = ctx["config"]
    out = ctx["out_dir"]
    section_name = cfg["section"]
    section = cfg[section_name]
    run_cfg = os.path.join(out, "config.json")
    with open(run_cfg, "w") as f:
        json.dump({section_name: section}, f)
    log_path = os.path.join(out, "anakin_tokens.log")
    argv = parentlib.child_args(ctx, run_cfg, section_name)
    rc = _child_process(ctx, argv, log_path, TIMEOUT_S)
    if rc == 3:
        raise ctx["NoDevice"]("JAX found no device of the expected platform")
    if rc == EXIT_UNSUPPORTED:
        with open(log_path) as f:
            said = [line.strip() for line in f if "[perfbench]" in line]
        raise ctx["RunFailed"](said[-1] if said else "unsupported configuration")
    result_path = os.path.join(out, "anakin_tokens_result.json")
    if rc != 0 or not os.path.exists(result_path):
        raise ctx["RunFailed"](
            f"anakin_tokens child ended with code {rc}; see {log_path}")
    with open(result_path) as f:
        res = json.load(f)
    # The chip is free again: the comparisons with the plain reference.
    check_log = os.path.join(out, "anakin_tokens_check.log")
    check_path = os.path.join(out, "anakin_tokens_check.json")
    rc = _child_process(ctx, [*argv, "--check", "1"], check_log, CHECK_TIMEOUT_S)
    if rc != 0 or not os.path.exists(check_path):
        raise ctx["RunFailed"](
            f"anakin_tokens check ended with code {rc}; see {check_log}")
    with open(check_path) as f:
        res.update(json.load(f))  # `reference` (a), `chunk` (b), `check_s`

    window = res["t1"] - res["t0"]
    updates = res["updates"]
    problems = parentlib.common_problems(res, cfg, updates)
    if res["bad_updates"]:
        problems.append(f"{res['bad_updates']} updates with a non-finite loss "
                        f"or a gradient norm that is not positive")
    if not res["chunk"]["ok"]:
        problems.append(f"the first warm chunk differs from the reference's "
                        f"replay of it: {res['chunk']}")
    if res["static"]["loop_passes"] != section["total_ut_steps"]:
        problems.append(f"the chunk runs {res['static']['loop_passes']} loop "
                        f"passes, the configuration {section['total_ut_steps']}")
    frames_per_update = res["num_envs"] * section["trajectory"]
    if frames_per_update != cfg.get("frames_per_update", frames_per_update):
        problems.append(f"{frames_per_update} frames an update, the "
                        f"configuration says {cfg['frames_per_update']}")
    notes = [f"window {window:.3f} s, {updates} updates in "
             f"{len(res['chunk_seconds'])} chunks of {res['chunk_updates']} "
             f"(chunk seconds: {[round(s, 4) for s in res['chunk_seconds']]}), "
             f"{res['num_envs']} envs, static {res['static']}, "
             f"counters {res['counters']}, parameter leaves moved "
             f"{res['leaves_moved']}, machine {res['machine']}, "
             f"device memory {res['memory_stats']}, "
             f"the check's process {res['check_s']} s and its device memory "
             f"{res['check_memory_stats']}, "
             f"reference {res['reference']}, chunk {res['chunk']}"]
    notes += [f"NOT CORRECT: {p}" for p in problems]
    e2e = {"frames_learned_per_s": updates * frames_per_update / window,
           "setup_s": res["t0"] - ctx["t_start"]}
    facts = {**res, "window_s": window, "run_dir": out, "section": section,
             "chips": ctx["chips"], "learn_batch": res["num_envs"],
             "frames_per_update": frames_per_update}
    return {"device": {**res["device"],
                       "memory_peak_bytes": res["memory_peak_bytes"]},
            "correct": not problems, "attempted": updates,
            "failed": res["bad_updates"], "e2e": e2e, "facts": facts,
            "notes": notes}


# ------------------------------------------------------------------ children


def _unsupported(config_path: str, section_name: str):
    """Why this program cannot run the section, or None: its
    `load_config` must know the section's family and its launcher must
    have the fused token loop."""
    from distributed_reinforcement_learning_tpu.runtime import launch
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    try:
        load_config(config_path, section_name)
    except (ValueError, KeyError) as e:
        return (f"this program's load_config cannot read section "
                f"{section_name!r} ({type(e).__name__}: {e}): it cannot run "
                f"this configuration")
    if not hasattr(launch, "train_anakin_tokens"):
        return ("this program's runtime/launch.py has no train_anakin_tokens: "
                "it cannot run this configuration")
    return None


class _AfterTheWindow:
    """The family as `modes/anakin.py`'s observer sees it in set-up:
    its comparison with the reference is the second process's."""

    @staticmethod
    def reference_check(agent, train_state, section, seed) -> dict:
        return {"ok": None, "made": "after the window, by a second process"}


def _watch_class(base, family):
    """`modes/anakin.py`'s observer, for a chunk whose metrics carry the
    rollout and the exit counters, and whose first warm chunk is
    recorded for the reference's replay."""
    import numpy as np

    class TokensWatch(base._ChunkWatch):
        def attach(self, anakin):
            super().attach(anakin)
            observed = anakin.train_chunk

            def and_keep(state, updates):
                result = observed(state, updates)
                if self.entries == 1:  # the first warm chunk
                    self.first_metrics = result[1]
                return result

            anakin.train_chunk = and_keep

        def _entry(self, anakin, jitted, state, updates):
            if self.entries == 0:
                self.out["static"] = anakin.static_facts
                self.sample_at_start = family.param_sample(state.train.params)
            if self.entries == 1:  # the first warm chunk has been read
                np.savez(os.path.join(self.run_dir, RECORD), **family.chunk_record(
                    self.sample_at_start,
                    family.param_sample(state.train.params),
                    self.first_metrics))
                del self.sample_at_start, self.first_metrics
            if self.entries == base.WARM_CHUNKS:  # still set-up
                self.sample_at_open = family.param_sample(state.train.params)
            super()._entry(anakin, jitted, state, updates)

        def _close(self, state, t1):
            super()._close(state, t1)
            # `childlib.param_fingerprint` is ONE float32 sum of |x| over
            # 612.5 M parameters (about 1e7): a window of 36 steps of 1e-5
            # x clipped gradient (3.8e-4 in norm each) is under its last
            # bit. So parameters are compared themselves, on a strided
            # sample of every leaf: each leaf has to have moved.
            after = family.param_sample(state.train.params)
            moved = [bool(np.any(a != b)) and bool(np.all(np.isfinite(b)))
                     for a, b in zip(self.sample_at_open, after)]
            self.out["params_changed"] = all(moved)
            self.out["leaves_moved"] = f"{sum(moved)} of {len(moved)}"
            every = lambda key: np.concatenate(
                [np.asarray(m[key]).reshape(-1) for m in self.metrics])
            self.out["counters"] = {k: float(every(k).mean()) for k in COUNTERS
                                    if k in self.metrics[0]}
            self.out["counters"]["train_step"] = int(state.train.step)

    return TokensWatch


def _launch_watched(args, params: dict, on_built) -> None:
    """`launch.train_anakin_tokens` as `train_ximpala.py --mode anakin`
    calls it, with `on_built(anakin)` run on the loop it builds."""
    from distributed_reinforcement_learning_tpu.runtime import (
        anakin_tokens, launch)

    built = anakin_tokens.AnakinTokens.__init__

    def build_and_watch(self, *a, **kw):
        built(self, *a, **kw)
        on_built(self)

    anakin_tokens.AnakinTokens.__init__ = build_and_watch
    try:
        launch.train_anakin_tokens(
            args.config, args.section, num_updates=10 ** 9,
            chunk=int(params["chunk_updates"]), seed=args.seed,
            num_envs=int(params["num_envs"]))
    finally:
        anakin_tokens.AnakinTokens.__init__ = built


def _child(args) -> int:
    import childlib
    import discover

    params = json.loads(args.params)
    from distributed_reinforcement_learning_tpu.utils.device import (
        enable_compile_cache)

    enable_compile_cache()
    clock = childlib.CompileClock()
    device = childlib.open_chip("perfbench", args.expect_platform, args.chips)
    with open(args.config) as f:
        section = json.load(f)[args.section]
    base = discover.module(os.path.dirname(HERE), "modes", "anakin")
    algo, family = discover.family(args.data_dir, args.section, section)
    out: dict = {"device": device, "machine": childlib.machine_facts(),
                 "algorithm": algo}
    tracer = (childlib.TraceWindow(os.path.join(args.out, "profile"))
              if args.trace else None)
    watch = _watch_class(base, family)(
        _AfterTheWindow, section, args.seed, args.seconds, tracer, clock, out)
    watch.run_dir = args.out
    try:
        _launch_watched(args, params, watch.attach)
    except base._WindowClosed:
        pass
    if "t1" not in out:
        print("[perfbench] train_anakin_tokens ended before the window closed",
              file=sys.stderr)
        return 1
    if tracer is not None:
        out["trace"] = tracer.reduce(device["platform"], args.chips,
                                     watch.spans, args.out)
        if out["trace"] is None:
            return 4
    childlib.write_result(args.out, "anakin_tokens_result.json", out)
    return 0


class _Built(Exception):
    """The program has built its loop and its initial state."""


def _check(args) -> int:
    """The second process: both comparisons with the plain reference,
    on the state the program builds from the seed."""
    import time

    import childlib
    import discover
    import numpy as np

    from distributed_reinforcement_learning_tpu.utils.device import (
        enable_compile_cache)

    t_start = time.time()
    enable_compile_cache()
    childlib.open_chip("perfbench-check", args.expect_platform, args.chips)
    with open(args.config) as f:
        section = json.load(f)[args.section]
    _, family = discover.family(args.data_dir, args.section, section)
    grabbed: dict = {}

    def stop_at_the_first_chunk(anakin):
        def stop(state, updates):
            grabbed.update(agent=anakin.agent, train=state.train)
            raise _Built

        anakin.train_chunk = stop

    def initial_state():
        try:
            _launch_watched(args, json.loads(args.params),
                            stop_at_the_first_chunk)
        except _Built:
            pass
        return grabbed.pop("train")

    with np.load(os.path.join(args.out, RECORD)) as f:
        record = dict(f)
    params = initial_state().params
    out = {"chunk": family.chunk_check(grabbed["agent"], params, record)}
    del params
    print(f"[perfbench] chunk check: {out['chunk']}", flush=True)
    out["reference"] = family.reference_check(
        grabbed["agent"], initial_state(), section, args.seed)
    print(f"[perfbench] reference check: {out['reference']}", flush=True)
    out["check_s"] = round(time.time() - t_start, 1)
    out["check_memory_stats"] = childlib.memory_stats()
    childlib.write_result(args.out, "anakin_tokens_check.json", out)
    return 0


def _main() -> int:
    import childlib

    ap = childlib.child_parser()
    ap.add_argument("--check", type=int, default=0)
    args = ap.parse_args()
    if args.expect_platform == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
    why_not = _unsupported(args.config, args.section)
    if why_not:  # before the chip is opened or anything is built
        print(f"[perfbench] UNSUPPORTED: {why_not}", file=sys.stderr)
        return EXIT_UNSUPPORTED
    return _check(args) if args.check else _child(args)


if __name__ == "__main__":
    sys.exit(_main())

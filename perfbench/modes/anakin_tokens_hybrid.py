"""Mode `anakin_tokens_hybrid`: the fused on-device token loop
(`runtime/anakin_tokens.py`) with a hybrid state-space / attention
language model as its policy (family `hybridlm`), built and driven by the
program's own `runtime/launch.train_anakin_tokens`, with the benchmark
watching.

Everything a run does is `modes/anakin_tokens.py`'s, loaded from there as
that file loads `modes/anakin.py`: the child that is timed and only
RECORDS its first warm chunk, the SECOND process that makes both
comparisons with the plain reference after the window
(`families/hybridlm.py`), the exit with `EXIT_UNSUPPORTED` and one line,
before the chip is opened, for a program whose `load_config` does not
know the family (every commit before PR 32). This file's own: what a
hybrid stack is held to (the order of its layers where a looped model is
held to its passes), the counters it logs, and the children's entry.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
COUNTERS = ("rho_clipped_share", "behaviour_logp_mean", "dt_mean",
            "decay_min", "state_norm_mean")


def _base():
    """`modes/anakin_tokens.py` with this model's counters, and an
    observer that also says WHICH parameter leaves did not move over the
    window (that file says how many)."""
    import discover

    base = discover.module(os.path.dirname(HERE), "modes", "anakin_tokens")
    base.COUNTERS = COUNTERS
    tokens_watch = base._watch_class

    def watch_class(anakin_mode, family):
        import numpy as np

        class HybridWatch(tokens_watch(anakin_mode, family)):
            def _close(self, state, t1):
                super()._close(state, t1)
                after = family.param_sample(state.train.params)
                self.out["leaves_stuck"] = [
                    i for i, (a, b) in enumerate(zip(self.sample_at_open, after))
                    if not np.any(a != b)]

        return HybridWatch

    base._watch_class = watch_class
    return base


def under_the_last_bit(res: dict) -> list:
    """The leaves that stayed where they were over the window, if every
    one of them is a leaf whose step the REFERENCE's own replay of the
    first warm chunk puts under float32's spacing at the parameter
    (`step_over_last_bit` under 1: 1/2 is where a step is rounded away, so
    there is a factor of two between the two readings); None if some leaf
    stayed that the reference moves. A learning rate of 1e-5 on `dt_bias`,
    whose values are -6.9 to -2.3 and whose gradient carries a factor
    dt = 1e-3..0.1, is such a step; the matrices' are not."""
    stuck = res.get("leaves_stuck", [])
    bits = res["chunk"].get("step_over_last_bit", [])
    if stuck and len(stuck) < len(bits) and all(bits[i] < 1.0 for i in stuck):
        return stuck
    return None


def _child_process(ctx: dict, argv: list, log_path: str, timeout: float):
    """Run THIS file as a child to its end (or `timeout`) -> exit code,
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


def state_problems(static: dict, section: dict, num_envs: int) -> list:
    """What the chunk says of itself against the configuration: the order
    of its layers, and the three kinds of act-time state at the sizes and
    in the precision the file states (a float32 recurrent state and
    window: by its accuracy a bfloat16 state cannot be told from the
    float32 one behind bfloat16 matmul operands, PERF.md section 6, so it
    is held to its bytes)."""
    problems = []
    kinds = list(section["layer_types"])
    if list(static.get("layer_order", ())) != kinds:
        problems.append(f"the chunk's layers are {static.get('layer_order')}, "
                        f"the configuration's {kinds}")
    inner = section["mamba_n_heads"] * section["mamba_d_head"]
    heads = section["num_attention_heads"]
    want = {
        "ssm_state_bytes": 4 * kinds.count("mamba") * num_envs * inner
        * section["mamba_d_state"],
        "conv_state_bytes": 4 * kinds.count("mamba") * num_envs
        * (section["mamba_d_conv"] - 1) * (inner + 2 * section["mamba_d_state"]),
        "kv_cache_bytes": 2 * 2 * kinds.count("attention") * num_envs
        * section["trajectory"] * section["num_key_value_heads"]
        * (section["hidden_size"] // heads)}
    if section.get("dtype") != "bfloat16":
        del want["kv_cache_bytes"]  # the cache is in the compute dtype
    for kind, size in want.items():
        if static.get(kind) != size:
            problems.append(f"the chunk carries {static.get(kind)} B of {kind}, "
                            f"the configuration's sizes and precision make {size}")
    return problems


def run(ctx: dict) -> dict:
    import parentlib

    base = _base()
    cfg = ctx["config"]
    out = ctx["out_dir"]
    section_name = cfg["section"]
    section = cfg[section_name]
    run_cfg = os.path.join(out, "config.json")
    with open(run_cfg, "w") as f:
        json.dump({section_name: section}, f)
    log_path = os.path.join(out, "anakin_tokens.log")
    argv = parentlib.child_args(ctx, run_cfg, section_name)
    rc = _child_process(ctx, argv, log_path, base.TIMEOUT_S)
    if rc == 3:
        raise ctx["NoDevice"]("JAX found no device of the expected platform")
    if rc == base.EXIT_UNSUPPORTED:
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
    rc = _child_process(ctx, [*argv, "--check", "1"], check_log,
                        base.CHECK_TIMEOUT_S)
    if rc != 0 or not os.path.exists(check_path):
        raise ctx["RunFailed"](
            f"anakin_tokens check ended with code {rc}; see {check_log}")
    with open(check_path) as f:
        res.update(json.load(f))  # `reference` (a), `chunk` (b), `check_s`

    window = res["t1"] - res["t0"]
    updates = res["updates"]
    rounded_away = None if res["params_changed"] else under_the_last_bit(res)
    if rounded_away is not None:
        res["params_changed"] = True
    problems = parentlib.common_problems(res, cfg, updates)
    if res["bad_updates"]:
        problems.append(f"{res['bad_updates']} updates with a non-finite loss "
                        f"or a gradient norm that is not positive")
    if not res["chunk"]["ok"]:
        problems.append(f"the first warm chunk differs from the reference's "
                        f"replay of it: {res['chunk']}")
    static = res["static"]
    problems += state_problems(static, section, res["num_envs"])
    frames_per_update = res["num_envs"] * section["trajectory"]
    if frames_per_update != cfg.get("frames_per_update", frames_per_update):
        problems.append(f"{frames_per_update} frames an update, the "
                        f"configuration says {cfg['frames_per_update']}")
    notes = [f"window {window:.3f} s, {updates} updates in "
             f"{len(res['chunk_seconds'])} chunks of {res['chunk_updates']} "
             f"(chunk seconds: {[round(s, 4) for s in res['chunk_seconds']]}), "
             f"{res['num_envs']} envs, static {static}, "
             f"counters {res['counters']}, parameter leaves moved "
             f"{res['leaves_moved']}, machine {res['machine']}, "
             f"device memory {res['memory_stats']}, "
             f"the check's process {res['check_s']} s and its device memory "
             f"{res['check_memory_stats']}, "
             f"reference {res['reference']}, chunk {res['chunk']}"]
    if rounded_away is not None:
        notes.append(f"parameter leaves {rounded_away} stayed where they were: "
                     f"the reference's own step on them is "
                     f"{[res['chunk']['step_over_last_bit'][i] for i in rounded_away]}"
                     f" of float32's spacing, under the last bit")
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


def _main() -> int:
    import childlib

    base = _base()
    ap = childlib.child_parser()
    ap.add_argument("--check", type=int, default=0)
    args = ap.parse_args()
    if args.expect_platform == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
    why_not = base._unsupported(args.config, args.section)
    if why_not:  # before the chip is opened or anything is built
        print(f"[perfbench] UNSUPPORTED: {why_not}", file=sys.stderr)
        return base.EXIT_UNSUPPORTED
    return base._check(args) if args.check else base._child(args)


if __name__ == "__main__":
    sys.exit(_main())

"""Mode `anakin_tokens_swa`: the fused on-device token loop
(`runtime/anakin_tokens.py`) with a sliding-window / global-attention
sparse-expert language model as its policy (family `swalm`), built and
driven by the program's own `runtime/launch.train_anakin_tokens`, with
the benchmark watching.

Everything a run does is `modes/anakin_tokens_moe.py`'s, loaded from
there as `modes/anakin_tokens_conv.py` loads it: the child that is timed
and only RECORDS its first warm chunk, the SECOND process that makes the
comparisons with the plain reference after the window
(`families/swalm.py`), the leaves that may stay under float32's last
bit, no pair dropped in any update, the exit with `EXIT_UNSUPPORTED` and
one line, before the chip is opened, for a program whose `load_config`
does not know the family (every commit before PR 49). This file's own:
what this stack is held to (the order of its layers by attention kind,
the BYTES of its rings and of its one full cache, its share of the
experts), the counters it logs, the traced interval (ONE chunk: an
update is 8,192 decode steps, and `modes/anakin.py`'s two chunks would
be 16,384 of them in one trace, four times any traced cell's), the
children's entry, and the second process's order (`_check`: the state
built ONCE, comparison (a) ahead of (b)).
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
COUNTERS = ("rho_clipped_share", "behaviour_logp_mean", "held_pair_share",
            "expert_load_max_over_mean", "router_load_max_over_mean",
            "experts_untouched", "dropped_pairs", "pair_slabs_mean",
            "pair_slabs_max", "held_experts_touched_mean", "relu_gate_zero_share",
            "ring_read_share", "window_pair_share")
TRACE_CHUNKS = 1  # `modes/anakin.py` covers two


def _moe():
    import discover

    return discover.module(os.path.dirname(HERE), "modes", "anakin_tokens_moe")


def _base():
    """`modes/anakin_tokens.py` under `anakin_tokens_hybrid`'s observer
    (which also says WHICH leaves stayed), with this model's counters and
    a traced interval of `TRACE_CHUNKS`."""
    base = _moe()._hybrid()._base()
    base.COUNTERS = COUNTERS
    watch_class = base._watch_class

    def traced_for_one_chunk(anakin_mode, family):
        anakin_mode.TRACE_CHUNKS = TRACE_CHUNKS
        return watch_class(anakin_mode, family)

    base._watch_class = traced_for_one_chunk
    return base


def state_problems(static: dict, section: dict, num_envs: int) -> list:
    """What the chunk says of itself against the configuration: the order
    of its layers by attention kind (`sliding_window_layout`: 0 global, 1
    window), the global layers' caches and the window layers' rings at
    the sizes and in the precision the file states (keys and values of
    `num_key_value_heads` heads a position; `trajectory` positions a
    global layer, `min(sliding_window_size, trajectory)` a ring: a full
    cache where a ring is stated, a ring of the query heads, or either in
    float32, is refused by its BYTES), and its share of the experts."""
    kinds = ["window" if w else "global" for w in section["sliding_window_layout"]]
    problems = []
    if list(static.get("layer_order", ())) != kinds:
        problems.append(f"the chunk's layers are {static.get('layer_order')}, "
                        f"the configuration's {kinds}")
    ring = min(section["sliding_window_size"], section["trajectory"])
    position = 2 * 2 * num_envs * section["num_key_value_heads"] * section["head_dim"]
    want = {"kv_cache_bytes": kinds.count("global") * section["trajectory"] * position,
            "ring_bytes": kinds.count("window") * ring * position,
            "ring_positions": ring,
            "experts_held": section["moe_num_primary_experts"],
            "router_width": section["router_width"],
            "first_expert": section["first_expert"]}
    if section.get("dtype") != "bfloat16":  # both are in the compute dtype
        del want["kv_cache_bytes"], want["ring_bytes"]
    for kind, size in want.items():
        if static.get(kind) != size:
            problems.append(f"the chunk says {static.get(kind)} of {kind}, the "
                            f"configuration's sizes and precision make {size}")
    return problems


def run(ctx: dict) -> dict:
    moe = _moe()
    # `anakin_tokens_moe.run` (no pair dropped, over `anakin_tokens_hybrid.run`)
    # with THIS file as the children's entry and this stack's account of itself.
    moe._child_process = _child_process
    moe.state_problems = state_problems
    return moe.run(ctx)


def _child_process(ctx: dict, argv: list, log_path: str, timeout: float):
    """Run THIS file as a child to its end (or `timeout`) -> exit code,
    None if it had to be killed."""
    import subprocess

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


def _check(base, args) -> int:
    """`modes/anakin_tokens._check`, the same two comparisons on the same
    state, in the order that costs this cell least (a whole run of it has
    to end inside the driver's 360 s, and the second process was 242 s of
    405: PERF.md section 6, PR 49): the program builds its initial state
    ONCE (that file lets `train_anakin_tokens` run up to its first chunk
    twice, once a comparison, because each comparison consumes the
    parameters; here a copy on the host brings them back), and (a) runs
    AHEAD of (b), so that the reference's compiled pieces, which (a)'s
    `jax.clear_caches()` between the program's two sides would throw
    away, are loaded once and serve both."""
    import json
    import time

    import childlib
    import discover
    import jax
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
            raise base._Built

        anakin.train_chunk = stop

    try:
        base._launch_watched(args, json.loads(args.params), stop_at_the_first_chunk)
    except base._Built:
        pass
    agent, train = grabbed.pop("agent"), grabbed.pop("train")
    with np.load(os.path.join(args.out, base.RECORD)) as f:
        record = dict(f)
    kept = jax.device_get(train.params)
    out = {"reference": family.reference_check(agent, train, section, args.seed)}
    del train
    print(f"[perfbench] reference check: {out['reference']}", flush=True)
    out["chunk"] = family.chunk_check(agent, jax.device_put(kept), record)
    del kept
    print(f"[perfbench] chunk check: {out['chunk']}", flush=True)
    out["check_s"] = round(time.time() - t_start, 1)
    out["check_memory_stats"] = childlib.memory_stats()
    childlib.write_result(args.out, "anakin_tokens_check.json", out)
    return 0


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
    return _check(base, args) if args.check else base._child(args)


if __name__ == "__main__":
    sys.exit(_main())

"""Mode `anakin_tokens_ssmoe`: the fused on-device token loop
(`runtime/anakin_tokens.py`) with a state-space / sparse-expert /
attention language model whose layers are one sublayer each as its policy
(family `ssmoelm`), built and driven by the program's own
`runtime/launch.train_anakin_tokens`, with the benchmark watching.

Everything a run does is `modes/anakin_tokens_moe.py`'s, loaded DIRECTLY
from there (ROADMAP D16: no fifth level of rebinding): the child that is
timed and only RECORDS its first warm chunk, the SECOND process that
makes the comparisons with the plain reference after the window
(`families/ssmoelm.py`), the leaves that may stay under float32's last
bit, no pair dropped in any update, the exit with `EXIT_UNSUPPORTED` and
one line, before the chip is opened, for a program whose `load_config`
does not know the family (every commit before PR 53). This file's own:
what this stack is held to (the order of its layers as the published
string, the BYTES of its recurrent states, windows and one cache, its
share of the experts), the counters it logs, the traced interval (ONE
chunk: an update is 2,048 decode steps of nine layers), the children's
entry, and the second process's order (`modes/anakin_tokens_swa.py`'s
`_check`, as it stands: the state built ONCE, comparison (a) ahead of
(b), so that a whole run ends inside the driver's 360 s).
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
COUNTERS = ("rho_clipped_share", "behaviour_logp_mean", "held_pair_share",
            "expert_load_max_over_mean", "router_load_max_over_mean",
            "experts_untouched", "router_experts_untouched", "dropped_pairs",
            "pair_slabs_mean", "pair_slabs_max", "router_score_mean",
            "bias_abs_max", "relu2_zero_share", "dt_mean", "state_norm_mean",
            "held_experts_touched_mean")
TRACE_CHUNKS = 1  # `modes/anakin.py` covers two


def _mode(name: str):
    import discover

    return discover.module(os.path.dirname(HERE), "modes", name)


def _base():
    """`modes/anakin_tokens.py` under `anakin_tokens_hybrid`'s observer
    (which also says WHICH leaves stayed), with this model's counters and
    a traced interval of `TRACE_CHUNKS`."""
    base = _mode("anakin_tokens_moe")._hybrid()._base()
    base.COUNTERS = COUNTERS
    watch_class = base._watch_class

    def traced_for_one_chunk(anakin_mode, family):
        anakin_mode.TRACE_CHUNKS = TRACE_CHUNKS
        return watch_class(anakin_mode, family)

    base._watch_class = traced_for_one_chunk
    return base


def state_problems(static: dict, section: dict, num_envs: int) -> list:
    """What the chunk says of itself against the configuration: the order
    of its layers (`hybrid_override_pattern`, one character a layer), the
    recurrent states and windows in float32, the attention layers' cache
    of the KEY/VALUE heads in the compute dtype and the route record, at
    the sizes the file states (a bfloat16 recurrent state, a cache of the
    query heads or a cache a state-space layer also held is refused by its
    BYTES), and its share of the experts."""
    pattern = section["hybrid_override_pattern"]
    problems = []
    if static.get("layer_order") != pattern:
        problems.append(f"the chunk's layers are {static.get('layer_order')}, "
                        f"the configuration's {pattern}")
    mamba, experts, attention = (pattern.count(c) for c in "ME*")
    inner = section["mamba_num_heads"] * section["mamba_head_dim"]
    channels = inner + 2 * section["n_groups"] * section["ssm_state_size"]
    want = {"ssm_state_bytes": 4 * mamba * num_envs * inner * section["ssm_state_size"],
            "conv_state_bytes": 4 * mamba * num_envs
            * (section["conv_kernel"] - 1) * channels,
            "kv_cache_bytes": 2 * 2 * attention * num_envs * section["trajectory"]
            * section["num_key_value_heads"] * section["head_dim"],
            "route_record_bytes": 2 * num_envs * section["trajectory"] * experts
            * section["num_experts_per_tok"],
            "experts_held": section["n_routed_experts"],
            "router_width": section["router_width"],
            "first_expert": section["first_expert"]}
    if section.get("dtype") != "bfloat16":
        del want["kv_cache_bytes"]  # the cache is in the compute dtype
    for kind, size in want.items():
        if static.get(kind) != size:
            problems.append(f"the chunk says {static.get(kind)} of {kind}, the "
                            f"configuration's sizes and precision make {size}")
    return problems


def run(ctx: dict) -> dict:
    moe = _mode("anakin_tokens_moe")
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
    if args.check:  # the state built once, (a) ahead of (b)
        return _mode("anakin_tokens_swa")._check(base, args)
    return base._child(args)


if __name__ == "__main__":
    sys.exit(_main())

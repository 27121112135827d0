"""Mode `anakin_tokens_conv`: the fused on-device token loop
(`runtime/anakin_tokens.py`) with a gated-short-convolution sparse-expert
language model as its policy (family `convlm`), built and driven by the
program's own `runtime/launch.train_anakin_tokens`, with the benchmark
watching.

Everything a run does is `modes/anakin_tokens_moe.py`'s, loaded from
there as `modes/anakin_tokens_mla.py` loads it: the child that is timed
and only RECORDS its first warm chunk, the SECOND process that makes the
comparisons with the plain reference after the window
(`families/convlm.py`), the leaves that may stay under float32's last
bit, no pair dropped in any update, the exit with `EXIT_UNSUPPORTED` and
one line, before the chip is opened, for a program whose `load_config`
does not know the family (every commit before PR 46). This file's own:
what this stack is held to (the order of its layers as `mixer+mlp`, the
BYTES of its windows and of its one cache, its share of the experts),
the counters it logs, and the children's entry.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
COUNTERS = ("rho_clipped_share", "behaviour_logp_mean", "held_pair_share",
            "expert_load_max_over_mean", "router_load_max_over_mean",
            "experts_untouched", "dropped_pairs", "pair_slabs_mean",
            "pair_slabs_max", "router_score_mean", "bias_abs_max",
            "conv_gate_abs_mean", "conv_state_abs_max")


def _moe():
    import discover

    return discover.module(os.path.dirname(HERE), "modes", "anakin_tokens_moe")


def _base():
    """`modes/anakin_tokens.py` under `anakin_tokens_hybrid`'s observer
    (which also says WHICH leaves stayed), with this model's counters."""
    base = _moe()._hybrid()._base()
    base.COUNTERS = COUNTERS
    return base


def state_problems(static: dict, section: dict, num_envs: int) -> list:
    """What the chunk says of itself against the configuration: the order
    of its layers (`mixer+mlp`, the leading `num_dense_layers` dense), the
    windows and the one cache at the sizes and in the precision the file
    states (`conv_L_cache - 1` columns of `hidden_size` a row a
    convolution layer; keys and values of `num_key_value_heads` heads a
    token an attention layer: a window of three columns, a cache of the
    query heads, or either in float32, is refused by its BYTES), and its
    share of the experts."""
    mixers = list(section["layer_types"])
    kinds = [f"{mixer}+{'dense' if i < section['num_dense_layers'] else 'moe'}"
             for i, mixer in enumerate(mixers)]
    problems = []
    if list(static.get("layer_order", ())) != kinds:
        problems.append(f"the chunk's layers are {static.get('layer_order')}, "
                        f"the configuration's {kinds}")
    d = section["hidden_size"]
    want = {"conv_state_bytes": 2 * mixers.count("conv") * num_envs
            * (section["conv_L_cache"] - 1) * d,
            "kv_cache_bytes": 2 * 2 * mixers.count("full_attention") * num_envs
            * section["trajectory"] * section["num_key_value_heads"]
            * (d // section["num_attention_heads"]),
            "experts_held": section["num_experts"],
            "router_width": section["router_width"],
            "first_expert": section["first_expert"]}
    if section.get("dtype") != "bfloat16":  # both are in the compute dtype
        del want["conv_state_bytes"], want["kv_cache_bytes"]
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

"""Mode `anakin_tokens_moe`: the fused on-device token loop
(`runtime/anakin_tokens.py`) with a sparse-expert hybrid language model
as its policy (family `moelm`), built and driven by the program's own
`runtime/launch.train_anakin_tokens`, with the benchmark watching.

Everything a run does is `modes/anakin_tokens_hybrid.py`'s, loaded from
there as that file loads `modes/anakin_tokens.py`: the child that is
timed and only RECORDS its first warm chunk, the SECOND process that
makes the comparisons with the plain reference after the window
(`families/moelm.py`), the leaves that may stay under float32's last
bit, the exit with `EXIT_UNSUPPORTED` and one line, before the chip is
opened, for a program whose `load_config` does not know the family
(every commit before PR 36). This file's own: what this stack is held to
(the order of its layers, the bytes of its three kinds of state, its
share of the experts, no pair dropped in any update), the counters it
logs, and the children's entry.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
COUNTERS = ("rho_clipped_share", "behaviour_logp_mean", "held_pair_share",
            "expert_load_max_over_mean", "experts_untouched", "dropped_pairs",
            "router_entropy", "shared_gate_mean", "beta_mean", "decay_min",
            "state_norm_mean")


def _hybrid():
    import discover

    return discover.module(os.path.dirname(HERE), "modes", "anakin_tokens_hybrid")


def _base():
    """`modes/anakin_tokens.py` under `anakin_tokens_hybrid`'s observer
    (which also says WHICH leaves stayed), with this model's counters."""
    base = _hybrid()._base()
    base.COUNTERS = COUNTERS
    return base


def state_problems(static: dict, section: dict, num_envs: int) -> list:
    """What the chunk says of itself against the configuration: the order
    of its layers, the three kinds of act-time state at the sizes and in
    the precision the file states (a bfloat16 state is refused by its
    BYTES, as granite's: by its accuracy it cannot be told from a float32
    one behind bfloat16 operands), and its share of the experts."""
    problems = []
    kinds = list(section["layer_types"])
    if list(static.get("layer_order", ())) != kinds:
        problems.append(f"the chunk's layers are {static.get('layer_order')}, "
                        f"the configuration's {kinds}")
    linear, full = kinds.count("linear_attention"), kinds.count("full_attention")
    keys = section["linear_num_key_heads"] * section["linear_key_head_dim"]
    values = section["linear_num_value_heads"] * section["linear_value_head_dim"]
    want = {
        "gdn_state_bytes": 4 * linear * num_envs * values * section["linear_key_head_dim"],
        "conv_state_bytes": 4 * linear * num_envs
        * (section["linear_conv_kernel_dim"] - 1) * (2 * keys + values),
        "kv_cache_bytes": 2 * 2 * full * num_envs * section["trajectory"]
        * section["num_key_value_heads"] * section["head_dim"],
        "experts_held": section["num_experts"],
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
    hybrid = _hybrid()
    # `anakin_tokens_hybrid.run` with THIS file as the children's entry
    # and this stack's account of itself.
    hybrid._child_process = _child_process
    hybrid.state_problems = state_problems
    result = hybrid.run(ctx)
    dropped = result["facts"]["counters"].get("dropped_pairs")
    if dropped != 0:
        result["correct"] = False
        result["notes"].append(f"NOT CORRECT: dropped_pairs {dropped} over the "
                               f"window's updates: the layer is dropless")
    return result


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

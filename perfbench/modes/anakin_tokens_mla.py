"""Mode `anakin_tokens_mla`: the fused on-device token loop
(`runtime/anakin_tokens.py`) with a latent-attention sparse-expert
language model as its policy (family `mlalm`), built and driven by the
program's own `runtime/launch.train_anakin_tokens`, with the benchmark
watching.

Everything a run does is `modes/anakin_tokens_moe.py`'s, loaded from
there as that file loads `modes/anakin_tokens_hybrid.py`: the child that
is timed and only RECORDS its first warm chunk, the SECOND process that
makes the comparisons with the plain reference after the window
(`families/mlalm.py`), the leaves that may stay under float32's last
bit, no pair dropped in any update, the exit with `EXIT_UNSUPPORTED` and
one line, before the chip is opened, for a program whose `load_config`
does not know the family (every commit before PR 40). This file's own:
what this stack is held to (the order of its layers, the BYTES of its
latent cache, its share of the experts), the counters it logs, and the
children's entry.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
COUNTERS = ("rho_clipped_share", "behaviour_logp_mean", "held_pair_share",
            "expert_load_max_over_mean", "router_load_max_over_mean",
            "experts_untouched", "dropped_pairs", "router_score_mean",
            "bias_abs_max", "mtp_loss", "mtp_agreement")


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
    of its layers, the latent cache at the size and in the precision the
    file states (`kv_lora_rank + qk_rope_head_dim` values a token a
    layer: a cache of expanded keys and values, or one in float32, is
    refused by its BYTES), and its share of the experts."""
    dense = section["first_k_dense_replace"]
    kinds = ["dense"] * dense + ["moe"] * (section["num_hidden_layers"] - dense)
    problems = []
    if list(static.get("layer_order", ())) != kinds:
        problems.append(f"the chunk's layers are {static.get('layer_order')}, "
                        f"the configuration's {kinds}")
    per_token = 2 * len(kinds) * (section["kv_lora_rank"]
                                  + section["qk_rope_head_dim"])
    want = {"latent_cache_bytes": per_token * num_envs * section["trajectory"],
            "cache_bytes_per_token": per_token,
            "experts_held": section["n_routed_experts"],
            "router_width": section["router_width"],
            "first_expert": section["first_expert"]}
    if section.get("dtype") != "bfloat16":  # the cache is in the compute dtype
        del want["latent_cache_bytes"], want["cache_bytes_per_token"]
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

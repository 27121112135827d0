"""Greedy-eval a checkpointed on-device replay-family run (Ape-X/R2D2).

The behavior curves in `benchmarks/anakin/apex_*` keep the epsilon
ladder's exploration mixed into the score (the ladder floors at ~0.05,
so ~1 in 20 behavior actions is random); this tool answers "how good is
the POLICY" — argmax-Q rollouts on fresh on-device envs from a saved
TrainState, the same ground-truth metric `AnakinImpala.greedy_eval`
gives the IMPALA runs.

    python scripts/eval_anakin_replay.py --algo apex \
        --config runs/apex_pong_config.json --section apex \
        --checkpoint_dir runs/apex_pong_ckpt --eval-envs 32 \
        --eval-steps 3000 --seeds 3

Prints one JSON line: per-seed mean returns + the pooled mean.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--algo", required=True, choices=["apex", "r2d2"])
    p.add_argument("--config", required=True)
    p.add_argument("--section", required=True)
    p.add_argument("--checkpoint_dir", required=True)
    p.add_argument("--eval-envs", type=int, default=32)
    p.add_argument("--eval-steps", type=int, default=3000)
    p.add_argument("--seeds", type=int, default=3,
                   help="independent eval rollout batches")
    p.add_argument("--platform", default=None)
    return p.parse_args()


def main() -> None:
    args = parse_args()
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    import jax

    from distributed_reinforcement_learning_tpu.runtime import launch
    from distributed_reinforcement_learning_tpu.utils.device import (
        enable_compile_cache, open_devices)

    enable_compile_cache()
    open_devices("eval")

    agent_cfg, rt = launch.load_config(args.config, args.section)
    env_mod, obs_transform = launch._jittable_env_for(agent_cfg, rt)
    if args.algo == "apex":
        from distributed_reinforcement_learning_tpu.agents.apex import ApexAgent
        from distributed_reinforcement_learning_tpu.runtime.anakin_apex import AnakinApex

        agent = ApexAgent(agent_cfg)
        n = rt.num_actors * rt.envs_per_actor
        steps = 16
        anakin = AnakinApex(agent, num_envs=n, batch_size=rt.batch_size,
                            capacity=n * steps, steps_per_collect=steps,
                            env=env_mod, obs_transform=obs_transform)
    else:
        from distributed_reinforcement_learning_tpu.agents.r2d2 import R2D2Agent
        from distributed_reinforcement_learning_tpu.runtime.anakin_r2d2 import AnakinR2D2

        agent = R2D2Agent(agent_cfg)
        n = rt.num_actors * rt.envs_per_actor
        anakin = AnakinR2D2(agent, num_envs=n, batch_size=rt.batch_size,
                            capacity=n, env=env_mod,
                            obs_transform=obs_transform)

    train = agent.init_state(jax.random.PRNGKey(0))
    _ckpt, train = launch._restore_train(args.checkpoint_dir, train)
    step = int(train.step)
    if step == 0:
        print("[eval] WARNING: checkpoint restore found step=0 — evaluating "
              "fresh params?", file=sys.stderr)

    per_seed = []
    episodes = 0
    return_sum = 0.0
    for s in range(args.seeds):
        out = anakin.greedy_eval(train.params, args.eval_envs,
                                 args.eval_steps, jax.random.PRNGKey(1000 + s))
        per_seed.append(round(out["mean_return"], 2))
        episodes += out["episodes"]
        return_sum += out["mean_return"] * out["episodes"]
        print(f"[eval] seed {s}: mean_return {out['mean_return']:.2f} "
              f"({out['episodes']} episodes)", file=sys.stderr)
    # Pool by EPISODE (a short-budget seed with few completed games must
    # not get equal weight with a full one).
    pooled = return_sum / max(episodes, 1)
    print(json.dumps({
        "algo": args.algo, "section": args.section, "train_step": step,
        "greedy_mean_return": round(pooled, 2), "per_seed": per_seed,
        "episodes": episodes, "eval_envs": args.eval_envs,
        "eval_steps": args.eval_steps,
    }))


if __name__ == "__main__":
    main()

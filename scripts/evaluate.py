"""Evaluate a trained checkpoint: greedy(ish) episodes, no training.

The reference has no evaluation mode at all — scores exist only as
TensorBoard curves logged during training (`/root/reference/
train_impala.py:170-172`). This gives every algorithm family a
standalone rollout evaluator:

    python scripts/evaluate.py --section impala_cartpole \
        --checkpoint_dir ckpts --episodes 20 --platform cpu

Reuses the REAL actor classes (same preprocessing, action aliasing,
POMDP projection, windowed transformer act) against a sink queue, with
the exploration schedule pinned to its asymptote: the Q-family actors'
epsilon `1/(decay*episode+1)` is evaluated at episode=1e9 (epsilon~0),
and the actor-critic families act by their stochastic policy, which is
their on-policy evaluation regime. Prints one JSON line with return
statistics.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


class _SinkQueue:
    """Queue surface the actors write to; evaluation discards trajectories."""

    capacity = 1 << 30

    def put(self, item, timeout=None):
        return True

    def put_many(self, items, timeout=None):
        return len(items)

    def size(self):
        return 0


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="config.json")
    p.add_argument("--section", default="impala_cartpole")
    p.add_argument("--checkpoint_dir", default=None,
                   help="restore the latest checkpoint (omit = random init)")
    p.add_argument("--episodes", type=int, default=20)
    p.add_argument("--max_unrolls", type=int, default=2000)
    p.add_argument("--seed", type=int, default=1234)
    p.add_argument("--platform", default=None, choices=[None, "cpu", "tpu"])
    args = p.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    import numpy as np

    from distributed_reinforcement_learning_tpu.runtime.launch import (
        _algo_of, make_actor, make_agent)
    from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    agent_cfg, rt = load_config(args.config, args.section)
    algo = _algo_of(agent_cfg)
    agent = make_agent(algo, agent_cfg, rt, actor=True)
    state = agent.init_state(jax.random.PRNGKey(0))

    step = None
    if args.checkpoint_dir:
        from distributed_reinforcement_learning_tpu.utils.checkpoint import Checkpointer

        ckpt = Checkpointer(args.checkpoint_dir)
        got = ckpt.restore(state)
        if got is None:
            raise SystemExit(f"no checkpoint found under {args.checkpoint_dir}")
        state, _, step = got

    weights = WeightStore()
    weights.publish(state.params, step or 0)

    actor = make_actor(algo, agent_cfg, rt, task=0, queue=_SinkQueue(),
                       weights=weights, seed=args.seed, agent=agent)
    if hasattr(actor, "_episodes"):
        # Q-family epsilon schedule at its asymptote: epsilon ~ 1e-9.
        actor._episodes = np.full_like(actor._episodes, 10**9)

    # Ape-X's actor surface is step-based; the others are unroll-based.
    advance = (actor.run_unroll if hasattr(actor, "run_unroll")
               else lambda: actor.run_steps(32))
    unrolls = 0
    while len(actor.episode_returns) < args.episodes and unrolls < args.max_unrolls:
        advance()
        unrolls += 1
    returns = np.asarray(actor.episode_returns[: args.episodes], np.float64)
    if returns.size == 0:
        raise SystemExit(
            f"no episodes completed in {unrolls} unrolls — raise --max_unrolls")
    out = {
        "section": args.section,
        "algorithm": algo,
        "checkpoint_step": step,
        "episodes": int(returns.size),
        "return_mean": round(float(returns.mean()), 2),
        "return_std": round(float(returns.std()), 2),
        "return_min": float(returns.min()),
        "return_max": float(returns.max()),
        "unrolls": unrolls,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""R2D2-family stability ablation harness (round-4 tooling).

Runs one CartPole-POMDP training configuration and reports the
collapse-cycle statistics that drove the round-4 stable-mode ablation:
the 50-episode rolling mean sampled across the run, upward crossings of
the "performing" threshold (cycle count), the minimum of the rolling
mean after first reaching peak (collapse depth), and the late-20 mean.

This is the committed form of the probes behind the ablation table in
benchmarks/curves/ANALYSIS.md: every stabilizer knob
the framework ships is reachable from the CLI, so the next
investigation (the cycle survives all 8 combinations tried so far)
starts from a reproducible harness instead of ad-hoc scripts.

Usage:
    python scripts/stability_probe.py --updates 2000 --seed 0 \
        --priority-eta 0.9 --adam-clip 40 --epsilon-floor 0.02 \
        --timeout-nonterminal --target-sync 100
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # CartPole-sized: a CPU job

import jax

import numpy as np


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--section", default="r2d2", choices=["r2d2", "xformer"])
    p.add_argument("--updates", type=int, default=2000)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--priority-eta", type=float, default=None)
    p.add_argument("--adam-clip", type=float, default=None)
    p.add_argument("--epsilon-floor", type=float, default=None,
                   help="residual exploration floor; default keeps each "
                        "family's own (r2d2 0.0, xformer 0.15)")
    p.add_argument("--timeout-nonterminal", action="store_true")
    p.add_argument("--target-sync", type=int, default=None)
    p.add_argument("--replay-capacity", type=int, default=None)
    p.add_argument("--threshold", type=float, default=100.0,
                   help="rolling-mean level that counts as 'performing'")
    args = p.parse_args()

    from distributed_reinforcement_learning_tpu.runtime.launch import build_local
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    agent_cfg, rt = load_config("config.json", args.section)
    agent_over = {}
    if args.priority_eta is not None:
        agent_over["priority_eta"] = args.priority_eta
    if args.adam_clip is not None:
        agent_over["gradient_clip_norm"] = args.adam_clip
    if agent_over:
        agent_cfg = dataclasses.replace(agent_cfg, **agent_over)
    rt_over = {"timeout_nonterminal": args.timeout_nonterminal}
    if args.epsilon_floor is not None:
        rt_over["epsilon_floor"] = args.epsilon_floor
    if args.target_sync is not None:
        rt_over["target_sync_interval"] = args.target_sync
    if args.replay_capacity is not None:
        rt_over["replay_capacity"] = args.replay_capacity
    rt = dataclasses.replace(rt, **rt_over)

    learner, actors, run_fn = build_local(agent_cfg, rt, seed=args.seed)
    result = run_fn(learner, actors, args.updates)

    r = np.asarray(result["episode_returns"], float)
    roll = (np.convolve(r, np.ones(50) / 50, mode="valid")
            if r.size >= 50 else r)
    hi = roll > args.threshold
    upcrossings = int(((~hi[:-1]) & hi[1:]).sum()) if roll.size > 1 else 0
    first_hi = int(np.argmax(hi)) if hi.any() else None
    post_min = (round(float(roll[first_hi:].min()), 1)
                if first_hi is not None else None)
    print(json.dumps({
        "section": args.section,
        "updates": args.updates,
        "seed": args.seed,
        "knobs": {**agent_over, **rt_over},
        "episodes": int(r.size),
        "late20": round(float(r[-20:].mean()), 2) if r.size else None,
        "best20": round(max(
            (float(r[i:i + 20].mean()) for i in range(0, max(1, r.size - 20), 10)),
            default=float("nan")), 2) if r.size >= 20 else None,
        "cycle_upcrossings": upcrossings,
        "min_roll_after_first_peak": post_min,
        "roll_curve": [round(float(roll[int(f * (roll.size - 1))]), 1)
                       for f in np.linspace(0, 1, 40)] if roll.size else [],
    }))


if __name__ == "__main__":
    main()

"""Chip-rate Breakout training: Anakin IMPALA over the pure-JAX env.

The endurance runs (`benchmarks/longrun/ANALYSIS.md`) established that
the host loop on this image's single CPU core caps Breakout at a few
hundred frames/s — two orders of magnitude under IMPALA's Atari sample
budget. This driver is the chip-scale path those runs pointed at:
collect + learn entirely on the TPU (`runtime/anakin.py` over
`envs/breakout_jax.py`), dispatching U updates per host round-trip, with
periodic checkpoints and on-device greedy evaluation.

Emits one JSON line per chunk to `<out>/progress.jsonl` and checkpoints
the TrainState through `utils.checkpoint.Checkpointer` (resume with
`--resume`).

Example (50M env frames at B=128, T=20):
    python scripts/anakin_breakout_train.py --out runs/anakin_breakout \
        --num-envs 128 --total-frames 50_000_000
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def parse_args() -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    p.add_argument("--out", required=True)
    p.add_argument("--env", default="breakout",
                   choices=["breakout", "pong", "invaders"],
                   help="which on-device pixel env to train "
                        "(envs/breakout_jax.py / envs/pong_jax.py)")
    p.add_argument("--num-envs", type=int, default=128)
    p.add_argument("--trajectory", type=int, default=20)
    p.add_argument("--updates-per-chunk", type=int, default=50)
    p.add_argument("--total-frames", type=int, default=50_000_000,
                   help="env frames (post-frameskip actions x num_envs)")
    p.add_argument("--num-actions", type=int, default=None,
                   help="policy head width (default: the env's own action "
                        "count); wider exercises the reference's "
                        "action %% available_action aliasing")
    p.add_argument("--lstm", type=int, default=256)
    p.add_argument("--entropy", type=float, default=0.01)
    p.add_argument("--baseline-coef", type=float, default=0.5)
    p.add_argument("--lr", type=float, default=6e-4)
    p.add_argument("--end-lr", type=float, default=0.0)
    p.add_argument("--learning-frames", type=int, default=0,
                   help="LR-decay horizon in frames (0 = --total-frames)")
    p.add_argument("--reward-clip", default="abs_one",
                   choices=["abs_one", "soft_asymmetric", "none"])
    p.add_argument("--clip-norm", type=float, default=40.0,
                   help="global-norm gradient clip (reference 40; with "
                        "SUM losses the norm scales with batch, so large "
                        "--num-envs runs may want it raised)")
    p.add_argument("--torso", default="nature", choices=["nature", "resnet"],
                   help="conv torso: reference Nature-CNN, or the IMPALA "
                        "paper's deep ResNet (the MXU-dense variant)")
    p.add_argument("--torso-width", type=int, default=1,
                   help="ResNet channel multiplier (the MXU-dense "
                        "configuration uses 4)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--platform", default=None,
                   help="force a JAX platform (e.g. cpu for smoke tests)")
    p.add_argument("--f32", action="store_true",
                   help="float32 compute (default bf16 on accelerators)")
    p.add_argument("--checkpoint-every", type=int, default=20,
                   help="chunks between checkpoints")
    p.add_argument("--eval-every", type=int, default=10,
                   help="chunks between greedy evals (0 = never)")
    p.add_argument("--eval-envs", type=int, default=32)
    p.add_argument("--eval-steps", type=int, default=None,
                   help="adapter steps per eval rollout (default: the "
                        "env's episode frame cap / frameskip + slack, so "
                        "even a cap-length game completes inside the eval)")
    p.add_argument("--resume", action="store_true")
    return p.parse_args()


def main() -> None:
    args = parse_args()
    if args.torso != "resnet" and args.torso_width != 1:
        sys.exit("--torso-width only applies to --torso resnet "
                 "(the Nature CNN has fixed channel counts)")
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent, ImpalaConfig
    from distributed_reinforcement_learning_tpu.envs import breakout_jax, invaders_jax, pong_jax
    from distributed_reinforcement_learning_tpu.runtime.anakin import AnakinImpala
    from distributed_reinforcement_learning_tpu.utils.checkpoint import Checkpointer
    from distributed_reinforcement_learning_tpu.utils.device import (
        enable_compile_cache, open_devices)

    enable_compile_cache()
    open_devices("anakin")

    env_mod = {"breakout": breakout_jax, "pong": pong_jax,
               "invaders": invaders_jax}[args.env]
    if args.eval_steps is None:
        # Episode frame caps baked into each env's step() default.
        cap = {"breakout": 10_000, "pong": 20_000, "invaders": 10_000}[args.env]
        args.eval_steps = cap // 4 + 500

    platform = jax.default_backend()
    on_accel = platform not in ("cpu",)
    dtype = jnp.float32 if (args.f32 or not on_accel) else jnp.bfloat16

    # The LR schedule counts OPTIMIZER UPDATES (agents/common.py
    # polynomial_lr, stepped with state.step), so the frame-denominated
    # horizon converts through frames-per-update; without this the decay
    # denominator would be ~2500x the actual step count and --end-lr a
    # silent no-op.
    frames_per_update = args.num_envs * args.trajectory
    horizon_updates = max(
        1, (args.learning_frames or args.total_frames) // frames_per_update)
    cfg = ImpalaConfig(
        obs_shape=env_mod.OBS_SHAPE,
        num_actions=args.num_actions or env_mod.NUM_ACTIONS,
        trajectory=args.trajectory,
        lstm_size=args.lstm,
        entropy_coef=args.entropy,
        baseline_loss_coef=args.baseline_coef,
        start_learning_rate=args.lr,
        end_learning_rate=args.end_lr,
        learning_frame=horizon_updates,
        reward_clipping=args.reward_clip,
        gradient_clip_norm=args.clip_norm,
        torso=args.torso,
        torso_width=args.torso_width,
        dtype=dtype,
    )
    agent = ImpalaAgent(cfg)
    anakin = AnakinImpala(agent, num_envs=args.num_envs, env=env_mod)

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / "config.json").write_text(json.dumps(
        {k: str(v) if k == "dtype" else v
         for k, v in {**vars(args), "platform": platform,
                      "dtype": dtype.__name__}.items()}, indent=2))
    ck = Checkpointer(out / "ckpt", retain=3)
    progress = out / "progress.jsonl"

    state = anakin.init(jax.random.PRNGKey(args.seed))
    frames_per_chunk = frames_per_update * args.updates_per_chunk
    frames = 0
    chunk = 0
    if args.resume:
        restored = ck.restore(state.train)
        if restored is not None:
            train, extra, step = restored
            state = state._replace(train=train)
            frames = int(extra.get("frames", 0))
            chunk = int(extra.get("chunk", 0))
            print(f"[resume] step={step} frames={frames:,}", file=sys.stderr)

    eval_key = jax.random.PRNGKey(args.seed + 1000)
    t_start = time.monotonic()
    while frames < args.total_frames:
        t0 = time.monotonic()
        state, m = anakin.train_chunk(state, args.updates_per_chunk)
        m = jax.device_get(m)
        dt = time.monotonic() - t0
        chunk += 1
        frames += frames_per_chunk

        return_sum = float(m["episode_return_sum"].sum())
        episodes = float(m["episodes_done"].sum())  # true game ends
        row = {
            "chunk": chunk,
            "updates": int(state.train.step),
            "frames": frames,
            "fps": round(frames_per_chunk / dt, 1),
            "chunk_s": round(dt, 3),
            "total_loss": round(float(m["total_loss"][-1]), 4),
            "entropy": round(float(m["entropy"][-1]), 4),
            "grad_norm": round(float(m["grad_norm"][-1]), 4),
            "lr": float(m["learning_rate"][-1]),
            "return_sum": round(return_sum, 1),
            "episodes": episodes,
            "mean_return": round(return_sum / max(episodes, 1.0), 2),
            "boundaries": float(m["boundaries_done"].sum()),
            "wall_s": round(time.monotonic() - t_start, 1),
        }

        if args.eval_every and chunk % args.eval_every == 0:
            eval_key, k = jax.random.split(eval_key)
            t0 = time.monotonic()
            ev = anakin.greedy_eval(state.train.params, args.eval_envs,
                                    args.eval_steps, k)
            row["eval_mean_return"] = round(ev["mean_return"], 2)
            row["eval_episodes"] = ev["episodes"]
            row["eval_s"] = round(time.monotonic() - t0, 1)

        if chunk % args.checkpoint_every == 0 or frames >= args.total_frames:
            ck.save(int(state.train.step), state.train,
                    extra={"frames": frames, "chunk": chunk})
            row["checkpoint"] = int(state.train.step)

        with progress.open("a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()

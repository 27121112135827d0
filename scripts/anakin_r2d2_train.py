"""Train pixel R2D2 on an on-device game (Breakout/Pong) — the Anakin
recurrent-replay configuration at chip rate.

The reference's R2D2 is its CartPole downscaling (MLP torso,
`/root/reference/model/r2d2_lstm.py:26-47`); the R2D2 paper itself is an
Atari agent with the Nature-DQN conv stack in front of the LSTM. This
script runs that configuration with everything on-device: jittable env
(`envs/{breakout,pong}_jax.py`), conv-torso `R2D2Net`
(`models/r2d2_net.py`, `torso="nature"`), per-sequence prioritized ring
in HBM (`runtime/anakin_r2d2.py`), stored-state + burn-in learning.

    python scripts/anakin_r2d2_train.py --out runs/r2d2_breakout \
        --env breakout --num-envs 128 --total-frames 60000000

Emits one JSON line per chunk to `<out>/progress.jsonl`, checkpoints the
TrainState (resume with `--resume`), periodic on-device greedy evals.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", required=True)
    p.add_argument("--env", default="breakout", choices=["breakout", "pong"])
    p.add_argument("--num-envs", type=int, default=128)
    p.add_argument("--seq-len", type=int, default=20)
    p.add_argument("--burn-in", type=int, default=10)
    p.add_argument("--lstm", type=int, default=256)
    p.add_argument("--capacity", type=int, default=8192,
                   help="replay ring capacity in SEQUENCES (each pixel "
                        "sequence is seq_len x 28 KB of uint8 frames)")
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--updates-per-collect", type=int, default=2,
                   help="prioritized learn batches per collected unroll")
    p.add_argument("--updates-per-chunk", type=int, default=50)
    p.add_argument("--total-frames", type=int, default=60_000_000,
                   help="env frames (post-frameskip actions x num_envs)")
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--discount", type=float, default=0.997)
    p.add_argument("--priority-eta", type=float, default=0.9,
                   help="R2D2-paper priority mixture eta*max+(1-eta)*mean "
                        "(the reference's |mean TD| quirk starves on "
                        "sparse-reward pixels); pass -1 for the reference "
                        "quirk")
    p.add_argument("--adam-clip", type=float, default=None,
                   help="optional global-norm clip in front of Adam")
    p.add_argument("--target-sync", type=int, default=400,
                   help="learn steps between target-net syncs")
    p.add_argument("--epsilon-decay", type=float, default=0.1)
    p.add_argument("--epsilon-floor", type=float, default=0.02)
    p.add_argument("--warmup-collects", type=int, default=8,
                   help="ring-fill collects before training starts")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--platform", default=None)
    p.add_argument("--f32", action="store_true")
    p.add_argument("--checkpoint-every", type=int, default=50)
    p.add_argument("--eval-every", type=int, default=20)
    p.add_argument("--eval-envs", type=int, default=32)
    p.add_argument("--eval-steps", type=int, default=None)
    p.add_argument("--resume", action="store_true")
    return p.parse_args()


def main() -> None:
    args = parse_args()
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.r2d2 import R2D2Agent, R2D2Config
    from distributed_reinforcement_learning_tpu.envs import breakout_jax, pong_jax
    from distributed_reinforcement_learning_tpu.runtime.anakin_r2d2 import AnakinR2D2
    from distributed_reinforcement_learning_tpu.utils.checkpoint import Checkpointer
    from distributed_reinforcement_learning_tpu.utils.device import (
        enable_compile_cache, open_devices)

    enable_compile_cache()
    open_devices("anakin-r2d2")

    env_mod = {"breakout": breakout_jax, "pong": pong_jax}[args.env]
    if args.eval_steps is None:
        cap = {"breakout": 10_000, "pong": 20_000}[args.env]
        args.eval_steps = cap // 4 + 500
    # Ring writes stay num_envs-aligned (AnakinR2D2 requirement).
    args.capacity -= args.capacity % args.num_envs
    if args.capacity < args.num_envs:
        sys.exit(f"--capacity must be at least --num-envs "
                 f"({args.num_envs}); alignment left {args.capacity}")
    ring_gb = args.capacity * args.seq_len * 84 * 84 * 4 / 2**30
    if ring_gb > 8:
        sys.exit(f"--capacity prices {ring_gb:.1f} GB of HBM frames; "
                 "lower it (v5e holds 16 GB total)")

    platform = jax.default_backend()
    on_accel = platform not in ("cpu",)
    dtype = jnp.float32 if (args.f32 or not on_accel) else jnp.bfloat16

    cfg = R2D2Config(
        obs_shape=env_mod.OBS_SHAPE,
        num_actions=env_mod.NUM_ACTIONS,
        seq_len=args.seq_len,
        burn_in=args.burn_in,
        lstm_size=args.lstm,
        discount_factor=args.discount,
        learning_rate=args.lr,
        priority_eta=None if args.priority_eta < 0 else args.priority_eta,
        gradient_clip_norm=args.adam_clip,
        torso="nature",
        dtype=dtype,
    )
    agent = R2D2Agent(cfg)
    anakin = AnakinR2D2(
        agent, num_envs=args.num_envs, batch_size=args.batch_size,
        capacity=args.capacity, target_sync_interval=args.target_sync,
        updates_per_collect=args.updates_per_collect,
        epsilon_decay=args.epsilon_decay, epsilon_floor=args.epsilon_floor,
        env=env_mod)

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / "config.json").write_text(json.dumps(
        {k: str(v) if k == "dtype" else v
         for k, v in {**vars(args), "platform": platform,
                      "dtype": dtype.__name__}.items()}, indent=2))
    ck = Checkpointer(out / "ckpt", retain=3)
    progress = out / "progress.jsonl"

    state = anakin.init(jax.random.PRNGKey(args.seed))
    # env frames per chunk: each update collects one seq_len unroll from
    # every env (training frames; greedy-eval rollouts not counted).
    frames_per_update = args.num_envs * args.seq_len
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
            # Restore the per-env episode counters, or the epsilon ladder
            # snaps back to 1.0 and a trained policy resumes collecting
            # pure noise. (Best effort: an env-count change falls back to
            # fresh counters.)
            eps_saved = extra.get("episodes_per_env")
            if eps_saved is not None and len(eps_saved) == args.num_envs:
                state = state._replace(
                    episodes=jnp.asarray(eps_saved, jnp.int32))
            print(f"[resume] step={step} frames={frames:,} "
                  f"eps_mean={float(anakin._epsilon(state.episodes).mean()):.3f}",
                  file=sys.stderr)
    # Ring fill: also on resume — the replay ring is NOT checkpointed, so
    # a resumed learner must not sample from an empty/near-empty ring.
    if args.warmup_collects:
        state, _ = anakin.collect_chunk(state, args.warmup_collects)
        frames += args.warmup_collects * frames_per_update

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
            "loss": round(float(m["loss"][-1]), 5),
            "grad_norm": round(float(m["grad_norm"][-1]), 4),
            "return_sum": round(return_sum, 1),
            "episodes": episodes,
            "mean_return": round(return_sum / max(episodes, 1.0), 2),
            "boundaries": float(m["boundaries_done"].sum()),
            "epsilon": round(float(m["epsilon_mean"][-1]), 4),
            "replay_size": int(m["replay_size"][-1]),
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
                    extra={"frames": frames, "chunk": chunk,
                           "episodes_per_env":
                           np.asarray(state.episodes).tolist()})
            row["checkpoint"] = int(state.train.step)

        with progress.open("a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""Transformer-IMPALA launcher: IMPALA's actor/learner FIFO topology
(`/root/reference/train_impala.py`) with the causal transformer
actor-critic (agents/ximpala.py) — no reference counterpart; this family
composes V-trace with the framework's long-context machinery (ring
sequence parallelism, MoE, pipelining, remat all apply).

    python train_ximpala.py --section ximpala --updates 300

`--mode anakin` is the fused on-device loop of the token families
(runtime/anakin_tokens.py): a language model as the policy of a
token-level IMPALA, generation by decode through its act-time state and
the learn step in one compiled chunk. The families are the rows of
`agents/token_families.TOKEN_FAMILIES`; a section names one as its
`algorithm`, and another family's section is refused with that list.

    python train_ximpala.py --mode anakin --section ouro_looplm --updates 8
    python train_ximpala.py --mode anakin --section granite_hybrid --updates 2 --anakin_chunk 1
    python train_ximpala.py --mode anakin --section qwen3_next --updates 2 --anakin_chunk 1
    python train_ximpala.py --mode anakin --section joyai_flash --updates 2 --anakin_chunk 1
    python train_ximpala.py --mode anakin --section lfm2_moe --updates 2 --anakin_chunk 1
    python train_ximpala.py --mode anakin --section smallthinker_moe --updates 2 --anakin_chunk 1
    python train_ximpala.py --mode anakin --section nemotron_h_moe --updates 2 --anakin_chunk 1

Adding a token family: its model file (`models/`), its agent file with
its config class (`agents/`, a subclass of `agents/looplm.TokenLMConfig`
that says which published keys a section must carry and what is refused),
ONE row of `agents/token_families.TOKEN_FAMILIES`, and a section of
`config.json`. What the benchmark needs of a new cell is in
`perfbench/README.md`.
"""

from __future__ import annotations

import argparse


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default="config.json")
    p.add_argument("--section", default="ximpala")
    p.add_argument("--mode", default="local", choices=["local", "learner", "actor", "inference", "anakin"])
    p.add_argument("--task", type=int, default=-1)
    p.add_argument("--updates", type=int, default=1000)
    p.add_argument("--run_dir", default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--checkpoint_dir", default=None,
                   help="learner mode: save/resume TrainState checkpoints here")
    p.add_argument("--checkpoint_interval", type=int, default=500)
    p.add_argument("--actor_grace", type=float, default=120.0,
                   help="actor mode: seconds to ride out a learner outage before exiting")
    p.add_argument("--platform", default=None,
                   help="force a jax platform (e.g. 'cpu'); actors default to cpu "
                        "so they never grab the TPU chip")
    p.add_argument("--anakin_envs", type=int, default=None,
                   help="anakin mode: parallel on-device envs (default "
                        "num_actors * envs_per_actor from the section)")
    p.add_argument("--anakin_chunk", type=int, default=2,
                   help="anakin mode: updates per compiled chunk")
    p.add_argument("--serve_inference", action="store_true",
                   help="learner mode: serve SEED-style centralized inference")
    p.add_argument("--remote_act", action="store_true",
                   help="actor mode: offload act() to the learner's inference service")
    args = p.parse_args()

    # Actors AND inference replicas default to cpu: neither may grab
    # the TPU chip the learner process holds (single-owner libtpu) —
    # pass --platform explicitly when a replica has its own accelerator.
    platform = args.platform or (
        "cpu" if args.mode in ("actor", "inference") else None)
    if platform:
        import jax
        jax.config.update("jax_platforms", platform)
    from distributed_reinforcement_learning_tpu.utils.device import enable_compile_cache

    enable_compile_cache()

    if args.mode == "anakin":
        # Fully on-device decode + learn (runtime/anakin_tokens.py).
        from distributed_reinforcement_learning_tpu.runtime.launch import train_anakin_tokens

        print(train_anakin_tokens(args.config, args.section, args.updates,
                                  chunk=args.anakin_chunk, seed=args.seed,
                                  num_envs=args.anakin_envs,
                                  checkpoint_dir=args.checkpoint_dir,
                                  run_dir=args.run_dir))
        return
    if args.mode == "local":
        from distributed_reinforcement_learning_tpu.runtime.launch import train_local

        result = train_local(args.config, args.section, args.updates,
                             run_dir=args.run_dir, seed=args.seed,
                             checkpoint_dir=args.checkpoint_dir,
                             checkpoint_interval=args.checkpoint_interval)
        print({k: v for k, v in result.items() if k != "episode_returns"})
    else:
        from distributed_reinforcement_learning_tpu.runtime.transport import run_role

        run_role("ximpala", args.config, args.section, args.mode, args.task,
                 num_updates=args.updates, run_dir=args.run_dir, seed=args.seed,
                 checkpoint_dir=args.checkpoint_dir,
                 checkpoint_interval=args.checkpoint_interval,
                 actor_grace=args.actor_grace,
                 serve_inference=args.serve_inference,
                 remote_act=args.remote_act)


if __name__ == "__main__":
    main()

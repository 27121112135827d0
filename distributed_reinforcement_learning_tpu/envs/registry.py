"""Env registry: name -> constructor, the `gym.make` seam.

The reference resolves env names via `gym.make` (`train_impala.py:117`,
`wrappers.py:115-138`). Resolution order here:

- an explicitly registered factory (`register_env`) always wins;
- `CartPole-v*` goes through **gymnasium** (installed in this image) so
  training is validated against an environment the framework didn't
  write; set `DRL_NO_GYMNASIUM=1` to force the in-tree numpy physics
  (tests use it for determinism, and it is the automatic fallback);
- Atari names (`*Deterministic-v4`, `*NoFrameskip-v4`) use gymnasium +
  `ale-py` when the emulator is importable; otherwise `Breakout*`,
  `Pong*` and `SpaceInvaders*` fall back to the in-tree simulators (real
  game dynamics at ALE specs, through the same GymnasiumRawFrames
  adapter — envs/{breakout,pong,invaders}_sim; Pong/SpaceInvaders adapt
  without fire-reset, the reference's `make_uint8_env_no_fire` path)
  and other titles fall back to the full preprocessing pipeline over
  `SyntheticAtari`. All
  fallbacks say so on stderr, once per name, because training
  "Breakout" on a stand-in silently is how a benchmark lies
  (`DRL_SYNTHETIC_ATARI=1` opts into silence).
"""

from __future__ import annotations

import os
import re
import sys
from typing import Callable

from distributed_reinforcement_learning_tpu.envs.atari import AtariPreprocessor, SyntheticAtari
from distributed_reinforcement_learning_tpu.envs.base import Env
from distributed_reinforcement_learning_tpu.envs.cartpole import CartPoleEnv

_REGISTRY: dict[str, Callable[..., Env]] = {}

_ATARI_PATTERN = re.compile(r".*(Deterministic|NoFrameskip)-v\d+$")
_warned_synthetic: set[str] = set()


def register_env(name: str, factory: Callable[..., Env]) -> None:
    _REGISTRY[name] = factory


def _use_gymnasium() -> bool:
    if os.environ.get("DRL_NO_GYMNASIUM", "0") == "1":
        return False
    from distributed_reinforcement_learning_tpu.envs.gymnasium_env import gymnasium_available

    return gymnasium_available()


def _sim_fallback(name: str, sim_mod, id_prefix: str, seed: int,
                  fire_reset: bool, raw_cls, game: str) -> Env:
    """Shared no-ALE fallback: warn once, then route through gymnasium's
    registration of the in-tree simulator (the exact `GymnasiumRawFrames`
    adapter an ale-py install would use) or the raw-protocol class.

    The Deterministic name encodes ALE's built-in frameskip 4 (see
    GymnasiumRawFrames docstring) — honored in the sim either way.
    """
    if name not in _warned_synthetic and os.environ.get("DRL_SYNTHETIC_ATARI") != "1":
        _warned_synthetic.add(name)
        print(f"[envs] WARNING: no ALE emulator available; {name!r} resolves "
              f"to the in-tree {game} simulator (real game dynamics, not "
              f"the 2600 ROM). Install ale-py for the real game.",
              file=sys.stderr)
    skip = 4 if "Deterministic" in name else 1
    if _use_gymnasium() and sim_mod.register_gymnasium():
        from distributed_reinforcement_learning_tpu.envs.gymnasium_env import GymnasiumRawFrames

        sim_name = (f"{id_prefix}Deterministic-v0" if skip == 4
                    else f"{id_prefix}-v0")
        return AtariPreprocessor(GymnasiumRawFrames(sim_name, seed=seed),
                                 fire_reset=fire_reset)
    return AtariPreprocessor(raw_cls(seed=seed, frameskip=skip),
                             fire_reset=fire_reset)


def make_jittable_env(name: str, **params):
    """The on-device (fused-loop) envs that are built from a section's
    own sizes rather than imported as a module: name -> env object of the
    `cartpole_jax` contract."""
    if name.startswith("TokenRecall"):
        from distributed_reinforcement_learning_tpu.envs.token_recall_jax import TokenRecall

        return TokenRecall(**params)
    raise ValueError(f"unknown jittable env {name!r}")


def make_env(name: str, seed: int = 0, num_actions: int = 18) -> Env:
    if name in _REGISTRY:
        return _REGISTRY[name](seed=seed)
    if name in ("CartPole-v0", "CartPole-v1"):
        if _use_gymnasium():
            from distributed_reinforcement_learning_tpu.envs.gymnasium_env import GymnasiumEnv

            return GymnasiumEnv(name, seed=seed)
        return CartPoleEnv(seed=seed, max_steps=200 if name.endswith("v0") else 500)
    if _ATARI_PATTERN.match(name):
        if _use_gymnasium():
            from distributed_reinforcement_learning_tpu.envs.gymnasium_env import (
                GymnasiumRawFrames, ale_available)

            if ale_available():
                return AtariPreprocessor(GymnasiumRawFrames(name, seed=seed))
        # No emulator importable. Breakout falls back to the in-tree
        # Breakout simulator — a real game (paddle/ball/brick dynamics,
        # 2600 palette, FIRE launch, 5 lives) rendered at ALE specs —
        # through the SAME GymnasiumRawFrames adapter an ALE install
        # would use. Other titles fall back to SyntheticAtari noise.
        if name.startswith("Breakout"):
            from distributed_reinforcement_learning_tpu.envs import breakout_sim

            return _sim_fallback(name, breakout_sim, "BreakoutSim", seed,
                                 fire_reset=True,
                                 raw_cls=breakout_sim.BreakoutSimRaw,
                                 game="Breakout")
        if name.startswith("Pong"):
            # Second faithful game (envs/pong_sim): 6-action set, signed
            # rewards, no lives. Adapted WITHOUT fire-reset — the
            # reference's `make_uint8_env_no_fire` path
            # (`wrappers.py:132-138`); serves are FIRE or auto.
            from distributed_reinforcement_learning_tpu.envs import pong_sim

            return _sim_fallback(name, pong_sim, "PongSim", seed,
                                 fire_reset=False,
                                 raw_cls=pong_sim.PongSimRaw, game="Pong")
        if name.startswith("SpaceInvaders"):
            # Third faithful game (envs/invaders_sim): 6-action set with
            # combined move+fire, enemy projectiles, destructible
            # shields, mid-episode lives — the structurally-different
            # objective the paddle pair doesn't exercise. No fire-reset:
            # FIRE shoots (not a serve), so the wrapper would just waste
            # the first frame.
            from distributed_reinforcement_learning_tpu.envs import invaders_sim

            return _sim_fallback(name, invaders_sim, "SpaceInvadersSim", seed,
                                 fire_reset=False,
                                 raw_cls=invaders_sim.InvadersSimRaw,
                                 game="Space-Invaders")
        # Synthetic frames through the real preprocessing pipeline (same
        # shapes/dtypes/life semantics).
        if name not in _warned_synthetic and os.environ.get("DRL_SYNTHETIC_ATARI") != "1":
            _warned_synthetic.add(name)
            print(f"[envs] WARNING: no ALE emulator available; {name!r} resolves to "
                  f"SyntheticAtari (random frames through the real preprocessing "
                  f"pipeline). Install ale-py for the real game.", file=sys.stderr)
        return AtariPreprocessor(SyntheticAtari(num_actions=num_actions, seed=seed))
    raise ValueError(f"unknown env {name!r}; register a factory with register_env")

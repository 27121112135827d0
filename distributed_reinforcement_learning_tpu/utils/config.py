"""Config system: JSON schema parity with the reference's `config.json`.

Loads the same per-algorithm JSON sections (`config.json:2,25,68`) into
typed runtime configs and applies the reference's validation rules
(`utils.py:33-44` check_properties). Extra fields introduced by this
framework (actor batching, transport ports) have defaults so reference
configs load unchanged.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any

from distributed_reinforcement_learning_tpu.agents.apex import ApexConfig
from distributed_reinforcement_learning_tpu.agents.impala import ImpalaConfig
from distributed_reinforcement_learning_tpu.agents.r2d2 import R2D2Config
from distributed_reinforcement_learning_tpu.agents.xformer import XformerConfig


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Topology + data-plane settings shared by all three algorithms."""

    algorithm: str
    server_ip: str = "localhost"
    server_port: int = 8000
    num_actors: int = 1
    envs: tuple[str, ...] = ("CartPole-v0",)
    available_action: tuple[int, ...] = (2,)
    queue_size: int = 128
    batch_size: int = 32
    envs_per_actor: int = 1  # actor-side env batching (new: one jitted act serves all)
    replay_capacity: int = 100_000
    target_sync_interval: int = 100  # `train_apex.py:151-152`, `train_r2d2.py:163-164`
    train_start_factor: int = 3  # learner trains when queue > factor*batch (`train_impala.py:94`)
    publish_interval: int = 1  # IMPALA weight-publish cadence (1 = reference parity)
    updates_per_call: int = 1  # K optimizer steps per learn_many dispatch (all families)
    seq_parallel: int = 1  # xformer: devices carving the mesh's `seq` axis
    expert_parallel: int = 1  # xformer MoE: devices carving the `expert` axis
    epsilon_floor: float | None = None  # r2d2/xformer actors: residual
    # exploration floor. None = each family's own default (r2d2 0.0 =
    # reference-parity decay to ~greedy, xformer 0.15); stable-R2D2 mode
    # uses e.g. 0.02.
    timeout_nonterminal: bool = False  # r2d2/xformer actors: record
    # time-limit truncations as non-terminal (stable mode; removes the
    # time-limit-aliasing collapse cycle. False = reference parity)


def check_config(rt: RuntimeConfig, num_actions: int) -> None:
    """Validation parity with `utils.py:33-44`."""
    for a in rt.available_action:
        if num_actions < a:
            raise ValueError(f"available_action {a} exceeds model_output {num_actions}")
    if rt.num_actors != len(rt.available_action):
        raise ValueError("num_actors != len(available_action)")
    if rt.num_actors != len(rt.envs):
        raise ValueError("num_actors != len(env)")


def _runtime_from_section(algo: str, d: dict[str, Any]) -> RuntimeConfig:
    return RuntimeConfig(
        algorithm=algo,
        server_ip=d.get("server_ip", "localhost"),
        server_port=d.get("server_port", 8000),
        num_actors=d.get("num_actors", 1),
        envs=tuple(d.get("env", ("CartPole-v0",))),
        available_action=tuple(d.get("available_action", (d.get("model_output", 2),))),
        queue_size=d.get("queue_size", 128),
        batch_size=d.get("batch_size", 32),
        envs_per_actor=d.get("envs_per_actor", 1),
        replay_capacity=int(d.get("replay_capacity", 1e5)),
        target_sync_interval=d.get("target_sync_interval", 100),
        train_start_factor=d.get("train_start_factor", 3),
        publish_interval=d.get("publish_interval", 1),
        updates_per_call=d.get("updates_per_call", 1),
        seq_parallel=d.get("seq_parallel", 1),
        expert_parallel=d.get("expert_parallel", 1),
        epsilon_floor=d.get("epsilon_floor"),
        timeout_nonterminal=d.get("timeout_nonterminal", False),
    )


_DTYPES = ("float32", "bfloat16")


def _compute_dtype(d: dict[str, Any], default: str):
    """A section's `dtype` key (the transformer families' matmul
    operands and activations): `float32` or `bfloat16`, anything else is
    an error naming the key."""
    import jax.numpy as jnp

    name = d.get("dtype", default)
    if name not in _DTYPES:
        raise ValueError(f"dtype {name!r}: one of {_DTYPES}")
    return jnp.dtype(name).type


def _token_config(cls, d: dict[str, Any]):
    """A token family's section -> its config, by the class's own
    declaration (`agents/looplm.TokenLMConfig`): the model's keys are the
    source's own, so a `MUST` key the section lacks is an error and not a
    default (a width is never guessed), what the family's config can say
    and this program does not compute is refused by name (`ONLY`,
    `check_section`), and every other field is the section's value or the
    dataclass's default. The three renames are here and nowhere else."""
    for key in cls.MUST:
        if key not in d:
            raise KeyError(key)
    for key, only in cls.ONLY.items():
        if d.get(key, only) != only:
            raise ValueError(f"{key} {d[key]!r}: only {only!r} is computed")
    cls.check_section(d)
    values = {"dtype": _compute_dtype(d, "bfloat16")}
    for f in dataclasses.fields(cls):
        key = "initializer_range" if f.name == "init_std" else f.name
        if key in d and f.name != "dtype" and f.metadata.get("section_key", True):
            values[f.name] = tuple(d[key]) if isinstance(d[key], list) else d[key]
    if "learning_frame" in values:
        values["learning_frame"] = int(values["learning_frame"])
    return cls(**values)


def load_config(path: str | Path, section: str):
    """Load one config section -> (agent_config, runtime_config).

    Accepts the reference's `config.json` verbatim (same keys:
    `config.json:2-24` r2d2, `:25-67` impala, `:68-106` apex). Extra
    sections like `impala_cartpole` resolve their algorithm from the
    section-name prefix (or an explicit `"algorithm"` key).
    """
    data = json.loads(Path(path).read_text())
    d = data[section]
    algorithm = d.get("algorithm", section.split("_")[0])
    rt = _runtime_from_section(algorithm, d)
    # Not a switch any more: integer frames always stay bytes until conv0
    # (`agents/common.prep_obs`). Sections written before that carry the
    # key; `true` is what the program does, `false` cannot be had.
    if not d.get("fold_normalize", True):
        raise ValueError(
            f"section {section!r}: \"fold_normalize\": false is refused: the "
            "agent-side /255 pass is gone (integer frames go to the model raw "
            "and conv0's kernel carries the 1/255); drop the key")

    if algorithm == "impala":
        agent_cfg = ImpalaConfig(
            obs_shape=tuple(d["model_input"]),
            num_actions=d["model_output"],
            trajectory=d.get("trajectory", 20),
            lstm_size=d.get("lstm_size", 256),
            discount_factor=d.get("discount_factor", 0.99),
            baseline_loss_coef=d.get("baseline_loss_coef", 1.0),
            entropy_coef=d.get("entropy_coef", 0.05),
            gradient_clip_norm=d.get("gradient_clip_norm", 40.0),
            reward_clipping=d.get("reward_clipping", "abs_one"),
            start_learning_rate=d.get("start_learning_rate", 6e-4),
            end_learning_rate=d.get("end_learning_rate", 0.0),
            learning_frame=int(d.get("learning_frame", 1e9)),
            torso=d.get("torso", "nature"),
            torso_width=d.get("torso_width", 1),
        )
    elif algorithm == "apex":
        agent_cfg = ApexConfig(
            obs_shape=tuple(d["model_input"]),
            num_actions=d["model_output"],
            discount_factor=d.get("discount_factor", 0.99),
            reward_clipping=d.get("reward_clipping", "abs_one"),
            gradient_clip_norm=d.get("gradient_clip_norm", 40.0),
            start_learning_rate=d.get("start_learning_rate", 1e-4),
            end_learning_rate=d.get("end_learning_rate", 0.0),
            learning_frame=int(d.get("learning_frame", 1e9)),
        )
    elif algorithm == "r2d2":
        agent_cfg = R2D2Config(
            obs_shape=tuple(d["model_input"]),
            num_actions=d["model_output"],
            seq_len=d.get("seq_len", 10),
            burn_in=d.get("burn_in", 5),
            lstm_size=d.get("lstm_size", 512),
            discount_factor=d.get("discount_factor", 0.997),
            learning_rate=d.get("start_learning_rate", 1e-4),
            priority_eta=d.get("priority_eta", None),
            # NOT the section's `gradient_clip_norm`: the reference
            # carries that key but never applies it to R2D2
            # (`agent/r2d2.py:91-92`), and honoring it would silently
            # change reference-config behavior. Stable mode opts in via
            # the distinct `adam_clip_norm` key.
            gradient_clip_norm=d.get("adam_clip_norm", None),
            # Pixel-R2D2 extensions (models/r2d2_net.py): the reference's
            # R2D2 is MLP/CartPole-only, so these keys have no reference
            # counterpart.
            torso=d.get("torso", "mlp"),
            torso_width=d.get("torso_width", 1),
            # The paper's Atari configuration (section `r2d2_atari`);
            # absent = the reference's 1-step targets and Dense(128) head.
            n_step=d.get("n_step", 1),
            dueling_hidden=d.get("dueling_hidden", None),
        )
    elif algorithm == "xformer":
        agent_cfg = XformerConfig(
            obs_shape=tuple(d["model_input"]),
            num_actions=d["model_output"],
            seq_len=d.get("seq_len", 10),
            burn_in=d.get("burn_in", 5),
            d_model=d.get("d_model", 128),
            num_heads=d.get("num_heads", 4),
            num_layers=d.get("num_layers", 2),
            discount_factor=d.get("discount_factor", 0.997),
            learning_rate=d.get("start_learning_rate", 1e-4),
            attention=d.get("attention", "dense"),
            num_experts=d.get("num_experts", 0),
            moe_top_k=d.get("moe_top_k", 2),
            moe_capacity_factor=d.get("moe_capacity_factor", 2.0),
            moe_aux_weight=d.get("moe_aux_weight", 1e-2),
            pipeline=d.get("pipeline", False),
            pipeline_microbatches=d.get("pipeline_microbatches", 2),
            pipeline_stages=d.get("pipeline_stages", 0),
            remat=d.get("remat", False),
            priority_eta=d.get("priority_eta", None),
            gradient_clip_norm=d.get("adam_clip_norm", None),
        )
    elif algorithm == "ximpala":
        from distributed_reinforcement_learning_tpu.agents.ximpala import XImpalaConfig

        agent_cfg = XImpalaConfig(
            obs_shape=tuple(d["model_input"]),
            num_actions=d["model_output"],
            trajectory=d.get("trajectory", 20),
            d_model=d.get("d_model", 128),
            num_heads=d.get("num_heads", 4),
            num_layers=d.get("num_layers", 2),
            discount_factor=d.get("discount_factor", 0.99),
            baseline_loss_coef=d.get("baseline_loss_coef", 1.0),
            entropy_coef=d.get("entropy_coef", 0.05),
            gradient_clip_norm=d.get("gradient_clip_norm", 40.0),
            reward_clipping=d.get("reward_clipping", "abs_one"),
            start_learning_rate=d.get("start_learning_rate", 6e-4),
            end_learning_rate=d.get("end_learning_rate", 0.0),
            learning_frame=int(d.get("learning_frame", 1e9)),
            attention=d.get("attention", "dense"),
            num_experts=d.get("num_experts", 0),
            moe_top_k=d.get("moe_top_k", 2),
            moe_capacity_factor=d.get("moe_capacity_factor", 2.0),
            moe_aux_weight=d.get("moe_aux_weight", 1e-2),
            pipeline=d.get("pipeline", False),
            pipeline_microbatches=d.get("pipeline_microbatches", 2),
            pipeline_stages=d.get("pipeline_stages", 0),
            remat=d.get("remat", False),
            dtype=_compute_dtype(d, "float32"),
        )
    else:
        from distributed_reinforcement_learning_tpu.agents.token_families import (
            TOKEN_FAMILIES)

        if algorithm not in TOKEN_FAMILIES:
            raise ValueError(f"unknown algorithm {algorithm!r}")
        agent_cfg = _token_config(TOKEN_FAMILIES[algorithm][0], d)

    check_config(rt, agent_cfg.num_actions)
    return agent_cfg, rt

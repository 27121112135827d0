"""Build-and-run helpers shared by the CLI launchers.

Replaces the reference's per-launcher graph assembly
(`train_impala.py:22-87` and analogues): resolves envs from the
registry, instantiates agent + queue + weight store + learner + actors
from a config section, and runs either the synchronous single-process
loop or free-running threads. The multi-process topology (one learner
process + N actor processes over the socket transport) layers on top in
runtime/transport.
"""

from __future__ import annotations

import math
import time
from typing import Any

import jax

from distributed_reinforcement_learning_tpu.agents.apex import ApexAgent, ApexConfig
from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent, ImpalaConfig
from distributed_reinforcement_learning_tpu.agents.r2d2 import R2D2Agent, R2D2Config
from distributed_reinforcement_learning_tpu.agents.xformer import XformerAgent, XformerConfig
from distributed_reinforcement_learning_tpu.agents.ximpala import XImpalaAgent, XImpalaConfig
from distributed_reinforcement_learning_tpu.data import device_replay
from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue
from distributed_reinforcement_learning_tpu.envs.batched import BatchedEnv
from distributed_reinforcement_learning_tpu.envs.cartpole import pomdp_project
from distributed_reinforcement_learning_tpu.envs.registry import make_env
from distributed_reinforcement_learning_tpu.observability import TELEMETRY as _OBS
from distributed_reinforcement_learning_tpu.observability import (
    chip_span,
    maybe_configure,
    scopes,
)
from distributed_reinforcement_learning_tpu.observability.trace import (
    HOST_RECORD as _HOST,
)
from distributed_reinforcement_learning_tpu.runtime import (
    apex_runner,
    impala_runner,
    r2d2_runner,
    xformer_runner,
    ximpala_runner,
)
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore
from distributed_reinforcement_learning_tpu.utils.config import RuntimeConfig, load_config
from distributed_reinforcement_learning_tpu.utils.device import open_devices
from distributed_reinforcement_learning_tpu.utils.logger import MetricsLogger
from distributed_reinforcement_learning_tpu.utils.profiling import ProfilerSession


def _make_batched_env(rt: RuntimeConfig, actor_index: int, num_actions: int) -> BatchedEnv:
    name = rt.envs[actor_index % len(rt.envs)]
    n = rt.envs_per_actor
    return BatchedEnv([
        (lambda s=seed: make_env(name, seed=s, num_actions=num_actions))
        for seed in range(actor_index * n, actor_index * n + n)
    ])


def _is_atari(rt: RuntimeConfig) -> bool:
    return any("v4" in e for e in rt.envs)


def _algo_of(agent_cfg: Any) -> str:
    if isinstance(agent_cfg, ImpalaConfig):
        return "impala"
    if isinstance(agent_cfg, ApexConfig):
        return "apex"
    if isinstance(agent_cfg, R2D2Config):
        return "r2d2"
    if isinstance(agent_cfg, XformerConfig):
        return "xformer"
    if isinstance(agent_cfg, XImpalaConfig):
        return "ximpala"
    raise TypeError(f"unknown agent config {type(agent_cfg)}")


_AGENT_CLS = {"impala": ImpalaAgent, "apex": ApexAgent, "r2d2": R2D2Agent,
              "xformer": XformerAgent, "ximpala": XImpalaAgent}

# Families whose learn step can shard beyond data parallelism (ring/
# pipeline/expert) and whose actors therefore need plain-apply twins.
_TRANSFORMER_ALGOS = ("xformer", "ximpala")


def mesh_axes_for(agent_cfg: Any, rt: RuntimeConfig) -> tuple[int, int, int]:
    """(seq, pipe, expert) axis sizes the learner mesh should carve for
    this config — the single source of truth for run_role, make_agent
    and build_local (the three places that size meshes / pick actor
    twins must agree or GSPMD errors replace config errors).

    pipeline forces dense attention, so it also forces the seq axis to 1
    (a leftover seq_parallel would idle devices).
    """
    pipelined = getattr(agent_cfg, "pipeline", False)
    return (
        1 if pipelined else rt.seq_parallel,
        (getattr(agent_cfg, "pipeline_stages", 0)
         or getattr(agent_cfg, "num_layers", 1)) if pipelined else 1,
        rt.expert_parallel if getattr(agent_cfg, "num_experts", 0) else 1,
    )


def needs_sharded_learner(algo: str, agent_cfg: Any, rt: RuntimeConfig) -> bool:
    """True when the learn step is sharded beyond data parallelism (and
    actors therefore need a plain-apply twin)."""
    return algo in _TRANSFORMER_ALGOS and (
        agent_cfg.attention != "dense"
        or agent_cfg.pipeline
        or (agent_cfg.num_experts > 0 and rt.expert_parallel > 1)
    )


def make_agent(algo: str, agent_cfg: Any, rt: RuntimeConfig, mesh=None, actor: bool = False):
    """Construct the algorithm's agent.

    Only the transformer family needs care — its learn step can be
    sharded three ways, each needing a mesh built here (over local
    devices, axis sizes from the config) when the caller has none:

    - `attention="ring"|"ring_zigzag"|"ulysses"`: sequence dim over a
      `seq` axis of `rt.seq_parallel` devices;
    - `pipeline=true`: layers as GPipe stages over a `pipe` axis of
      `num_layers` devices;
    - `num_experts>0` with `rt.expert_parallel>1`: MoE experts over an
      `expert` axis.

    ACTORS always get a plain-apply twin (dense attention, no pipeline
    schedule — but the SAME param layout, incl. the stacked layout a
    pipelined learner publishes): an actor acts on a small
    [N, seq_len] window on its own (often single-device) host where a
    collective mesh is wrong or impossible.
    """
    if needs_sharded_learner(algo, agent_cfg, rt):
        import dataclasses

        cls = _AGENT_CLS[algo]
        if actor:
            return cls(dataclasses.replace(
                agent_cfg, attention="dense", pipeline=False,
                stacked=agent_cfg.pipeline or agent_cfg.stacked))
        if mesh is None:
            from distributed_reinforcement_learning_tpu.parallel import make_mesh

            seq, pipe, expert = mesh_axes_for(agent_cfg, rt)
            mesh = make_mesh(
                seq_parallel=seq, pipe_parallel=pipe, expert_parallel=expert)
        return cls(agent_cfg, mesh=mesh)
    return _AGENT_CLS[algo](agent_cfg)


def make_learner(algo: str, agent_cfg: Any, rt: RuntimeConfig, queue, weights,
                 logger: MetricsLogger | None = None, rng: Any = None, agent=None,
                 prefetch: bool = False, mesh=None, replay_service=None):
    """Learner runner over any queue/weight-store (in-process or served).

    `mesh`: optional `jax.sharding.Mesh` — the learn step is pjit-sharded
    over it (batch on the data axis) instead of running single-device.
    `replay_service`: optional sharded replay (data/replay_service.py,
    wired by run_role through runtime/replay_shard.py) — the prioritized
    learners sample/update against it while it is healthy."""
    agent = agent or make_agent(algo, agent_cfg, rt, mesh=mesh)
    if algo in ("impala", "ximpala"):
        cls = (ximpala_runner.XImpalaLearner if algo == "ximpala"
               else impala_runner.ImpalaLearner)
        return cls(
            agent, queue, weights, rt.batch_size, logger=logger, rng=rng,
            prefetch=prefetch, mesh=mesh, publish_interval=rt.publish_interval,
            updates_per_call=rt.updates_per_call)
    if algo == "apex":
        return apex_runner.ApexLearner(
            agent, queue, weights, rt.batch_size,
            replay_capacity=rt.replay_capacity,
            target_sync_interval=rt.target_sync_interval, logger=logger, rng=rng,
            mesh=mesh, publish_interval=rt.publish_interval,
            updates_per_call=rt.updates_per_call, replay_service=replay_service)
    cls = (xformer_runner.XformerLearner if algo == "xformer"
           else r2d2_runner.R2D2Learner)
    return cls(
        agent, queue, weights, rt.batch_size,
        replay_capacity=rt.replay_capacity,
        target_sync_interval=rt.target_sync_interval, logger=logger, rng=rng,
        mesh=mesh, publish_interval=rt.publish_interval,
        updates_per_call=rt.updates_per_call, replay_service=replay_service)


def make_actor(algo: str, agent_cfg: Any, rt: RuntimeConfig, task: int, queue, weights,
               seed: int = 0, agent=None, remote_act=None):
    """Actor `task` of the topology, over any queue/weight-store.

    The queue/weights may be the learner's own objects (single process) or
    transport adapters (multi-process) — same construction either way.
    Pass `agent` to share one jit cache across runners in-process;
    `remote_act` (any algorithm) switches the actor to SEED-style
    centralized inference on the learner.
    """
    agent = agent or make_agent(algo, agent_cfg, rt, actor=True)
    env = _make_batched_env(rt, task, agent_cfg.num_actions)
    atari = _is_atari(rt)
    if algo == "impala":
        return impala_runner.ImpalaActor(
            agent, env, queue, weights, seed=seed,
            available_action=rt.available_action[task % len(rt.available_action)],
            life_loss_shaping=atari, remote_act=remote_act)
    if algo == "apex":
        return apex_runner.ApexActor(
            agent, env, queue, weights, seed=seed, life_loss_shaping=atari,
            remote_act=remote_act)
    transform = pomdp_project if agent_cfg.obs_shape == (2,) else None
    if algo == "ximpala":
        return ximpala_runner.XImpalaActor(
            agent, env, queue, weights, seed=seed,
            available_action=rt.available_action[task % len(rt.available_action)],
            life_loss_shaping=atari, obs_transform=transform,
            remote_act=remote_act)
    # None = keep the actor family's own epsilon-floor default (r2d2 0.0
    # reference parity, xformer 0.15) instead of overriding it.
    floor = {} if rt.epsilon_floor is None else {"epsilon_floor": rt.epsilon_floor}
    if algo == "xformer":
        return xformer_runner.XformerActor(
            agent, env, queue, weights, seed=seed, obs_transform=transform,
            timeout_nonterminal=rt.timeout_nonterminal, remote_act=remote_act,
            **floor)
    return r2d2_runner.R2D2Actor(
        agent, env, queue, weights, seed=seed, obs_transform=transform,
        timeout_nonterminal=rt.timeout_nonterminal, remote_act=remote_act,
        **floor)


_RUN_SYNC = {
    "impala": impala_runner.run_sync,
    "apex": apex_runner.run_sync,
    "r2d2": r2d2_runner.run_sync,
    "xformer": xformer_runner.run_sync,
    "ximpala": ximpala_runner.run_sync,
}


def build_local(agent_cfg: Any, rt: RuntimeConfig, run_dir: str | None = None, seed: int = 0):
    """-> (learner, actors, run_fn) for single-host training."""
    algo = _algo_of(agent_cfg)
    logger = MetricsLogger(run_dir)
    queue = TrajectoryQueue(rt.queue_size)
    weights = WeightStore()
    sp = needs_sharded_learner(algo, agent_cfg, rt)
    # One jit cache for all runners — except a sharded (ring/pipeline/
    # expert-parallel) learner, whose collective schedules the actors
    # must not share.
    agent = make_agent(algo, agent_cfg, rt)
    actor_agent = make_agent(algo, agent_cfg, rt, actor=True) if sp else agent
    learner = make_learner(algo, agent_cfg, rt, queue, weights,
                           logger=logger, rng=jax.random.PRNGKey(seed), agent=agent)
    actors = [
        make_actor(algo, agent_cfg, rt, i, queue, weights, seed=seed + 1 + i,
                   agent=actor_agent)
        for i in range(rt.num_actors)
    ]
    return learner, actors, _RUN_SYNC[algo]


def _jittable_env_for(agent_cfg, rt):
    """-> (env_module | None, obs_transform | None) for the anakin modes.

    Pixel sections route to the on-device game implementations; vector
    sections default to the JAX CartPole (module None), with the POMDP
    projection when the agent observes the 2-feature view."""
    env_name = rt.envs[0] if rt.envs else ""
    if env_name.startswith("Breakout"):
        from distributed_reinforcement_learning_tpu.envs import breakout_jax

        return breakout_jax, None
    if env_name.startswith("SpaceInvaders"):
        from distributed_reinforcement_learning_tpu.envs import invaders_jax

        return invaders_jax, None
    if env_name.startswith("Pong"):
        from distributed_reinforcement_learning_tpu.envs import pong_jax

        return pong_jax, None
    if tuple(agent_cfg.obs_shape) == (2,):
        return None, pomdp_project  # jnp-compatible slicing + scale
    return None, None


def _restore_train(checkpoint_dir, train):
    """-> (Checkpointer | None, train) with the latest checkpoint loaded."""
    if not checkpoint_dir:
        return None, train
    from distributed_reinforcement_learning_tpu.utils.checkpoint import Checkpointer

    ckpt = Checkpointer(checkpoint_dir)
    got = ckpt.restore(train)
    if got is not None:
        train = got[0]
    return ckpt, train


def _read_step(state) -> int:
    """The optimizer step at a fused loop's head (a device read)."""
    with chip_span(scopes.STEP_READ, _OBS.trace):
        return int(state.train.step)


def _run_chunk(anakin, state, u: int, steps_per_update: int, log, ckpt,
               label: str):
    """One chunk of a fused loop's host side under its `chip_span`s
    (observability/scopes.py): dispatch `u` chunk updates of
    `steps_per_update` optimizer steps each, wait, report
    (`log(step, mean_return, episodes, metrics)` is the chunk's line),
    checkpoint; then the line of the chunk's spans with their wall start
    (`[<label>] chunk <n>: ...`) and, after the first chunk during which
    nothing compiled, the start's line (`observability.trace.HostRecord`).
    Per chunk and not the whole loop, so that no frame keeps an earlier
    state's device buffers alive. -> (state, mean return)."""
    import numpy as np

    trace = _OBS.trace
    t0 = time.perf_counter()
    with chip_span(scopes.DISPATCH, trace):
        state, m = anakin.train_chunk(state, u)
    with chip_span(scopes.WAIT, trace):
        episodes_done = np.asarray(m["episodes_done"])
    # The read above is the chunk's device sync, so dt is honest device
    # time for the whole compiled chunk.
    dt = time.perf_counter() - t0
    with chip_span(scopes.REPORT, trace):
        eps = float(episodes_done.sum())
        mean_ret = float(np.asarray(m["episode_return_sum"]).sum()) / max(eps, 1.0)
        if _OBS.enabled:
            _OBS.count("anakin/updates", u * steps_per_update)
            _OBS.gauge("anakin/device_chunk_s", dt)
        step = int(state.train.step)
        print(log(step, mean_ret, eps, m))
    if ckpt is not None:
        with chip_span(scopes.CHECKPOINT, trace):
            ckpt.save(step, state.train, {})
    print("\n".join(_HOST.end_chunk(label)))
    return state, mean_ret


def _print_unclosed_start(label: str) -> None:
    """A loop that ends before a chunk closed its start (a short run, a
    failure in the first chunk) still prints where the start went."""
    line = _HOST.close_start(label)
    if line is not None:
        print(line)


def train_anakin(config_path: str, section: str, num_updates: int,
                 chunk: int = 50, seed: int = 0, num_envs: int | None = None,
                 checkpoint_dir: str | None = None,
                 run_dir: str | None = None) -> dict:
    """Fully on-device IMPALA training (runtime/anakin.py): jittable-env
    sections only (CartPole-family). Collect + learn run as compiled
    chunks of `chunk` updates; per-chunk mean episode returns stream to
    stdout. No queue, no transport, no host loop. `checkpoint_dir`
    saves/restores the TrainState per chunk (env/LSTM state is
    ephemeral: a resume starts fresh episodes, same as every
    actor restart in the distributed topology)."""
    maybe_configure("anakin", 0, run_dir)  # env-gated run-wide telemetry
    open_devices("anakin")
    with chip_span(scopes.START_BUILD, _OBS.trace):
        agent_cfg, rt = load_config(config_path, section)
        if _algo_of(agent_cfg) != "impala":
            raise ValueError("anakin mode currently runs the IMPALA family")
        from distributed_reinforcement_learning_tpu.runtime.anakin import AnakinImpala

        env_mod, _ = _jittable_env_for(agent_cfg, rt)
        agent = ImpalaAgent(agent_cfg)
        anakin = AnakinImpala(
            agent, num_envs or rt.num_actors * rt.envs_per_actor, env=env_mod)
    print(f"[anakin] learn handoff: {anakin.handoff}")  # static, as compiled
    with chip_span(scopes.START_INIT, _OBS.trace):
        state = anakin.init(jax.random.PRNGKey(seed))
    with chip_span(scopes.START_RESTORE, _OBS.trace):
        ckpt, train = _restore_train(checkpoint_dir, state.train)
        state = state._replace(train=train)
    chunk = max(1, min(chunk, num_updates))
    last = {"loss": None}

    def log(step: int, mean_ret: float, eps: float, m) -> str:
        last["loss"] = float(m["total_loss"][-1])
        return (f"[anakin] step {step}: mean_return {mean_ret:.1f} "
                f"({eps:.0f} episodes, loss {last['loss']:.2f})")

    returns = []
    profiler = ProfilerSession.from_env()  # DRL_PROFILE_DIR: one on_step a chunk
    try:
        while (step := _read_step(state)) < num_updates:
            profiler.on_step(step)
            state, mean_ret = _run_chunk(
                anakin, state, min(chunk, num_updates - step), 1, log, ckpt,
                "anakin")
            returns.append(mean_ret)
    finally:
        profiler.close()
        _print_unclosed_start("anakin")
    return {
        "frames": int(state.train.step) * anakin.num_envs * agent_cfg.trajectory,
        "last_loss": last["loss"],
        "chunk_mean_returns": [round(r, 2) for r in returns],
        "mean_return_last_chunk": round(returns[-1], 2) if returns else None,
    }


def _replay_chunk_loop(anakin, state, num_updates: int, chunk: int, ckpt,
                       label: str, frames_per_collect: int, warm: int) -> dict:
    """Shared warm-up + chunked train loop for the on-device replay
    families (AnakinR2D2 / AnakinApex — same train_chunk/metrics
    contract). `num_updates` counts OPTIMIZER steps; each chunk update is
    one collect + K learns (K = updates_per_collect), so chunk sizing
    and the frame count are in collect-updates and the final chunk may
    overshoot by up to K-1 optimizer steps."""
    print(f"[{label}] {device_replay.describe_storage(state.replay)}")
    with chip_span(scopes.START_WARM_COLLECT, _OBS.trace):
        state, _ = anakin.collect_chunk(state, warm)
    K = anakin.updates_per_collect

    def log(step: int, mean_ret: float, eps: float, m) -> str:
        return (f"[{label}] step {step}: mean_return {mean_ret:.1f} "
                f"({eps:.0f} episodes, loss {float(m['loss'][-1]):.4f}, "
                f"eps {float(m['epsilon_mean'][-1]):.3f})")

    collects = warm
    returns = []
    profiler = ProfilerSession.from_env()  # DRL_PROFILE_DIR: one on_step a chunk
    try:
        while (step := _read_step(state)) < num_updates:
            profiler.on_step(step)
            u = max(1, min(chunk, -(-(num_updates - step) // K)))
            state, mean_ret = _run_chunk(anakin, state, u, K, log, ckpt, label)
            collects += u
            returns.append(mean_ret)
    finally:
        profiler.close()
        _print_unclosed_start(label)
    return {
        "frames": collects * frames_per_collect,
        "chunk_mean_returns": [round(r, 2) for r in returns],
        "mean_return_last_chunk": round(returns[-1], 2) if returns else None,
    }


def train_anakin_apex(config_path: str, section: str, num_updates: int,
                      chunk: int = 50, seed: int = 0,
                      num_envs: int | None = None,
                      capacity: int | None = None,
                      checkpoint_dir: str | None = None,
                      run_dir: str | None = None) -> dict:
    """Fully on-device Ape-X (runtime/anakin_apex.py): transition
    collection, the prioritized ring, double-DQN training, and target
    syncs inside compiled chunks. With a pixel section this trains the
    dueling conv net on real game dynamics at chip rate.

    `capacity` defaults to min(replay_capacity, 32768) transitions —
    each pixel transition stores TWO 84x84x4 uint8 stacks (s and s',
    ~56 KB), so the default ring costs ~1.8 GB of device memory; the
    host topology's 100k default would triple that."""
    maybe_configure("anakin-apex", 0, run_dir)  # env-gated run-wide telemetry
    open_devices("anakin-apex")
    with chip_span(scopes.START_BUILD, _OBS.trace):
        agent_cfg, rt = load_config(config_path, section)
        if _algo_of(agent_cfg) != "apex":
            raise ValueError("anakin-apex mode runs the Ape-X family")
        from distributed_reinforcement_learning_tpu.runtime.anakin_apex import AnakinApex

        env_mod, obs_transform = _jittable_env_for(agent_cfg, rt)
        agent = ApexAgent(agent_cfg)
        n = num_envs or rt.num_actors * rt.envs_per_actor
        steps = 16
        width = n * steps
        cap = capacity or min(rt.replay_capacity, 32768)
        cap = max(width, cap - cap % width)  # ring writes stay width-aligned
        anakin = AnakinApex(
            agent, num_envs=n, batch_size=rt.batch_size, capacity=cap,
            steps_per_collect=steps,
            target_sync_interval=rt.target_sync_interval,
            updates_per_collect=rt.updates_per_call,
            epsilon_floor=rt.epsilon_floor or 0.0,
            env=env_mod, obs_transform=obs_transform)
    with chip_span(scopes.START_INIT, _OBS.trace):
        state = anakin.init(jax.random.PRNGKey(seed))
    with chip_span(scopes.START_RESTORE, _OBS.trace):
        ckpt, train = _restore_train(checkpoint_dir, state.train)
        state = state._replace(train=train)
    warm = -(-rt.train_start_factor * rt.batch_size // width)
    return _replay_chunk_loop(anakin, state, num_updates, chunk, ckpt,
                              "anakin-apex", width, warm)


def train_anakin_r2d2(config_path: str, section: str, num_updates: int,
                      chunk: int = 50, seed: int = 0,
                      num_envs: int | None = None,
                      capacity: int | None = None,
                      checkpoint_dir: str | None = None,
                      run_dir: str | None = None) -> dict:
    """Fully on-device R2D2 (runtime/anakin_r2d2.py): collect, the
    prioritized replay ring, and training all inside compiled chunks.
    Jittable envs only (CartPole-family sections via the POMDP
    projection, pixel sections via envs/{breakout,pong}_jax). `capacity`
    defaults to min(replay_capacity, 4096) sequences — the ring lives in
    device memory, so the host topology's 100k default would swamp HBM
    for pixel observations."""
    maybe_configure("anakin-r2d2", 0, run_dir)  # env-gated run-wide telemetry
    open_devices("anakin-r2d2")
    with chip_span(scopes.START_BUILD, _OBS.trace):
        agent_cfg, rt = load_config(config_path, section)
        if _algo_of(agent_cfg) != "r2d2":
            raise ValueError("anakin-r2d2 mode runs the R2D2 family")
        from distributed_reinforcement_learning_tpu.runtime.anakin_r2d2 import AnakinR2D2

        env_mod, obs_transform = _jittable_env_for(agent_cfg, rt)
        agent = R2D2Agent(agent_cfg)
        n = num_envs or rt.num_actors * rt.envs_per_actor
        cap = capacity or min(rt.replay_capacity, 4096)
        cap = max(n, cap - cap % n)  # ring writes stay n-aligned
        anakin = AnakinR2D2(
            agent, num_envs=n, batch_size=rt.batch_size, capacity=cap,
            target_sync_interval=rt.target_sync_interval,
            updates_per_collect=rt.updates_per_call,
            epsilon_floor=rt.epsilon_floor or 0.0,
            env=env_mod, obs_transform=obs_transform)
    print(f"[anakin-r2d2] score order: {anakin.score_order}")  # static, as compiled
    with chip_span(scopes.START_INIT, _OBS.trace):
        state = anakin.init(jax.random.PRNGKey(seed))
    with chip_span(scopes.START_RESTORE, _OBS.trace):
        ckpt, train = _restore_train(checkpoint_dir, state.train)
        state = state._replace(train=train)
    # Warm-up: the host learner's train-start gate, as collect-only chunks.
    warm = -(-rt.train_start_factor * rt.batch_size // n)
    return _replay_chunk_loop(anakin, state, num_updates, chunk, ckpt,
                              "anakin-r2d2", n * agent_cfg.seq_len, warm)


def train_local(config_path: str, section: str, num_updates: int,
                run_dir: str | None = None, seed: int = 0,
                checkpoint_dir: str | None = None,
                checkpoint_interval: int = 500) -> dict:
    """Single-process training entry used by the CLI launchers.

    With `checkpoint_dir`, resumes from the latest checkpoint and saves
    every `checkpoint_interval` updates by running the sync loop in
    chunks (the loops target absolute `learner.train_steps`, so chunked
    calls compose; actor episode returns persist across chunks)."""
    open_devices("local")
    agent_cfg, rt = load_config(config_path, section)
    learner, actors, run_fn = build_local(agent_cfg, rt, run_dir=run_dir, seed=seed)
    maybe_configure("local", 0, run_dir)  # env-gated run-wide telemetry
    checkpoint_interval = max(1, int(checkpoint_interval))  # 0 would spin forever
    ckpt = None
    if checkpoint_dir:
        from distributed_reinforcement_learning_tpu.utils.checkpoint import Checkpointer

        ckpt = Checkpointer(checkpoint_dir)
        learner.restore_checkpoint(ckpt)
    frames = 0
    result: dict = {"frames": 0, "last_metrics": {}, "episode_returns": []}
    if learner.train_steps >= num_updates:
        # Resumed at/past the target: report, don't silently print {}.
        result["skipped"] = (
            f"checkpoint already at step {learner.train_steps} >= {num_updates}")
    try:
        while learner.train_steps < num_updates:
            target = (num_updates if ckpt is None else
                      min(learner.train_steps + checkpoint_interval, num_updates))
            # close_learner=False: this loop owns the learner across chunks.
            result = run_fn(learner, actors, target, close_learner=False)
            frames += result.get("frames", 0)
            if ckpt is not None:
                learner.save_checkpoint(ckpt)
    finally:
        learner.close()
        _OBS.close()  # final shard flush + trace terminator
    if "frames" in result:
        result["frames"] = frames
    returns = result.get("episode_returns", [])
    if returns:
        import numpy as np

        result["mean_return_last20"] = float(np.mean(returns[-20:]))
    return result


def train_anakin_tokens(config_path: str, section: str, num_updates: int,
                        chunk: int = 2, seed: int = 0,
                        num_envs: int | None = None,
                        checkpoint_dir: str | None = None,
                        run_dir: str | None = None) -> dict:
    """Fully on-device token-level IMPALA on a looped language model
    (runtime/anakin_tokens.py; `train_ximpala.py --mode anakin`): N token
    envs each play one episode by decode through a per-pass key/value
    cache, then one V-trace learn step, in compiled chunks of `chunk`
    updates on the same `_run_chunk` as the other fused loops."""
    maybe_configure("anakin-tokens", 0, run_dir)  # env-gated run-wide telemetry
    open_devices("anakin-tokens")
    with chip_span(scopes.START_BUILD, _OBS.trace):
        agent_cfg, rt = load_config(config_path, section)
        # One token-level actor-critic, as many models as
        # `agents/token_families.TOKEN_FAMILIES` has rows.
        from distributed_reinforcement_learning_tpu.envs.registry import make_jittable_env
        from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import AnakinTokens

        agent = _token_agent(agent_cfg)  # at the end of this file; refuses the rest
        env = make_jittable_env(
            rt.envs[0], vocab=agent_cfg.vocab_size,
            episode_len=agent_cfg.trajectory, distance=agent_cfg.recall_distance)
        anakin = AnakinTokens(
            agent, num_envs or rt.num_actors * rt.envs_per_actor, env)
    # static, as compiled
    print(f"[anakin-tokens] {anakin.static_facts}{_expert_calls(anakin)}")
    with chip_span(scopes.START_INIT, _OBS.trace):
        state = anakin.init(jax.random.PRNGKey(seed))
    with chip_span(scopes.START_RESTORE, _OBS.trace):
        ckpt, train = _restore_train(checkpoint_dir, state.train)
        state = state._replace(train=train)
    chunk = max(1, min(chunk, num_updates))
    last = {"loss": None}

    def log(step: int, mean_ret: float, eps: float, m) -> str:
        last["loss"] = float(m["total_loss"][-1])
        cdf = [round(float(m[f"exit_cdf_pass{i}"][-1]), 3)
               for i in range(1, agent_cfg.total_ut_steps)]
        return (f"[anakin-tokens] step {step}: mean_return {mean_ret:.2f} "
                f"({eps:.0f} episodes, loss {last['loss']:.2f}, exit cdf {cdf}, "
                f"rho clipped {float(m['rho_clipped_share'][-1]):.3f}{_pair_slabs(m)})")

    returns = []
    profiler = ProfilerSession.from_env()  # DRL_PROFILE_DIR: one on_step a chunk
    try:
        while (step := _read_step(state)) < num_updates:
            profiler.on_step(step)
            state, mean_ret = _run_chunk(
                anakin, state, min(chunk, num_updates - step), 1, log, ckpt,
                "anakin-tokens")
            returns.append(mean_ret)
    finally:
        profiler.close()
        _print_unclosed_start("anakin-tokens")
    return {
        "frames": int(state.train.step) * anakin.num_envs * agent_cfg.trajectory,
        "last_loss": last["loss"],
        "chunk_mean_returns": [round(r, 2) for r in returns],
        "mean_return_last_chunk": round(returns[-1], 2) if returns else None,
    }


def _token_agent(agent_cfg):
    """The token-level agent of a configuration of one of the token
    families (`agents/token_families.py`); any other is refused."""
    from distributed_reinforcement_learning_tpu.agents.token_families import (
        TOKEN_FAMILIES)

    for config, agent in TOKEN_FAMILIES.values():
        if isinstance(agent_cfg, config):
            return agent(agent_cfg)
    raise ValueError("anakin-tokens mode runs the "
                     f"{', '.join(TOKEN_FAMILIES)} families")


def _expert_calls(anakin) -> str:
    """The form and shape of the held experts' call on a decode step's rows
    and on a row block of the learner's `[B, T]` (`ops/expert_share.
    call_form`: chosen from the shapes when the chunk is traced), for the
    token loop's start-up line; nothing for a family without an expert
    share."""
    model = anakin.agent.model
    if not hasattr(model, "experts_held"):
        return ""
    from distributed_reinforcement_learning_tpu.ops.expert_share import call_form

    act, learn = (call_form(n, model.top_k, model.experts_held, model.num_experts,
                            (model.d_model, model.expert_width))
                  for n in (anakin.num_envs, math.gcd(anakin.num_envs, model.row_block)
                            * anakin.agent.cfg.trajectory))
    return f", held experts at act time: {act}; at learn time: {learn}"


def _pair_slabs(m) -> str:
    """The chunk's last update's slabs a call of the held experts (mean and
    max over layers and row blocks), for the token loop's log line; nothing
    for a family without an expert share."""
    if "pair_slabs_mean" not in m:
        return ""
    return (f", pair slabs {float(m['pair_slabs_mean'][-1]):.3f} "
            f"max {float(m['pair_slabs_max'][-1]):.0f}")


# The record's first span, `start/import`: from the kernel's start of this
# process to here, the last line of this module's import (the interpreter,
# JAX, flax, the package).
_HOST.add(scopes.START_IMPORT, None, _HOST.process_start,
          time.time() - _HOST.process_start)

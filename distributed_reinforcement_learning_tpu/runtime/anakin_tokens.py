"""Anakin-style fully-on-device token-level IMPALA: a looped language
model generates and learns inside one compiled chunk.

Per update, N on-device token envs (`envs/token_recall_jax.py`) each
play one whole episode of T = `trajectory` steps. One env step is ONE
decode step of the model at batch N (`LoopLMAgent._act`): embed the
token on show, R passes x L layers, each writing its key and value at
position t of ITS OWN cache and attending over positions <= t; sample an
action from the last pass's vocabulary head; record the token, the
action, log mu(action) (one float) and the reward. The key/value cache
`[R, L, N, T, H, d]` x 2 rides in the scan's carry and is zeroed at the
start of every update: episode = unroll, so the learner's `[N, T]`
forward sees exactly the context the actor saw (`ximpala`'s rule). Then
one learn step over the N x T rollout (`LoopLMAgent._learn`).

The T steps are S scans, not one (`looped_lm.decode_spans`: 8 of 16 at
T = 128): a decode step is bound by the bytes it reads, and a cache row
holds nothing past t, so the steps of segment i read the static prefix
`spans[i]` of their rows: 9/16 of the cache on average where one scan
read all of it at every step. The carry passes from scan to scan in
place; the compiled chunk holds S decode bodies of one layer each.

The rollout is five `[T, N]` scalars a step, so its swap to the `[N, T]`
that the attention wants moves 80 KB: there is no layout question here
(PR 29's was about frames). The chunk DONATES its state (PR 26): the
parameters and their second moments are 8 bytes a parameter.

Same chunk contract as `runtime/anakin.py` (`init`, `train_chunk(state,
updates) -> (state, stacked metrics)` with `episode_return_sum` and
`episodes_done`), so `launch._run_chunk` drives it unchanged.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.agents.common import TrainState
from distributed_reinforcement_learning_tpu.agents.looplm import (
    LoopLMAgent, LoopLMBatch)
from distributed_reinforcement_learning_tpu.models import looped_lm
from distributed_reinforcement_learning_tpu.observability import scopes
from distributed_reinforcement_learning_tpu.runtime.anakin_mesh import own_buffers


class TokensState(NamedTuple):
    train: TrainState
    env: Any  # TokenRecallState
    obs: jax.Array  # [N] i32 the token on show
    rng: jax.Array


class AnakinTokens:
    def __init__(self, agent: LoopLMAgent, num_envs: int, env):
        if env.NUM_ACTIONS != agent.cfg.vocab_size:
            raise ValueError(f"env vocabulary {env.NUM_ACTIONS} != the "
                             f"model's {agent.cfg.vocab_size}")
        if env.episode_len != agent.cfg.trajectory:
            raise ValueError(
                f"episode length {env.episode_len} != trajectory "
                f"{agent.cfg.trajectory}: the cache is reset every update, so "
                f"an episode has to be exactly one unroll")
        self.agent, self.env, self.num_envs = agent, env, num_envs
        self.decode_spans = looped_lm.decode_spans(agent.cfg.trajectory)
        self.train_chunk = jax.jit(scopes.tagged(self._train_chunk),
                                   static_argnums=(1,), donate_argnums=(0,))

    @property
    def static_facts(self) -> dict:
        """What the compiled chunk is, said once at start-up."""
        cfg, spans = self.agent.cfg, self.decode_spans
        steps = [hi - lo for lo, hi in zip((0, *spans), spans)]
        return {"loop_passes": cfg.total_ut_steps,  # and the act-time state by kind
                **self.agent.state_facts(self.num_envs),
                "compute_dtype": jnp.dtype(cfg.dtype).name,
                "decode_spans": spans,
                # mean over the T steps of the share of its row a step reads
                "cache_read_share": sum(n * span for n, span in zip(steps, spans))
                / cfg.trajectory ** 2}

    def init(self, rng: jax.Array) -> TokensState:
        k_train, k_env, k_run = jax.random.split(rng, 3)
        env, obs = self.env.reset(k_env, self.num_envs)
        return own_buffers(TokensState(
            train=self.agent.init_state(k_train), env=env, obs=obs, rng=k_run))

    # -- one env step = one decode step (scanned T times per update) -----
    def _env_step(self, act_params, span, carry, t):
        env, obs, cache, rng = carry
        rng, k_act, k_env = jax.random.split(rng, 3)
        with jax.named_scope(scopes.ACT):
            action, logp, cache = self.agent._act(act_params, obs, t, cache,
                                                  k_act, span)
        with jax.named_scope(scopes.ENV):
            env, next_obs, reward, done, ep_ret = self.env.step(env, action, k_env)
        record = dict(tokens=obs, action=action, behaviour_logp=logp,
                      reward=reward, done=done, episode_return=ep_ret)
        return (env, next_obs, cache, rng), record

    def _collect(self, act_params, carry, lo: int, hi: int, span: int):
        """Steps `[lo, hi)` of the episode, each reading the first `span`
        positions of its cache rows -> (carry, `[hi - lo, N]` records)."""
        if not lo < hi <= span:
            raise ValueError(
                f"decode steps {lo}..{hi - 1} under a cache prefix of {span} "
                f"positions: a step past its prefix cannot see what it wrote")
        return jax.lax.scan(
            functools.partial(self._env_step, act_params, span), carry,
            jnp.arange(lo, hi, dtype=jnp.int32))

    # -- one update: a T-step episode by decode, then learn --------------
    def _update(self, state: TokensState, _):
        agent, spans = self.agent, self.decode_spans
        if spans[-1] != agent.cfg.trajectory:
            raise ValueError(f"spans {spans} do not end at the episode's "
                             f"{agent.cfg.trajectory} steps")
        with jax.named_scope(scopes.COLLECT):
            with jax.named_scope(scopes.ACT_CACHE):
                cache = agent.init_cache(self.num_envs)
            act_params = agent.for_acting(state.train.params)
            carry, recs = (state.env, state.obs, cache, state.rng), []
            for lo, span in zip((0, *spans), spans):
                carry, rec = self._collect(act_params, carry, lo, span, span)
                recs.append(rec)
            env, obs, cache, rng = carry
            rec = jax.tree.map(lambda *xs: jnp.concatenate(xs), *recs)
        batch = LoopLMBatch(**{f: rec[f].swapaxes(0, 1)
                               for f in LoopLMBatch._fields})
        train, metrics = agent._learn(state.train, batch)
        metrics.update(agent.state_counters(cache))  # of the episode's last step
        metrics["episode_return_sum"] = rec["episode_return"].sum()
        metrics["episodes_done"] = rec["done"].sum().astype(jnp.float32)
        # The rollout itself (80 KB at 32 x 128): what a replay of this update needs.
        metrics["rollout"] = batch._asdict()
        return TokensState(train, env, obs, rng), metrics

    def _train_chunk(self, state: TokensState, num_updates: int):
        """U updates in one compiled program -> (state, stacked metrics)."""
        return jax.lax.scan(self._update, state, None, length=num_updates)

"""The comparisons that decide `correct` in `ouro_looplm.anakin_tokens`
(`families/looplm.py`) refuse what they are there to refuse: each wrong
program is PLANTED here, at a small size on the CPU, run through
`reference_check` (a) or recorded and replayed through `chunk_check` (b)
under the limits as committed, and `ok` has to come out false, by the
limit that is there for it. The right program passes both. (The same
faults at the published widths, on the chip: PERF.md section 6.)

Sizes: hidden 64, 4 heads of 16, SwiGLU 176, V 512, L 2, R 4, T 16,
N 4, float32; `init_std` 0.2 so that the passes and the caches differ
visibly, learning rate 1e-3 so that a step is over float32's last bit.
"""

import dataclasses

import jax
import jax.numpy as jnp
import optax
import pytest

import discover
from conftest import BENCH_DIR
from distributed_reinforcement_learning_tpu.agents.looplm import (
    LoopLMAgent, LoopLMConfig)
from distributed_reinforcement_learning_tpu.envs.token_recall_jax import (
    TokenRecall)
from distributed_reinforcement_learning_tpu.models import looped_lm
from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
    AnakinTokens)

V, T, N = 512, 16, 4
CFG = LoopLMConfig(
    vocab_size=V, hidden_size=64, num_attention_heads=4, head_dim=16,
    intermediate_size=176, num_hidden_layers=2, total_ut_steps=4,
    trajectory=T, dtype=jnp.float32, head_block=32, start_learning_rate=1e-3,
    init_std=0.2)
SECTION = {"trajectory": T, "vocab_size": V}
SEED = 3000000019

family = discover.module(BENCH_DIR, "families", "looplm")


# -- the wrong programs ---------------------------------------------------------


class Bf16LogSoftmax(looped_lm.LoopedLM):
    def token_stats(self, h, actions):
        z = looped_lm.rms_norm(h, self.final_norm, self.rms_eps)
        logp_all = jax.nn.log_softmax(
            self._mm(z, self.w_out).astype(jnp.bfloat16), axis=-1)
        taken = jnp.take_along_axis(logp_all, actions[..., None], axis=-1)[..., 0]
        entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
        _, gate, value = self.logits(h)
        return {"logp": taken.astype(jnp.float32),
                "entropy": entropy.astype(jnp.float32),
                "gate": gate, "value": value}


class SharedCache(looped_lm.LoopedLM):
    def _decode_layer(self, carry, xs, slot, t):
        return super()._decode_layer(carry, xs, slot * 0, t)


class HalfBatch(LoopLMAgent):
    def _learn(self, state, batch):
        return super()._learn(state, jax.tree.map(lambda x: x[:N // 2], batch))


class SwappedFields(LoopLMAgent):
    def _learn(self, state, batch):
        return super()._learn(state, batch._replace(
            tokens=batch.action, action=batch.tokens))


def with_model(cls) -> LoopLMAgent:
    agent = LoopLMAgent(CFG)
    m = agent.model
    agent.model = cls(**{f.name: getattr(m, f.name)
                         for f in dataclasses.fields(m)
                         if f.name not in ("parent", "name")})
    return agent


def wrong_sign() -> LoopLMAgent:
    agent = LoopLMAgent(CFG)
    agent.tx = optax.chain(agent.tx, optax.scale(-1.0))  # p - u
    return agent


def three_passes() -> LoopLMAgent:
    return LoopLMAgent(dataclasses.replace(CFG, total_ut_steps=3))


def cast(dtype):
    return lambda state: state.replace(params=jax.tree.map(
        lambda x: x.astype(dtype), state.params))


# -- (a) the seeded batch ---------------------------------------------------------


def seeded(agent, state=lambda s: s) -> dict:
    good = LoopLMAgent(CFG)
    train = state(good.init_state(jax.random.PRNGKey(3)))
    return family.reference_check(agent, train, SECTION, SEED,
                                  hp=family.hyper(good))


def over(dist: dict, limits: dict) -> set:
    return {k for k in limits if not dist[k] <= limits[k]}  # a NaN is over


def test_the_right_program_passes_the_seeded_batch():
    got = seeded(LoopLMAgent(CFG))
    assert got["ok"], got["distance"]


@pytest.mark.parametrize("name, agent, state, refused_by", [
    ("bfloat16_parameters", lambda: LoopLMAgent(CFG), cast(jnp.bfloat16),
     "update_norm"),
    ("float16_parameters", lambda: LoopLMAgent(CFG), cast(jnp.float16),
     "update_norm"),
    ("bfloat16_log_softmax", lambda: with_model(Bf16LogSoftmax), lambda s: s,
     "head_logp"),
    ("three_passes", three_passes, lambda s: s, "logits"),
])
def test_seeded_batch_refuses(name, agent, state, refused_by):
    got = seeded(agent(), state)
    assert got["ok"] is False, (name, got["distance"])
    assert refused_by in over(got["distance"]["stated"], family.STATED), \
        (name, got["distance"]["stated"])


# -- (b) the replay of a compiled chunk ---------------------------------------------


def replayed(agent) -> dict:
    """A chunk of two updates of `agent`'s fused loop, recorded as the
    mode records the first warm chunk, and replayed by the reference
    under the RIGHT configuration."""
    good = LoopLMAgent(CFG)
    env = TokenRecall(vocab=V, episode_len=T, distance=8)
    anakin = AnakinTokens(agent, N, env)
    state = anakin.init(jax.random.PRNGKey(7))
    before = family.param_sample(state.train.params)
    state, metrics = anakin.train_chunk(state, 2)
    record = family.chunk_record(
        before, family.param_sample(state.train.params),
        jax.device_get(metrics))
    fresh = AnakinTokens(good, N, env).init(jax.random.PRNGKey(7)).train.params
    return family.chunk_check(good, fresh, record)


def test_the_right_program_passes_the_replay():
    got = replayed(LoopLMAgent(CFG))
    assert got["ok"], got
    assert got["updates"] == 2 and got["steps"] == 2 * N * T
    assert got["reference_moved"] > 0


@pytest.mark.parametrize("name, agent, refused_by", [
    ("learns_half_the_batch", lambda: HalfBatch(CFG), "loss"),
    ("fields_swapped", lambda: SwappedFields(CFG), "loss"),
    ("p_minus_u", wrong_sign, "step"),
    ("cache_shared_between_passes", lambda: with_model(SharedCache),
     "logp_max_abs"),
    ("three_passes", three_passes, "logp_max_abs"),
])
def test_replay_refuses(name, agent, refused_by):
    got = replayed(agent())
    assert got["ok"] is False, (name, got)
    assert refused_by in over(got["distance"], family.CHUNK), \
        (name, got["distance"])


def test_replay_refuses_another_start():
    """Parameters that are not those the chunk started from: nothing is
    compared."""
    agent = LoopLMAgent(CFG)
    env = TokenRecall(vocab=V, episode_len=T, distance=8)
    anakin = AnakinTokens(agent, N, env)
    state = anakin.init(jax.random.PRNGKey(7))
    before = family.param_sample(state.train.params)
    state, metrics = anakin.train_chunk(state, 2)
    record = family.chunk_record(before, before, jax.device_get(metrics))
    other = anakin.init(jax.random.PRNGKey(8)).train.params
    got = family.chunk_check(agent, other, record)
    assert got["ok"] is False and "made anew from the seed" in got["why"]

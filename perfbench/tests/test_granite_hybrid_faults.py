"""The comparisons that decide `correct` in
`granite_hybrid.anakin_tokens_1k` (`families/hybridlm.py`) refuse what
they are there to refuse: each wrong program is PLANTED here, at a small
size on the CPU, run through `reference_check` (a) or recorded and
replayed through `chunk_check` (b) under the limits as committed, and
`ok` has to come out false. The right program passes both. (The faults
that a precision hides at this size, at the published widths on the
chip: PERF.md section 6.)

Sizes: hidden 32, 4 query / 2 key-value heads of 8, SwiGLU 48, 4
state-space heads of 16 with a state of 8, chunks of 8, V 96, the order
mamba, mamba, attention, mamba, T 32, N 4, float32; `init_std` 0.2 so
that the layers differ visibly, learning rate 1e-3 so that a step is
over float32's last bit. A fault lives in the AGENT's class, so that the
`highest` twin, built as `type(agent)(cfg)`, carries it too.
"""

import dataclasses

import jax
import jax.numpy as jnp
import optax
import pytest

import discover
from conftest import BENCH_DIR
from distributed_reinforcement_learning_tpu.agents.hybridlm import (
    HybridLMAgent, HybridLMConfig)
from distributed_reinforcement_learning_tpu.envs.token_recall_jax import (
    TokenRecall)
from distributed_reinforcement_learning_tpu.models import hybrid_lm, looped_lm
from distributed_reinforcement_learning_tpu.ops import ssd
from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
    AnakinTokens)

V, T, N = 96, 32, 4
ORDER = ("mamba", "mamba", "attention", "mamba")
CFG = HybridLMConfig(
    vocab_size=V, hidden_size=32, layer_types=ORDER, num_attention_heads=4,
    num_key_value_heads=2, shared_intermediate_size=48, mamba_n_heads=4,
    mamba_d_head=16, mamba_d_state=8, mamba_chunk_size=8, trajectory=T,
    dtype=jnp.float32, attention_backend="reference", row_block=2,
    head_block=32, start_learning_rate=1e-3, init_std=0.2)
SECTION = {"trajectory": T, "vocab_size": V}
SEED = 3000000019

family = discover.module(BENCH_DIR, "families", "hybridlm")


def _remembered(fn):
    """The reference's answer to one question, asked once: every planted
    program is held against the same reference on the same batch."""
    memo = {}

    def wrapper(ref, theirs, batch, hp, *args, **kwargs):
        key = (batch["tokens"].tobytes(), batch["action"].tobytes(),
               str(jax.tree.leaves(theirs)[0].dtype),
               float(jnp.sum(jnp.abs(theirs["embed"].astype(jnp.float32)))),
               args, tuple(sorted(kwargs.items())))
        if key not in memo:
            memo[key] = fn(ref, theirs, batch, hp, *args, **kwargs)
        want, grads = memo[key]
        return dict(want), list(grads)  # `reference_step` empties its list

    return wrapper


family.reference_sums = _remembered(family.reference_sums)


# -- the wrong programs ---------------------------------------------------------


class Wrong(hybrid_lm.HybridLM):
    """One wrong model a name: each overrides one small method."""

    fault: str = ""

    def _rate(self, lp):
        rate = super()._rate(lp)
        return -rate / 8 if self.fault == "decay_of_the_wrong_sign" else rate

    def _step_size(self, dt, lp):
        if self.fault == "dt_without_its_bias":
            return jax.nn.softplus(dt)
        return super()._step_size(dt, lp)

    def _gated_out(self, y, x, z, lp):
        if self.fault != "gate_after_the_norm":
            return super()._gated_out(y, x, z, lp)
        g = (y + lp["D"][:, None] * x).reshape(*z.shape)
        return self._mm(looped_lm.rms_norm(g, lp["gate_norm"], self.rms_eps)
                        * jax.nn.silu(z), lp["out_proj"])

    def _grouped(self, q):
        if self.fault != "heads_not_grouped":
            return super()._grouped(q)
        groups = self.num_heads // self.num_kv_heads  # head i reads i % KV
        return jnp.swapaxes(q.reshape(*q.shape[:-2], groups, self.num_kv_heads,
                                      self.head_dim), -3, -2)

    def _attention(self, y, lp, seg):
        if self.fault != "heads_not_grouped":
            return super()._attention(y, lp, seg)
        b, t, _ = y.shape  # the learner's side of the same fault
        q = self._mm(y, lp["wq"]).reshape(b, t, self.num_heads, self.head_dim)
        k, v = jnp.split(self._mm(y, lp["wkv"]).reshape(
            b, t, 2 * self.num_kv_heads, self.head_dim), 2, axis=2)
        groups = self.num_heads // self.num_kv_heads
        s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.tile(k, (1, 1, groups, 1))
                       ) * self.attention_multiplier
        mask = ((jnp.arange(t)[:, None] >= jnp.arange(t)[None])[None, None]
                & (seg[:, None, :, None] == seg[:, None, None, :]))
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), -1)
        att = jnp.einsum("bhqk,bkhd->bqhd", p, jnp.tile(v, (1, 1, groups, 1)))
        return self._mm(att.reshape(b, t, -1), lp["wo"])

    def _decode_mamba(self, h, lp, state, window):
        h, state, new = super()._decode_mamba(h, lp, state, window)
        if self.fault == "window_shifted_by_one":
            new = jnp.roll(new, 1, axis=1)
        return h, state, new

    def _mamba(self, y, lp, seg, pos):
        if self.fault == "chunk_boundary_drops_the_state":
            # every chunk an episode of its own: H_{c-1} reaches nothing
            chunk = jnp.arange(seg.shape[1]) // self.mamba_chunk
            seg = seg * (seg.shape[1] // self.mamba_chunk + 1) + chunk
        return super()._mamba(y, lp, seg, pos)

    def token_stats(self, p, h, actions):
        if self.fault != "bfloat16_log_softmax":
            return super().token_stats(p, h, actions)
        logits, gate, value = self.logits(p, h)
        logp_all = jax.nn.log_softmax(logits.astype(jnp.bfloat16), axis=-1)
        taken = jnp.take_along_axis(logp_all, actions[..., None], axis=-1)[..., 0]
        entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
        return {"logp": taken.astype(jnp.float32),
                "entropy": entropy.astype(jnp.float32),
                "gate": gate, "value": value}


def faulty(fault: str, **model_fields):
    """An agent class whose model carries `fault` (and `model_fields`
    replaced), whatever configuration it is built from."""

    class Faulty(HybridLMAgent):
        def __init__(self, cfg):
            super().__init__(cfg)
            fields = {f.name: getattr(self.model, f.name)
                      for f in dataclasses.fields(self.model)}
            self.model = dataclasses.make_dataclass(
                "WrongModel", [], bases=(Wrong,), frozen=True,
                namespace={"fault": fault})(**{**fields, **model_fields})

    return Faulty


class StateNotReset(HybridLMAgent):
    """An update that starts from a state that is not zero, as one carried
    over from the update before would be."""

    def init_cache(self, num_rows):
        return jax.tree.map(lambda x: x + jnp.asarray(1.0, x.dtype),
                            super().init_cache(num_rows))


class HalfBatch(HybridLMAgent):
    def _learn(self, state, batch):
        return super()._learn(state, jax.tree.map(lambda x: x[:N // 2], batch))


def wrong_sign() -> HybridLMAgent:
    agent = HybridLMAgent(CFG)
    agent.tx = optax.chain(agent.tx, optax.scale(-1.0))  # p - u
    return agent


def multiplier_of_one(name):
    return lambda: HybridLMAgent(dataclasses.replace(CFG, **{name: 1.0}))


def cast(dtype):
    return lambda state: state.replace(params=jax.tree.map(
        lambda x: x.astype(dtype), state.params))


MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")
MODEL_FAULTS = ("decay_of_the_wrong_sign", "dt_without_its_bias",
                "gate_after_the_norm", "heads_not_grouped",
                "chunk_boundary_drops_the_state")


# -- (a) the seeded batch ---------------------------------------------------------


def seeded(agent, state=lambda s: s) -> dict:
    good = HybridLMAgent(CFG)
    train = state(good.init_state(jax.random.PRNGKey(3)))
    return family.reference_check(agent, train, SECTION, SEED,
                                  hp=family.hyper(good))


def over(dist: dict, limits: dict) -> set:
    return {k for k in limits if not dist[k] <= limits[k]}  # a NaN is over


def test_the_right_program_passes_the_seeded_batch():
    got = seeded(HybridLMAgent(CFG))
    assert got["ok"], got["distance"]


@pytest.mark.parametrize("name, agent, state, precision, refused_by", [
    ("bfloat16_parameters", lambda: HybridLMAgent(CFG), cast(jnp.bfloat16),
     "stated", "update_norm"),
    ("bfloat16_log_softmax", lambda: faulty("bfloat16_log_softmax")(CFG),
     lambda s: s, "stated", "head_logp"),
    *[(fault, (lambda f: lambda: faulty(f)(CFG))(fault), lambda s: s,
       "highest", "logits") for fault in MODEL_FAULTS],
    *[(f"{name}_of_one", multiplier_of_one(name), lambda s: s, "highest",
       "logits") for name in MULTIPLIERS],
])
def test_seeded_batch_refuses(name, agent, state, precision, refused_by):
    got = seeded(agent(), state)
    assert got["ok"] is False, (name, got["distance"])
    limits = family.STATED if precision == "stated" else family.HIGHEST
    assert refused_by in over(got["distance"][precision], limits), \
        (name, got["distance"][precision])


# -- (b) the replay of a compiled chunk ---------------------------------------------


def replayed(agent) -> dict:
    """A chunk of two updates of `agent`'s fused loop, recorded as the
    mode records the first warm chunk, and replayed by the reference
    under the RIGHT configuration."""
    good = HybridLMAgent(CFG)
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
    got = replayed(HybridLMAgent(CFG))
    assert got["ok"], got
    assert got["updates"] == 2 and got["steps"] == 2 * N * T
    assert got["reference_moved"] > 0


@pytest.mark.parametrize("name, agent, refused_by", [
    ("state_not_reset_between_updates", lambda: StateNotReset(CFG),
     "logp_max_abs"),
    ("window_shifted_by_one", lambda: faulty("window_shifted_by_one")(CFG),
     "logp_max_abs"),
    ("decay_of_the_wrong_sign", lambda: faulty("decay_of_the_wrong_sign")(CFG),
     "state"),
    ("dt_without_its_bias", lambda: faulty("dt_without_its_bias")(CFG),
     "dt_mean"),
    ("learns_half_the_batch", lambda: HalfBatch(CFG), "loss"),
    ("p_minus_u", wrong_sign, "step"),
])
def test_replay_refuses(name, agent, refused_by):
    got = replayed(agent())
    assert got["ok"] is False, (name, got)
    assert refused_by in over(got["distance"], family.CHUNK), \
        (name, got["distance"])


def test_replay_refuses_another_start():
    """Parameters that are not those the chunk started from: nothing is
    compared."""
    agent = HybridLMAgent(CFG)
    env = TokenRecall(vocab=V, episode_len=T, distance=8)
    anakin = AnakinTokens(agent, N, env)
    state = anakin.init(jax.random.PRNGKey(7))
    before = family.param_sample(state.train.params)
    state, metrics = anakin.train_chunk(state, 1)
    record = family.chunk_record(before, before, jax.device_get(metrics))
    other = anakin.init(jax.random.PRNGKey(8)).train.params
    got = family.chunk_check(agent, other, record)
    assert got["ok"] is False and "made anew from the seed" in got["why"]

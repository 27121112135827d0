"""The comparisons that decide `correct` in
`nemotron_h_moe.anakin_tokens_ssm_2k` (`families/ssmoelm.py`) refuse what
they are there to refuse: each wrong program is PLANTED here, at a small
size on the CPU, run through `reference_check` (a) or recorded and
replayed through `chunk_check` (b) under the limits as committed, and
`ok` has to come out false: the gated norm over all channels, the gate
after the norm, heads reading group h % G or group 0, `relu` for `relu^2`,
a gate on the experts, the shared expert missing or gated, the scale or
the renormalisation missing, the bias inside the weights, an expert layer
without its residual, a rotary planted in attention, a clamped dt, a
bfloat16 state across chunks, a chunk that drops S_0, an absent expert's
pairs added, a pair dropped, a lower precision among them. (A bfloat16
recurrent state at act time and a cache of the query heads give the same
numbers and are refused by their BYTES: `state_problems`,
`test_nemotron_h_moe_cell.py`.) The right program passes both.

Sizes: hidden 32, `ME*ME`, 8 state-space heads of 8 in 4 groups with a
state of 8 and a chunk of 8, 4 query and 2 key/value heads of 8, a router
16 wide with 3 experts a token of which experts 4..7 are held beside a
shared expert, V 96, T 32, N 4, float32; `init_std` 0.3 so that the
layers differ visibly, learning rate 1e-3 so that a step is over
float32's last bit. A fault lives in the AGENT's class, so that the
`highest` twin, built as `type(agent)(cfg)`, carries it too.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import optax
import pytest

import discover
from conftest import BENCH_DIR, ROOT
from distributed_reinforcement_learning_tpu.agents.ssmoelm import (
    SSMoELMAgent, SSMoELMConfig)
from distributed_reinforcement_learning_tpu.envs.token_recall_jax import (
    TokenRecall)
from distributed_reinforcement_learning_tpu.models import ssm_moe_lm
from distributed_reinforcement_learning_tpu.models.looped_lm import rms_norm
from distributed_reinforcement_learning_tpu.models.transformer_net import rope
from distributed_reinforcement_learning_tpu.ops import expert_share, ssd
from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
    AnakinTokens)
from distributed_reinforcement_learning_tpu.utils.config import load_config

V, T, N = 96, 32, 4


def _cfg() -> SSMoELMConfig:
    with open(os.path.join(ROOT, "config.json")) as f:
        small = dict(json.load(f)["nemotron_h_moe_small"], vocab_size=V,
                     available_action=[V], start_learning_rate=1e-3)
    path = os.path.join(os.environ.get("TMPDIR", "/tmp"), "nemotron_h_moe_faults.json")
    with open(path, "w") as f:
        json.dump({"nemotron_h_moe_small": small}, f)
    return dataclasses.replace(load_config(path, "nemotron_h_moe_small")[0],
                               attention_backend="reference", head_block=32)


CFG = _cfg()
SECTION = {"trajectory": T, "vocab_size": V}
SEED = 3000000019
Plain = ssm_moe_lm.SSMoELM

family = discover.module(BENCH_DIR, "families", "ssmoelm")


# -- the wrong programs ---------------------------------------------------------


class Wrong(Plain):
    """One wrong model a name: each overrides one small method, or swaps
    one function of `ops/` for the time the method is traced."""

    fault: str = ""

    def _gated_out(self, y, x, z, lp):
        g = (y + lp["D"][:, None] * x).reshape(*z.shape)
        if self.fault == "norm_over_all_channels":
            return self._mm(rms_norm(g * jax.nn.silu(z), lp["gate_norm"],
                                     self.rms_eps), lp["out_proj"])
        if self.fault == "gate_after_the_norm":
            g = rms_norm(g.reshape(*g.shape[:-1], self.mamba_groups, -1),
                         jnp.ones(()), self.rms_eps).reshape(g.shape)
            return self._mm(g * lp["gate_norm"] * jax.nn.silu(z), lp["out_proj"])
        return super()._gated_out(y, x, z, lp)

    def _split_conv(self, xbc):
        x, b, c = super()._split_conv(xbc)
        per = self.mamba_heads // self.mamba_groups
        if self.fault == "head_reads_group_h_mod_g":  # one group a head: h -> h % G
            b, c = (jnp.concatenate([m] * per, axis=-2) for m in (b, c))
        if self.fault == "group_zero_for_every_head":
            b, c = (jnp.repeat(m[..., :1, :], self.mamba_groups, -2) for m in (b, c))
        return x, b, c

    def _per_head(self, m):
        if m.shape[1] == self.mamba_heads:  # already one group a head
            return m[:, :, None]
        return super()._per_head(m)

    def _step_size(self, dt, lp):
        dt = super()._step_size(dt, lp)
        return jnp.clip(dt, 1e-3, 0.1) if self.fault == "dt_clamped" else dt

    def _mamba(self, y, lp, seg, pos):
        if self.fault == "a_chunk_drops_its_past":
            whole = ssd.ssd_chunked

            def chunk_by_chunk(x, dt, a, b, c, seg, chunk, *rest):
                cut = lambda v: v.reshape(-1, chunk, *v.shape[2:])
                y, state = whole(cut(x), cut(dt), a, cut(b), cut(c), cut(seg),
                                 chunk, *rest)
                return y.reshape(x.shape), state
            with pytest.MonkeyPatch.context() as m:
                m.setattr(ssd, "ssd_chunked", chunk_by_chunk)
                return super()._mamba(y, lp, seg, pos)
        return super()._mamba(y, lp, seg, pos)

    def _qkv(self, kind, y, lp, pos):
        q, k, v = super()._qkv(kind, y, lp, pos)
        if self.fault == "rotary_in_attention":
            q, k = rope(q, pos, 1e4), rope(k, pos, 1e4)
        return q, k, v

    def _layer(self, kind, h, seg, pos, lp):
        out, chosen, stats = super()._layer(kind, h, seg, pos, lp)
        if self.fault == "experts_without_their_residual" and kind == "moe":
            out = (out.astype(jnp.float32) - h.astype(jnp.float32)).astype(out.dtype)
        return out, chosen, stats

    def _experts(self, y, lp, scope):
        route, pairs = expert_share.route, expert_share.held_pairs
        held, first = self.experts_held, self.first_expert

        def no_scale(x, w, k, scoring, bias, scale, *eps):
            return route(x, w, k, scoring, bias, 1.0, *eps)

        def not_renormalised(x, w, k, scoring, bias, scale, *eps):
            scores, chosen, _, load = route(x, w, k, scoring, bias, scale, *eps)
            return scores, chosen, scale * jnp.take_along_axis(scores, chosen, -1), load

        def bias_in_the_weights(x, w, k, scoring, bias, scale, *eps):
            scores, chosen, _, load = route(x, w, k, scoring, bias, scale, *eps)
            top = jnp.take_along_axis(scores + bias, chosen, -1)
            return scores, chosen, scale * top / jnp.sum(top, -1, keepdims=True), load

        def unbiased_selection(x, w, k, scoring, bias, scale, *eps):
            return route(x, w, k, scoring, jnp.zeros_like(bias), scale, *eps)

        def absent_added(chosen, first_expert, n):  # every pair lands on a held expert
            return pairs(first + chosen % held, first_expert, n)

        def pair_dropped(chosen, first_expert, n):  # a token's last choice is lost
            return pairs(chosen.at[:, -1].set(-1), first_expert, n)

        def relu_alone(activation, up, counted):
            return jax.nn.relu(up), jnp.sum(counted & (up <= 0), dtype=jnp.int32)

        def gated(activation, up, counted):
            return (jnp.square(jax.nn.relu(up)) * jax.nn.sigmoid(up),
                    jnp.sum(counted & (up <= 0), dtype=jnp.int32))

        swap = {"scale_missing": ("route", no_scale),
                "weights_not_renormalised": ("route", not_renormalised),
                "bias_inside_the_weights": ("route", bias_in_the_weights),
                "selected_by_the_unbiased_scores": ("route", unbiased_selection),
                "absent_expert_added": ("held_pairs", absent_added),
                "pair_dropped": ("held_pairs", pair_dropped),
                "relu_for_relu2": ("_inner", relu_alone),
                "a_gate_on_the_experts": ("_inner", gated)}.get(self.fault)
        if self.fault == "shared_expert_missing":
            lp = {**lp, "shared_wd": jnp.zeros_like(lp["shared_wd"])}
        if self.fault == "shared_expert_gated":  # by a sigmoid of its first unit
            gate = jax.nn.sigmoid(self._mm(y, lp["shared_wu"][:, :1]))
            out, chosen, stats = super()._experts(y, lp, scope)
            shared = self._mm(jnp.square(jax.nn.relu(self._mm(y, lp["shared_wu"]))),
                              lp["shared_wd"])
            return out - (1.0 - gate) * shared, chosen, stats
        with pytest.MonkeyPatch.context() as m:
            if swap:
                m.setattr(expert_share, *swap)
            return super()._experts(y, lp, scope)

    def token_stats(self, p, h, actions):
        if self.fault != "bfloat16_log_softmax":
            return super().token_stats(p, h, actions)
        logits, gate, value = self.logits(p, h)
        logp_all = jax.nn.log_softmax(logits.astype(jnp.bfloat16), axis=-1)
        taken = jnp.take_along_axis(logp_all, actions[..., None], axis=-1)[..., 0]
        entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
        return {"logp": taken.astype(jnp.float32),
                "entropy": entropy.astype(jnp.float32), "gate": gate, "value": value}

    # -- at act time alone: the learner's forward stays right
    def _decode_ssm(self, y, lp, state, window):
        if self.fault == "state_not_carried_at_act_time":
            state = jnp.zeros_like(state)
        mix, state, taps = super()._decode_ssm(y, lp, state, window)
        if self.fault == "window_not_shifted_at_act_time":
            taps = jnp.concatenate([window[:, :1], window], axis=1)
        return mix, state, taps


def faulty(fault: str, base=SSMoELMAgent, **model_fields):
    """An agent class whose model carries `fault` (and `model_fields`
    replaced), whatever configuration it is built from."""

    class Faulty(base):
        def __init__(self, cfg):
            super().__init__(cfg)
            fields = {f.name: getattr(self.model, f.name)
                      for f in dataclasses.fields(self.model)}
            self.model = dataclasses.make_dataclass(
                "WrongModel", [], bases=(Wrong,), frozen=True,
                namespace={"fault": fault})(**{**fields, **model_fields})

    return Faulty


class HalfBatch(SSMoELMAgent):
    def _learn(self, state, batch):
        train, metrics = super()._learn(
            state, jax.tree.map(lambda x: x[:N // 2], batch))
        for key in ("routes", "route_scores"):  # logged as if for the whole batch
            metrics[key] = jnp.concatenate([metrics[key]] * 2, axis=1)
        return train, metrics


class BiasLeftToTheOptimizer(SSMoELMAgent):
    def _learn(self, state, batch):  # `LoopLMAgent._learn`: no `rebias` after it
        new, metrics = super(SSMoELMAgent.__mro__[1], self)._learn(state, batch)
        return new, {**metrics, "bias_abs_max": jnp.zeros(())}


def wrong_sign() -> SSMoELMAgent:
    agent = SSMoELMAgent(CFG)
    agent.tx = optax.chain(agent.tx, optax.scale(-1.0))  # p - u
    return agent


def cast(dtype):
    return lambda state: state.replace(params=jax.tree.map(
        lambda x: x.astype(dtype), state.params))


MODEL_FAULTS = ("norm_over_all_channels", "gate_after_the_norm",
                "head_reads_group_h_mod_g", "group_zero_for_every_head",
                "dt_clamped", "a_chunk_drops_its_past", "rotary_in_attention",
                "experts_without_their_residual", "relu_for_relu2",
                "a_gate_on_the_experts", "shared_expert_missing",
                "shared_expert_gated", "scale_missing", "weights_not_renormalised",
                "bias_inside_the_weights", "selected_by_the_unbiased_scores",
                "absent_expert_added", "pair_dropped")


# -- (a) the seeded batch ---------------------------------------------------------


def seeded(agent, state=lambda s: s) -> dict:
    good = SSMoELMAgent(agent.cfg if isinstance(agent.cfg, SSMoELMConfig) else CFG)
    train = state(good.init_state(jax.random.PRNGKey(3)))
    return family.reference_check(agent, train, SECTION, SEED,
                                  hp=family.hyper(good))


def over(dist: dict, limits: dict) -> set:
    return {k for k in limits if not dist[k] <= limits[k]}  # a NaN is over


def test_the_right_program_passes_the_seeded_batch():
    got = seeded(SSMoELMAgent(CFG))
    assert got["ok"], got
    for side in ("stated", "highest"):
        assert got["routing"][side]["flips_over_margin"] == 0
        assert family.routes_ok(got["routing"][side])
        assert got["distance"][side]["router_prob"] < 1e-4
        assert got["distance"][side]["load"] == 0
    assert "reference_check" in got["seconds"]


@pytest.mark.parametrize("fault", MODEL_FAULTS)
def test_seeded_batch_refuses_a_wrong_model(fault):
    got = seeded(faulty(fault)(CFG))
    assert got["ok"] is False, (fault, got["distance"])
    wrong = (over(got["distance"]["highest"], family.HIGHEST)
             or not family.routes_ok(got["routing"]["highest"]))
    assert wrong, (fault, got["distance"]["highest"], got["routing"])


@pytest.mark.parametrize("name, agent, state, refused_by", [
    # at the cell's learning rate, where a step is under bfloat16's last bit
    ("bfloat16_parameters", lambda: SSMoELMAgent(dataclasses.replace(
        CFG, start_learning_rate=1e-5)), cast(jnp.bfloat16), None),
    ("bfloat16_log_softmax", lambda: faulty("bfloat16_log_softmax")(CFG),
     lambda s: s, "head_logp"),
    ("bfloat16_state_across_chunks",
     lambda: faulty("", state_dtype=jnp.bfloat16)(CFG), lambda s: s, None),
])
def test_seeded_batch_refuses_a_lower_precision(name, agent, state, refused_by):
    got = seeded(agent(), state)
    assert got["ok"] is False, (name, got["distance"])
    refused = over(got["distance"]["stated"], family.STATED)
    if refused_by is None:
        # at this small float32 size the stated limits (set for bfloat16
        # operands at the cell's size) hide it; the `highest` side, whose
        # twin carries the fault, refuses it
        refused = over(got["distance"]["highest"], family.HIGHEST)
    assert refused and (refused_by is None or refused_by in refused), \
        (name, got["distance"])


# -- (b) the replay of a compiled chunk ---------------------------------------------


def replayed(agent) -> dict:
    """A chunk of two updates of `agent`'s fused loop, recorded as the
    mode records the first warm chunk, and replayed by the reference
    under the RIGHT configuration."""
    good = SSMoELMAgent(CFG)
    env = TokenRecall(vocab=V, episode_len=T, distance=8)
    anakin = AnakinTokens(agent, N, env)
    anakin.decode_spans = (12, 20, T)
    state = anakin.init(jax.random.PRNGKey(7))
    before = family.param_sample(state.train.params)
    state, metrics = anakin.train_chunk(state, 2)
    record = family.chunk_record(
        before, family.param_sample(state.train.params),
        jax.device_get(metrics))
    fresh = AnakinTokens(good, N, env).init(jax.random.PRNGKey(7)).train.params
    return family.chunk_check(good, fresh, record)


def test_the_right_program_passes_the_replay():
    got = replayed(SSMoELMAgent(CFG))
    assert got["ok"], got
    assert got["updates"] == 2 and got["steps"] == 2 * N * T
    assert got["reference_moved"] > 0
    assert got["routing"]["flips_over_margin"] == 0
    assert not {"pairs", "load", "bias"} & set(got["distance"])
    program, reference = got["counters_program_reference"]["held_pair_share"]
    assert abs(program - reference) < 1e-6 and 0.1 < program < 0.5
    program, reference = got["counters_program_reference"]["relu2_zero_share"]
    assert abs(program - reference) < 1e-6 and 0.3 < program < 0.7
    assert got["distance"]["state"] < 1e-4
    assert len(got["step_over_last_bit"]) == len(jax.tree.leaves(
        SSMoELMAgent(CFG).init_state(jax.random.PRNGKey(7)).params))


@pytest.mark.parametrize("name, agent, refused_by", [
    ("state_not_carried_at_act_time",
     lambda: faulty("state_not_carried_at_act_time")(CFG), "logp_max_abs"),
    ("window_not_shifted_at_act_time",
     lambda: faulty("window_not_shifted_at_act_time")(CFG), "logp_max_abs"),
    ("relu_for_relu2", lambda: faulty("relu_for_relu2")(CFG), "logp_max_abs"),
    ("dt_clamped", lambda: faulty("dt_clamped")(CFG), "dt_mean"),
    ("pair_dropped", lambda: faulty("pair_dropped")(CFG), "pairs"),
    ("absent_expert_added", lambda: faulty("absent_expert_added")(CFG), "pairs"),
    ("bias_left_to_the_optimizer", lambda: BiasLeftToTheOptimizer(CFG), "bias"),
    ("learns_half_the_batch", lambda: HalfBatch(CFG), "step"),
    ("p_minus_u", wrong_sign, "step"),
])
def test_replay_refuses(name, agent, refused_by):
    got = replayed(agent())
    assert got["ok"] is False, (name, got)
    refused = over(got["distance"], family.CHUNK) | (
        {"pairs", "load", "bias"} & set(got["distance"]))
    assert refused_by in refused, (name, got["distance"])


def test_replay_refuses_another_start():
    """Parameters that are not those the chunk started from: nothing is
    compared."""
    agent = SSMoELMAgent(CFG)
    env = TokenRecall(vocab=V, episode_len=T, distance=8)
    anakin = AnakinTokens(agent, N, env)
    state = anakin.init(jax.random.PRNGKey(7))
    before = family.param_sample(state.train.params)
    state, metrics = anakin.train_chunk(state, 1)
    record = family.chunk_record(before, before, jax.device_get(metrics))
    other = anakin.init(jax.random.PRNGKey(8)).train.params
    got = family.chunk_check(agent, other, record)
    assert got["ok"] is False and "made anew from the seed" in got["why"]

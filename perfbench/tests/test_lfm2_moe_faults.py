"""The comparisons that decide `correct` in
`lfm2_moe.anakin_tokens_conv_1k` (`families/convlm.py`) refuse what they
are there to refuse: each wrong program is PLANTED here, at a small size
on the CPU, run through `reference_check` (a) or recorded and replayed
through `chunk_check` (b) under the limits as committed, and `ok` has to
come out false: a fault in the convolution, in the window's order, in the
bias and in the held range among them. The right program passes both.

Sizes: hidden 32, the published order's first period behind one dense
layer (conv + dense 48 wide; attention of 4 query and 2 key/value heads
of 8 + experts; three conv + experts), a router 16 wide with 3 experts a
token of which experts 4..7 are held, V 96, T 32, N 4, float32;
`init_std` 0.3 so that the layers differ visibly, learning rate 1e-3 so
that a step is over float32's last bit. A fault lives in the AGENT's
class, so that the `highest` twin, built as `type(agent)(cfg)`, carries
it too.
"""

import dataclasses

import jax
import jax.numpy as jnp
import optax
import pytest

import discover
from conftest import BENCH_DIR
from distributed_reinforcement_learning_tpu.agents.convlm import (
    ConvLMAgent, ConvLMConfig)
from distributed_reinforcement_learning_tpu.envs.token_recall_jax import (
    TokenRecall)
from distributed_reinforcement_learning_tpu.models import conv_moe_lm
from distributed_reinforcement_learning_tpu.ops import expert_share
from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
    AnakinTokens)

V, T, N = 96, 32, 4
CFG = ConvLMConfig(
    vocab_size=V, hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    rope_theta=1e4, intermediate_size=48, num_experts=4, router_width=16,
    first_expert=4, num_experts_per_tok=3, moe_intermediate_size=16,
    trajectory=T, dtype=jnp.float32, attention_backend="reference", row_block=2,
    head_block=32, start_learning_rate=1e-3, init_std=0.3)
SECTION = {"trajectory": T, "vocab_size": V}
SEED = 3000000019

family = discover.module(BENCH_DIR, "families", "convlm")


# -- the wrong programs ---------------------------------------------------------


class Wrong(conv_moe_lm.ConvMoELM):
    """One wrong model a name: each overrides one small method, or swaps
    one function of `ops/` for the time the method is traced."""

    fault: str = ""

    def _gates(self, bcx):
        b, c, x = jnp.split(bcx, 3, axis=-1)
        if self.fault == "streams_in_another_order":
            b, c, x = c, b, x
        if self.fault == "output_gate_left_out":
            c = jnp.ones_like(c)
        if self.fault == "an_activation_after_the_taps":
            c = jax.nn.silu(c)
        return ((b * x).astype(self.dtype), c,
                jax.lax.stop_gradient(jnp.sum(jnp.abs(b)) + jnp.sum(jnp.abs(c))))

    def _conv(self, y, lp, pos):
        if self.fault == "taps_reversed":
            lp = {**lp, "conv_w": lp["conv_w"][:, ::-1]}
        if self.fault == "taps_cross_an_episodes_end":
            pos = jnp.broadcast_to(jnp.arange(pos.shape[1]), pos.shape)
        if self.fault == "a_window_of_four_taps":  # the other hybrids' width
            mix, stats = super()._conv(y, lp, pos)
            u = self._gates(self._mm(y, lp["in_proj"]))[0].astype(jnp.float32)
            older = jnp.where((pos >= 3)[..., None],
                              jnp.pad(u, ((0, 0), (3, 0), (0, 0)))[:, :u.shape[1]], 0)
            c = jnp.split(self._mm(y, lp["in_proj"]), 3, -1)[1]
            return mix + self._mm(c * lp["conv_w"][:, 0] * older, lp["out_proj"]), stats
        return super()._conv(y, lp, pos)

    def _qkv(self, y, lp, pos):
        if self.fault == "keys_not_normed":
            lp = {**lp, "k_norm": jnp.ones_like(lp["k_norm"])}
        if self.fault == "rotary_at_position_zero":
            pos = jnp.zeros_like(pos)
        return super()._qkv(y, lp, pos)

    def _attention(self, y, lp, seg, pos):
        if self.fault == "attends_across_an_episode_end":
            seg = jnp.zeros_like(seg)
        return super()._attention(y, lp, seg, pos)

    def _ffn(self, mlp, u, lp, scope):
        route, pairs = expert_share.route, expert_share.held_pairs
        held, first = self.experts_held, self.first_expert

        def by_unbiased_scores(x, w, k, scoring, bias, scale, eps):
            return route(x, w, k, scoring, 0 * bias, scale, eps)

        def weights_from_biased_scores(x, w, k, scoring, bias, scale, eps):
            scores, chosen, _, load = route(x, w, k, scoring, bias, scale, eps)
            top = jnp.take_along_axis(scores + bias, chosen, -1)
            return scores, chosen, scale * top / jnp.sum(top, -1, keepdims=True), load

        def not_renormalised(x, w, k, scoring, bias, scale, eps):
            scores, chosen, _, load = route(x, w, k, scoring, bias, scale, eps)
            return scores, chosen, scale * jnp.take_along_axis(scores, chosen, -1), load

        def softmax_scores(x, w, k, scoring, bias, scale, eps):
            probs, chosen, weight = route(x, w, k)
            load = jnp.sum(chosen[..., None] == jnp.arange(w.shape[-1]), (0, 1),
                           dtype=jnp.int32)
            return probs, chosen, scale * weight, load

        def absent_added(chosen, first_expert, n):  # every pair lands on a held expert
            return pairs(first + chosen % held, first_expert, n)

        def pair_dropped(chosen, first_expert, n):  # a token's last choice is lost
            return pairs(chosen.at[:, -1].set(-1), first_expert, n)

        def another_range(chosen, first_expert, n):  # experts 8..11's pairs, held as 4..7's
            return pairs(chosen, first_expert + held, n)

        swap = {"selected_by_unbiased_scores": ("route", by_unbiased_scores),
                "weights_from_biased_scores": ("route", weights_from_biased_scores),
                "weights_not_renormalised": ("route", not_renormalised),
                "softmax_scores": ("route", softmax_scores),
                "absent_expert_added": ("held_pairs", absent_added),
                "pair_dropped": ("held_pairs", pair_dropped),
                "another_range_held": ("held_pairs", another_range)}.get(self.fault)
        if mlp == "moe" and self.fault == "a_shared_expert_beside_them":
            out, chosen, stats = super()._ffn(mlp, u, lp, scope)
            x = self._norm(u, lp["norms"][1])  # expert 0 of the held, for every token
            gate, up = jnp.split(self._mm(x, lp["expert_wgu"][0]), 2, axis=-1)
            return (self._residual(out, self._mm(jax.nn.silu(gate) * up,
                                                 lp["expert_wd"][0])), chosen, stats)
        with pytest.MonkeyPatch.context() as m:
            if swap and mlp == "moe":
                m.setattr(expert_share, *swap)
            return super()._ffn(mlp, u, lp, scope)

    def token_stats(self, p, h, actions):
        if self.fault != "bfloat16_log_softmax":
            return super().token_stats(p, h, actions)
        logits, gate, value = self.logits(p, h)
        logp_all = jax.nn.log_softmax(logits.astype(jnp.bfloat16), axis=-1)
        taken = jnp.take_along_axis(logp_all, actions[..., None], axis=-1)[..., 0]
        entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
        return {"logp": taken.astype(jnp.float32),
                "entropy": entropy.astype(jnp.float32), "gate": gate, "value": value}

    def _decode_conv(self, y, lp, window):
        if self.fault == "window_in_another_order":  # the taps read u_{t-1}, u_{t-2}, u_t
            return (super()._decode_conv(y, lp, window[:, ::-1])[0],
                    super()._decode_conv(y, lp, window)[1])
        mix, new = super()._decode_conv(y, lp, window)
        if self.fault == "window_not_shifted":  # the oldest column stays
            new = jnp.concatenate([window[:, :1], new[:, 1:]], axis=1)
        if self.fault == "window_of_one_column":
            new = new.at[:, 0].set(0.0)
        return mix, new

    def _decode_attention(self, y, lp, keys, values, t, span):
        if self.fault != "decode_rotary_at_position_zero":
            return super()._decode_attention(y, lp, keys, values, t, span)
        # q and k both turned as at step 0: the score loses its position term
        mix, k2, v2 = super()._decode_attention(
            y, lp, jnp.roll(keys, -t, 1), jnp.roll(values, -t, 1), 0 * t, span)
        return mix, jnp.roll(k2, t, 1), jnp.roll(v2, t, 1)


def faulty(fault: str, base=ConvLMAgent, **model_fields):
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


class BiasTrainedByTheOptimizer(ConvLMAgent):
    """The bias treated as any parameter: a gradient (of a balance term
    on the scores it biases) and RMSProp's step, no rule of its own."""

    def _loss(self, params, batch):
        total, metrics = super()._loss(params, batch)
        aux = sum(jnp.sum(jnp.square(b + 1.0)) for b in self.router_biases(params))
        return total + aux, metrics

    def _learn(self, state, batch):
        new, metrics = super(ConvLMAgent, self)._learn(state, batch)
        return new, {**metrics, "bias_abs_max": jnp.zeros(())}


class BiasNeverMoves(ConvLMAgent):
    def _learn(self, state, batch):
        new, metrics = super()._learn(state, batch)
        return new.replace(params=self.model.rebias(
            state.params, new.params, metrics["router_load"], 0.0)), metrics


class BiasMovesTheWrongWay(ConvLMAgent):
    def _learn(self, state, batch):
        new, metrics = super()._learn(state, batch)
        return new.replace(params=self.model.rebias(
            state.params, new.params, metrics["router_load"],
            -self.cfg.bias_update_speed)), metrics


class BiasFromTheHeldExpertsCounts(ConvLMAgent):
    """The counts of the experts held here alone: the absent experts'
    biases never move."""

    def _learn(self, state, batch):
        new, metrics = super()._learn(state, batch)
        lo, hi = self.cfg.first_expert, self.cfg.first_expert + self.cfg.num_experts
        load = metrics["router_load"]
        held = jnp.where((jnp.arange(load.shape[-1]) >= lo)
                         & (jnp.arange(load.shape[-1]) < hi), load,
                         jnp.mean(load.astype(jnp.float32), -1, keepdims=True))
        return new.replace(params=self.model.rebias(
            state.params, new.params, held, self.cfg.bias_update_speed)), metrics


class StateNotReset(ConvLMAgent):
    """An update that starts from windows and a cache that are not zero."""

    def init_cache(self, num_rows):
        state = super().init_cache(num_rows)
        plus = lambda xs: tuple(None if x is None else x + 0.5 for x in xs)
        return state._replace(window=plus(state.window), k=plus(state.k),
                              v=plus(state.v))

    def _act(self, act_params, tokens, t, cache, rng, span=None):
        return super()._act(act_params, tokens, jnp.int32(T - 1) + 0 * t, cache,
                            rng, span)


class HalfBatch(ConvLMAgent):
    def _learn(self, state, batch):
        train, metrics = super()._learn(
            state, jax.tree.map(lambda x: x[:N // 2], batch))
        for key in ("routes", "route_scores"):  # logged as if for the whole batch
            metrics[key] = jnp.concatenate([metrics[key]] * 2, axis=1)
        return train, metrics


def wrong_sign() -> ConvLMAgent:
    agent = ConvLMAgent(CFG)
    agent.tx = optax.chain(agent.tx, optax.scale(-1.0))  # p - u
    return agent


def cast(dtype):
    return lambda state: state.replace(params=jax.tree.map(
        lambda x: x.astype(dtype), state.params))


MODEL_FAULTS = ("streams_in_another_order", "output_gate_left_out",
                "an_activation_after_the_taps", "taps_reversed",
                "taps_cross_an_episodes_end", "a_window_of_four_taps",
                "keys_not_normed", "rotary_at_position_zero",
                "attends_across_an_episode_end", "selected_by_unbiased_scores",
                "weights_from_biased_scores", "weights_not_renormalised",
                "softmax_scores", "a_shared_expert_beside_them",
                "absent_expert_added", "pair_dropped", "another_range_held")


# -- (a) the seeded batch ---------------------------------------------------------


def seeded(agent, state=lambda s: s) -> dict:
    good = ConvLMAgent(agent.cfg if isinstance(agent.cfg, ConvLMConfig) else CFG)
    train = state(good.init_state(jax.random.PRNGKey(3)))
    return family.reference_check(agent, train, SECTION, SEED,
                                  hp=family.hyper(good))


def over(dist: dict, limits: dict) -> set:
    return {k for k in limits if not dist[k] <= limits[k]}  # a NaN is over


def test_the_right_program_passes_the_seeded_batch():
    got = seeded(ConvLMAgent(CFG))
    assert got["ok"], got
    for side in ("stated", "highest"):
        assert got["routing"][side]["flips_over_margin"] == 0
        assert family.routes_ok(got["routing"][side])
        assert got["distance"][side]["router_prob"] < 1e-4
        assert got["distance"][side]["load"] == 0


@pytest.mark.parametrize("fault", MODEL_FAULTS)
def test_seeded_batch_refuses_a_wrong_model(fault):
    got = seeded(faulty(fault)(CFG))
    assert got["ok"] is False, (fault, got["distance"])
    wrong = (over(got["distance"]["highest"], family.HIGHEST)
             or not family.routes_ok(got["routing"]["highest"]))
    assert wrong, (fault, got["distance"]["highest"], got["routing"])


@pytest.mark.parametrize("name, agent, state, refused_by", [
    # at the cell's learning rate, where a step is under bfloat16's last bit
    ("bfloat16_parameters", lambda: ConvLMAgent(dataclasses.replace(
        CFG, start_learning_rate=1e-5)), cast(jnp.bfloat16), "update_norm"),
    ("bfloat16_log_softmax", lambda: faulty("bfloat16_log_softmax")(CFG),
     lambda s: s, "head_logp"),
])
def test_seeded_batch_refuses_a_lower_precision(name, agent, state, refused_by):
    got = seeded(agent(), state)
    assert got["ok"] is False, (name, got["distance"])
    assert refused_by in over(got["distance"]["stated"], family.STATED), \
        (name, got["distance"]["stated"])


def test_the_margin_is_taken_on_the_biased_scores():
    """A set that differs where s alone is a near tie but s + b is not
    is a fault: the margin `routing_facts` reads is the reference's own,
    of s + b, relative to the last chosen."""
    import numpy as np

    routing = {"probs": np.array([[[[0.5, 0.49, 0.1]]]]),
               "same_set": np.array([[[False]]]),
               "margin": np.array([[[0.21]]]), "edge": np.array([[[0.7]]])}
    facts = family.routing_facts(routing, np.array([[[[0, 2]]]]))
    assert abs(float(facts["margin"][0, 0, 0]) - 0.3) < 1e-9
    assert family.route_distances([facts])["flips_over_margin"] == 1


# -- (b) the replay of a compiled chunk ---------------------------------------------


def replayed(agent) -> dict:
    """A chunk of two updates of `agent`'s fused loop, recorded as the
    mode records the first warm chunk, and replayed by the reference
    under the RIGHT configuration."""
    good = ConvLMAgent(CFG)
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
    got = replayed(ConvLMAgent(CFG))
    assert got["ok"], got
    assert got["updates"] == 2 and got["steps"] == 2 * N * T
    assert got["reference_moved"] > 0
    assert got["routing"]["flips_over_margin"] == 0
    assert not {"pairs", "load", "bias"} & set(got["distance"])
    program, reference = got["counters_program_reference"]["held_pair_share"]
    assert abs(program - reference) < 1e-6 and 0.1 < program < 0.5
    bits = got["step_over_last_bit"]  # the two bias leaves are not the optimizer's
    fresh = ConvLMAgent(CFG).init_state(jax.random.PRNGKey(7)).params
    assert [bits[i] for i in family.bias_leaves(fresh)] == [float("inf")] * 2


@pytest.mark.parametrize("name, agent, refused_by", [
    ("window_in_another_order", lambda: faulty("window_in_another_order")(CFG),
     "logp_max_abs"),
    ("window_not_shifted", lambda: faulty("window_not_shifted")(CFG), "logp_max_abs"),
    ("window_of_one_column", lambda: faulty("window_of_one_column")(CFG),
     "logp_max_abs"),
    ("state_not_reset", lambda: StateNotReset(CFG), "logp_max_abs"),
    ("decode_rotary_at_position_zero",
     lambda: faulty("decode_rotary_at_position_zero")(CFG), "logp_max_abs"),
    ("output_gate_left_out", lambda: faulty("output_gate_left_out")(CFG), "conv_gate"),
    ("pair_dropped", lambda: faulty("pair_dropped")(CFG), "pairs"),
    ("absent_expert_added", lambda: faulty("absent_expert_added")(CFG), "pairs"),
    ("another_range_held", lambda: faulty("another_range_held")(CFG), "pairs"),
    ("bias_trained_by_the_optimizer", lambda: BiasTrainedByTheOptimizer(CFG), "bias"),
    ("bias_never_moves", lambda: BiasNeverMoves(CFG), "bias"),
    ("bias_moves_the_wrong_way", lambda: BiasMovesTheWrongWay(CFG), "bias"),
    ("bias_from_the_held_experts_counts", lambda: BiasFromTheHeldExpertsCounts(CFG),
     "bias"),
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
    agent = ConvLMAgent(CFG)
    env = TokenRecall(vocab=V, episode_len=T, distance=8)
    anakin = AnakinTokens(agent, N, env)
    state = anakin.init(jax.random.PRNGKey(7))
    before = family.param_sample(state.train.params)
    state, metrics = anakin.train_chunk(state, 1)
    record = family.chunk_record(before, before, jax.device_get(metrics))
    other = anakin.init(jax.random.PRNGKey(8)).train.params
    got = family.chunk_check(agent, other, record)
    assert got["ok"] is False and "made anew from the seed" in got["why"]

"""The comparisons that decide `correct` in
`joyai_flash.anakin_tokens_mla_2k` (`families/mlalm.py`) refuse what they
are there to refuse: each wrong program is PLANTED here, at a small size
on the CPU, run through `reference_check` (a) or recorded and replayed
through `chunk_check` (b) under the limits as committed, and `ok` has to
come out false. The right program passes both. (The faults that a
precision hides at this size, at the published widths on the chip:
PERF.md section 6.)

Sizes: hidden 32, 4 heads of 8 + 4 rotary with values of 8, a query
latent of 24 and a key/value latent of 16, a dense layer 48 wide and two
expert layers (a router 16 wide with 3 experts a token of which experts
4..7 are held), V 96, T 32, N 4, float32; `init_std` 0.3 so that the
layers differ visibly, learning rate 1e-3 so that a step is over
float32's last bit. A fault lives in the AGENT's class, so that the
`highest` twin, built as `type(agent)(cfg)`, carries it too.
"""

import dataclasses

import jax
import jax.numpy as jnp
import optax
import pytest

import discover
from conftest import BENCH_DIR
from distributed_reinforcement_learning_tpu.agents.mlalm import (
    MLALMAgent, MLALMConfig)
from distributed_reinforcement_learning_tpu.envs.token_recall_jax import (
    TokenRecall)
from distributed_reinforcement_learning_tpu.models import latent_moe_lm
from distributed_reinforcement_learning_tpu.ops import (
    expert_share, latent_attention)
from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
    AnakinTokens)

V, T, N = 96, 32, 4
CFG = MLALMConfig(
    vocab_size=V, hidden_size=32, num_hidden_layers=3, num_attention_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, rope_theta=1e4, intermediate_size=48, n_routed_experts=4,
    router_width=16, first_expert=4, num_experts_per_tok=3,
    moe_intermediate_size=16, trajectory=T, dtype=jnp.float32,
    attention_backend="reference", row_block=2, head_block=32,
    start_learning_rate=1e-3, init_std=0.3)
SECTION = {"trajectory": T, "vocab_size": V}
SEED = 3000000019

family = discover.module(BENCH_DIR, "families", "mlalm")


# -- the wrong programs ---------------------------------------------------------


class Wrong(latent_moe_lm.LatentMoELM):
    """One wrong model a name: each overrides one small method, or swaps
    one function of `ops/` for the time the method is traced."""

    fault: str = ""

    def _latents(self, y, lp):
        q_n, q_r, c, k_r = super()._latents(y, lp)
        if self.fault == "latent_not_normed":
            c = jnp.split(self._mm(y, lp["wkva"]), [self.kv_rank], -1)[0]
        return q_n, q_r, c, k_r

    def _layer(self, kind, scope, h, seg, pos, lp):
        swap = None
        expanded, rotary = latent_attention.expanded, latent_attention.rotary_interleaved
        if self.fault == "scale_of_the_nope_width":
            def swap(q_n, q_r, *rest):  # 1/sqrt(n) where 1/sqrt(n + r) is published
                n, r = q_n.shape[-1], q_r.shape[-1]
                scale = ((n + r) / n) ** 0.5
                return expanded(q_n * scale, q_r * scale, *rest)
        if self.fault == "rotary_on_the_key_only":
            def swap(q_n, q_r, c, k_r, w, seg_, pos_, theta, *rest):
                return expanded(q_n, rotary(q_r, -pos_[..., None], theta), c, k_r,
                                w, seg_, pos_, theta, *rest)
        if self.fault == "attends_across_an_episode_end":
            seg = jnp.zeros_like(seg)
        if self.fault == "rotary_at_position_zero":  # no position term at all
            pos = jnp.zeros_like(pos)
        with pytest.MonkeyPatch.context() as m:
            if swap:
                m.setattr(latent_attention, "expanded", swap)
            return super()._layer(kind, scope, h, seg, pos, lp)

    def _ffn(self, kind, u, lp, scope):
        route, pairs = expert_share.route, expert_share.held_pairs
        held, first = self.experts_held, self.first_expert

        def by_unbiased_scores(x, w, k, scoring, bias, scale):
            return route(x, w, k, scoring, 0 * bias, scale)

        def weights_from_biased_scores(x, w, k, scoring, bias, scale):
            scores, chosen, _, load = route(x, w, k, scoring, bias, scale)
            top = jnp.take_along_axis(scores + bias, chosen, -1)
            return scores, chosen, scale * top / jnp.sum(top, -1, keepdims=True), load

        def scale_missing(x, w, k, scoring, bias, scale):
            return route(x, w, k, scoring, bias, 1.0)

        def not_renormalised(x, w, k, scoring, bias, scale):
            scores, chosen, _, load = route(x, w, k, scoring, bias, scale)
            return scores, chosen, scale * jnp.take_along_axis(scores, chosen, -1), load

        def softmax_scores(x, w, k, scoring, bias, scale):
            probs, chosen, weight = route(x, w, k)
            load = jnp.sum(chosen[..., None] == jnp.arange(w.shape[-1]), (0, 1),
                           dtype=jnp.int32)
            return probs, chosen, scale * weight, load

        def absent_added(chosen, first_expert, n):  # every pair lands on a held expert
            return pairs(first + chosen % held, first_expert, n)

        def pair_dropped(chosen, first_expert, n):  # a token's last choice is lost
            return pairs(chosen.at[:, -1].set(-1), first_expert, n)

        swap = {"selected_by_unbiased_scores": ("route", by_unbiased_scores),
                "weights_from_biased_scores": ("route", weights_from_biased_scores),
                "scale_missing": ("route", scale_missing),
                "weights_not_renormalised": ("route", not_renormalised),
                "softmax_scores": ("route", softmax_scores),
                "absent_expert_added": ("held_pairs", absent_added),
                "pair_dropped": ("held_pairs", pair_dropped)}.get(self.fault)
        if kind == "moe" and self.fault == "shared_expert_gated":
            x = self._norm(u, lp["norms"][1])  # sigmoid(w . x) as Qwen3-Next's
            gate = jax.nn.sigmoid(x @ lp["router"][:, 0])
            out, chosen, stats = super()._ffn(kind, u, lp, scope)
            shared = self._swiglu(x, lp["shared_wgu"], lp["shared_wd"])
            return (self._residual(out, (gate[:, None] - 1.0) * shared), chosen, stats)
        with pytest.MonkeyPatch.context() as m:
            if swap and kind == "moe":
                m.setattr(expert_share, *swap)
            return super()._ffn(kind, u, lp, scope)

    def mtp(self, p, h, tokens, done):
        if self.fault == "module_fed_the_token_on_show":  # x_t for x_{t+1}
            tokens = jnp.roll(tokens, 1, axis=1)
        if self.fault == "module_reads_the_normed_state":
            h = self._norm(h, p["final_norm"])
        return super().mtp(p, h, tokens, done)

    def mtp_stats(self, p, h2, targets):
        if self.fault == "module_without_its_last_norm":
            p = {**p, "mtp": {**p["mtp"], "norm_out": jnp.ones_like(p["mtp"]["norm_out"])}}
        return super().mtp_stats(p, h2, targets)

    def token_stats(self, p, h, actions):
        if self.fault != "bfloat16_log_softmax":
            return super().token_stats(p, h, actions)
        logits, gate, value = self.logits(p, h)
        logp_all = jax.nn.log_softmax(logits.astype(jnp.bfloat16), axis=-1)
        taken = jnp.take_along_axis(logp_all, actions[..., None], axis=-1)[..., 0]
        entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
        return {"logp": taken.astype(jnp.float32),
                "entropy": entropy.astype(jnp.float32), "gate": gate,
                "value": value, "greedy": jnp.argmax(logits, -1).astype(jnp.int32)}

    def _decode_mla(self, y, lp, cache, t, span):
        rotary = latent_attention.rotary_interleaved
        if self.fault not in ("decode_rotary_at_position_zero",
                              "decode_caches_the_latent_before_its_norm"):
            return super()._decode_mla(y, lp, cache, t, span)
        q_n, q_r, c, k_r = self._latents(y, lp)
        t_write = t
        if self.fault == "decode_rotary_at_position_zero":
            t_write, q_r = 0 * t, rotary(q_r, -t, self.rope_theta)  # turned back
        else:  # W_kva y as it comes, not N(.; g_kv)
            c = jnp.split(self._mm(y, lp["wkva"]), [self.kv_rank], -1)[0]
        cache = jax.lax.dynamic_update_slice(
            cache, latent_attention.cache_entry(c, k_r, t_write, self.rope_theta,
                                                self.dtype), (0, t, 0))
        att = latent_attention.absorbed_step(q_n, q_r, cache, lp["wkvb"], t, span,
                                             self.rope_theta, self.dtype)
        return self._mm(att.reshape(y.shape[0], -1), lp["wo"]), cache


def faulty(fault: str, base=MLALMAgent, **model_fields):
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


class TargetsNotShifted(MLALMAgent):
    """The module predicts a_t, what the main head predicts, not a_{t+1}."""

    def _stats(self, params, batch):
        shown = batch._replace(action=jnp.roll(batch.action, 1, axis=1))
        out = super()._stats(params, shown)
        right = super()._stats(params, batch)
        return {**right, "counters": {**right["counters"],
                                      "mtp_loss": out["counters"]["mtp_loss"]}}


class TargetsAcrossAnEpisodeEnd(MLALMAgent):
    """L_mtp over every position, the episode's last ones too."""

    def _stats(self, params, batch):
        out = super()._stats(params, batch._replace(done=jnp.zeros_like(batch.done)))
        right = super()._stats(params, batch)
        return {**right, "counters": {**right["counters"],
                                      "mtp_loss": out["counters"]["mtp_loss"]}}


class MtpLossLeftOut(MLALMAgent):
    def _loss(self, params, batch):
        total, metrics = super()._loss(params, batch)
        total = total - (self.cfg.mtp_loss_coef * metrics["mtp_positions"]
                         * metrics["mtp_loss"])
        return total, {**metrics, "total_loss": total}


class BiasTrainedByTheOptimizer(MLALMAgent):
    """The bias treated as any parameter: a gradient (of a balance term
    on the scores it biases) and RMSProp's step, no rule of its own."""

    def _loss(self, params, batch):
        total, metrics = super()._loss(params, batch)
        aux = sum(jnp.sum(jnp.square(b + 1.0)) for b in self.router_biases(params))
        return total + aux, metrics

    def _learn(self, state, batch):
        new, metrics = super(MLALMAgent, self)._learn(state, batch)
        return new, {**metrics, "bias_abs_max": jnp.zeros(())}


class BiasNeverMoves(MLALMAgent):
    def _learn(self, state, batch):
        new, metrics = super()._learn(state, batch)
        return new.replace(params=self.model.rebias(
            state.params, new.params, metrics["router_load"], 0.0)), metrics


class BiasMovesTheWrongWay(MLALMAgent):
    def _learn(self, state, batch):
        new, metrics = super()._learn(state, batch)
        return new.replace(params=self.model.rebias(
            state.params, new.params, metrics["router_load"],
            -self.cfg.bias_update_speed)), metrics


class CacheNotReset(MLALMAgent):
    """An update that starts from a cache that is not zero AND a decode
    step that reads all of it, as a mask dropped would."""

    def init_cache(self, num_rows):
        cache = super().init_cache(num_rows)
        return cache._replace(cache=jax.tree.map(lambda x: x + 0.5, cache.cache))

    def _act(self, act_params, tokens, t, cache, rng, span=None):
        return super()._act(act_params, tokens, jnp.int32(T - 1) + 0 * t, cache,
                            rng, span)


class HalfBatch(MLALMAgent):
    def _learn(self, state, batch):
        train, metrics = super()._learn(
            state, jax.tree.map(lambda x: x[:N // 2], batch))
        for key in ("routes", "route_scores"):  # logged as if for the whole batch
            metrics[key] = jnp.concatenate([metrics[key]] * 2, axis=1)
        return train, metrics


def wrong_sign() -> MLALMAgent:
    agent = MLALMAgent(CFG)
    agent.tx = optax.chain(agent.tx, optax.scale(-1.0))  # p - u
    return agent


def cast(dtype):
    return lambda state: state.replace(params=jax.tree.map(
        lambda x: x.astype(dtype), state.params))


MODEL_FAULTS = ("scale_of_the_nope_width", "latent_not_normed",
                "rotary_on_the_key_only", "attends_across_an_episode_end",
                "rotary_at_position_zero", "selected_by_unbiased_scores",
                "weights_from_biased_scores", "scale_missing",
                "weights_not_renormalised", "softmax_scores", "shared_expert_gated",
                "absent_expert_added", "pair_dropped",
                "module_fed_the_token_on_show", "module_reads_the_normed_state",
                "module_without_its_last_norm")
AGENT_FAULTS = {"targets_not_shifted": TargetsNotShifted,
                "targets_across_an_episode_end": TargetsAcrossAnEpisodeEnd,
                "mtp_loss_left_out": MtpLossLeftOut}


# -- (a) the seeded batch ---------------------------------------------------------


def seeded(agent, state=lambda s: s) -> dict:
    good = MLALMAgent(agent.cfg if isinstance(agent.cfg, MLALMConfig) else CFG)
    train = state(good.init_state(jax.random.PRNGKey(3)))
    return family.reference_check(agent, train, SECTION, SEED,
                                  hp=family.hyper(good))


def over(dist: dict, limits: dict) -> set:
    return {k for k in limits if not dist[k] <= limits[k]}  # a NaN is over


def test_the_right_program_passes_the_seeded_batch():
    got = seeded(MLALMAgent(CFG))
    assert got["ok"], got
    for side in ("stated", "highest"):
        assert got["routing"][side]["flips_over_margin"] == 0
        assert family.routes_ok(got["routing"][side])
        assert got["distance"][side]["router_prob"] < 1e-4
        assert got["distance"][side]["mtp_loss"] < 1e-4


@pytest.mark.parametrize("fault", MODEL_FAULTS)
def test_seeded_batch_refuses_a_wrong_model(fault):
    got = seeded(faulty(fault)(CFG))
    assert got["ok"] is False, (fault, got["distance"])
    wrong = (over(got["distance"]["highest"], family.HIGHEST)
             or not family.routes_ok(got["routing"]["highest"]))
    assert wrong, (fault, got["distance"]["highest"], got["routing"])


@pytest.mark.parametrize("fault", sorted(AGENT_FAULTS))
def test_seeded_batch_refuses_a_wrong_prediction_loss(fault):
    got = seeded(AGENT_FAULTS[fault](CFG))
    assert got["ok"] is False, (fault, got["distance"])
    refused = over(got["distance"]["highest"], family.HIGHEST)
    assert refused & {"mtp_loss", "loss", "grad_norm"}, (fault, got["distance"])


@pytest.mark.parametrize("name, agent, state, refused_by", [
    # at the cell's learning rate, where a step is under bfloat16's last bit
    ("bfloat16_parameters", lambda: MLALMAgent(dataclasses.replace(
        CFG, start_learning_rate=1e-5)), cast(jnp.bfloat16), "update_norm"),
    ("bfloat16_log_softmax", lambda: faulty("bfloat16_log_softmax")(CFG),
     lambda s: s, "head_logp"),
])
def test_seeded_batch_refuses_a_lower_precision(name, agent, state, refused_by):
    got = seeded(agent(), state)
    assert got["ok"] is False, (name, got["distance"])
    assert refused_by in over(got["distance"]["stated"], family.STATED), \
        (name, got["distance"]["stated"])


def test_a_flip_past_the_margin_is_a_fault_and_a_near_tie_is_not():
    import numpy as np

    flip = np.array([[[True, False, True]]])
    near = {"flip": flip, "margin": np.array([[[0.01, 0.9, 0.02]]])}
    far = {"flip": flip, "margin": np.array([[[0.01, 0.9, 0.2]]])}
    assert family.route_distances([near])["flips_over_margin"] == 0
    assert family.route_distances([near], "highest")["flips_over_margin"] == 2
    assert family.route_distances([far])["flips_over_margin"] == 1
    assert not family.routes_ok(family.route_distances([far]))
    assert not family.routes_ok(family.route_distances([near]))  # 2 of 3 differ
    quiet = {"flip": np.zeros((1, 1, 64), bool), "margin": np.ones((1, 1, 64))}
    quiet["flip"][0, 0, 0], quiet["margin"][0, 0, 0] = True, 0.001
    assert family.routes_ok(family.route_distances([quiet]))


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
    good = MLALMAgent(CFG)
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
    got = replayed(MLALMAgent(CFG))
    assert got["ok"], got
    assert got["updates"] == 2 and got["steps"] == 2 * N * T
    assert got["reference_moved"] > 0
    assert got["routing"]["flips_over_margin"] == 0
    assert not {"pairs", "load", "bias"} & set(got["distance"])
    program, reference = got["counters_program_reference"]["held_pair_share"]
    assert abs(program - reference) < 1e-6 and 0.1 < program < 0.5
    bits = got["step_over_last_bit"]  # the two bias leaves are not the optimizer's
    fresh = MLALMAgent(CFG).init_state(jax.random.PRNGKey(7)).params
    assert [bits[i] for i in family.bias_leaves(fresh)] == [float("inf")] * 2


@pytest.mark.parametrize("name, agent, refused_by", [
    ("cache_not_reset_and_read_whole", lambda: CacheNotReset(CFG), "logp_max_abs"),
    ("decode_rotary_at_position_zero",
     lambda: faulty("decode_rotary_at_position_zero")(CFG), "logp_max_abs"),
    ("decode_caches_the_latent_before_its_norm",
     lambda: faulty("decode_caches_the_latent_before_its_norm")(CFG), "logp_max_abs"),
    ("pair_dropped", lambda: faulty("pair_dropped")(CFG), "pairs"),
    ("absent_expert_added", lambda: faulty("absent_expert_added")(CFG), "pairs"),
    ("bias_trained_by_the_optimizer", lambda: BiasTrainedByTheOptimizer(CFG), "bias"),
    ("bias_never_moves", lambda: BiasNeverMoves(CFG), "bias"),
    ("bias_moves_the_wrong_way", lambda: BiasMovesTheWrongWay(CFG), "bias"),
    ("targets_not_shifted", lambda: TargetsNotShifted(CFG), "mtp_loss"),
    ("module_fed_the_token_on_show",
     lambda: faulty("module_fed_the_token_on_show")(CFG), "mtp_loss"),
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
    agent = MLALMAgent(CFG)
    env = TokenRecall(vocab=V, episode_len=T, distance=8)
    anakin = AnakinTokens(agent, N, env)
    state = anakin.init(jax.random.PRNGKey(7))
    before = family.param_sample(state.train.params)
    state, metrics = anakin.train_chunk(state, 1)
    record = family.chunk_record(before, before, jax.device_get(metrics))
    other = anakin.init(jax.random.PRNGKey(8)).train.params
    got = family.chunk_check(agent, other, record)
    assert got["ok"] is False and "made anew from the seed" in got["why"]

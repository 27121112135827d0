"""The comparisons that decide `correct` in
`qwen3_next.anakin_tokens_moe_1k` (`families/moelm.py`) refuse what they
are there to refuse: each wrong program is PLANTED here, at a small size
on the CPU, run through `reference_check` (a) or recorded and replayed
through `chunk_check` (b) under the limits as committed, and `ok` has to
come out false. The right program passes both. (The faults that a
precision hides at this size, at the published widths on the chip:
PERF.md section 6.)

Sizes: hidden 32, 4 query / 2 key-value heads of 16 (rotary on 4), 2 key
/ 4 value heads of 8 for the delta rule, chunks of 8, a router 16 wide
with 3 experts a token of which experts 4..7 are held, V 96, the
published order, T 32, N 4, float32; `init_std` 0.3 so that the layers
differ visibly, learning rate 1e-3 so that a step is over float32's last
bit. A fault lives in the AGENT's class, so that the `highest` twin,
built as `type(agent)(cfg)`, carries it too.
"""

import dataclasses

import jax
import jax.numpy as jnp
import optax
import pytest

import discover
from conftest import BENCH_DIR
from distributed_reinforcement_learning_tpu.agents.moelm import (
    MoELMAgent, MoELMConfig)
from distributed_reinforcement_learning_tpu.envs.token_recall_jax import (
    TokenRecall)
from distributed_reinforcement_learning_tpu.models import moe_lm
from distributed_reinforcement_learning_tpu.ops import expert_share, gated_delta
from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
    AnakinTokens)

V, T, N = 96, 32, 4
CFG = MoELMConfig(
    vocab_size=V, hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=8, num_experts=4,
    router_width=16, first_expert=4, num_experts_per_tok=3,
    moe_intermediate_size=16, shared_expert_intermediate_size=16, trajectory=T,
    gdn_chunk=8, dtype=jnp.float32, attention_backend="reference", row_block=2,
    head_block=32, start_learning_rate=1e-3, init_std=0.3)
SECTION = {"trajectory": T, "vocab_size": V}
SEED = 3000000019

family = discover.module(BENCH_DIR, "families", "moelm")


# -- the wrong programs ---------------------------------------------------------


class Wrong(moe_lm.MoELM):
    """One wrong model a name: each overrides one small method, or swaps
    one function of `ops/` for the time the method is traced."""

    fault: str = ""

    def _rotary(self, x, pos):
        if self.fault == "rotary_over_the_whole_head":
            return moe_lm.rope(x, pos, self.rope_theta)
        return super()._rotary(x, pos)

    def _norm(self, x, scale):
        if self.fault == "g_for_1_plus_g":
            return moe_lm.zero_centred_norm(x, scale - 1.0, self.rms_eps)
        return super()._norm(x, scale)

    def _split_conv(self, qkv):
        if self.fault != "qk_not_l2_normalised":
            return super()._split_conv(qkv)
        with pytest.MonkeyPatch.context() as m:
            m.setattr(gated_delta, "l2_normalize", lambda x: x.astype(jnp.float32))
            return super()._split_conv(qkv)

    def _gates(self, ba, lp):
        g, beta = super()._gates(ba, lp)
        return ((jnp.zeros_like(g) if self.fault == "decay_missing" else g),
                (jnp.ones_like(beta) if self.fault == "beta_missing" else beta))

    def _delta_rule(self, y, lp, seg, pos):
        if self.fault == "chunk_boundary_drops_s0":
            # every chunk an episode of its own: S_0 reaches nothing
            chunk = jnp.arange(seg.shape[1]) // self.gdn_chunk
            seg = seg * (seg.shape[1] // self.gdn_chunk + 1) + chunk
        if self.fault != "kk_correction_missing":
            return super()._delta_rule(y, lp, seg, pos)
        dv = self.gdn_value_dim

        def no_solve(matrix, rhs, **_):  # u = beta v: what the state holds is not read
            return jnp.concatenate([rhs[..., :dv], 0 * rhs[..., dv:]], axis=-1)

        with pytest.MonkeyPatch.context() as m:
            m.setattr(jax.scipy.linalg, "solve_triangular", no_solve)
            return super()._delta_rule(y, lp, seg, pos)

    def _decode_delta_rule(self, y, lp, state, window):
        mix, state, new = super()._decode_delta_rule(y, lp, state, window)
        if self.fault == "window_shifted_by_one":
            new = jnp.roll(new, 1, axis=1)
        return mix, state, new

    def _attention(self, y, lp, seg, pos):
        if self.fault != "output_gate_missing":
            return super()._attention(y, lp, seg, pos)
        wq = lp["wq"].reshape(lp["wq"].shape[0], self.num_heads, 2, self.head_dim)
        ungated = wq.at[:, :, 1].set(0.0).reshape(lp["wq"].shape)
        return 2.0 * super()._attention(y, {**lp, "wq": ungated}, seg, pos)

    def _moe(self, u, lp, scope):
        route, pairs = expert_share.route, expert_share.held_pairs
        held, first = self.experts_held, self.first_expert

        def not_renormalised(x, w, k):
            probs, chosen, _ = route(x, w, k)
            return probs, chosen, jnp.take_along_axis(probs, chosen, -1)

        def absent_added(chosen, first_expert, n):  # every pair lands on a held expert
            return pairs(first + chosen % held, first_expert, n)

        def pair_dropped(chosen, first_expert, n):  # a token's last choice is lost
            return pairs(chosen.at[:, -1].set(-1), first_expert, n)

        swap = {"weights_not_renormalised": ("route", not_renormalised),
                "absent_expert_added": ("held_pairs", absent_added),
                "pair_dropped": ("held_pairs", pair_dropped)}.get(self.fault)
        if self.fault == "shared_gate_missing":  # the same 1/2 for every token
            lp = {**lp, "shared_gate": jnp.zeros_like(lp["shared_gate"])}
        with pytest.MonkeyPatch.context() as m:
            if swap:
                m.setattr(expert_share, *swap)
            return super()._moe(u, lp, scope)

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

    class Faulty(MoELMAgent):
        def __init__(self, cfg):
            super().__init__(cfg)
            fields = {f.name: getattr(self.model, f.name)
                      for f in dataclasses.fields(self.model)}
            self.model = dataclasses.make_dataclass(
                "WrongModel", [], bases=(Wrong,), frozen=True,
                namespace={"fault": fault})(**{**fields, **model_fields})

    return Faulty


class StateNotReset(MoELMAgent):
    """An update that starts from a state that is not zero, as one carried
    over from the update before would be."""

    def init_cache(self, num_rows):
        cache = super().init_cache(num_rows)
        return cache._replace(gdn=jax.tree.map(lambda x: x + 1.0, cache.gdn))


class HalfBatch(MoELMAgent):
    def _learn(self, state, batch):
        train, metrics = super()._learn(
            state, jax.tree.map(lambda x: x[:N // 2], batch))
        for key in ("routes", "route_probs"):  # logged as if for the whole batch
            metrics[key] = jnp.concatenate([metrics[key]] * 2, axis=1)
        return train, metrics


def wrong_sign() -> MoELMAgent:
    agent = MoELMAgent(CFG)
    agent.tx = optax.chain(agent.tx, optax.scale(-1.0))  # p - u
    return agent


def cast(dtype):
    return lambda state: state.replace(params=jax.tree.map(
        lambda x: x.astype(dtype), state.params))


MODEL_FAULTS = ("weights_not_renormalised", "shared_gate_missing",
                "absent_expert_added", "pair_dropped", "beta_missing",
                "decay_missing", "kk_correction_missing", "qk_not_l2_normalised",
                "rotary_over_the_whole_head", "output_gate_missing",
                "g_for_1_plus_g", "chunk_boundary_drops_s0")


# -- (a) the seeded batch ---------------------------------------------------------


def seeded(agent, state=lambda s: s) -> dict:
    good = MoELMAgent(CFG)
    train = state(good.init_state(jax.random.PRNGKey(3)))
    return family.reference_check(agent, train, SECTION, SEED,
                                  hp=family.hyper(good))


def over(dist: dict, limits: dict) -> set:
    return {k for k in limits if not dist[k] <= limits[k]}  # a NaN is over


def test_the_right_program_passes_the_seeded_batch():
    got = seeded(MoELMAgent(CFG))
    assert got["ok"], got
    for side in ("stated", "highest"):
        assert got["routing"][side]["flips_over_margin"] == 0
        assert family.routes_ok(got["routing"][side])
        assert got["distance"][side]["router_prob"] < 1e-4


@pytest.mark.parametrize("fault", MODEL_FAULTS)
def test_seeded_batch_refuses_a_wrong_model(fault):
    got = seeded(faulty(fault)(CFG))
    assert got["ok"] is False, (fault, got["distance"])
    wrong = (over(got["distance"]["highest"], family.HIGHEST)
             or not family.routes_ok(got["routing"]["highest"]))
    assert wrong, (fault, got["distance"]["highest"], got["routing"])


@pytest.mark.parametrize("name, agent, state, refused_by", [
    ("bfloat16_parameters", lambda: MoELMAgent(CFG), cast(jnp.bfloat16),
     "update_norm"),
    ("bfloat16_log_softmax", lambda: faulty("bfloat16_log_softmax")(CFG),
     lambda s: s, "head_logp"),
])
def test_seeded_batch_refuses_a_lower_precision(name, agent, state, refused_by):
    got = seeded(agent(), state)
    assert got["ok"] is False, (name, got["distance"])
    assert refused_by in over(got["distance"]["stated"], family.STATED), \
        (name, got["distance"]["stated"])


def test_seeded_batch_refuses_a_bfloat16_state_across_chunks():
    """The `highest` twin's to refuse: behind float32 operands a state
    that crosses a chunk boundary in bfloat16 is the largest error."""
    got = seeded(faulty("", state_dtype=jnp.bfloat16)(CFG))
    assert got["ok"] is False, got["distance"]
    assert over(got["distance"]["highest"], family.HIGHEST)


def test_a_flip_past_the_margin_is_a_fault_and_a_near_tie_is_not():
    import numpy as np

    flip = np.array([[[True, False, True]]])
    near = {"flip": flip, "margin": np.array([[[0.01, 0.9, 0.04]]])}
    far = {"flip": flip, "margin": np.array([[[0.01, 0.9, 0.2]]])}
    assert family.route_distances([near])["flips_over_margin"] == 0
    assert family.route_distances([near], "highest")["flips_over_margin"] == 2
    assert family.route_distances([far])["flips_over_margin"] == 1
    assert not family.routes_ok(family.route_distances([far]))
    assert not family.routes_ok(family.route_distances([near]))  # 2 of 3 differ
    quiet = {"flip": np.zeros((1, 1, 64), bool), "margin": np.ones((1, 1, 64))}
    quiet["flip"][0, 0, 0], quiet["margin"][0, 0, 0] = True, 0.001
    assert family.routes_ok(family.route_distances([quiet]))


# -- (b) the replay of a compiled chunk ---------------------------------------------


def replayed(agent) -> dict:
    """A chunk of two updates of `agent`'s fused loop, recorded as the
    mode records the first warm chunk, and replayed by the reference
    under the RIGHT configuration."""
    good = MoELMAgent(CFG)
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
    got = replayed(MoELMAgent(CFG))
    assert got["ok"], got
    assert got["updates"] == 2 and got["steps"] == 2 * N * T
    assert got["reference_moved"] > 0
    assert got["routing"]["flips_over_margin"] == 0
    program, reference = got["counters_program_reference"]["held_pair_share"]
    assert abs(program - reference) < 1e-6 and 0.1 < program < 0.5


@pytest.mark.parametrize("name, agent, refused_by", [
    ("state_not_reset_between_updates", lambda: StateNotReset(CFG), "state"),
    ("window_shifted_by_one", lambda: faulty("window_shifted_by_one")(CFG),
     "logp_max_abs"),
    ("decay_missing", lambda: faulty("decay_missing")(CFG), "state"),
    ("beta_missing", lambda: faulty("beta_missing")(CFG), "beta_mean"),
    ("pair_dropped", lambda: faulty("pair_dropped")(CFG), "pairs"),
    ("absent_expert_added", lambda: faulty("absent_expert_added")(CFG), "pairs"),
    ("learns_half_the_batch", lambda: HalfBatch(CFG), "grad_norm"),
    ("p_minus_u", wrong_sign, "step"),
])
def test_replay_refuses(name, agent, refused_by):
    got = replayed(agent())
    assert got["ok"] is False, (name, got)
    refused = over(got["distance"], family.CHUNK) | (
        {"pairs"} if "pairs" in got["distance"] else set())
    assert refused_by in refused, (name, got["distance"])


def test_replay_refuses_another_start():
    """Parameters that are not those the chunk started from: nothing is
    compared."""
    agent = MoELMAgent(CFG)
    env = TokenRecall(vocab=V, episode_len=T, distance=8)
    anakin = AnakinTokens(agent, N, env)
    state = anakin.init(jax.random.PRNGKey(7))
    before = family.param_sample(state.train.params)
    state, metrics = anakin.train_chunk(state, 1)
    record = family.chunk_record(before, before, jax.device_get(metrics))
    other = anakin.init(jax.random.PRNGKey(8)).train.params
    got = family.chunk_check(agent, other, record)
    assert got["ok"] is False and "made anew from the seed" in got["why"]

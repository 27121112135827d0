"""The comparisons that decide `correct` in
`smallthinker_moe.anakin_tokens_swa_8k` (`families/swalm.py`) refuse what
they are there to refuse: each wrong program is PLANTED here, at a small
size on the CPU, run through `reference_check` (a) or recorded and
replayed through `chunk_check` (b) under the limits as committed, and
`ok` has to come out false: rotary on the global layer, none on a window
layer, a window one position short, the router fed the normed state, SiLU
experts, a ring whose keys are rotated by their slot, a lower precision
among them. (A full cache where a ring is stated and a float32 ring give
the same numbers and are refused by their BYTES: `state_problems`,
`test_smallthinker_moe_cell.py`.) The right program passes both.

Sizes: hidden 32, one period (one global NoPE layer, three rotary window
layers of 8), 4 query and 2 key/value heads of 8, a router 16 wide with 3
experts a token of which experts 4..7 are held, V 96, T 32 (four
windows), N 4, float32; `init_std` 0.3 so that the layers differ visibly,
learning rate 1e-3 so that a step is over float32's last bit. A fault
lives in the AGENT's class, so that the `highest` twin, built as
`type(agent)(cfg)`, carries it too.
"""

import dataclasses

import jax
import jax.numpy as jnp
import optax
import pytest

import discover
from conftest import BENCH_DIR
from distributed_reinforcement_learning_tpu.agents.swalm import (
    SwaLMAgent, SwaLMConfig)
from distributed_reinforcement_learning_tpu.envs.token_recall_jax import (
    TokenRecall)
from distributed_reinforcement_learning_tpu.models import window_moe_lm
from distributed_reinforcement_learning_tpu.ops import expert_share
from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
    AnakinTokens)

V, T, N, W = 96, 32, 4, 8
CFG = SwaLMConfig(
    vocab_size=V, hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, rope_theta=1e4, sliding_window_size=W, moe_num_primary_experts=4,
    router_width=16, first_expert=4, moe_num_active_primary_experts=3,
    moe_ffn_hidden_size=16, trajectory=T, dtype=jnp.float32,
    attention_backend="reference", head_block=32, start_learning_rate=1e-3,
    init_std=0.3)
SECTION = {"trajectory": T, "vocab_size": V}
SEED = 3000000019
Plain = window_moe_lm.WindowMoELM

family = discover.module(BENCH_DIR, "families", "swalm")


# -- the wrong programs ---------------------------------------------------------


class Wrong(Plain):
    """One wrong model a name: each overrides one small method, or swaps
    one function of `ops/` for the time the method is traced."""

    fault: str = ""

    def _qkv(self, kind, y, lp, pos):
        acting = pos.ndim == 1  # a decode step's one position
        if self.fault == "rotary_on_the_global_layer":
            kind = "window"
        if self.fault == "no_rotary_on_a_window_layer":
            kind = "global"
        if self.fault == "rotary_at_position_zero":
            pos = jnp.zeros_like(pos)
        if self.fault == "decode_rotary_on_the_global_layer" and acting:
            kind = "window"
        if self.fault == "ring_keys_rotated_by_their_slot" and acting:
            pos = pos % self.window
        return super()._qkv(kind, y, lp, pos)

    def _attention(self, kind, y, lp, seg, pos):
        if self.fault == "attends_across_an_episode_end":
            seg = jnp.zeros_like(seg)
        if self.fault == "a_window_one_short":
            return Plain._attention(dataclasses.replace(self, window=self.window - 1),
                                    kind, y, lp, seg, pos)
        if self.fault == "the_global_layer_windowed" and kind == "global":
            # window layer's mask, the global layer's lack of positions
            q, k, v = self._qkv("global", y, lp, pos)
            groups = self.num_heads // self.num_kv_heads
            att = window_moe_lm.causal_attention(
                q, jnp.repeat(k, groups, 2), jnp.repeat(v, groups, 2), q_seg=seg,
                k_seg=seg, backend=self.attention_backend, window=self.window)
            return self._mm(att.reshape(*y.shape[:2], -1), lp["wo"])
        return super()._attention(kind, y, lp, seg, pos)

    def _route(self, h, lp, scope):
        route = expert_share.route
        if self.fault == "router_fed_the_normed_state":
            h = self._norm(h, lp["norms"][0])

        def not_renormalised(x, w, k):
            probs, chosen, _ = route(x, w, k)
            return probs, chosen, jnp.take_along_axis(probs, chosen, -1)

        def sigmoid_scores(x, w, k):
            return route(x, w, k, scoring="sigmoid",
                         select_bias=jnp.zeros(w.shape[-1]))[:3]

        swap = {"weights_not_renormalised": not_renormalised,
                "sigmoid_scores": sigmoid_scores}.get(self.fault)
        with pytest.MonkeyPatch.context() as m:
            if swap:
                m.setattr(expert_share, "route", swap)
            return super()._route(h, lp, scope)

    def _experts(self, u, routed_by, lp, scope):
        pairs = expert_share.held_pairs
        held, first = self.experts_held, self.first_expert

        def absent_added(chosen, first_expert, n):  # every pair lands on a held expert
            return pairs(first + chosen % held, first_expert, n)

        def pair_dropped(chosen, first_expert, n):  # a token's last choice is lost
            return pairs(chosen.at[:, -1].set(-1), first_expert, n)

        def another_range(chosen, first_expert, n):  # experts 8..11's pairs, held as 4..7's
            return pairs(chosen, first_expert + held, n)

        def silu(activation, gate, counted):
            return jax.nn.silu(gate), jnp.sum(counted & (gate <= 0), dtype=jnp.int32)

        swap = {"absent_expert_added": ("held_pairs", absent_added),
                "pair_dropped": ("held_pairs", pair_dropped),
                "another_range_held": ("held_pairs", another_range),
                "silu_experts": ("_gated", silu)}.get(self.fault)
        if self.fault == "gate_and_up_swapped":
            gate, up = jnp.split(lp["expert_wgu"], 2, axis=-1)
            lp = {**lp, "expert_wgu": jnp.concatenate([up, gate], axis=-1)}
        if self.fault == "a_shared_expert_beside_them":
            out, chosen, stats = super()._experts(u, routed_by, lp, scope)
            x = self._norm(u, lp["norms"][1])  # expert 0 of the held, for every token
            gate, up = jnp.split(self._mm(x, lp["expert_wgu"][0]), 2, axis=-1)
            return (self._residual(out, self._mm(jax.nn.relu(gate) * up,
                                                 lp["expert_wd"][0])), chosen, stats)
        with pytest.MonkeyPatch.context() as m:
            if swap:
                m.setattr(expert_share, *swap)
            return super()._experts(u, routed_by, lp, scope)

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
    def init_state(self, num_rows, length):
        if self.fault == "rings_one_slot_short":
            return Plain.init_state(dataclasses.replace(self, window=self.window - 1),
                                    num_rows, length)
        return super().init_state(num_rows, length)

    def _decode_attention(self, kind, y, lp, keys, values, t, span):
        if self.fault == "the_newest_key_not_written" and kind == "window":
            mix, _, _ = super()._decode_attention(kind, y, lp, keys, values, t, span)
            return mix, keys, values  # every step reads a ring of zeros
        return super()._decode_attention(kind, y, lp, keys, values, t, span)


def faulty(fault: str, base=SwaLMAgent, **model_fields):
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


class HalfBatch(SwaLMAgent):
    def _learn(self, state, batch):
        train, metrics = super()._learn(
            state, jax.tree.map(lambda x: x[:N // 2], batch))
        for key in ("routes", "route_scores"):  # logged as if for the whole batch
            metrics[key] = jnp.concatenate([metrics[key]] * 2, axis=1)
        return train, metrics


def wrong_sign() -> SwaLMAgent:
    agent = SwaLMAgent(CFG)
    agent.tx = optax.chain(agent.tx, optax.scale(-1.0))  # p - u
    return agent


def cast(dtype):
    return lambda state: state.replace(params=jax.tree.map(
        lambda x: x.astype(dtype), state.params))


MODEL_FAULTS = ("rotary_on_the_global_layer", "no_rotary_on_a_window_layer",
                "rotary_at_position_zero", "attends_across_an_episode_end",
                "a_window_one_short", "the_global_layer_windowed",
                "router_fed_the_normed_state", "silu_experts", "gate_and_up_swapped",
                "weights_not_renormalised", "sigmoid_scores",
                "a_shared_expert_beside_them", "absent_expert_added",
                "pair_dropped", "another_range_held")


# -- (a) the seeded batch ---------------------------------------------------------


def seeded(agent, state=lambda s: s) -> dict:
    good = SwaLMAgent(agent.cfg if isinstance(agent.cfg, SwaLMConfig) else CFG)
    train = state(good.init_state(jax.random.PRNGKey(3)))
    return family.reference_check(agent, train, SECTION, SEED,
                                  hp=family.hyper(good))


def over(dist: dict, limits: dict) -> set:
    return {k for k in limits if not dist[k] <= limits[k]}  # a NaN is over


def test_distances_reduced_on_the_device_read_what_the_hosts_float64_reads():
    """`families/swalm.distances` reduces what reads the logits a row at a
    time on the device; `families/moelm.distances` (the procedure it
    stands in for) makes float64 passes on the host."""
    import numpy as np

    rng = np.random.default_rng(7)
    rows, steps = 3, 16
    f32 = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    want = {"logits": 4 * f32(1, rows, steps, V), "value": f32(1, rows, steps),
            "logp": -np.abs(f32(1, rows, steps)), "grad_norm": 3.0, "update_norm": 1e-4,
            "routing": {"picked": np.abs(f32(4, rows, steps, 3))},
            "router_load": rng.integers(0, 9, (4, 16)), "pi_scale": 5.0,
            **{k: float(f32()) for k in family.LOSS_TERMS}}
    near = lambda x: x + 1e-3 * f32(*np.shape(x)) if np.ndim(x) else x * 1.001
    got = {k: near(v) for k, v in want.items() if k not in ("routing", "router_load")}
    got.update(route_scores=near(want["routing"]["picked"]), stats_logp=got["logp"],
               router_load=want["router_load"] + (rng.random((4, 16)) < 0.1))
    action = rng.integers(0, V, (rows, steps))
    ours = family.distances(got, want, action)
    theirs = discover.module(BENCH_DIR, "families", "moelm").distances(
        {**got, "route_probs": got["route_scores"]}, want, action)
    assert set(ours) == set(theirs) | {"load"} and ours["load"] == 1
    for k, v in theirs.items():
        assert ours[k] == pytest.approx(v, rel=2e-5, abs=1e-9), k


@pytest.mark.parametrize("keep", [True, False])
def test_the_compiled_leaf_step_is_the_leaf_by_leaf_step(keep):
    """`families/swalm.reference_step` (one `jax.jit` a leaf) against
    `families/hybridlm.reference_step` (op by op), which it stands in
    for in both comparisons: the same parameters, moments, norm and
    last-bit readings."""
    import numpy as np

    ref = family.reference_module()
    agent = SwaLMAgent(CFG)
    hp = family.hyper(agent)
    theirs = ref.rekey(agent.init_state(jax.random.PRNGKey(3)).params,
                       hp["layer_order"])
    leaves = jax.tree.leaves(theirs)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    grads = lambda: [jax.random.normal(k, x.shape) for k, x in zip(keys, leaves)]
    norm = float(jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads())))
    plain = discover.module(BENCH_DIR, "families", "hybridlm").reference_step
    assert family.moelm().hybridlm().reference_step is family.reference_step
    got, want = (step(ref, theirs, None, grads(), hp, 0, norm, keep=keep)
                 for step in (family.reference_step, plain))
    assert got[2] == pytest.approx(want[2], rel=1e-6)
    if not keep:
        assert got[0] is got[1] is got[3] is want[0] is want[1] is want[3] is None
        return
    for mine, its in zip(jax.tree.leaves((got[0], got[1], got[3])),
                         jax.tree.leaves((want[0], want[1], want[3]))):
        np.testing.assert_allclose(mine, its, rtol=2e-6, atol=0)


def test_the_right_program_passes_the_seeded_batch():
    got = seeded(SwaLMAgent(CFG))
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
    ("bfloat16_parameters", lambda: SwaLMAgent(dataclasses.replace(
        CFG, start_learning_rate=1e-5)), cast(jnp.bfloat16), None),
    ("bfloat16_log_softmax", lambda: faulty("bfloat16_log_softmax")(CFG),
     lambda s: s, "head_logp"),
])
def test_seeded_batch_refuses_a_lower_precision(name, agent, state, refused_by):
    got = seeded(agent(), state)
    assert got["ok"] is False, (name, got["distance"])
    refused = over(got["distance"]["stated"], family.STATED)
    if refused_by is None:
        # bfloat16 parameters at this small size: the few leaves whose step
        # survives (a bias near zero) are most of the step's norm on both
        # sides and the float32 arithmetic on rounded weights stays inside
        # the stated limits, so it is the `highest` side that refuses them
        # here; at the cell's size `update_norm` reads 0.89
        # (`test_smallthinker_moe_control.py`, on the chip)
        refused = over(got["distance"]["highest"], family.HIGHEST)
    assert refused and (refused_by is None or refused_by in refused), \
        (name, got["distance"])


def test_the_highest_twin_keeps_the_programs_attention():
    """This family's twin is float32 through whatever attention the program
    runs (the flash kernels on the chip); `families/looplm.py`'s own takes
    the dense path, whose backward does not fit at 8,192 positions."""
    agent = SwaLMAgent(dataclasses.replace(CFG, dtype=jnp.bfloat16,
                                           attention_backend="auto"))
    twin = family.looplm().highest_twin(agent)
    assert type(twin) is SwaLMAgent and twin.cfg.dtype == jnp.float32
    assert twin.cfg.attention_backend == "auto" and twin.model.window == W
    assert family.moelm().looplm is family.looplm  # the shared procedure's too


# -- (b) the replay of a compiled chunk ---------------------------------------------


def replayed(agent) -> dict:
    """A chunk of two updates of `agent`'s fused loop, recorded as the
    mode records the first warm chunk, and replayed by the reference
    under the RIGHT configuration."""
    good = SwaLMAgent(CFG)
    env = TokenRecall(vocab=V, episode_len=T, distance=8)
    anakin = AnakinTokens(agent, N, env)
    anakin.decode_spans = (W, 2 * W, T)  # scans inside, at and past the window
    state = anakin.init(jax.random.PRNGKey(7))
    before = family.param_sample(state.train.params)
    state, metrics = anakin.train_chunk(state, 2)
    record = family.chunk_record(
        before, family.param_sample(state.train.params),
        jax.device_get(metrics))
    fresh = AnakinTokens(good, N, env).init(jax.random.PRNGKey(7)).train.params
    return family.chunk_check(good, fresh, record)


def test_the_right_program_passes_the_replay():
    got = replayed(SwaLMAgent(CFG))
    assert got["ok"], got
    assert got["updates"] == 2 and got["steps"] == 2 * N * T
    assert got["reference_moved"] > 0
    assert got["routing"]["flips_over_margin"] == 0
    assert not {"pairs", "load"} & set(got["distance"])
    program, reference = got["counters_program_reference"]["held_pair_share"]
    assert abs(program - reference) < 1e-6 and 0.1 < program < 0.5
    program, reference = got["counters_program_reference"]["relu_gate_zero_share"]
    assert abs(program - reference) < 1e-6 and 0.3 < program < 0.7
    assert len(got["step_over_last_bit"]) == len(jax.tree.leaves(
        SwaLMAgent(CFG).init_state(jax.random.PRNGKey(7)).params))


@pytest.mark.parametrize("name, agent, refused_by", [
    ("ring_keys_rotated_by_their_slot",
     lambda: faulty("ring_keys_rotated_by_their_slot")(CFG), "logp_max_abs"),
    ("decode_rotary_on_the_global_layer",
     lambda: faulty("decode_rotary_on_the_global_layer")(CFG), "logp_max_abs"),
    ("rings_one_slot_short", lambda: faulty("rings_one_slot_short")(CFG),
     "logp_max_abs"),
    ("the_newest_key_not_written", lambda: faulty("the_newest_key_not_written")(CFG),
     "logp_max_abs"),
    ("gate_and_up_swapped", lambda: faulty("gate_and_up_swapped")(CFG), "relu_zero"),
    ("pair_dropped", lambda: faulty("pair_dropped")(CFG), "pairs"),
    ("absent_expert_added", lambda: faulty("absent_expert_added")(CFG), "pairs"),
    ("another_range_held", lambda: faulty("another_range_held")(CFG), "pairs"),
    ("learns_half_the_batch", lambda: HalfBatch(CFG), "step"),
    ("p_minus_u", wrong_sign, "step"),
])
def test_replay_refuses(name, agent, refused_by):
    got = replayed(agent())
    assert got["ok"] is False, (name, got)
    refused = over(got["distance"], family.CHUNK) | (
        {"pairs", "load"} & set(got["distance"]))
    assert refused_by in refused, (name, got["distance"])


def test_replay_refuses_another_start():
    """Parameters that are not those the chunk started from: nothing is
    compared."""
    agent = SwaLMAgent(CFG)
    env = TokenRecall(vocab=V, episode_len=T, distance=8)
    anakin = AnakinTokens(agent, N, env)
    state = anakin.init(jax.random.PRNGKey(7))
    before = family.param_sample(state.train.params)
    state, metrics = anakin.train_chunk(state, 1)
    record = family.chunk_record(before, before, jax.device_get(metrics))
    other = anakin.init(jax.random.PRNGKey(8)).train.params
    got = family.chunk_check(agent, other, record)
    assert got["ok"] is False and "made anew from the seed" in got["why"]

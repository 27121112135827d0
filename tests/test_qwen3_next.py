"""The `qwen3_next` configuration at a small size on the CPU: the
sparse-expert hybrid model (`models/moe_lm.py`), its chunked delta rule
(`ops/gated_delta.py`), its share of an expert layer
(`ops/expert_share.py`), the shared token-level loss (`agents/looplm.py`
through `agents/moelm.py`) and the fused loop
(`runtime/anakin_tokens.py`) against the plain reference
(`reference/qwen3_next.py`), which imports nothing of the program.

Sizes: hidden 32, 4 query / 2 key-value heads of 16 (rotary on 4), 2 key
/ 4 value heads of 8 for the delta rule, chunks of 8, a router 16 wide
with 3 experts a token of which experts 4..7 are held here, experts 16
wide, V 64, the published order (three linear-attention layers, one
attention layer), T 32, N 4; float32 so that the agreement is the
arithmetic's. The reference is given the sets the PROGRAM chose
(`routes`): at float32 they are its own.
"""

import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.agents.looplm import LoopLMBatch
from distributed_reinforcement_learning_tpu.agents.moelm import (
    MoELMAgent, MoELMConfig)
from distributed_reinforcement_learning_tpu.envs.token_recall_jax import TokenRecall
from distributed_reinforcement_learning_tpu.models import looped_lm, moe_lm
from distributed_reinforcement_learning_tpu.ops import expert_share, gated_delta
from distributed_reinforcement_learning_tpu.reference import qwen3_next as ref
from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import AnakinTokens
from distributed_reinforcement_learning_tpu.utils.config import load_config

V, T, N = 64, 32, 4
CFG = MoELMConfig(
    vocab_size=V, hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=8, num_experts=4,
    router_width=16, first_expert=4, num_experts_per_tok=3,
    moe_intermediate_size=16, shared_expert_intermediate_size=16, trajectory=T,
    gdn_chunk=8, dtype=jnp.float32, attention_backend="reference", row_block=2,
    head_block=32, start_learning_rate=1e-3, init_std=0.3)  # wide enough to see


def hyper(cfg: MoELMConfig) -> dict:
    return dict(num_heads=cfg.num_attention_heads,
                num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
                rotary_dim=int(cfg.head_dim * cfg.partial_rotary_factor),
                rope_theta=cfg.rope_theta, gdn_key_heads=cfg.linear_num_key_heads,
                gdn_value_heads=cfg.linear_num_value_heads,
                gdn_key_dim=cfg.linear_key_head_dim,
                gdn_value_dim=cfg.linear_value_head_dim,
                top_k=cfg.num_experts_per_tok, first_expert=cfg.first_expert,
                experts_held=cfg.num_experts, rms_eps=cfg.rms_norm_eps,
                layer_order=tuple(cfg.layer_types), discount=cfg.discount_factor,
                baseline_loss_coef=cfg.baseline_loss_coef,
                entropy_coef=cfg.entropy_coef,
                reward_clipping=cfg.reward_clipping,
                gradient_clip_norm=cfg.gradient_clip_norm,
                learning_rate=cfg.start_learning_rate,
                end_learning_rate=cfg.end_learning_rate,
                learning_frame=cfg.learning_frame)


def seeded_batch(seed: int, mid_episode_end: bool = True) -> dict:
    r = np.random.RandomState(seed)
    done = np.zeros((N, T), bool)
    done[:, -1] = True
    if mid_episode_end:
        done[0, 11] = True  # inside the second chunk of 8
        done[2, 7] = True  # the last step of the first chunk
    return {"tokens": r.randint(0, V, (N, T)).astype(np.int32),
            "action": r.randint(0, V, (N, T)).astype(np.int32),
            "behaviour_logp": (np.log(1.0 / V) + 0.3 * r.normal(size=(N, T))
                               ).astype(np.float32),
            "reward": r.choice([0.0, 0.0, 1.0, 2.0], size=(N, T)).astype(np.float32),
            "done": done}


def perturbed(params, seed=1):
    """Norm scales, the bias and the delta rule's `dt_bias` off their
    initial 0 and 1."""
    key = jax.random.PRNGKey(seed)
    moved = ("norms", "final_norm", "b_value", "q_norm", "k_norm", "gate_norm",
             "dt_bias")
    count = [0]

    def move(path, x):
        if path[-1].key not in moved:
            return x
        count[0] += 1
        return x + 0.2 * jax.random.normal(jax.random.fold_in(key, count[0]),
                                           x.shape, x.dtype)

    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def agent():
    return MoELMAgent(CFG)


@pytest.fixture(scope="module")
def params(agent):
    return perturbed(agent.init_state(jax.random.PRNGKey(0)).params)


def _program(agent, params, nb):
    model = agent.model
    batch = LoopLMBatch(**{k: jnp.asarray(v) for k, v in nb.items()})
    hs, counters = model.apply(params, batch.tokens, batch.done, method=model.trunk)
    logits, _, value = model.apply(params, hs, method=model.logits)
    grads, metrics = jax.grad(agent._loss, has_aux=True)(params, batch)
    updates, _ = agent.tx.update(grads, agent.tx.init(params), params)
    return {"logits": logits, "value": value,
            "logp": jnp.take_along_axis(jax.nn.log_softmax(logits), jnp.asarray(
                nb["action"])[None, ..., None], -1)[..., 0],
            "stats_logp": agent._stats(params, batch)["logp"], "grads": grads,
            "grad_norm": common.global_norm(grads),
            "update_norm": common.global_norm(updates),
            "routes": np.asarray(counters["routes"]), **metrics}


@pytest.fixture(scope="module")
def program_out(agent, params):
    return _program(agent, params, seeded_batch(0))


@pytest.fixture(scope="module")
def reference_out(params, program_out):
    return ref.evaluate(params, seeded_batch(0), hyper(CFG),
                        routes=program_out["routes"])


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(1e-30, np.max(np.abs(want))))


# -- the chunked delta rule against the step-by-step recurrence ------------


def _rule_inputs(seed, steps, ends, rows=2, h=3, dk=8, dv=6):
    """Inputs of the rule: `ends` is one step of row 0, a list of `(row,
    step)` at which an episode ends, or None."""
    r = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)
    done = np.zeros((rows, steps), bool)
    for row, step in ([] if ends is None else
                      [(0, ends)] if isinstance(ends, int) else ends):
        done[row, step] = True
    seg, pos = ref.episode_positions(jnp.asarray(done))
    q = gated_delta.l2_normalize(f(rows, steps, h, dk)) * dk ** -0.5
    k = gated_delta.l2_normalize(f(rows, steps, h, dk) + 0.7)  # keys that overlap
    g = -jnp.asarray(r.uniform(0.02, 0.4, (rows, steps, h)), jnp.float32)
    beta = jnp.asarray(r.uniform(0.2, 0.9, (rows, steps, h)), jnp.float32)
    return (q, k, f(rows, steps, h, dv), g, beta), seg, pos == 0


@pytest.mark.parametrize("boundary", [None, 2, 7, 8, 11],
                         ids=["one_episode", "end_inside_chunk0",
                              "end_at_chunk_edge", "end_on_chunk1s_first_step",
                              "end_inside_chunk1"])
@pytest.mark.parametrize("steps", [8, 16, 20, 29],
                         ids=["one_chunk", "two_chunks", "T_not_whole_chunks",
                              "T_odd"])
def test_chunked_delta_rule_equals_the_recurrence_forward_and_backward(
        steps, boundary):
    if boundary is not None and boundary >= steps - 1:
        boundary = steps // 2
    xs, seg, start = _rule_inputs(steps + (boundary or 0), steps, boundary)

    def chunked(*xs):
        return gated_delta.gated_delta_chunked(*xs, seg, 8, jnp.float32)

    def recurrence(q, k, v, g, beta):
        return ref.delta_recurrence(q, k, v, jnp.exp(g), beta, start)

    weigh = lambda f: lambda *xs: sum(
        jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(out.shape)))
        for out in f(*xs))
    with jax.default_matmul_precision("highest"):
        got, want = chunked(*xs), recurrence(*xs)
        g_got = jax.grad(weigh(chunked), argnums=range(5))(*xs)
        g_want = jax.grad(weigh(recurrence), argnums=range(5))(*xs)
    assert _rel(got[0], want[0]) < 2e-5  # the read-outs
    assert _rel(got[1], want[1]) < 2e-5  # the state after the last step
    for a, b in zip(g_got, g_want):
        assert _rel(a, b) < 1e-4


@pytest.mark.parametrize("fault", ["no_decay", "no_beta", "no_kk_correction",
                                   "chunk_drops_s0"])
def test_a_wrong_delta_rule_is_seen(fault):
    """What the inputs above read for each wrong recurrence: well over
    the agreement the right one is held to."""
    (q, k, v, g, beta), seg, start = _rule_inputs(3, 24, 11)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.delta_recurrence(q, k, v, jnp.exp(g), beta, start)
        if fault == "no_decay":
            got, _ = gated_delta.gated_delta_chunked(
                q, k, v, jnp.zeros_like(g), beta, seg, 8, jnp.float32)
        elif fault == "no_beta":
            got, _ = gated_delta.gated_delta_chunked(
                q, k, v, g, jnp.ones_like(beta), seg, 8, jnp.float32)
        elif fault == "no_kk_correction":  # `ops/ssd.py`'s kind of recurrence
            def step(state, xs):
                q_t, k_t, v_t, a_t, b_t, new = xs
                state = jnp.where(new[:, None, None, None], 0, state)
                state = (a_t[..., None, None] * state
                         + k_t[..., None] * (b_t[..., None] * v_t)[..., None, :])
                return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)
            t_major = lambda x: jnp.moveaxis(x, 1, 0)
            _, got = jax.lax.scan(step, jnp.zeros((2, 3, 8, 6)), tuple(
                t_major(x) for x in (q, k, v, jnp.exp(g), beta, start)))
            got = jnp.moveaxis(got, 0, 1)
        else:  # every chunk starts from no past
            got = jnp.concatenate([gated_delta.gated_delta_chunked(
                *(x[:, i:i + 8] for x in (q, k, v, g, beta)), seg[:, i:i + 8], 8,
                jnp.float32)[0] for i in range(0, 24, 8)], axis=1)
    assert _rel(got, want) > 0.02


# -- the hoisted form against the form it replaced (PR 37) -------------------


def _parent_chunked(q, k, v, g, beta, seg, chunk=64, dtype=jnp.bfloat16,
                    carry_dtype=jnp.float32):
    """`gated_delta_chunked` as it was before PR 37, kept here as the plain
    version: EVERYTHING inside the scan over chunks, the solve included."""
    F32 = jnp.float32
    b, t, h, dk = q.shape
    c = min(chunk, t)
    pad = -t % c
    if pad:
        tail = lambda x, mode="constant": jnp.pad(
            x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2), mode=mode)
        q, k, v, g, beta = (tail(x) for x in (q, k, v, g, beta))
        seg = tail(seg, "edge")
    n = (t + pad) // c
    mm = lambda spec, x, y: jnp.einsum(spec, x.astype(dtype), y.astype(dtype),
                                       preferred_element_type=F32)
    steps = jnp.arange(c)
    lower = steps[:, None] > steps[None, :]
    causal = steps[:, None] >= steps[None, :]

    @jax.checkpoint
    def one_chunk(carry, xs):
        state, seg_before = carry
        q_c, k_c, v_c, g_c, b_c, seg_c = xs
        cs = jnp.moveaxis(jnp.cumsum(g_c.astype(F32), axis=1), 2, 1)
        b_h = jnp.moveaxis(b_c.astype(F32), 2, 1)
        same = (seg_c[:, :, None] == seg_c[:, None, :])[:, None]
        decay = lambda pairs: jnp.exp(jnp.where(
            pairs, cs[..., :, None] - cs[..., None, :], -jnp.inf))
        live = (seg_c == seg_before[:, None])[:, None]
        from_past = jnp.where(live, jnp.exp(cs), 0.0)
        kk = mm("bihd,bjhd->bhij", k_c, k_c)
        lmat = b_h[..., None] * decay(lower & same) * kk
        k_h, v_h = (jnp.moveaxis(x.astype(F32), 2, 1) for x in (k_c, v_c))
        rhs = jnp.concatenate([b_h[..., None] * v_h,
                               (b_h * from_past)[..., None] * k_h], axis=-1)
        solved = jax.scipy.linalg.solve_triangular(
            lmat + jnp.eye(c, dtype=F32), rhs, lower=True, unit_diagonal=True)
        u, w = solved[..., :v_h.shape[-1]], solved[..., v_h.shape[-1]:]
        u = u - mm("bhik,bhkv->bhiv", w, state)
        qk = mm("bihd,bjhd->bhij", q_c, k_c) * decay(causal & same)
        o = (mm("bihk,bhkv->bhiv", q_c, state) * from_past[..., None]
             + mm("bhij,bhjv->bhiv", qk, u))
        ends = seg_c[:, -1]
        to_end = jnp.where((seg_c == ends[:, None])[:, None],
                           jnp.exp(cs[..., -1:] - cs), 0.0)
        kept = jnp.where((ends == seg_before)[:, None], jnp.exp(cs[..., -1]), 0.0)
        state = (kept[..., None, None] * state.astype(F32)
                 + mm("bhjk,bhjv->bhkv", k_h * to_end[..., None], u))
        return (state.astype(carry_dtype), ends), jnp.moveaxis(o, 1, 2)

    chunks = lambda x: jnp.moveaxis(x.reshape(b, n, c, *x.shape[2:]), 1, 0)
    carry = (jnp.zeros((b, h, dk, v.shape[-1]), carry_dtype),
             jnp.full((b,), -1, seg.dtype))
    (state, _), o = jax.lax.scan(
        one_chunk, carry, tuple(chunks(x) for x in (q, k, v, g, beta, seg)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t + pad, h, -1)[:, :t]
    return o, state.astype(F32)


_SMALL = (2, 3, 8, 6, 8)  # rows, heads, K, V, chunk
_PUBLISHED = (1, 32, 128, 128, 64)
_HOISTED_CASES = [
    # T whole chunks / a ragged tail / under one chunk, each with an episode
    # end inside a chunk, one exactly on a chunk edge, and none
    ("whole_end_inside", _SMALL, 24, [(0, 11), (1, 3)], jnp.float32),
    ("whole_end_on_edge", _SMALL, 24, [(0, 7), (1, 15)], jnp.float32),
    ("whole_no_end", _SMALL, 24, [], jnp.float32),
    ("ragged_end_inside", _SMALL, 27, [(0, 20), (1, 25)], jnp.float32),
    ("ragged_end_on_edge", _SMALL, 27, [(0, 7), (1, 23)], jnp.float32),
    ("ragged_no_end", _SMALL, 27, [], jnp.float32),
    ("under_one_chunk_end_inside", _SMALL, 5, [(0, 2)], jnp.float32),
    ("under_one_chunk_end_on_edge", _SMALL, 5, [(0, 4)], jnp.float32),
    ("under_one_chunk_no_end", _SMALL, 5, [], jnp.float32),
    ("whole_bfloat16_carry", _SMALL, 24, [(0, 11)], jnp.bfloat16),
    ("ragged_bfloat16_carry", _SMALL, 27, [(0, 7)], jnp.bfloat16),
    ("published_whole_ends_inside_and_on_edge", _PUBLISHED, 256,
     [(0, 70), (0, 127)], jnp.float32),
    ("published_ragged_end_on_edge", _PUBLISHED, 200, [(0, 63)], jnp.float32),
    ("published_no_end", _PUBLISHED, 128, [], jnp.float32),
]


@pytest.mark.parametrize("sizes,steps,ends,carry", [c[1:] for c in _HOISTED_CASES],
                         ids=[c[0] for c in _HOISTED_CASES])
def test_hoisted_delta_rule_equals_the_form_it_replaced(sizes, steps, ends, carry):
    """Outputs, final state and the gradients of all five inputs, at
    float32 and `highest`: the two forms differ by the order of float32
    sums alone (readings when written: at most 3e-7)."""
    rows, h, dk, dv, chunk = sizes
    xs, seg, _ = _rule_inputs(steps + len(ends), steps, ends, rows, h, dk, dv)
    form = lambda f: lambda *xs: f(*xs, seg, chunk, jnp.float32, carry)
    weigh = lambda f: lambda *xs: sum(
        jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(out.shape)))
        for out in f(*xs))
    new, old = form(gated_delta.gated_delta_chunked), form(_parent_chunked)
    with jax.default_matmul_precision("highest"):
        got, want = new(*xs), old(*xs)
        g_got = jax.grad(weigh(new), argnums=range(5))(*xs)
        g_want = jax.grad(weigh(old), argnums=range(5))(*xs)
    assert got[0].shape == (rows, steps, h, dv) and got[1].shape == (rows, h, dk, dv)
    assert _rel(got[0], want[0]) < 2e-6
    assert _rel(got[1], want[1]) < 2e-6
    for a, b in zip(g_got, g_want):
        assert _rel(a, b) < 2e-6


def _primitives(jaxpr, counts=None):
    """How often each primitive occurs in a jaxpr, sub-jaxprs included."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, counts)
    return counts


def _scans_with_a_carry(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["num_carry"]:
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans_with_a_carry(sub)


_SEQUENTIAL = ("dot_general", "triangular_solve", "exp", "cumsum", "transpose")


@pytest.mark.parametrize("form,counts", [
    ("hoisted", (3, 0, 0, 0, 0)), ("replaced", (6, 1, 5, 1, 5))])
def test_only_the_state_stays_in_the_scan_over_chunks(form, counts):
    """The FORWARD jaxpr at the published head sizes: the scan over chunks
    holds the three products that touch S and no solve, `exp`, `cumsum` or
    transposition (the form it replaced: 6, 1, 5, 1, 5: the walker sees
    them), and the one solve of the whole rule stands outside it."""
    xs, seg, _ = _rule_inputs(0, 256, 70, 1, 32, 128, 128)
    rule = {"hoisted": gated_delta.gated_delta_chunked,
            "replaced": _parent_chunked}[form]
    whole = jax.make_jaxpr(lambda *xs: rule(*xs, seg, 64))(*xs).jaxpr
    (scan,) = _scans_with_a_carry(whole)
    assert scan.params["length"] == 4
    body = _primitives(scan.params["jaxpr"].jaxpr)
    assert tuple(body.get(name, 0) for name in _SEQUENTIAL) == counts
    assert _primitives(whole)["triangular_solve"] == 1


def test_one_step_of_the_rule_is_the_recurrence():
    (q, k, v, g, beta), _, start = _rule_inputs(5, 12, None)
    state = jnp.zeros((2, 3, 8, 6))
    outs = []
    for t in range(12):
        o, state = gated_delta.gated_delta_step(
            state, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        outs.append(o)
    want, want_state = ref.delta_recurrence(q, k, v, jnp.exp(g), beta, start)
    assert _rel(jnp.stack(outs, 1), want) < 1e-5
    assert _rel(state, want_state) < 1e-5


# -- the expert layer: this chip's share, dropless, no capacity ---------------


def _expert_layer(seed, tokens=24, d=32, width=16, experts=16, bias=None):
    r = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(r.normal(size=shape) * 0.3, jnp.float32)
    router, x = f(d, experts), f(1, tokens, d) * 3.0
    if bias is not None:  # every token's choices among `bias`: a constant feature
        x = x.at[..., 0].set(4.0)
        router = (0.05 * router).at[0].set(jnp.where(
            jnp.isin(jnp.arange(experts), jnp.asarray(list(bias))), 3.0, -3.0))
    return {"x": x, "router": router,
            "expert_wgu": f(experts, d, 2 * width), "expert_wd": f(experts, width, d),
            "shared_wgu": f(d, 2 * width), "shared_wd": f(width, d),
            "shared_gate": f(d)}


def _share(layer, first, held, top_k=3):
    """(the routed part the share `[first, first + held)` gives, counters)."""
    x = layer["x"][0]
    _, chosen, weight = expert_share.route(x, layer["router"], top_k)
    return expert_share.held_experts(
        x, chosen, weight, layer["expert_wgu"][first:first + held],
        layer["expert_wd"][first:first + held], first, 16, jnp.float32)


@pytest.mark.parametrize("shares", [1, 2, 4, 16])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """The share test of the `model-configs` guide, section 4: the routed
    parts that all the shares give, plus the shared expert counted once,
    are what the uncut reference gives for the whole layer."""
    layer = _expert_layer(0)
    held = 16 // shares
    with jax.default_matmul_precision("highest"):
        parts = [_share(layer, first, held) for first in range(0, 16, held)]
        routed, shared, facts = ref.moe(
            layer["x"], layer, dict(top_k=3, first_expert=0, experts_held=16))
        assert _rel(sum(p[0] for p in parts), routed[0]) < 1e-5
        assert sum(int(p[1]["held_pairs"]) for p in parts) == 24 * 3
        # and one share alone is the reference's share
        one, _, _ = ref.moe(layer["x"], {**layer, "expert_wgu": layer["expert_wgu"][:held],
                                         "expert_wd": layer["expert_wd"][:held]},
                            dict(top_k=3, first_expert=0, experts_held=held))
        assert _rel(parts[0][0], one[0]) < 1e-5
    assert float(jnp.max(jnp.abs(shared))) > 0  # counted once, by the caller


@pytest.mark.parametrize("first", [0, 4, 12])
def test_every_pair_is_computed_when_all_choices_are_held_here(first):
    """DROPLESS: a router biased so that every token's choices lie in the
    four experts held here. All `tokens x top_k` pairs are here (the
    worst case the buffer is sized for), none is dropped, and the result
    is the reference's."""
    layer = _expert_layer(1, bias=range(first, first + 4))
    with jax.default_matmul_precision("highest"):
        out, counters = _share(layer, first, 4)
        routed, _, facts = ref.moe(
            layer["x"], {**layer, "expert_wgu": layer["expert_wgu"][first:first + 4],
                         "expert_wd": layer["expert_wd"][first:first + 4]},
            dict(top_k=3, first_expert=first, experts_held=4))
    assert int(counters["held_pairs"]) == 24 * 3 == int(facts["held_pairs"])
    assert int(counters["dropped_pairs"]) == 0
    assert int(jnp.sum(counters["expert_pairs"])) == 24 * 3
    assert _rel(out, routed[0]) < 1e-5


def test_an_imbalance_onto_one_expert_drops_nothing():
    layer = _expert_layer(2, bias=[5])  # every token takes expert 5 first
    with jax.default_matmul_precision("highest"):
        out, counters = _share(layer, 4, 4)
        routed, _, _ = ref.moe(
            layer["x"], {**layer, "expert_wgu": layer["expert_wgu"][4:8],
                         "expert_wd": layer["expert_wd"][4:8]},
            dict(top_k=3, first_expert=4, experts_held=4))
    assert int(counters["expert_pairs"][1]) == 24 and int(counters["dropped_pairs"]) == 0
    assert _rel(out, routed[0]) < 1e-5


def test_rows_past_the_last_group_reach_neither_the_result_nor_a_gradient(monkeypatch):
    """On the chip the grouped product leaves the rows past its last group
    as it found them, in its backward's products too (my chip run, PR 36:
    max abs 4.29 where the CPU writes zeros). Planted here as NaN: the
    layer's result and every gradient are those of the clean product."""
    layer = _expert_layer(4)
    x = layer["x"][0]
    _, chosen, weight = expert_share.route(x, layer["router"], 3)
    clean = jax.lax.ragged_dot

    def poison(rows, sizes):
        live = jnp.arange(rows.shape[0]) < jnp.sum(sizes)
        return jnp.where(live[:, None], rows, jnp.nan)

    @jax.custom_vjp
    def dirty(lhs, rhs, sizes):
        return poison(clean(lhs, rhs, sizes, preferred_element_type=jnp.float32), sizes)

    def fwd(lhs, rhs, sizes):
        return dirty(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        d_lhs, d_rhs = jax.vjp(lambda a, b: clean(
            a, b, sizes, preferred_element_type=jnp.float32), lhs, rhs)[1](g)
        return poison(d_lhs, sizes), d_rhs, None

    dirty.defvjp(fwd, bwd)

    def loss(x, weight, wgu, wd):
        out, _ = expert_share.held_experts(x, chosen, weight, wgu, wd, 4, 16,
                                             jnp.float32)
        return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(out.shape)))

    args = (x, weight, layer["expert_wgu"][4:8], layer["expert_wd"][4:8])
    want = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(*args)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        lambda lhs, rhs, sizes, **_: dirty(lhs, rhs, sizes))
    got = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(*args)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a))) and _rel(a, b) < 1e-5


@pytest.mark.parametrize("fault", ["weights_not_renormalised",
                                   "absent_expert_added", "pair_dropped"])
def test_a_wrong_expert_share_is_seen(fault):
    layer = _expert_layer(3)
    x = layer["x"][0]
    with jax.default_matmul_precision("highest"):
        probs, chosen, weight = expert_share.route(x, layer["router"], 3)
        routed, _, _ = ref.moe(
            layer["x"], {**layer, "expert_wgu": layer["expert_wgu"][4:8],
                         "expert_wd": layer["expert_wd"][4:8]},
            dict(top_k=3, first_expert=4, experts_held=4))
        first = 4
        if fault == "weights_not_renormalised":
            weight = jnp.take_along_axis(probs, chosen, -1)
        elif fault == "absent_expert_added":  # expert 3's part under expert 4's name
            chosen = jnp.where(chosen == 3, 4, chosen)
        else:  # the second choice of every token is lost
            chosen = chosen.at[:, 1].set(15)
        out, _ = expert_share.held_experts(
            x, chosen, weight, layer["expert_wgu"][4:8], layer["expert_wd"][4:8],
            first, 16, jnp.float32)
    assert _rel(out, routed[0]) > 0.02


def test_no_array_of_the_learn_step_has_an_expert_and_a_capacity_axis(
        agent, params, monkeypatch):
    """`ops/moe.py` dispatches through `[tokens, experts, capacity]`
    one-hot arrays; this layer's lowered learn step has no array with
    the tokens of a row block AND the router's width beside a third axis
    (at the timed sizes that one would be 4,096 x 512 x 100 and more).
    In the SORTED form, which every cell's learner takes: this section's
    row block is 64 tokens, few enough for the dense form, whose `[held,
    tokens, F]` has F = 16 = the router's width here."""
    monkeypatch.setattr(expert_share, "one_slab_form", lambda *_: "sorted")
    nb = seeded_batch(0)
    batch = LoopLMBatch(**{k: jnp.asarray(v) for k, v in nb.items()})
    text = jax.jit(jax.grad(agent._loss, has_aux=True)).lower(params, batch).as_text()
    tokens, width = CFG.row_block * T, CFG.router_width
    shapes = {tuple(int(d) for d in m.split("x")[:-1])
              for m in re.findall(r"tensor<((?:\d+x)+)[a-z]\w*>", text)}
    assert (tokens, width) in shapes  # the router's probabilities are there
    for shape in shapes:
        assert not (tokens in shape and width in shape and len(shape) > 2), shape


# -- the whole section against the reference --------------------------------


@pytest.mark.parametrize("what", ["logits", "value", "logp"])
def test_forward_matches_reference(program_out, reference_out, what):
    assert _rel(program_out[what], reference_out[what]) < 2e-4
    assert _rel(program_out["stats_logp"], reference_out["logp"]) < 2e-4


def test_the_program_chose_the_references_sets(reference_out):
    assert bool(np.all(reference_out["routing"]["same_set"]))
    assert float(np.min(reference_out["routing"]["margin"])) >= 0


@pytest.mark.parametrize("term", ["total_loss", "pi_loss", "baseline_loss",
                                  "entropy", "beta_mean", "decay_min",
                                  "router_entropy", "shared_gate_mean",
                                  "held_pair_share"])
def test_loss_terms_and_counters_match_reference(program_out, reference_out, term):
    assert _rel(program_out[term], reference_out[term]) < 5e-4


def test_the_counters_of_the_share(program_out):
    assert float(program_out["dropped_pairs"]) == 0
    assert 0.1 < float(program_out["held_pair_share"]) < 0.5  # 4 of 16: about 1/4
    assert float(program_out["expert_load_max_over_mean"]) >= 1
    assert float(program_out["experts_untouched"]) == 0
    assert program_out["routes"].shape == (4, N, T, 3)
    assert program_out["routes"].min() >= 0 and program_out["routes"].max() < 16


def test_gradients_match_reference(params, program_out):
    _, grads = ref.loss_and_grads(params, seeded_batch(0), hyper(CFG),
                                  routes=program_out["routes"])
    want = ref.stacked(grads)
    flat = jax.tree_util.tree_leaves_with_path(program_out["grads"])
    assert len(flat) == len(jax.tree.leaves(want)) == 5 + 14 + 12
    for (path, got), theirs in zip(flat, jax.tree.leaves(want)):
        assert _rel(got, theirs) < 2e-3, jax.tree_util.keystr(path)


def test_gradient_norm_and_update_norm_match_reference(program_out, reference_out):
    assert _rel(program_out["grad_norm"], reference_out["grad_norm"]) < 5e-4
    assert _rel(program_out["update_norm"], reference_out["update_norm"]) < 5e-4


def test_rekey_and_stacked_are_inverses(params):
    theirs = ref.rekey(params, CFG.layer_types)
    assert [ref.layer_kind(lp) for lp in theirs["layers"]] == list(CFG.layer_types)
    back = ref.stacked(theirs)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        assert a.shape == b.shape and bool(jnp.all(a == b))
    with pytest.raises(ValueError, match="the configuration says"):
        ref.rekey(params, CFG.layer_types[::-1])


# -- each mechanism of the two mixers and of the MLP, planted wrong -----------


class _Wrong(moe_lm.MoELM):
    """The model with one mechanism planted wrong (`fault`)."""

    fault = ""

    def _rotary(self, x, pos):
        if self.fault == "rotary_over_the_whole_head":
            return moe_lm.rope(x, pos, self.rope_theta)
        return super()._rotary(x, pos)

    def _norm(self, x, scale):
        if self.fault == "g_for_1_plus_g":
            return moe_lm.zero_centred_norm(x, scale - 1.0, self.rms_eps)
        if self.fault == "no_qk_norm" and scale.shape[-1] == self.head_dim:
            return x.astype(jnp.float32)
        return super()._norm(x, scale)

    def _split_conv(self, qkv):
        if self.fault != "qk_not_l2_normalised":
            return super()._split_conv(qkv)
        with pytest.MonkeyPatch.context() as m:
            m.setattr(gated_delta, "l2_normalize", lambda x: x.astype(jnp.float32))
            return super()._split_conv(qkv)

    def _gates(self, ba, lp):
        g, beta = super()._gates(ba, lp)
        return ((jnp.zeros_like(g) if self.fault == "no_decay" else g),
                (jnp.ones_like(beta) if self.fault == "no_beta" else beta))

    def _moe(self, u, lp, scope):
        if self.fault == "no_shared_gate":
            lp = {**lp, "shared_gate": jnp.zeros_like(lp["shared_gate"])}
            return super()._moe(u, lp, scope)  # sigmoid(0) = 1/2 for every token
        return super()._moe(u, lp, scope)

    def _attention(self, y, lp, seg, pos):
        if self.fault != "no_output_gate":
            return super()._attention(y, lp, seg, pos)
        wq = lp["wq"].reshape(lp["wq"].shape[0], self.num_heads, 2, self.head_dim)
        ungated = wq.at[:, :, 1].set(0.0).reshape(lp["wq"].shape)
        return 2.0 * super()._attention(y, {**lp, "wq": ungated}, seg, pos)


MECHANISMS = ["rotary_over_the_whole_head", "g_for_1_plus_g", "no_qk_norm",
              "qk_not_l2_normalised", "no_decay", "no_beta", "no_shared_gate",
              "no_output_gate"]


@pytest.mark.parametrize("fault", MECHANISMS)
def test_each_mechanism_planted_wrong_is_seen(agent, params, program_out,
                                              reference_out, fault):
    """Partial rotary, the q/k norms, the output gate, the zero-centred
    scale, the l2 norms, the decay, beta and the shared expert's gate,
    each against the reference: the right program agrees to 2e-4, the
    wrong one does not by a factor of fifty and more."""
    wrong = _Wrong(**dataclasses.asdict(agent.model) | {
        "layer_types": agent.model.layer_types})
    object.__setattr__(wrong, "fault", fault)
    nb = seeded_batch(0)
    hs, _ = wrong.apply(params, jnp.asarray(nb["tokens"]), jnp.asarray(nb["done"]),
                        method=wrong.trunk)
    logits, _, _ = wrong.apply(params, hs, method=wrong.logits)
    assert _rel(program_out["logits"], reference_out["logits"]) < 2e-4
    assert _rel(logits, reference_out["logits"]) > 0.01


# -- acting as decode through three kinds of state ---------------------------


def _decode_all(agent, params, tokens, spans=None, model=None):
    """Every step of the `[N, T]` episode by decode -> (logits `[N, T,
    V]`, the state after the last step)."""
    model = model or agent.model
    act_params = agent.for_acting(params)
    state = model.init_state(tokens.shape[0], T)
    spans = spans or (T,)
    out = []
    for t in range(T):
        span = next(s for s in spans if t < s)
        h, state = model.apply(act_params, jnp.asarray(tokens[:, t]), jnp.int32(t),
                               state, span, method=model.decode)
        out.append(model.apply(act_params, h, method=model.logits)[0])
    return jnp.stack(out, axis=1), state


@pytest.fixture(scope="module")
def whole_episode(agent, params):
    nb = seeded_batch(3, mid_episode_end=False)
    logits, state = _decode_all(agent, params, nb["tokens"])
    routes = np.moveaxis(np.asarray(state.routes), 2, 0)  # [layers, N, T, k]
    return nb, logits, state, ref.forward(params, nb["tokens"], nb["done"],
                                          hyper(CFG), routes=routes)


@pytest.mark.parametrize("segments", [1, 2, 4])
def test_decode_through_state_equals_full_forward_at_every_step(
        agent, params, whole_episode, segments):
    """Logits, not tokens: decode through the delta-rule state, the
    convolution window and the key/value cache, whatever prefix of the
    cache a step reads, is the reference's full forward."""
    nb, _, _, want = whole_episode
    logits, state = _decode_all(agent, params, nb["tokens"],
                                looped_lm.decode_spans(T, segments))
    assert _rel(logits, want["logits"][0]) < 2e-4
    for got, theirs in zip([s for s in state.gdn if s is not None], want["states"]):
        assert _rel(got, theirs) < 1e-4
    assert all(bool(np.all(r["same_set"])) for r in want["routing"])


def test_state_is_of_three_kinds_side_by_side_and_a_record(agent):
    state = agent.init_cache(N)
    kinds = CFG.layer_types
    assert [s is not None for s in state.gdn] == [k == "linear_attention" for k in kinds]
    assert [s is not None for s in state.k] == [k == "full_attention" for k in kinds]
    assert state.gdn[0].shape == (N, 4, 8, 8) and state.gdn[0].dtype == jnp.float32
    assert state.conv[0].shape == (N, 3, 2 * 16 + 32)
    assert state.k[3].shape == (N, T, 2, 16)
    assert state.routes.shape == (N, T, 4, 3) and state.routes.dtype == jnp.int16
    facts = agent.state_facts(N)
    assert facts["gdn_state_bytes"] == 3 * N * 4 * 8 * 8 * 4
    assert facts["conv_state_bytes"] == 3 * N * 3 * 64 * 4
    assert facts["kv_cache_bytes"] == 2 * N * T * 2 * 16 * 4
    assert (facts["experts_held"], facts["router_width"], facts["first_expert"]) == (4, 16, 4)


@pytest.mark.parametrize("fault", ["window_shifted_by_one", "state_in_bfloat16",
                                   "rotary_at_position_zero"])
def test_a_wrong_decode_step_is_seen(agent, params, whole_episode, fault):
    nb, _, _, want = whole_episode
    model = agent.model
    if fault == "state_in_bfloat16":
        model = dataclasses.replace(model, state_dtype=jnp.bfloat16)
    else:
        class Wrong(moe_lm.MoELM):
            def _decode_delta_rule(self, y, lp, state, window):
                mix, state, taps = super()._decode_delta_rule(y, lp, state, window)
                return mix, state, (jnp.roll(taps, 1, axis=1)
                                    if fault == "window_shifted_by_one" else taps)

            def _decode_attention(self, y, lp, keys, values, t, span):
                if fault != "rotary_at_position_zero":
                    return super()._decode_attention(y, lp, keys, values, t, span)
                rotary, self_ = self._rotary, self
                object.__setattr__(self_, "_rotary", lambda x, pos: rotary(x, pos * 0))
                try:
                    return super()._decode_attention(y, lp, keys, values, t, span)
                finally:
                    object.__delattr__(self_, "_rotary")

        model = Wrong(**{f.name: getattr(model, f.name)
                         for f in dataclasses.fields(model)})
    logits, _ = _decode_all(agent, params, nb["tokens"], model=model)
    assert _rel(logits, want["logits"][0]) > (1e-3 if fault == "state_in_bfloat16"
                                              else 0.01)


def test_a_span_past_the_cache_is_refused(agent, params):
    with pytest.raises(ValueError, match="span"):
        agent.model.apply(agent.for_acting(params), jnp.zeros((N,), jnp.int32),
                          jnp.int32(0), agent.init_cache(N), T + 1,
                          method=agent.model.decode)


# -- the section in `config.json` ---------------------------------------------


def _section(**changes):
    with open("config.json") as f:
        section = json.load(f)["qwen3_next"]
    section.update(changes)
    return section


def test_load_config_reads_the_section(tmp_path):
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"qwen3_next": _section()}))
    cfg, rt = load_config(str(path), "qwen3_next")
    assert isinstance(cfg, MoELMConfig) and rt.algorithm == "moelm"
    assert cfg.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_attention_heads,
            cfg.num_key_value_heads) == (2048, 256, 16, 2)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim) == (16, 32, 128, 128, 4)
    assert (cfg.num_experts, cfg.router_width, cfg.first_expert,
            cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.shared_expert_intermediate_size) == (32, 512, 0, 10, 512, 512)
    assert (cfg.vocab_size, cfg.trajectory, cfg.rope_theta,
            cfg.partial_rotary_factor) == (18_992, 1024, 1e7, 0.25)
    assert cfg.dtype == jnp.bfloat16 and cfg.total_ut_steps == 1
    assert rt.num_actors * rt.envs_per_actor == 32
    model = MoELMAgent(cfg).model
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(params)) == 625_669_185
    assert model.rotary_dim == 64 and model.conv_channels == 8192


@pytest.mark.parametrize("changes, message", [
    ({"layer_types": ["linear_attention", "mamba", "mamba", "full_attention"]},
     "unknown layer type"),
    ({"num_hidden_layers": 5}, "layer_types for"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"mlp_only_layers": [0]}, "mlp_only_layers"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
])
def test_load_config_refuses_what_is_not_computed(tmp_path, changes, message):
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"qwen3_next": _section(**changes)}))
    with pytest.raises(ValueError, match=message):
        load_config(str(path), "qwen3_next")


@pytest.mark.parametrize("key", ["moe_intermediate_size", "linear_key_head_dim",
                                 "router_width", "head_dim"])
def test_load_config_refuses_a_missing_width(tmp_path, key):
    section = _section()
    del section[key]
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"qwen3_next": section}))
    with pytest.raises(KeyError, match=key):
        load_config(str(path), "qwen3_next")


def test_a_share_past_the_routers_width_is_refused():
    with pytest.raises(ValueError, match="of a router"):
        MoELMAgent(dataclasses.replace(CFG, first_expert=14))


# -- the fused loop -----------------------------------------------------------


@pytest.fixture(scope="module")
def chunk():
    agent = MoELMAgent(CFG)
    anakin = AnakinTokens(agent, N, TokenRecall(V, T, 8))
    state = anakin.init(jax.random.PRNGKey(5))
    before = jax.device_get(state.train.params)
    state, metrics = anakin.train_chunk(state, 2)
    return anakin, before, jax.device_get(state), jax.device_get(metrics)


def test_fused_chunk_losses_are_finite_and_every_leaf_moves(chunk):
    anakin, before, state, metrics = chunk
    assert np.all(np.isfinite(metrics["total_loss"])) and np.all(metrics["grad_norm"] > 0)
    assert np.all(metrics["dropped_pairs"] == 0)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(before),
                            jax.tree.leaves(state.train.params)):
        assert np.any(a != b), jax.tree_util.keystr(path)
    facts = anakin.static_facts
    assert facts["layer_order"] == CFG.layer_types and facts["experts_held"] == 4
    assert metrics["act_routes"].shape == (2, N, T, 4, 3)
    assert metrics["routes"].shape == (2, 4, N, T, 3)


def test_collect_logp_and_final_state_are_the_reference_forward(chunk):
    """Update 0: the log mu(a_t) that collect wrote through the three
    kinds of state, and the delta-rule state the episode ended with, are
    the reference's full forward from zero state, computed on the sets
    the decode steps chose."""
    _, before, _, metrics = chunk
    rollout = {k: v[0] for k, v in metrics["rollout"].items()}
    routes = np.moveaxis(metrics["act_routes"][0], 2, 0)
    with jax.default_matmul_precision("highest"):
        out = ref.forward(before, rollout["tokens"], rollout["done"], hyper(CFG),
                          routes=routes)
    logp = ref.logp_of(out["logits"][0], rollout["action"])
    assert float(np.max(np.abs(np.asarray(logp) - rollout["behaviour_logp"]))) < 2e-4
    assert all(bool(np.all(r["same_set"])) for r in out["routing"])
    every = max(1, sum(s.size for s in out["states"]) // 16384)
    want = np.concatenate([np.asarray(s).reshape(-1)[::every] for s in out["states"]])
    assert _rel(metrics["state_sample"][0], want) < 1e-4
    assert float(metrics["state_norm_mean"][0]) > 0

"""The `smallthinker_moe` configuration at a small size on the CPU: the
sliding-window / global-attention sparse-expert model
(`models/window_moe_lm.py`), its act-time rings and full cache, the
router that reads the layer's INPUT over a QUARTER of a layer's ReGLU
experts with no shared expert (`ops/expert_share.py`) and the fused loop
(`runtime/anakin_tokens.py`) against the plain reference
(`reference/smallthinker_moe.py`), which imports nothing of the program.

Sizes (section `smallthinker_moe_small` of `config.json`): hidden 32, one
period of the published order (one global NoPE layer, three rotary
window layers), 4 query and 2 key/value heads of 8, a window of 8 in an
episode of 32 (the rings wrap three times), a router 16 wide with 3
experts a token of which experts 4..7 are held here (a quarter), experts
16 wide; V 64, N 4; float32 so that the agreement is the arithmetic's.
The reference is given the sets the PROGRAM chose (`routes`): at float32
they are its own.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.agents.looplm import LoopLMBatch
from distributed_reinforcement_learning_tpu.agents.swalm import (
    SwaLMAgent, SwaLMConfig)
from distributed_reinforcement_learning_tpu.agents.token_families import (
    TOKEN_FAMILIES)
from distributed_reinforcement_learning_tpu.envs.token_recall_jax import TokenRecall
from distributed_reinforcement_learning_tpu.models import looped_lm, window_moe_lm
from distributed_reinforcement_learning_tpu.ops import expert_share
from distributed_reinforcement_learning_tpu.reference import smallthinker_moe as ref
from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import AnakinTokens
from distributed_reinforcement_learning_tpu.utils.config import load_config

V, T, N, W = 64, 32, 4, 8
CFG = dataclasses.replace(
    load_config("config.json", "smallthinker_moe_small")[0],
    attention_backend="reference", head_block=32)
ORDER = ("global", "window", "window", "window")


def hyper(cfg: SwaLMConfig) -> dict:
    return dict(num_heads=cfg.num_attention_heads,
                num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
                rope_theta=cfg.rope_theta, window=cfg.sliding_window_size,
                top_k=cfg.moe_num_active_primary_experts,
                first_expert=cfg.first_expert,
                experts_held=cfg.moe_num_primary_experts, rms_eps=cfg.rms_norm_eps,
                layer_order=cfg.layer_types, discount=cfg.discount_factor,
                baseline_loss_coef=cfg.baseline_loss_coef,
                entropy_coef=cfg.entropy_coef, reward_clipping=cfg.reward_clipping,
                gradient_clip_norm=cfg.gradient_clip_norm,
                learning_rate=cfg.start_learning_rate,
                end_learning_rate=cfg.end_learning_rate,
                learning_frame=cfg.learning_frame)


def seeded_batch(seed: int) -> dict:
    """Episode ends inside two rows, one past a window's length into the
    row and one before it: the second episode of row 0 (21 steps) wraps
    its rings again."""
    r = np.random.RandomState(seed)
    done = np.zeros((N, T), bool)
    done[:, -1] = True
    done[0, 10] = True
    done[2, 5] = True
    return {"tokens": r.randint(0, V, (N, T)).astype(np.int32),
            "action": r.randint(0, V, (N, T)).astype(np.int32),
            "behaviour_logp": (np.log(1.0 / V) + 0.3 * r.normal(size=(N, T))
                               ).astype(np.float32),
            "reward": r.choice([0.0, 0.0, 1.0, 2.0], size=(N, T)).astype(np.float32),
            "done": done}


def perturbed(params, seed=1):
    """Norm scales and the value bias off their initial 1 and 0."""
    key = jax.random.PRNGKey(seed)

    def move(path, x):
        if path[-1].key not in ("norms", "final_norm", "b_value"):
            return x
        return x + 0.1 * jax.random.normal(
            jax.random.fold_in(key, hash(str(path)) % 1000), x.shape, x.dtype)

    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def agent():
    return SwaLMAgent(CFG)


@pytest.fixture(scope="module")
def params(agent):
    return perturbed(agent.model.init(jax.random.PRNGKey(7)))


@pytest.fixture(scope="module")
def program_out(agent, params):
    nb, model = seeded_batch(3), agent.model
    with jax.default_matmul_precision("highest"):
        grads, metrics = jax.grad(agent._loss, has_aux=True)(params, LoopLMBatch(**nb))
        hs, facts = model.apply(params, nb["tokens"], nb["done"], method=model.trunk)
        logits, _, value = model.apply(params, hs, method=model.logits)
        updates, _ = agent.tx.update(grads, agent.tx.init(params), params)
    logp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               nb["action"][None, ..., None], -1)[..., 0]
    return {"logits": logits, "value": value, "logp": logp, "grads": grads,
            "grad_norm": common.global_norm(grads),
            "update_norm": common.global_norm(updates), "facts": facts, **metrics}


@pytest.fixture(scope="module")
def reference_out(params, program_out):
    return ref.evaluate(params, seeded_batch(3), hyper(CFG),
                        routes=np.asarray(program_out["routes"]))


def test_two_runs_of_two_kinds_in_the_published_order(agent):
    model = agent.model
    assert CFG.layer_types == ORDER == model.layer_types
    assert model.runs == (("global", 1), ("window", 3))
    assert (model.window, model.num_experts, model.experts_held, model.top_k) \
        == (W, 16, 4, 3)
    assert TOKEN_FAMILIES["swalm"] == (SwaLMConfig, SwaLMAgent)
    assert agent.cfg.total_ut_steps == 1 and T > 3 * W


def test_the_parameters_are_the_equations_leaves(agent, params):
    p = params["params"]
    assert set(p) == {"embed", "head", "final_norm", "w_value", "b_value",
                      "run0", "run1"}
    assert p["embed"].shape == p["head"].shape == (V, 32)  # untied: two leaves
    assert not np.array_equal(p["embed"], p["head"])
    for run, n in (("run0", 1), ("run1", 3)):
        shapes = {k: v.shape for k, v in p[run].items()}
        assert shapes == {"norms": (n, 2, 32), "wq": (n, 32, 32), "wkv": (n, 32, 32),
                          "wo": (n, 32, 32), "router": (n, 32, 16),
                          "expert_wgu": (n, 4, 32, 32), "expert_wd": (n, 4, 16, 32)}
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(params))
    # no per-head norm, no bias, no selection bias, no shared expert
    assert not [k for k in p["run1"] if "bias" in k or "shared" in k or "q_norm" in k]


@pytest.mark.parametrize("key, leaf, other", [
    ("embedding_initializer_range", "embed", "head"),
    ("initializer_range", "head", "embed")])
def test_each_range_is_read_by_its_own_key(tmp_path, key, leaf, other):
    """The embedding's range and the matrices' are two keys of the section:
    neither leaf ignores its own, neither reads the other's."""
    import json

    with open("config.json") as f:
        section = json.load(f)["smallthinker_moe_small"]
    assert (section["embedding_initializer_range"], section["initializer_range"]) \
        == (1.0, 0.3)
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"a": section, "b": {**section, key: 2 * section[key]}}))
    init = lambda name: SwaLMAgent(load_config(str(path), name)[0]).model.init(
        jax.random.PRNGKey(5))["params"]
    one, two = init("a"), init("b")
    np.testing.assert_allclose(two[leaf], 2 * one[leaf], rtol=1e-6)
    np.testing.assert_array_equal(two[other], one[other])
    assert abs(float(jnp.std(one["embed"])) - 1.0) < 0.1  # 64 x 32 draws


@pytest.mark.parametrize("what", ["logits", "value", "logp"])
def test_forward_matches_reference(program_out, reference_out, what):
    got, want = np.asarray(program_out[what]), np.asarray(reference_out[what])
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())


def test_the_program_chose_the_references_sets(program_out, reference_out):
    routing = reference_out["routing"]
    assert bool(np.all(routing["same_set"]))
    np.testing.assert_array_equal(np.sort(np.asarray(program_out["routes"]), -1),
                                  np.sort(np.asarray(routing["chosen"]), -1))
    picked = np.take_along_axis(np.asarray(routing["probs"]),
                                np.asarray(program_out["routes"], np.int64), -1)
    np.testing.assert_allclose(program_out["route_scores"], picked, atol=2e-5)


@pytest.mark.parametrize("term", ["total_loss", "pi_loss", "baseline_loss", "entropy",
                                  "held_pair_share", "relu_gate_zero_share",
                                  "grad_norm", "update_norm"])
def test_loss_terms_and_counters_match_reference(program_out, reference_out, term):
    got, want = float(program_out[term]), float(reference_out[term])
    assert abs(got - want) <= 2e-5 * max(1.0, abs(want)), (got, want)


def test_the_counters_of_the_share_and_the_window(program_out, reference_out):
    np.testing.assert_array_equal(program_out["router_load"],
                                  reference_out["router_load"])
    assert float(program_out["dropped_pairs"]) == 0
    assert 0.1 < float(program_out["held_pair_share"]) < 0.4  # a quarter, by chance
    assert 0.3 < float(program_out["relu_gate_zero_share"]) < 0.7
    # visible pairs of a window layer over a global layer's, by hand: episode
    # ends cut both (rows of 32; 11 + 21; 6 + 26)
    done = seeded_batch(3)["done"]
    seen = np.concatenate([np.arange(1, n + 1) for row in done
                           for n in np.diff(np.r_[-1, np.flatnonzero(row)])])
    want = np.minimum(seen, W).sum() / seen.sum()
    assert abs(float(program_out["window_pair_share"]) - want) < 1e-6
    assert 0.3 < want < 0.6


def test_gradients_match_reference(params, program_out, reference_out):
    theirs = ref.stacked(reference_out["grads"], ORDER)
    flat = jax.tree_util.tree_leaves_with_path(program_out["grads"])
    for (path, got), want in zip(flat, jax.tree.leaves(theirs)):
        scale = max(float(jnp.max(jnp.abs(want))), 1e-6)
        assert float(jnp.max(jnp.abs(got - want))) <= 1e-4 * scale, path
    assert len(flat) == len(jax.tree.leaves(params))


def test_rekey_and_stacked_are_inverses(params):
    theirs = ref.rekey(params, ORDER)
    assert len(theirs["layers"]) == 4 and ref.rekey(theirs) is theirs
    back = ref.stacked(theirs, ORDER)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="4 layers"):
        ref.rekey(params, ORDER[:3])


def test_the_four_shares_add_up_to_the_uncut_layer(agent):
    """THE SHARE: the routed parts that the four quarters (experts 0-3,
    4-7, 8-11, 12-15) compute for the same tokens and the same router add
    up to the reference's layer holding all 16 experts."""
    key = jax.random.split(jax.random.PRNGKey(11), 6)
    d, f, e, k = 32, 16, 16, 3
    x = jax.random.normal(key[0], (2, 12, d))
    h = jax.random.normal(key[1], (2, 12, d))
    lp = {"router": 0.5 * jax.random.normal(key[2], (d, e)),
          "expert_wgu": 0.3 * jax.random.normal(key[3], (e, d, 2 * f)),
          "expert_wd": 0.3 * jax.random.normal(key[4], (e, f, d))}
    whole = dict(top_k=k, first_expert=0, experts_held=e)
    with jax.default_matmul_precision("highest"):
        chosen, weight, _ = ref.router(h, lp, whole)
        uncut, facts = ref.moe(x, chosen, weight, lp, whole)
        parts, pairs = 0.0, 0
        for first in range(0, e, 4):
            probs, mine, w = expert_share.route(h.reshape(-1, d), lp["router"], k)
            np.testing.assert_array_equal(mine, chosen.reshape(-1, k))
            out, counters = expert_share.held_experts(
                x.reshape(-1, d), mine, w, lp["expert_wgu"][first:first + 4],
                lp["expert_wd"][first:first + 4], first, e, jnp.float32, "relu")
            parts, pairs = parts + out, pairs + int(counters["held_pairs"])
            quarter, _ = ref.moe(x, chosen, weight, {
                **lp, "expert_wgu": lp["expert_wgu"][first:first + 4],
                "expert_wd": lp["expert_wd"][first:first + 4]},
                dict(top_k=k, first_expert=first, experts_held=4))
            np.testing.assert_allclose(out.reshape(x.shape), quarter, atol=1e-5)
    assert pairs == 2 * 12 * k == int(facts["held_pairs"])  # every pair once
    np.testing.assert_allclose(parts.reshape(x.shape), uncut, atol=2e-5)


def test_the_router_reads_the_layers_input_and_the_experts_the_normed_state(
        agent, params):
    """A layer by hand from the equations: r = h W_r on the layer's INPUT
    (not on N(h), not on the state after attention), ReGLU on N(u)."""
    model, p = agent.model, params["params"]
    nb = seeded_batch(5)
    lp = {k: v[0] for k, v in p["run1"].items()}
    h = jax.random.normal(jax.random.PRNGKey(2), (N, T, 32))
    seg = ref.episode_positions(jnp.asarray(nb["done"]))
    with jax.default_matmul_precision("highest"):
        got, (routes, _), _ = model._layer("window", h, seg[0], seg[1], lp)
        want, facts = ref.layer("window", h, lp, seg[0], seg[1], hyper(CFG))
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(np.sort(routes.reshape(N, T, 3), -1),
                                  np.sort(facts["chosen"], -1))
    probs = jax.nn.softmax(h @ lp["router"], -1)
    np.testing.assert_allclose(facts["probs"], probs, atol=1e-6)
    normed = jax.nn.softmax(ref.norm(h, lp["norms"][0], 1e-6) @ lp["router"], -1)
    assert float(jnp.max(jnp.abs(normed - probs))) > 1e-2  # the fault would show


def test_an_episode_end_cuts_the_window_the_attention_and_the_positions(agent, params):
    """Tokens before an episode's end change nothing after it, in either
    kind of layer; tokens further back than the window change nothing in a
    stack of window layers alone (and do through the global layer)."""
    model = agent.model
    nb = seeded_batch(4)
    other = nb["tokens"].copy()
    other[0, :11] = (other[0, :11] + 1) % V  # the first episode of row 0
    with jax.default_matmul_precision("highest"):
        a = model.apply(params, nb["tokens"], nb["done"], method=model.trunk)[0]
        b = model.apply(params, other, nb["done"], method=model.trunk)[0]
    np.testing.assert_array_equal(a[0, 0, 11:], b[0, 0, 11:])
    assert float(jnp.max(jnp.abs(a[0, 0, :11] - b[0, 0, :11]))) > 1e-3
    windows = SwaLMAgent(dataclasses.replace(CFG, sliding_window_layout=(1,)))
    p1 = windows.model.init(jax.random.PRNGKey(3))
    far = nb["tokens"].copy()
    far[1, 0] = (far[1, 0] + 1) % V  # row 1 is one episode of 32
    with jax.default_matmul_precision("highest"):
        a = windows.model.apply(p1, nb["tokens"], nb["done"], method=windows.model.trunk)[0]
        b = windows.model.apply(p1, far, nb["done"], method=windows.model.trunk)[0]
    np.testing.assert_array_equal(a[0, 1, W:], b[0, 1, W:])  # one layer: W - 1 back
    assert float(jnp.max(jnp.abs(a[0, 1, :W] - b[0, 1, :W]))) > 1e-3


# -- acting as decode ----------------------------------------------------------


@pytest.fixture(scope="module")
def whole_episode(agent, params):
    """One episode of T steps a row, and the learner's forward of it."""
    model = agent.model
    tokens = jax.random.randint(jax.random.PRNGKey(5), (N, T), 0, V)
    done = jnp.zeros((N, T), bool).at[:, -1].set(True)
    with jax.default_matmul_precision("highest"):
        hs, facts = model.apply(params, tokens, done, method=model.trunk)
        logits, _, value = model.apply(params, hs, method=model.logits)
    return tokens, logits[0], value[0], facts


def _decode(agent, params, tokens, spans):
    model = agent.model
    state = agent.init_cache(tokens.shape[0])
    step = jax.jit(lambda p, tok, t, s, span: model.apply(
        p, tok, t, s, span, method=model.decode), static_argnums=(4,))
    out = []
    with jax.default_matmul_precision("highest"):
        for lo, hi in zip((0, *spans), spans):
            for t in range(lo, hi):
                h, state = step(params, tokens[:, t], jnp.int32(t), state, hi)
                out.append(model.apply(params, h, method=model.logits))
    return (jnp.stack([o[0] for o in out], 1), jnp.stack([o[2] for o in out], 1),
            state)


@pytest.mark.parametrize("spans", [(T,), (W // 2, W, 2 * W, T), (12, 20, T)])
def test_decode_through_the_rings_and_the_cache_equals_the_full_forward(
        agent, params, whole_episode, spans):
    """Every step of an episode four windows long, whatever the scans'
    spans (one; spans inside, at and past the window; spans that are no
    multiple of it): the ring wraps three times, its slots are read in
    another order than time's, and the logits are the learner's."""
    tokens, logits, value, facts = whole_episode
    got_logits, got_value, state = _decode(agent, params, tokens, spans)
    np.testing.assert_allclose(got_logits, logits, atol=3e-4)
    np.testing.assert_allclose(got_value, value, atol=3e-4)
    np.testing.assert_array_equal(state.routes, jnp.moveaxis(facts["routes"], 0, 2))


def test_decode_with_the_acting_copy_is_decode_with_the_parameters(
        agent, params, whole_episode):
    tokens, logits, _, _ = whole_episode
    acting = agent.for_acting(params)["params"]
    assert "layers" in acting and len(acting["layers"]) == 4
    assert acting["head"].dtype == CFG.dtype and acting["embed"].dtype == jnp.float32
    assert all(lp["router"].dtype == jnp.float32 for lp in acting["layers"])
    got, _, _ = _decode(agent, {"params": acting}, tokens, (T,))
    np.testing.assert_allclose(got, logits, atol=3e-4)
    wide = SwaLMAgent(dataclasses.replace(CFG, dtype=jnp.bfloat16))
    copy = wide.for_acting(params)["params"]
    assert copy["head"].dtype == jnp.bfloat16
    assert {lp[k].dtype for lp in copy["layers"]
            for k in window_moe_lm.RUN_MATRICES} == {jnp.dtype(jnp.bfloat16)}
    assert all(lp["router"].dtype == jnp.float32 for lp in copy["layers"])


def test_the_state_is_one_full_cache_three_rings_and_a_record(agent, whole_episode,
                                                              params):
    state = agent.init_cache(N)
    assert [k.shape for k in state.k] == [(N, T, 2, 8)] + 3 * [(N, W, 2, 8)]
    assert [v.shape for v in state.v] == [k.shape for k in state.k]
    assert state.routes.shape == (N, T, 4, 3) and state.routes.dtype == jnp.int16
    facts = agent.state_facts(N)
    assert facts["kv_cache_bytes"] == 2 * N * T * 2 * 8 * 4
    assert facts["ring_bytes"] == 3 * 2 * N * W * 2 * 8 * 4
    assert facts["ring_positions"] == W and facts["layer_order"] == ORDER
    assert (facts["experts_held"], facts["router_width"], facts["first_expert"]) \
        == (4, 16, 4)
    tokens = whole_episode[0]
    _, _, state = _decode(agent, params, tokens, (T,))
    counters = agent.state_counters(state)
    held = np.isin(np.asarray(state.routes), np.arange(4, 8))
    by_hand = np.mean([len(set(np.asarray(state.routes)[:, t, layer][held[:, t, layer]]))
                       for t in range(T) for layer in range(4)])
    assert abs(float(counters["held_experts_touched_mean"]) - by_hand) < 1e-6
    assert float(counters["ring_read_share"]) == 1.0  # one scan of 32 >= the ring
    np.testing.assert_array_equal(counters["act_routes"], state.routes)
    # the share at the cell's sizes, a constant of the shapes: 1/4, 1/2, 3/4, 1 x 5
    full = SwaLMAgent(load_config("config.json", "smallthinker_moe")[0])
    spans = looped_lm.decode_spans(8192)
    assert spans == tuple(range(1024, 8193, 1024))
    zeros = window_moe_lm.WindowState((), (), jnp.zeros((1, 1, 4, 6), jnp.int16))
    assert float(full.state_counters(zeros)["ring_read_share"]) == 0.8125


@pytest.mark.parametrize("fault", ["no_rotary_at_act_time",
                                   "rotary_on_the_global_layer",
                                   "a_window_one_short"])
def test_a_wrong_decode_step_is_seen(agent, params, whole_episode, fault, monkeypatch):
    tokens, logits, _, _ = whole_episode
    model = agent.model
    if fault == "no_rotary_at_act_time":  # the ring's keys as they come
        monkeypatch.setattr(window_moe_lm, "rope", lambda x, pos, theta: x)
    elif fault == "rotary_on_the_global_layer":
        qkv = type(model)._qkv
        monkeypatch.setattr(type(model), "_qkv",
                            lambda self, kind, y, lp, pos: qkv(self, "window", y, lp, pos))
    else:  # rings of W - 1 slots: the oldest visible key is overwritten
        agent = SwaLMAgent(dataclasses.replace(CFG, sliding_window_size=W - 1))
    got, _, _ = _decode(agent, params, tokens, (T,))
    assert float(jnp.max(jnp.abs(got - logits))) > 1e-2


def test_a_span_past_the_cache_is_refused(agent, params):
    model = agent.model
    state = agent.init_cache(N)
    with pytest.raises(ValueError, match="span"):
        model.apply(params, jnp.zeros((N,), jnp.int32), jnp.int32(0), state, T + 1,
                    method=model.decode)


def test_at_the_cells_sizes_acting_is_touched_and_learning_in_slabs():
    """8 rows x 6 of 64: 48 pairs for 64 experts, under one pair a held
    expert a call, and experts of D 2,560 x F 768 (1.97 M weights a block,
    11.8 MB an expert: large enough for a trip), so the decode step takes
    the TOUCHED one-slab form (ISSUE 54; the sorted one until then): the
    held experts some row chose, each a plain product over the 8 rows, and
    no other expert's weights read; the learner's 8,192-token row block
    works its 49,152 pairs in slabs of 15,360."""
    widths = (2560, 768)
    assert expert_share.one_slab_form(8, 6, 64, widths) == "touched"
    assert expert_share.call_form(8, 6, 16, 64, widths) == "touched, 8 rows x up to 16 held"
    assert expert_share.call_form(8192, 6, 16, 64, widths) \
        == "sorted, 49152 pairs in slabs of 15360"
    full = SwaLMAgent(load_config("config.json", "smallthinker_moe")[0])
    assert full.model.pair_slab_rows(8, 8192) == 15360
    # expected held experts some row chose, a layer a step: 16 (1 - (58/64)^8)
    assert abs(16 * (1 - (58 / 64) ** 8) - 8.72) < 0.01


# -- the section -----------------------------------------------------------------


def test_load_config_reads_the_section_through_the_table():
    cfg, rt = load_config("config.json", "smallthinker_moe")
    assert isinstance(cfg, SwaLMConfig) and list(rt.envs) == ["TokenRecall-v0"]
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim) == (2560, 28, 4, 128)
    assert cfg.num_attention_heads * cfg.head_dim == 3584 != cfg.hidden_size
    assert (cfg.sliding_window_size, cfg.rope_theta, cfg.rms_norm_eps) \
        == (4096, 1.5e6, 1e-6)
    assert (cfg.router_width, cfg.moe_num_primary_experts, cfg.first_expert,
            cfg.moe_num_active_primary_experts, cfg.moe_ffn_hidden_size) \
        == (64, 16, 0, 6, 768)
    assert cfg.layer_types == ORDER and cfg.trajectory == 8192 == 2 * 4096
    assert cfg.vocab_size == 37_984 == 151_936 // 4 and cfg.dtype == jnp.bfloat16
    assert rt.num_actors * rt.envs_per_actor == 8


def test_the_small_section_is_the_full_ones_shape():
    import json

    with open("config.json") as f:
        sections = json.load(f)
    full, small = sections["smallthinker_moe"], sections["smallthinker_moe_small"]
    assert set(full) == set(small)
    same = ("algorithm", "sliding_window_layout", "rope_layout", "rope_scaling",
            "moe_primary_router_apply_softmax", "norm_topk_prob",
            "tie_word_embeddings", "rms_norm_eps", "discount_factor", "entropy_coef")
    assert all(full[k] == small[k] for k in same)
    assert small["trajectory"] == 4 * small["sliding_window_size"]
    assert small["router_width"] == 4 * small["moe_num_primary_experts"]


@pytest.mark.parametrize("changes, message", [
    ({"rope_layout": [1, 1, 1, 1]}, "rope_layout"),
    ({"sliding_window_layout": [0, 2, 1, 1], "rope_layout": [0, 2, 1, 1]}, "0 .global. or 1"),
    ({"num_hidden_layers": 5}, "4 entries"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"moe_primary_router_apply_softmax": False}, "moe_primary_router_apply_softmax"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"rope_scaling": {"factor": 2}}, "rope_scaling"),
])
def test_load_config_refuses_what_is_not_computed(tmp_path, changes, message):
    import json

    with open("config.json") as f:
        section = dict(json.load(f)["smallthinker_moe_small"], **changes)
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"s": section}))
    with pytest.raises(ValueError, match=message):
        load_config(str(path), "s")


@pytest.mark.parametrize("key", ["sliding_window_size", "head_dim", "rope_layout",
                                 "router_width", "moe_ffn_hidden_size", "rms_norm_eps"])
def test_load_config_refuses_a_missing_width(tmp_path, key):
    import json

    with open("config.json") as f:
        section = dict(json.load(f)["smallthinker_moe_small"])
    del section[key]
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"s": section}))
    with pytest.raises(KeyError, match=key):
        load_config(str(path), "s")


def test_a_share_past_the_routers_width_is_refused():
    with pytest.raises(ValueError, match="of a router 16 wide"):
        SwaLMAgent(dataclasses.replace(CFG, first_expert=13))
    with pytest.raises(ValueError, match="key/value heads"):
        SwaLMAgent(dataclasses.replace(CFG, num_key_value_heads=3))


# -- the fused chunk ---------------------------------------------------------------


@pytest.fixture(scope="module")
def chunk():
    agent = SwaLMAgent(CFG)
    anakin = AnakinTokens(agent, N, TokenRecall(V, T, CFG.recall_distance))
    anakin.decode_spans = (W, 2 * W, T)  # scans inside, at and past the window
    state = anakin.init(jax.random.PRNGKey(0))
    before = jax.device_get(state.train.params)
    with jax.default_matmul_precision("highest"):
        state, metrics = anakin.train_chunk(state, 2)
    return agent, before, jax.device_get(state.train.params), jax.device_get(metrics)


def test_fused_chunk_losses_are_finite_and_every_leaf_moves(chunk):
    _, before, after, m = chunk
    assert np.all(np.isfinite(m["total_loss"])) and np.all(m["grad_norm"] > 0)
    assert all(np.any(a != b) for a, b in zip(jax.tree.leaves(before),
                                              jax.tree.leaves(after)))
    assert np.all(m["dropped_pairs"] == 0)
    # a constant of the shapes' rule (one scan under 32 steps), not of the spans a test sets
    assert np.all(m["ring_read_share"] == 1.0)
    assert m["act_routes"].shape == (2, N, T, 4, 3)
    for k in ("held_experts_touched_mean", "relu_gate_zero_share",
              "window_pair_share", "held_pair_share", "pair_slabs_mean"):
        assert m[k].shape == (2,) and np.all(m[k] > 0), k
    want = sum(min(t + 1, W) for t in range(T)) / (T * (T + 1) / 2)
    np.testing.assert_allclose(m["window_pair_share"], want, rtol=1e-6)


def test_collect_logp_is_the_reference_forward_on_the_decode_steps_sets(chunk):
    agent, before, _, m = chunk
    rollout = {k: v[0] for k, v in m["rollout"].items()}
    routes = np.moveaxis(m["act_routes"][0], 2, 0)
    want = ref.taken_logp(before, rollout["tokens"], rollout["action"],
                          rollout["done"], hyper(CFG), routes=routes)
    np.testing.assert_allclose(rollout["behaviour_logp"], want, atol=2e-4)


def test_the_chunks_first_update_is_the_references_step(chunk):
    agent, before, _, m = chunk
    rollout = {k: v[0] for k, v in m["rollout"].items()}
    hp = hyper(CFG)
    out = ref.evaluate(before, rollout, hp, routes=np.asarray(m["routes"][0]))
    for term in ("total_loss", "pi_loss", "baseline_loss", "entropy", "grad_norm",
                 "held_pair_share", "relu_gate_zero_share"):
        assert abs(float(m[term][0]) - float(out[term])) \
            <= 5e-5 * max(1.0, abs(float(out[term]))), term
    np.testing.assert_array_equal(m["router_load"][0], out["router_load"])

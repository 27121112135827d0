"""The `lfm2_moe` configuration at a small size on the CPU: the
gated-short-convolution sparse-expert model (`models/conv_moe_lm.py`),
its act-time windows and cache, the sigmoid-scored bias-corrected router
over a QUARTER of a layer's experts with no shared expert
(`ops/expert_share.py`), the bias's update (`agents/convlm.py`) and the
fused loop (`runtime/anakin_tokens.py`) against the plain reference
(`reference/lfm2_moe.py`), which imports nothing of the program.

Sizes (section `lfm2_moe_small` of `config.json`): hidden 32, the
published order's first period behind one dense layer (conv + dense 48
wide; attention, 4 query and 2 key/value heads of 8, + experts; three
conv + experts), a router 16 wide with 3 experts a token of which experts
4..7 are held here (a quarter), experts 16 wide; V 64, T 32, N 4; float32
so that the agreement is the arithmetic's. The reference is given the
sets the PROGRAM chose (`routes`): at float32 they are its own.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.agents.convlm import (
    ConvLMAgent, ConvLMConfig)
from distributed_reinforcement_learning_tpu.agents.looplm import LoopLMBatch
from distributed_reinforcement_learning_tpu.agents.token_families import (
    TOKEN_FAMILIES)
from distributed_reinforcement_learning_tpu.envs.token_recall_jax import TokenRecall
from distributed_reinforcement_learning_tpu.models import conv_moe_lm, looped_lm
from distributed_reinforcement_learning_tpu.models.hybrid_lm import layer_runs
from distributed_reinforcement_learning_tpu.ops import expert_share
from distributed_reinforcement_learning_tpu.reference import lfm2_moe as ref
from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import AnakinTokens
from distributed_reinforcement_learning_tpu.utils.config import load_config

V, T, N = 64, 32, 4
CFG = dataclasses.replace(
    load_config("config.json", "lfm2_moe_small")[0],
    attention_backend="reference", row_block=2, head_block=32)
ORDER = ("conv+dense", "full_attention+moe", "conv+moe", "conv+moe", "conv+moe")


def hyper(cfg: ConvLMConfig) -> dict:
    return dict(num_heads=cfg.num_attention_heads,
                num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
                rope_theta=cfg.rope_theta, top_k=cfg.num_experts_per_tok,
                first_expert=cfg.first_expert, experts_held=cfg.num_experts,
                route_scale=cfg.routed_scaling_factor, rms_eps=cfg.norm_eps,
                layer_order=ORDER, discount=cfg.discount_factor,
                baseline_loss_coef=cfg.baseline_loss_coef,
                entropy_coef=cfg.entropy_coef, reward_clipping=cfg.reward_clipping,
                gradient_clip_norm=cfg.gradient_clip_norm,
                learning_rate=cfg.start_learning_rate,
                end_learning_rate=cfg.end_learning_rate,
                learning_frame=cfg.learning_frame,
                bias_update_speed=cfg.bias_update_speed)


def seeded_batch(seed: int, mid_episode_end: bool = True) -> dict:
    r = np.random.RandomState(seed)
    done = np.zeros((N, T), bool)
    done[:, -1] = True
    if mid_episode_end:
        done[0, 11] = True
        done[2, 7] = True
    return {"tokens": r.randint(0, V, (N, T)).astype(np.int32),
            "action": r.randint(0, V, (N, T)).astype(np.int32),
            "behaviour_logp": (np.log(1.0 / V) + 0.3 * r.normal(size=(N, T))
                               ).astype(np.float32),
            "reward": r.choice([0.0, 0.0, 1.0, 2.0], size=(N, T)).astype(np.float32),
            "done": done}


def perturbed(params, seed=1):
    """Norm scales, the value bias and the selection bias off their
    initial 1 and 0 (the bias by a tenth: enough to change sets)."""
    key = jax.random.PRNGKey(seed)
    moved = {"norms": 0.2, "final_norm": 0.2, "b_value": 0.2, "q_norm": 0.2,
             "k_norm": 0.2, "router_bias": 0.1}
    count = [0]

    def move(path, x):
        if path[-1].key not in moved:
            return x
        count[0] += 1
        return x + moved[path[-1].key] * jax.random.normal(
            jax.random.fold_in(key, count[0]), x.shape, x.dtype)

    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def agent():
    return ConvLMAgent(CFG)


@pytest.fixture(scope="module")
def params(agent):
    return perturbed(agent.init_state(jax.random.PRNGKey(0)).params)


def _batch(nb) -> LoopLMBatch:
    return LoopLMBatch(**{k: jnp.asarray(v) for k, v in nb.items()})


def _program(agent, params, nb):
    model = agent.model
    batch = _batch(nb)
    hs, _ = model.apply(params, batch.tokens, batch.done, method=model.trunk)
    logits, _, value = model.apply(params, hs, method=model.logits)
    grads, metrics = jax.grad(agent._loss, has_aux=True)(params, batch)
    updates, _ = agent.tx.update(grads, agent.tx.init(params), params)
    return {"logits": logits, "value": value,
            "logp": jnp.take_along_axis(jax.nn.log_softmax(logits), jnp.asarray(
                nb["action"])[None, ..., None], -1)[..., 0],
            "stats_logp": agent._stats(params, batch)["logp"], "grads": grads,
            "grad_norm": common.global_norm(grads),
            "update_norm": common.global_norm(updates), **metrics}


@pytest.fixture(scope="module")
def program_out(agent, params):
    return _program(agent, params, seeded_batch(0))


@pytest.fixture(scope="module")
def reference_out(params, program_out):
    return ref.evaluate(params, seeded_batch(0), hyper(CFG),
                        routes=np.asarray(program_out["routes"]))


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(1e-30, np.max(np.abs(want))))


# -- the stack's runs are keyed by (mixer, MLP) --------------------------------


def test_three_runs_of_two_mixers_and_two_mlps_that_do_not_line_up(agent):
    model = agent.model
    assert model.kinds == (("conv", "dense"), ("full_attention", "moe"),
                           ("conv", "moe"), ("conv", "moe"), ("conv", "moe"))
    assert model.runs == ((("conv", "dense"), 1), (("full_attention", "moe"), 1),
                          (("conv", "moe"), 3))
    assert model.bias_holders == (("run1",), ("run2",)) and model.expert_layers == 4
    # the published order's head: two dense layers, the second before an attention
    head = dataclasses.replace(model, layer_types=("conv", "conv", "full_attention",
                                                   "conv"), num_dense_layers=2)
    assert [n for _, n in head.runs] == [2, 1, 1]
    with pytest.raises(ValueError, match="unknown layer type"):
        layer_runs((("mamba", "dense"),), conv_moe_lm.LAYER_KINDS)


def test_the_parameters_are_the_equations_leaves(agent, params):
    p = params["params"]
    assert set(p) == {"embed", "final_norm", "w_value", "b_value", "run0", "run1", "run2"}
    assert set(p["run0"]) == {"norms", "in_proj", "conv_w", "out_proj", "wgu", "wd"}
    assert set(p["run1"]) == {"norms", "wq", "wkv", "q_norm", "k_norm", "wo", "router",
                              "router_bias", "expert_wgu", "expert_wd"}
    assert set(p["run2"]) == {"norms", "in_proj", "conv_w", "out_proj", "router",
                              "router_bias", "expert_wgu", "expert_wd"}
    assert p["run2"]["in_proj"].shape == (3, 32, 96)  # B | C | X
    assert p["run2"]["conv_w"].shape == (3, 32, 3)  # no bias beside it
    assert p["run1"]["wkv"].shape == (1, 32, 2 * 2 * 8)
    assert p["run2"]["expert_wgu"].shape == (3, 4, 32, 32)  # held: a quarter of 16
    assert p["run2"]["router"].shape == (3, 32, 16)  # the router keeps its width


# -- the convolution mixer ------------------------------------------------------


def _conv_pieces(seed, b=2, t=12, d=8):
    r = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(r.normal(size=shape) * 0.5, jnp.float32)
    return {"y": f(b, t, d), "in_proj": f(d, 3 * d), "conv_w": f(d, 3),
            "out_proj": f(d, d)}


def _conv_by_hand(x, pos):
    """The equations one step at a time, in numpy."""
    y, w_in, w, w_out = (np.asarray(x[k], np.float64)
                         for k in ("y", "in_proj", "conv_w", "out_proj"))
    d = y.shape[-1]
    bcx = y @ w_in
    u = bcx[..., :d] * bcx[..., 2 * d:]
    out = np.zeros_like(u)
    for row in range(y.shape[0]):
        for t in range(y.shape[1]):
            for j in range(3):
                back = 2 - j
                if pos[row, t] >= back:
                    out[row, t] += w[:, j] * u[row, t - back]
    return (bcx[..., d:2 * d] * out) @ w_out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_reference_convolution_is_the_equations(seed):
    x = _conv_pieces(seed)
    pos = np.tile(np.arange(12), (2, 1))
    pos[1, 5:] -= 5  # row 1 starts a new episode at step 5
    with jax.default_matmul_precision("highest"):
        got, gate_abs = ref.short_conv(x["y"], x, jnp.asarray(pos))
    assert _rel(got, _conv_by_hand(x, pos)) < 1e-5
    bcx = np.asarray(x["y"] @ x["in_proj"])
    assert _rel(gate_abs, np.abs(bcx[..., :8]).mean() + np.abs(bcx[..., 8:16]).mean()) < 1e-5


def test_the_taps_at_an_episodes_first_two_steps_read_zero(agent, params):
    """Row 0 ends an episode at step 11: step 12 reads one tap (its own),
    step 13 two, step 14 all three, so the mixer's output at 12 and 13
    does not move when what came before 12 changes, in the program and in
    the reference alike."""
    model = agent.model
    lp = {k: v[0] for k, v in params["params"]["run0"].items()}
    r = np.random.RandomState(4)
    y = jnp.asarray(r.normal(size=(1, T, 32)), jnp.float32)
    other = y.at[0, :12].add(1.0)
    done = np.zeros((1, T), bool)
    done[0, 11] = True
    pos = looped_lm.episode_positions(jnp.asarray(done))
    assert list(np.asarray(pos[0, 10:15])) == [10, 11, 0, 1, 2]
    run = lambda v: model._conv(v, lp, pos)[0]
    a, b = run(y), run(other)
    assert _rel(a[0, 12:], b[0, 12:]) < 1e-6 < _rel(a[0, :12], b[0, :12])
    # without the boundary the same steps do read what came before
    free = jnp.arange(T)[None]
    assert _rel(model._conv(y, lp, free)[0][0, 12:14],
                model._conv(other, lp, free)[0][0, 12:14]) > 1e-3
    with jax.default_matmul_precision("highest"):
        want, _ = ref.short_conv(y, lp, pos)
    assert _rel(a, want) < 1e-5


def test_there_is_no_activation_in_the_mixer(agent, params):
    """Mix(-y) = -Mix(y): three products of linear maps of y (B * X is
    even, C odd); a SiLU anywhere in it would break the symmetry."""
    lp = {k: v[0] for k, v in params["params"]["run0"].items()}
    y = jnp.asarray(np.random.RandomState(5).normal(size=(1, T, 32)), jnp.float32)
    pos = jnp.arange(T)[None]
    assert _rel(agent.model._conv(-y, lp, pos)[0], -agent.model._conv(y, lp, pos)[0]) < 1e-6


# -- an expert layer at a QUARTER of its experts, with no shared expert ----------


def _expert_layer(seed, tokens=24, d=32, width=16, experts=16):
    r = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(r.normal(size=shape) * 0.3, jnp.float32)
    return {"x": f(1, tokens, d) * 3.0, "router": f(d, experts),
            "router_bias": 0.05 * f(experts),
            "expert_wgu": f(experts, d, 2 * width), "expert_wd": f(experts, width, d)}


def test_the_routing_weights_take_the_familys_constant():
    layer = _expert_layer(0)
    route = lambda eps: expert_share.route(layer["x"][0], layer["router"], 3, "sigmoid",
                                           layer["router_bias"], 1.0, eps)
    scores, chosen, weight, _ = route(conv_moe_lm.WEIGHT_EPS)
    picked = np.take_along_axis(np.asarray(scores, np.float64), np.asarray(chosen), -1)
    assert _rel(weight, picked / (picked.sum(-1, keepdims=True) + 1e-6)) < 1e-6
    # the constant is the denominator's: a large one shows
    assert _rel(route(1.0)[2], picked / (picked.sum(-1, keepdims=True) + 1.0)) < 1e-6
    # and the default is the other sigmoid model's, as it was
    assert np.array_equal(route(1e-20)[2], expert_share.route(
        layer["x"][0], layer["router"], 3, "sigmoid", layer["router_bias"])[2])


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The share test of the `model-configs` guide, section 4, at this
    model's quarter (`first_expert` 0, 4, 8, 12 of 16): the parts that the
    four shares give add up to what the uncut reference gives for the
    whole layer. Nothing is counted once: there is no shared expert, so
    the layer's result IS the sum of its shares."""
    layer = _expert_layer(3)
    hp = dict(top_k=3, first_expert=0, experts_held=16, route_scale=1.0)
    with jax.default_matmul_precision("highest"):
        _, chosen, weight, _ = expert_share.route(
            layer["x"][0], layer["router"], 3, "sigmoid", layer["router_bias"], 1.0,
            conv_moe_lm.WEIGHT_EPS)
        parts = [expert_share.held_experts(
            layer["x"][0], chosen, weight, layer["expert_wgu"][first:first + 4],
            layer["expert_wd"][first:first + 4], first, 16, jnp.float32)
            for first in (0, 4, 8, 12)]
        whole, _ = ref.moe(layer["x"], layer, hp)
        assert _rel(sum(p[0] for p in parts), whole[0]) < 1e-5
        assert sum(int(p[1]["held_pairs"]) for p in parts) == 24 * 3
        assert all(int(p[1]["dropped_pairs"]) == 0 for p in parts)
        for first, part in zip((0, 4, 8, 12), parts):  # and each is its own share
            one, _ = ref.moe(layer["x"], {**layer,
                                          "expert_wgu": layer["expert_wgu"][first:first + 4],
                                          "expert_wd": layer["expert_wd"][first:first + 4]},
                             {**hp, "first_expert": first, "experts_held": 4})
            assert _rel(part[0], one[0]) < 1e-5
    assert float(jnp.max(jnp.abs(whole))) > 0


def test_the_models_four_shares_add_up_to_the_whole_models_layer(agent, params):
    """The same through `ConvMoELM._ffn`: four models that differ in
    `first_expert` alone, on one layer's parameters cut four ways."""
    lp = {k: v[0] for k, v in params["params"]["run2"].items()}
    r = np.random.RandomState(8)
    full = {**lp, "expert_wgu": jnp.asarray(r.normal(size=(16, 32, 32)) * 0.3, jnp.float32),
            "expert_wd": jnp.asarray(r.normal(size=(16, 16, 32)) * 0.3, jnp.float32)}
    u = jnp.asarray(r.normal(size=(N * T, 32)), jnp.float32)
    branch = lambda out: np.asarray(out[0] - u, np.float64)
    from distributed_reinforcement_learning_tpu.observability import scopes

    parts = []
    for first in (0, 4, 8, 12):
        model = dataclasses.replace(agent.model, first_expert=first)
        parts.append(branch(model._ffn(
            "moe", u, {**full, "expert_wgu": full["expert_wgu"][first:first + 4],
                       "expert_wd": full["expert_wd"][first:first + 4]},
            scopes.CONV_LEARN)))
    whole = dataclasses.replace(agent.model, first_expert=0, experts_held=16)
    assert _rel(sum(parts), branch(whole._ffn("moe", u, full, scopes.CONV_LEARN))) < 1e-5
    x = ref.norm(u, lp["norms"][1], CFG.norm_eps)[None]
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(x, full, {**hyper(CFG), "first_expert": 0, "experts_held": 16})
    assert _rel(sum(parts), want[0]) < 1e-5


# -- the model against the reference -----------------------------------------


@pytest.mark.parametrize("what", ["logits", "value", "logp"])
def test_forward_matches_reference(program_out, reference_out, what):
    assert _rel(program_out[what], reference_out[what]) < 2e-4
    assert _rel(program_out["stats_logp"], reference_out["logp"]) < 2e-4


def test_the_program_chose_the_references_sets_under_a_bias(agent, params,
                                                            reference_out):
    assert np.all(reference_out["routing"]["same_set"])
    assert float(np.max(np.abs(np.concatenate(
        [np.ravel(b) for b in agent.router_biases(params)])))) > 0.05


@pytest.mark.parametrize("term", ["total_loss", "pi_loss", "baseline_loss", "entropy",
                                  "held_pair_share", "router_score_mean",
                                  "conv_gate_abs_mean"])
def test_loss_terms_and_counters_match_reference(program_out, reference_out, term):
    assert _rel(program_out[term], reference_out[term]) < 2e-4


def test_the_counters_of_the_share(program_out, reference_out):
    assert float(program_out["dropped_pairs"]) == 0
    assert 0 < float(program_out["held_pair_share"]) < 1
    assert np.array_equal(program_out["router_load"], reference_out["router_load"])
    assert program_out["router_load"].shape == (4, 16)  # the four expert layers
    assert np.all(program_out["router_load"].sum(-1) == N * T * 3)
    assert float(program_out["router_load_max_over_mean"]) >= 1
    assert program_out["routes"].shape == (4, N, T, 3)
    assert float(program_out["pair_slabs_max"]) >= 1


def test_gradients_match_reference(params, program_out, reference_out):
    theirs = ref.stacked(reference_out["grads"])
    flat = jax.tree_util.tree_leaves_with_path(program_out["grads"])
    for (path, g), w in zip(flat, jax.tree.leaves(theirs)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.any(g) and not np.any(w), name
        else:
            assert _rel(g, w) < 2e-3 and np.any(g), name
    assert _rel(program_out["grad_norm"], reference_out["grad_norm"]) < 2e-4
    assert _rel(program_out["update_norm"], reference_out["update_norm"]) < 2e-4


def test_an_episode_end_cuts_the_windows_the_attention_and_the_positions(agent, params):
    """Row 0 ends an episode at step 11: what comes before it reaches
    nothing after it (logits of steps 12.. do not move when tokens 0..11
    change), through the taps and through the attention, and positions
    restart."""
    nb = seeded_batch(0)
    other = {**nb, "tokens": nb["tokens"].copy()}
    other["tokens"][0, :12] = (other["tokens"][0, :12] + 7) % V
    model = agent.model
    run = lambda b: model.apply(params, model.apply(
        params, jnp.asarray(b["tokens"]), jnp.asarray(b["done"]),
        method=model.trunk)[0], method=model.logits)[0][0]
    a, b = run(nb), run(other)
    assert _rel(a[0, 12:], b[0, 12:]) < 1e-6 < _rel(a[0, :12], b[0, :12])
    # the same tokens one step later in the episode give other logits: rotary
    shifted = {**nb, "done": nb["done"].copy()}
    shifted["done"][0, 11], shifted["done"][0, 12] = False, True
    assert _rel(run(shifted)[0, 16:], a[0, 16:]) > 1e-3


def test_rekey_and_stacked_are_inverses(params):
    back = ref.stacked(ref.rekey(params, ORDER))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params),
                            jax.tree.leaves(back)):
        assert np.array_equal(a, b), jax.tree_util.keystr(path)
    with pytest.raises(ValueError, match="configuration"):
        ref.rekey(params, ORDER[::-1])


# -- the bias's update ----------------------------------------------------------


def test_learn_moves_the_bias_by_gamma_from_the_counts_and_not_by_the_optimizer(
        agent, params):
    state = common.TrainState.create(params, agent.tx)
    new, metrics = jax.jit(agent._learn)(state, _batch(seeded_batch(0)))
    load = np.asarray(metrics["router_load"], np.float64)
    want = CFG.bias_update_speed * np.sign(load.mean(-1, keepdims=True) - load)
    before = np.concatenate([np.asarray(b) for b in agent.router_biases(params)])
    after = np.concatenate([np.asarray(b) for b in agent.router_biases(new.params)])
    assert np.array_equal(after, (before + want).astype(np.float32))
    assert np.any(want > 0) and np.any(want < 0)
    assert float(metrics["bias_abs_max"]) == float(np.max(np.abs(after)))
    # the reference's step makes the same biases
    terms, _ = ref.loss_and_grads(params, seeded_batch(0), hyper(CFG),
                                  routes=np.asarray(metrics["routes"]))
    theirs = ref.biases(ref.bias_step(ref.rekey(params), terms["router_load"],
                                      hyper(CFG)))
    assert np.array_equal(np.stack(theirs), after)
    # every other leaf is the optimizer's
    grads, _ = jax.grad(agent._loss, has_aux=True)(params, _batch(seeded_batch(0)))
    updates, _ = agent.tx.update(grads, state.opt_state, params)
    for (path, a), u, b in zip(jax.tree_util.tree_leaves_with_path(params),
                               jax.tree.leaves(updates), jax.tree.leaves(new.params)):
        if "router_bias" not in jax.tree_util.keystr(path):
            assert _rel(b, a + u) < 1e-6 and np.any(a != b), jax.tree_util.keystr(path)


def test_the_lifted_rule_walks_a_nested_holder_in_the_loads_order():
    """`expert_share.rebias` on a tree with a run of two layers and a
    holder two keys deep (the latent model's prediction module): rows of
    `load` go to the holders in the order given, whatever the optimizer
    wrote is dropped, and nothing else is touched."""
    bias = lambda n: jnp.zeros((n, 4), jnp.float32)
    before = {"run1": {"router_bias": bias(2), "w": jnp.ones(3)},
              "mtp": {"layer": {"router_bias": bias(1) + 0.5}, "proj": jnp.ones(2)}}
    after = jax.tree.map(lambda x: x + 7.0, before)
    load = jnp.asarray([[4, 0, 2, 2], [2, 2, 2, 2], [0, 8, 0, 0]])
    new = expert_share.rebias(before, after, load, 0.1, (("run1",), ("mtp", "layer")))
    assert np.allclose(new["run1"]["router_bias"], [[-0.1, 0.1, 0, 0], [0, 0, 0, 0]])
    assert np.allclose(new["mtp"]["layer"]["router_bias"], [[0.6, 0.4, 0.6, 0.6]])
    assert np.array_equal(new["run1"]["w"], after["run1"]["w"])
    assert np.array_equal(new["mtp"]["proj"], after["mtp"]["proj"])


# -- each mechanism planted wrong ------------------------------------------------


class _Wrong(conv_moe_lm.ConvMoELM):
    fault = ""

    def _gates(self, bcx):
        b, c, x = jnp.split(bcx, 3, axis=-1)
        if self.fault == "streams_in_another_order":
            b, c, x = x, b, c
        if self.fault == "input_gate_left_out":
            b = jnp.ones_like(b)
        if self.fault == "output_gate_left_out":
            c = jnp.ones_like(c)
        if self.fault == "an_activation_on_the_gate":
            b = jax.nn.silu(b)
        return (b * x).astype(self.dtype), c, jnp.sum(jnp.abs(b)) + jnp.sum(jnp.abs(c))

    def _conv(self, y, lp, pos):
        if self.fault == "taps_reversed":
            lp = {**lp, "conv_w": lp["conv_w"][:, ::-1]}
        if self.fault == "taps_cross_the_episodes_end":
            pos = jnp.broadcast_to(jnp.arange(pos.shape[1]), pos.shape)
        return super()._conv(y, lp, pos)

    def _qkv(self, y, lp, pos):
        if self.fault == "keys_not_normed":
            lp = {**lp, "k_norm": jnp.ones_like(lp["k_norm"])}
        if self.fault == "no_rotary":
            pos = jnp.zeros_like(pos)
        return super()._qkv(y, lp, pos)

    def _ffn(self, mlp, u, lp, scope):
        if mlp == "moe" and self.fault == "selected_by_unbiased_scores":
            lp = {**lp, "router_bias": 0 * lp["router_bias"]}
        if mlp == "dense" and self.fault == "dense_layer_left_out":
            lp = {**lp, "wd": 0 * lp["wd"]}
        return super()._ffn(mlp, u, lp, scope)


MECHANISMS = ("streams_in_another_order", "input_gate_left_out", "output_gate_left_out",
              "an_activation_on_the_gate", "taps_reversed", "taps_cross_the_episodes_end",
              "keys_not_normed", "no_rotary", "selected_by_unbiased_scores",
              "dense_layer_left_out", "a_scale_on_the_weights", "another_share_held")


@pytest.mark.parametrize("fault", MECHANISMS)
def test_each_mechanism_planted_wrong_is_seen(agent, params, program_out,
                                              reference_out, fault):
    """Each is a program that runs and is wrong; the distance that would
    refuse it on the chip is far over what float32 leaves."""
    fields = {f.name: getattr(agent.model, f.name)
              for f in dataclasses.fields(agent.model)}
    if fault == "a_scale_on_the_weights":
        fields["route_scale"] = 2.5
    if fault == "another_share_held":
        fields["first_expert"] = 8
    model = _Wrong(**fields)
    object.__setattr__(model, "fault", fault)
    wrong = ConvLMAgent(CFG)
    wrong.model = model
    batch = _batch(seeded_batch(0))

    @jax.jit
    def forward(p):  # the forward alone: a fault shows before any gradient
        hs, _ = model.apply(p, batch.tokens, batch.done, method=model.trunk)
        total, metrics = wrong._loss(p, batch)
        return {"logits": model.apply(p, hs, method=model.logits)[0],
                "total_loss": total, "routes": metrics["routes"]}

    out = forward(params)
    if fault == "selected_by_unbiased_scores":
        theirs = ref.evaluate(params, seeded_batch(0), hyper(CFG),
                              routes=np.asarray(out["routes"]))
        assert not np.all(theirs["routing"]["same_set"])
        return
    assert _rel(out["logits"], reference_out["logits"]) > 1e-3, fault
    assert _rel(out["total_loss"], program_out["total_loss"]) > 1e-4


# -- acting as decode through the windows and the cache -------------------------


def _decode_all(agent, params, tokens, spans=None, model=None):
    model = model or agent.model
    act = agent.for_acting(params)
    state = model.init_state(tokens.shape[0], T)
    spans = spans or (T,)
    out = []
    for t in range(T):
        span = next(s for s in spans if t < s)
        h, state = model.apply(act, jnp.asarray(tokens[:, t]), jnp.int32(t), state,
                               span, method=model.decode)
        out.append(model.apply(act, h, method=model.logits)[0])
    return jnp.stack(out, axis=1), state


@pytest.fixture(scope="module")
def whole_episode(agent, params):
    nb = seeded_batch(3, mid_episode_end=False)
    logits, state = _decode_all(agent, params, nb["tokens"])
    routes = np.moveaxis(np.asarray(state.routes), 2, 0)  # [layers, N, T, k]
    return nb, logits, state, ref.forward(params, nb["tokens"], nb["done"],
                                          hyper(CFG), routes=routes)


@pytest.mark.parametrize("segments", [1, 2, 4])
def test_decode_through_the_windows_and_the_cache_equals_the_full_forward(
        agent, params, whole_episode, segments):
    """Logits, not tokens, step by step from t = 0 with no prefill: four
    windows of two columns and one cache, whatever prefix of it a step
    reads, give the reference's full forward."""
    nb, _, _, want = whole_episode
    logits, state = _decode_all(agent, params, nb["tokens"],
                                looped_lm.decode_spans(T, segments))
    assert _rel(logits, want["logits"][0]) < 2e-4
    assert all(bool(np.all(r["same_set"])) for r in want["routing"])


def test_decode_across_an_episode_boundary_is_the_learners_forward(agent, params):
    """Two episodes of T steps, each decoded from a zeroed state, are the
    learner's ONE forward over the `[N, 2 T]` unroll with the episode's
    end inside it: the boundary that the taps, the attention and the
    positions respect in the learner is the reset of the windows and the
    cache at act time."""
    first, second = seeded_batch(3, False), seeded_batch(4, False)
    tokens = np.concatenate([first["tokens"], second["tokens"]], axis=1)
    done = np.concatenate([first["done"], second["done"]], axis=1)
    model = agent.model
    hs, facts = model.apply(params, jnp.asarray(tokens), jnp.asarray(done),
                            method=model.trunk)
    learner = model.apply(params, hs, method=model.logits)[0][0]
    acted = jnp.concatenate([_decode_all(agent, params, nb["tokens"])[0]
                             for nb in (first, second)], axis=1)
    assert _rel(acted, learner) < 2e-4
    with jax.default_matmul_precision("highest"):
        want = ref.forward(params, tokens, done, hyper(CFG),
                           routes=np.asarray(facts["routes"]))
    assert _rel(learner, want["logits"][0]) < 2e-4


def test_decode_in_the_dense_form_is_the_learners_forward_in_the_sorted(params):
    """The two forms of the held experts meet inside one model as they do
    in the cell: 8 rows a decode step (24 pairs over a router of 16: the
    dense form), a row block of 8 x T = 256 positions in the learner (768
    pairs in sorted slabs of 512), the same parameters, the same tokens."""
    from distributed_reinforcement_learning_tpu.ops import expert_share

    wide = ConvLMAgent(dataclasses.replace(CFG, row_block=8))
    assert expert_share.call_form(8, 3, 4, 16) == "dense, 8 rows x 4 held"
    assert expert_share.call_form(8 * T, 3, 4, 16) == "sorted, 768 pairs in slabs of 512"
    first, second = seeded_batch(3, False), seeded_batch(4, False)
    tokens = np.concatenate([first["tokens"], second["tokens"]], axis=0)  # [8, T]
    done = np.concatenate([first["done"], second["done"]], axis=0)
    model = wide.model
    hs, facts = model.apply(params, jnp.asarray(tokens), jnp.asarray(done),
                            method=model.trunk)
    learner = model.apply(params, hs, method=model.logits)[0][0]
    acted, state = _decode_all(wide, params, tokens)
    assert _rel(acted, learner) < 2e-4
    assert np.array_equal(np.moveaxis(np.asarray(state.routes), 2, 0),
                          np.asarray(facts["routes"]))
    assert int(jnp.sum(facts["dropped_pairs"])) == 0


def test_at_the_cells_sizes_acting_is_dense_and_learning_sorted():
    """The `lfm2_moe` section as the cell runs it (64 envs x 1,024 steps,
    a row block of 4): what the launcher's start-up line says of the held
    experts' calls."""
    import types

    from distributed_reinforcement_learning_tpu.runtime import launch

    cfg, rt = load_config("config.json", "lfm2_moe")
    anakin = types.SimpleNamespace(agent=ConvLMAgent(cfg),
                                   num_envs=rt.num_actors * rt.envs_per_actor)
    assert launch._expert_calls(anakin) == (
        ", held experts at act time: dense, 64 rows x 16 held; "
        "at learn time: sorted, 16384 pairs in slabs of 5120")


def test_the_state_is_two_columns_a_convolution_layer_and_one_cache(agent, params,
                                                                    whole_episode):
    nb, _, state, _ = whole_episode
    assert [w is None for w in state.window] == [False, True, False, False, False]
    assert [k is None for k in state.k] == [True, False, True, True, True]
    assert state.window[0].shape == (N, 2, 32) and state.k[1].shape == (N, T, 2, 8)
    assert state.routes.shape == (N, T, 4, 3) and state.routes.dtype == jnp.int16
    facts = agent.state_facts(N)
    assert facts["conv_state_bytes"] == 4 * N * 2 * 32 * 4
    assert facts["kv_cache_bytes"] == 2 * N * T * 2 * 8 * 4
    assert facts["layer_order"] == ORDER
    assert (facts["experts_held"], facts["router_width"], facts["first_expert"]) == (4, 16, 4)
    # every matrix a decode step reads whole, at float32 here
    conv, att = 32 * 96 + 32 * 32, 32 * 32 + 32 * 32 + 32 * 32
    experts, router = 4 * 3 * 32 * 16, 32 * 16
    assert facts["act_weight_bytes"] == 4 * (
        conv + 3 * 32 * 48 + att + 3 * conv + 4 * (experts + router) + V * 32)
    # layer 0's window after the last step: u_{T-2}, u_{T-1}, oldest first
    p = params["params"]
    lp = {k: v[0] for k, v in p["run0"].items()}
    y = looped_lm.rms_norm(p["embed"][nb["tokens"]], lp["norms"][0], CFG.norm_eps)
    bcx = y @ lp["in_proj"]
    u = bcx[..., :32] * bcx[..., 64:]
    assert _rel(state.window[0], u[:, -2:]) < 1e-5


@pytest.mark.parametrize("fault", ["window_in_another_order", "window_not_shifted",
                                   "window_of_one_column", "cache_in_bfloat16",
                                   "rotary_at_position_zero"])
def test_a_wrong_decode_step_is_seen(agent, params, whole_episode, fault):
    nb, _, _, want = whole_episode
    model = agent.model

    class Wrong(conv_moe_lm.ConvMoELM):
        def _decode_conv(self, y, lp, window):
            if fault == "window_in_another_order":
                window = window[:, ::-1]
            mix, new = super()._decode_conv(y, lp, window)
            if fault == "window_not_shifted":
                new = jnp.concatenate([window[:, :1], new[:, 1:]], axis=1)
            if fault == "window_of_one_column":
                new = new.at[:, 0].set(0.0)
            return mix, new

        def _decode_attention(self, y, lp, keys, values, t, span):
            if fault == "rotary_at_position_zero":
                mix, k2, v2 = super()._decode_attention(y, lp, keys, values, 0 * t, span)
                return mix, keys.at[:, t].set(k2[:, 0]), values.at[:, t].set(v2[:, 0])
            if fault == "cache_in_bfloat16":
                mix, keys, values = super()._decode_attention(
                    y, lp, keys.astype(jnp.float32), values.astype(jnp.float32), t, span)
                return mix, keys.astype(jnp.bfloat16), values.astype(jnp.bfloat16)
            return super()._decode_attention(y, lp, keys, values, t, span)

        def init_state(self, rows, length):
            state = super().init_state(rows, length)
            if fault != "cache_in_bfloat16":
                return state
            half = lambda xs: tuple(None if x is None else x.astype(jnp.bfloat16)
                                    for x in xs)
            return state._replace(k=half(state.k), v=half(state.v))

    wrong = Wrong(**{f.name: getattr(model, f.name)
                     for f in dataclasses.fields(model)})
    logits, _ = _decode_all(agent, params, nb["tokens"], model=wrong)
    assert _rel(logits, want["logits"][0]) > (1e-3 if fault == "cache_in_bfloat16"
                                              else 0.01)


def test_a_span_past_the_cache_is_refused(agent, params):
    with pytest.raises(ValueError, match="span"):
        agent.model.apply(agent.for_acting(params), jnp.zeros((N,), jnp.int32),
                          jnp.int32(0), agent.init_cache(N), T + 1,
                          method=agent.model.decode)


def test_acting_takes_the_bias_with_the_weights_and_leaves_the_router_float32(
        agent, params):
    act = conv_moe_lm.for_acting(params, jnp.bfloat16)["params"]
    assert len(act["layers"]) == 5 and act["embed_head"].dtype == jnp.bfloat16
    assert act["embed"].dtype == jnp.float32
    assert act["layers"][0]["in_proj"].dtype == jnp.bfloat16
    assert act["layers"][0]["conv_w"].dtype == jnp.float32
    assert act["layers"][1]["router"].dtype == jnp.float32
    assert act["layers"][2]["expert_wgu"].dtype == jnp.bfloat16
    assert np.array_equal(act["layers"][1]["router_bias"],
                          params["params"]["run1"]["router_bias"][0])


# -- the sections in `config.json` ----------------------------------------------


def _section(name="lfm2_moe", **changes):
    with open("config.json") as f:
        section = json.load(f)[name]
    section.update(changes)
    return section


def _load(tmp_path, section):
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"lfm2_moe": section}))
    return load_config(str(path), "lfm2_moe")


def test_load_config_reads_the_section_through_the_table(tmp_path):
    cfg, rt = _load(tmp_path, _section())
    assert type(cfg) is TOKEN_FAMILIES["convlm"][0] is ConvLMConfig
    assert rt.algorithm == "convlm"
    assert cfg.layer_types == ("conv", "full_attention", "conv", "conv", "conv")
    assert (cfg.num_dense_layers, cfg.conv_L_cache, cfg.head_dim) == (1, 3, 64)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads) == (32, 8)
    assert (cfg.num_experts, cfg.router_width, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.routed_scaling_factor) == (16, 64, 4, 1536, 1)
    assert (cfg.vocab_size, cfg.trajectory, cfg.intermediate_size) == (16384, 1024, 11776)
    assert (cfg.bias_update_speed, cfg.norm_eps, cfg.rope_theta) == (1e-3, 1e-5, 1e6)
    assert rt.num_actors * rt.envs_per_actor == 64
    agent = ConvLMAgent(cfg)
    shapes = jax.eval_shape(agent.init_state, jax.random.PRNGKey(0)).params
    count = sum(x.size for x in jax.tree.leaves(shapes))
    assert count == 788_054_145 + 4 * 64  # ISSUE 46's count and the four biases
    facts = agent.state_facts(64)
    assert facts["conv_state_bytes"] == 4 * 64 * 2 * 2048 * 2 == 2_097_152
    assert facts["kv_cache_bytes"] == 2 * 64 * 1024 * 8 * 64 * 2 == 134_217_728
    assert facts["pair_slab_rows"] == 5120  # 1.25 x (4 rows x 1,024 x 4 pairs) / 4
    # 1.58 GB: what a decode step reads whole, the floor under its time
    assert facts["act_weight_bytes"] == 2 * (
        4 * 16_777_216 + 72_351_744 + 10_485_760 + 4 * 150_994_944 + 33_554_432
    ) + 4 * 4 * 131_072 == 1_577_058_304
    with open("perfbench/configs/lfm2_moe.json") as f:
        assert json.load(f)["lfm2_moe"] == _section()


def test_the_small_section_is_the_full_ones_shape():
    small, full = _section("lfm2_moe_small"), _section()
    assert set(small) == set(full)
    same = ("algorithm", "layer_types", "num_dense_layers", "num_hidden_layers",
            "conv_L_cache", "routed_scaling_factor", "norm_eps", "bias_update_speed")
    assert all(small[k] == full[k] for k in same)
    # a quarter of the router's experts, as the full one
    assert small["router_width"] // small["num_experts"] == 4 == (
        full["router_width"] // full["num_experts"])


@pytest.mark.parametrize("changes, message", [
    ({"conv_bias": True}, "conv_bias"),
    ({"use_expert_bias": False}, "use_expert_bias"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
    ({"rope_parameters": {"rope_theta": 1000000, "rope_type": "yarn"}}, "rope_parameters"),
    ({"rope_parameters": {"rope_theta": 10000, "rope_type": "default"}}, "rope_parameters"),
    ({"rms_norm_eps": 1e-6}, "norm_eps"),
    ({"layer_types": ["conv", "mamba", "conv", "conv", "conv"]}, "unknown layer type"),
    ({"num_hidden_layers": 4}, "num_hidden_layers"),
])
def test_load_config_refuses_what_is_not_computed(tmp_path, changes, message):
    with pytest.raises(ValueError, match=message):
        _load(tmp_path, _section(**changes))


@pytest.mark.parametrize("key", ["conv_L_cache", "num_dense_layers", "norm_eps",
                                 "moe_intermediate_size", "router_width", "rope_theta"])
def test_load_config_refuses_a_missing_width(tmp_path, key):
    section = _section()
    del section[key]
    with pytest.raises(KeyError, match=key):
        _load(tmp_path, section)


def test_a_share_past_the_routers_width_is_refused():
    with pytest.raises(ValueError, match="router"):
        ConvLMAgent(dataclasses.replace(CFG, first_expert=13))
    with pytest.raises(ValueError, match="expert layer"):
        ConvLMAgent(dataclasses.replace(CFG, num_dense_layers=5))


# -- the fused loop -----------------------------------------------------------


@pytest.fixture(scope="module")
def chunk():
    agent = ConvLMAgent(CFG)
    anakin = AnakinTokens(agent, N, TokenRecall(V, T, 8))
    state = anakin.init(jax.random.PRNGKey(5))
    before = jax.device_get(state.train.params)
    state, metrics = anakin.train_chunk(state, 2)
    return anakin, before, jax.device_get(state), jax.device_get(metrics)


def test_fused_chunk_losses_are_finite_and_every_leaf_moves(chunk):
    anakin, before, state, metrics = chunk
    assert np.all(np.isfinite(metrics["total_loss"])) and np.all(metrics["grad_norm"] > 0)
    assert np.all(metrics["dropped_pairs"] == 0)
    assert np.all(metrics["conv_state_abs_max"] > 0)
    assert np.all(metrics["conv_gate_abs_mean"] > 0)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(before),
                            jax.tree.leaves(state.train.params)):
        assert np.any(a != b), jax.tree_util.keystr(path)
    facts = anakin.static_facts
    assert facts["layer_order"] == ORDER and facts["experts_held"] == 4
    assert facts["decode_spans"] == (16, 32) and facts["compute_dtype"] == "float32"
    assert metrics["act_routes"].shape == (2, N, T, 4, 3)
    assert metrics["routes"].shape == (2, 4, N, T, 3)
    assert metrics["router_load"].shape == (2, 4, 16)
    biases = np.concatenate([np.ravel(b) for b in
                             anakin.agent.router_biases(state.train.params)])
    assert set(np.round(np.unique(np.abs(biases)) * 1e3).astype(int)) <= {0, 1, 2}
    assert float(metrics["bias_abs_max"][-1]) == float(np.max(np.abs(biases)))


def test_collect_logp_is_the_reference_forward_on_the_decode_steps_sets(chunk):
    """Update 0: the log mu(a_t) that collect wrote through the windows
    and the cache is the reference's full forward from the same
    parameters, on the sets the decode steps chose."""
    _, before, _, metrics = chunk
    rollout = {k: v[0] for k, v in metrics["rollout"].items()}
    routes = np.moveaxis(metrics["act_routes"][0], 2, 0)
    with jax.default_matmul_precision("highest"):
        out = ref.forward(before, rollout["tokens"], rollout["done"], hyper(CFG),
                          routes=routes)
    logp = ref.logp_of(out["logits"][0], rollout["action"])
    assert float(np.max(np.abs(np.asarray(logp) - rollout["behaviour_logp"]))) < 2e-4
    assert all(bool(np.all(r["same_set"])) for r in out["routing"])


def test_the_chunks_first_update_is_the_references_step(chunk):
    """Loss terms, gradient norm, the biases and the parameters after
    update 0 against the reference's own RMSProp and bias steps, on the
    sets the learner chose."""
    anakin, before, _, metrics = chunk
    rollout = {k: v[0] for k, v in metrics["rollout"].items()}
    hp = hyper(CFG)
    terms, grads = ref.loss_and_grads(before, rollout, hp,
                                      routes=metrics["routes"][0])
    for k in ("total_loss", "pi_loss", "baseline_loss", "entropy"):
        assert _rel(metrics[k][0], terms[k]) < 5e-4, k
    assert _rel(metrics["grad_norm"][0], ref.clip_scale(grads, hp)[0]) < 5e-4
    assert np.array_equal(metrics["router_load"][0], terms["router_load"])
    # one update alone, to hold the parameters after it
    state = anakin.init(jax.random.PRNGKey(5))
    state, _ = anakin.train_chunk(state, 1)
    theirs, _ = ref.rmsprop_step(ref.rekey(before), None, grads, hp, 0)
    theirs = ref.stacked(ref.bias_step(theirs, terms["router_load"], hp))
    for (path, a), b, p0 in zip(
            jax.tree_util.tree_leaves_with_path(jax.device_get(state.train.params)),
            jax.tree.leaves(theirs), jax.tree.leaves(before)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert np.array_equal(a, b), name
        else:
            assert _rel(a - p0, b - p0) < 5e-3, name

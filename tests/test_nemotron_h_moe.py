"""The `nemotron_h_moe` configuration at a small size on the CPU: the
state-space / sparse-expert / attention model whose layers are one
sublayer each (`models/ssm_moe_lm.py`), its grouped chunked scan
(`ops/ssd.py`), its UNGATED relu^2 experts beside a shared expert
(`ops/expert_share.py`), its act-time state of three kinds beside a route
record and the fused loop (`runtime/anakin_tokens.py`) against the plain
reference (`reference/nemotron_h_moe.py`), which imports nothing of the
program.

Sizes (section `nemotron_h_moe_small` of `config.json`): hidden 32, the
order `ME*ME` (every kind, two expert layers), 8 state-space heads of 8 in
4 B/C groups with a state of 8 and a chunk of 8 (four chunks an episode
of 32), 4 query and 2 key/value heads of 8, a router 16 wide with 3
experts a token of which experts 4..7 are held here (a quarter), experts
16 wide beside a shared expert of 24; V 64, N 4; float32 so that the
agreement is the arithmetic's. The reference is given the sets the
PROGRAM chose (`routes`): at float32 they are its own.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.agents.looplm import LoopLMBatch
from distributed_reinforcement_learning_tpu.agents.ssmoelm import (
    SSMoELMAgent, SSMoELMConfig)
from distributed_reinforcement_learning_tpu.agents.token_families import (
    TOKEN_FAMILIES)
from distributed_reinforcement_learning_tpu.envs.token_recall_jax import TokenRecall
from distributed_reinforcement_learning_tpu.models import ssm_moe_lm
from distributed_reinforcement_learning_tpu.ops import expert_share
from distributed_reinforcement_learning_tpu.reference import nemotron_h_moe as ref
from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import AnakinTokens
from distributed_reinforcement_learning_tpu.utils.config import load_config

V, T, N = 64, 32, 4
CFG = dataclasses.replace(
    load_config("config.json", "nemotron_h_moe_small")[0],
    attention_backend="reference", head_block=32)
ORDER = "ME*ME"


def hyper(cfg: SSMoELMConfig) -> dict:
    return dict(num_heads=cfg.num_attention_heads,
                num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
                mamba_heads=cfg.mamba_num_heads, mamba_head_dim=cfg.mamba_head_dim,
                mamba_groups=cfg.n_groups, mamba_state=cfg.ssm_state_size,
                top_k=cfg.num_experts_per_tok, first_expert=cfg.first_expert,
                experts_held=cfg.n_routed_experts,
                route_scale=cfg.routed_scaling_factor,
                rms_eps=cfg.layer_norm_epsilon,
                layer_order=cfg.hybrid_override_pattern,
                discount=cfg.discount_factor,
                baseline_loss_coef=cfg.baseline_loss_coef,
                entropy_coef=cfg.entropy_coef, reward_clipping=cfg.reward_clipping,
                gradient_clip_norm=cfg.gradient_clip_norm,
                learning_rate=cfg.start_learning_rate,
                end_learning_rate=cfg.end_learning_rate,
                learning_frame=cfg.learning_frame,
                bias_update_speed=cfg.bias_update_speed)


def seeded_batch(seed: int) -> dict:
    """Episode ends inside two rows, both INSIDE a chunk of the scan (8
    steps): after step 10 and after step 5."""
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
    """Norm scales, D, the convolution's and the value's bias off their
    initial 1 and 0, and the selection bias off zero (it then changes some
    sets)."""
    key = jax.random.PRNGKey(seed)
    moved = {"norms": 0.1, "final_norm": 0.1, "b_value": 0.1, "gate_norm": 0.1,
             "D": 0.1, "conv_b": 0.1, "router_bias": 0.05}

    def move(path, x):
        if path[-1].key not in moved:
            return x
        return x + moved[path[-1].key] * jax.random.normal(
            jax.random.fold_in(key, hash(str(path)) % 1000), x.shape, x.dtype)

    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def agent():
    return SSMoELMAgent(CFG)


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


def test_five_runs_of_three_kinds_in_the_published_order(agent):
    model = agent.model
    assert model.layer_types == ("mamba", "moe", "attention", "mamba", "moe")
    assert model.runs == tuple((kind, 1) for kind in model.layer_types)
    assert model.bias_holders == (("run1",), ("run4",)) and model.expert_layers == 2
    assert TOKEN_FAMILIES["ssmoelm"] == (SSMoELMConfig, SSMoELMAgent)
    with pytest.raises(ValueError, match="not computed"):
        ssm_moe_lm.layer_kinds("ME-M")


def test_the_parameters_are_the_equations_leaves(agent, params):
    """One norm a layer; an expert layer has no mixer's leaves, a mixer no
    MLP's; the up matrix is ONE `[held, D, F]` (ungated); at the published
    widths the three kinds count what ISSUE 53 counts."""
    p = params["params"]
    assert set(p["run0"]) == {"norms", "in_proj", "conv_w", "conv_b", "dt_bias",
                              "A_log", "D", "gate_norm", "out_proj"}
    assert set(p["run2"]) == {"norms", "wq", "wkv", "wo"}
    assert set(p["run1"]) == {"norms", "router", "router_bias", "expert_wu",
                              "expert_wd", "shared_wu", "shared_wd"}
    assert p["run1"]["norms"].shape == (1, 1, 32)
    assert p["run1"]["expert_wu"].shape == (1, 4, 32, 16)
    assert p["run1"]["shared_wu"].shape == (1, 32, 24)
    assert p["run0"]["in_proj"].shape == (1, 32, 64 + (64 + 2 * 4 * 8) + 8)
    assert p["head"].shape == p["embed"].shape == (V, 32)
    full = SSMoELMAgent(load_config("config.json", "nemotron_h_moe")[0])
    shapes = jax.eval_shape(full.model.init, jax.random.PRNGKey(0))["params"]
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))
    assert count(shapes["run0"]) == 38_744_896  # M
    assert count(shapes["run5"]) == 23_399_040  # *
    assert count(shapes["run1"]) == 100_125_312 + 128  # E and its 128 bias values
    assert count(shapes) == 666_965_633 + 4 * 128


def test_the_out_projection_alone_is_rescaled_and_dt_is_drawn_from_its_range(agent):
    p = agent.model.init(jax.random.PRNGKey(3))["params"]
    assert abs(float(jnp.std(p["run0"]["out_proj"])) / (0.3 / 5 ** 0.5) - 1) < 0.1
    assert abs(float(jnp.std(p["run2"]["wo"])) / 0.3 - 1) < 0.1
    dt = jax.nn.softplus(p["run0"]["dt_bias"])
    assert 1e-3 <= float(dt.min()) and float(dt.max()) <= 0.1 + 1e-6
    assert float(jnp.abs(p["run1"]["router_bias"]).max()) == 0


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
                                  "held_pair_share", "relu2_zero_share", "dt_mean",
                                  "router_score_mean", "grad_norm", "update_norm"])
def test_loss_terms_and_counters_match_reference(program_out, reference_out, term):
    got, want = float(program_out[term]), float(reference_out[term])
    assert abs(got - want) <= 2e-5 * max(1.0, abs(want)), (got, want)


def test_the_counters_of_the_share(program_out, reference_out):
    np.testing.assert_array_equal(program_out["router_load"],
                                  reference_out["router_load"])
    assert float(program_out["dropped_pairs"]) == 0
    assert 0.1 < float(program_out["held_pair_share"]) < 0.4  # a quarter, by chance
    assert 0.3 < float(program_out["relu2_zero_share"]) < 0.7
    assert 1e-3 < float(program_out["dt_mean"]) < 0.2
    assert float(program_out["router_experts_untouched"]) == float(
        np.sum(np.asarray(reference_out["router_load"]) == 0))


def test_gradients_match_reference(params, program_out, reference_out):
    theirs = ref.stacked(reference_out["grads"])
    flat = jax.tree_util.tree_leaves_with_path(program_out["grads"])
    for (path, got), want in zip(flat, jax.tree.leaves(theirs)):
        scale = max(float(jnp.max(jnp.abs(want))), 1e-6)
        assert float(jnp.max(jnp.abs(got - want))) <= 1e-4 * scale, path
    assert len(flat) == len(jax.tree.leaves(params))
    assert float(jnp.abs(program_out["grads"]["params"]["run1"]["router_bias"]).max()) == 0


def test_rekey_and_stacked_are_inverses(params):
    theirs = ref.rekey(params, ORDER)
    assert len(theirs["layers"]) == 5 and ref.rekey(theirs) is theirs
    back = ref.stacked(theirs)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="the configuration"):
        ref.rekey(params, "MEM*E")


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """THE SHARE (guide section 4): the routed parts that sixteen chips
    (experts 0-1, 2-3, ..., 30-31 of 32) compute for the same tokens and
    the same router, and the shared expert counted ONCE, add up to the
    reference's layer holding all 32 experts."""
    key = jax.random.split(jax.random.PRNGKey(11), 8)
    d, f, fs, e, k = 32, 16, 24, 32, 3
    x = jax.random.normal(key[0], (2, 12, d))
    lp = {"router": 0.5 * jax.random.normal(key[1], (d, e)),
          "router_bias": 0.05 * jax.random.normal(key[2], (e,)),
          "expert_wu": 0.3 * jax.random.normal(key[3], (e, d, f)),
          "expert_wd": 0.3 * jax.random.normal(key[4], (e, f, d)),
          "shared_wu": 0.3 * jax.random.normal(key[5], (d, fs)),
          "shared_wd": 0.3 * jax.random.normal(key[6], (fs, d))}
    whole = dict(top_k=k, first_expert=0, experts_held=e, route_scale=2.5)
    with jax.default_matmul_precision("highest"):
        uncut, facts = ref.moe(x, lp, whole)
        shared = ref.relu2(x, lp["shared_wu"], lp["shared_wd"])[0]
        parts, pairs = shared.reshape(-1, d), 0
        for first in range(0, e, 2):
            _, mine, w, _ = expert_share.route(
                x.reshape(-1, d), lp["router"], k, "sigmoid", lp["router_bias"], 2.5)
            np.testing.assert_array_equal(mine, facts["chosen"].reshape(-1, k))
            out, counters = expert_share.held_experts(
                x.reshape(-1, d), mine, w, lp["expert_wu"][first:first + 2],
                lp["expert_wd"][first:first + 2], first, e, jnp.float32, "relu2")
            parts, pairs = parts + out, pairs + int(counters["held_pairs"])
            share, _ = ref.moe(x, {**lp, "expert_wu": lp["expert_wu"][first:first + 2],
                                   "expert_wd": lp["expert_wd"][first:first + 2]},
                               dict(whole, first_expert=first, experts_held=2))
            np.testing.assert_allclose(out.reshape(x.shape), share - shared, atol=2e-5)
    assert pairs == 2 * 12 * k == int(facts["held_pairs"])  # every pair once
    np.testing.assert_allclose(parts.reshape(x.shape), uncut, atol=3e-5)


def test_a_layer_is_one_sublayer_under_one_norm(agent, params):
    """h' - h of an expert layer is the experts' result on the NORMED
    input alone (no mixer ahead of it), and of a mixer layer the mixer's
    alone (no MLP behind it)."""
    model, p = agent.model, params["params"]
    h = jax.random.normal(jax.random.PRNGKey(2), (1, T, 32))
    seg = jnp.zeros((1, T), jnp.int32)
    pos = jnp.arange(T)[None]
    with jax.default_matmul_precision("highest"):
        for run, kind in (("run1", "moe"), ("run0", "mamba"), ("run2", "attention")):
            lp = jax.tree.map(lambda x: x[0], p[run])
            out, _, _ = model._layer(kind, h, seg, pos, lp)
            y = ref.norm(h, lp["norms"][0], 1e-5)
            if kind == "moe":
                want = ref.moe(y, lp, hyper(CFG))[0]
            elif kind == "mamba":
                want = ref.mamba(y, lp, pos, hyper(CFG))[0]
            else:
                want = ref.attention(y, lp, seg, hyper(CFG))
            np.testing.assert_allclose(out - h, want, atol=3e-5, err_msg=kind)


@pytest.fixture(scope="module")
def whole_episode(agent, params):
    """One episode of T steps a row, and the learner's forward of it."""
    model = agent.model
    tokens = jax.random.randint(jax.random.PRNGKey(5), (N, T), 0, V)
    done = jnp.zeros((N, T), bool).at[:, -1].set(True)
    with jax.default_matmul_precision("highest"):
        hs, facts = model.apply(params, tokens, done, method=model.trunk)
        logits, _, value = model.apply(params, hs, method=model.logits)
        states = ref.forward(params, tokens, done, hyper(CFG),
                             routes=np.asarray(facts["routes"]))["states"]
    return tokens, logits[0], value[0], facts, states


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


@pytest.mark.parametrize("spans", [(T,), (12, 20, T)])
def test_decode_through_the_four_kinds_of_state_equals_the_full_forward(
        agent, params, whole_episode, spans):
    """Every step of an episode through the recurrent states, the
    windows, the cache and the record, whatever the scans' spans: the
    logits are the learner's (the chunked scan's), the sets the learner's,
    and the recurrent state the episode ends with the reference's
    step-by-step one."""
    tokens, logits, value, facts, states = whole_episode
    got_logits, got_value, state = _decode(agent, params, tokens, spans)
    np.testing.assert_allclose(got_logits, logits, atol=3e-4)
    np.testing.assert_allclose(got_value, value, atol=3e-4)
    np.testing.assert_array_equal(state.routes, jnp.moveaxis(facts["routes"], 0, 2))
    ssm = [s for s in state.ssm if s is not None]
    assert len(ssm) == len(states) == 2
    for got, want in zip(ssm, states):
        np.testing.assert_allclose(got, want, atol=1e-4 * float(jnp.abs(want).max()))


def test_decode_with_the_acting_copy_and_the_state_it_carries(agent, params,
                                                              whole_episode):
    tokens, logits, _, _, _ = whole_episode
    acting = agent.for_acting(params)["params"]
    assert "layers" in acting and len(acting["layers"]) == 5
    assert acting["head"].dtype == CFG.dtype and acting["embed"].dtype == jnp.float32
    got, _, _ = _decode(agent, {"params": acting}, tokens, (T,))
    np.testing.assert_allclose(got, logits, atol=3e-4)
    wide = SSMoELMAgent(dataclasses.replace(CFG, dtype=jnp.bfloat16))
    copy = wide.for_acting(params)["params"]
    assert copy["head"].dtype == jnp.bfloat16
    assert {lp[k].dtype for lp in copy["layers"]
            for k in ssm_moe_lm.RUN_MATRICES if k in lp} == {jnp.dtype(jnp.bfloat16)}
    assert all(lp[k].dtype == jnp.float32 for lp in copy["layers"]
               for k in ("router", "router_bias", "conv_w") if k in lp)
    state = wide.init_cache(N)
    assert [None if s is None else (s.shape, s.dtype) for s in state.ssm] == [
        ((N, 8, 8, 8), jnp.float32), None, None, ((N, 8, 8, 8), jnp.float32), None]
    assert [None if c is None else c.shape for c in state.conv] == [
        (N, 3, 64 + 2 * 4 * 8), None, None, (N, 3, 64 + 2 * 4 * 8), None]
    assert [None if k is None else (k.shape, k.dtype) for k in state.k] == [
        None, None, ((N, T, 2, 8), jnp.bfloat16), None, None]
    assert state.routes.shape == (N, T, 2, 3) and state.routes.dtype == jnp.int16
    facts = wide.state_facts(N)
    assert facts["layer_order"] == ORDER and facts["experts_held"] == 4
    assert facts["ssm_state_bytes"] == 2 * N * 8 * 8 * 8 * 4
    assert facts["conv_state_bytes"] == 2 * N * 3 * 128 * 4
    assert facts["kv_cache_bytes"] == 2 * N * T * 2 * 8 * 2
    assert facts["route_record_bytes"] == N * T * 2 * 3 * 2


@pytest.mark.parametrize("fault", ["state_not_read", "group_zero_for_every_head",
                                   "window_not_shifted"])
def test_a_wrong_decode_step_is_seen(agent, params, whole_episode, fault, monkeypatch):
    tokens, logits, _, _, _ = whole_episode
    model = type(agent.model)
    if fault == "state_not_read":
        real = model._decode_ssm
        monkeypatch.setattr(model, "_decode_ssm", lambda self, y, lp, state, window:
                            real(self, y, lp, jnp.zeros_like(state), window))
    elif fault == "group_zero_for_every_head":
        monkeypatch.setattr(model, "_per_head", lambda self, m: m[:, :1, None, :])
    else:
        real = model._decode_ssm
        monkeypatch.setattr(model, "_decode_ssm", lambda self, y, lp, state, window:
                            (lambda out: (out[0], out[1], jnp.concatenate(
                                [window[:, :1], window], 1)))(
                                    real(self, y, lp, state, window)))
    got, _, _ = _decode(agent, params, tokens, (T,))
    assert float(jnp.abs(got - logits).max()) > 1e-2


def test_at_the_cells_sizes_acting_is_touched_and_learning_in_slabs():
    """16 rows x 6 of 128 at a decode step: 96 pairs for 128 experts, under
    one pair an expert, and experts of D 2,688 x F 1,856 (4.99 M weights a
    block): the TOUCHED form (ISSUE 54). Those widths are 10.5 and 7.25 of
    the grouped product's tiles, which sent this call to the dense form in
    PR 53 (`one_slab_form`'s table); plain products do not care, that
    clause is gone, and the call reads the 3-4 of 8 held experts some row
    chose. What the widths still decide is whether an expert is large
    enough for a trip. The learner's row block of 4 x 2,048 tokens works
    its 49,152 pairs in slabs of 4,096."""
    widths = (2688, 1856)
    assert expert_share.one_slab_form(16, 6, 128, widths) == "touched"
    assert expert_share.call_form(16, 6, 8, 128, widths) == "touched, 16 rows x up to 8 held"
    assert expert_share.call_form(4 * 2048, 6, 8, 128, widths) == \
        "sorted, 49152 pairs in slabs of 4096"
    # whole tiles or not, above `TRIP_WEIGHTS`: touched; under it: sorted
    for large in ((2048, 1536, 768), (2560, 1536, 768), (2560, 1856)):
        assert expert_share.one_slab_form(16, 6, 128, large) == "touched"
    for small in ((2048, 1024, 512), (32, 16)):
        assert expert_share.one_slab_form(16, 6, 128, small) == "sorted"
    assert expert_share.one_slab_form(21, 6, 128, widths) == "touched"  # 126 pairs of 128
    assert expert_share.one_slab_form(22, 6, 128, widths) == "dense"  # over one pair an expert
    assert expert_share.one_slab_form(257, 6, 2048, widths) == "sorted"  # the row bound


def test_load_config_reads_the_section_through_the_table():
    cfg, rt = load_config("config.json", "nemotron_h_moe")
    assert isinstance(cfg, SSMoELMConfig) and rt.algorithm == "ssmoelm"
    assert (cfg.hidden_size, cfg.hybrid_override_pattern) == (2688, "MEMEM*EME")
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
            cfg.ssm_state_size, cfg.conv_kernel, cfg.chunk_size) == (64, 64, 8, 128, 4, 128)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.n_routed_experts, cfg.router_width, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.moe_shared_expert_intermediate_size,
            cfg.routed_scaling_factor) == (8, 128, 6, 1856, 3712, 2.5)
    assert (cfg.vocab_size, cfg.trajectory, cfg.layer_norm_epsilon) == (16384, 2048, 1e-5)
    assert cfg.dtype == jnp.bfloat16 and cfg.row_block == 4
    assert rt.num_actors * rt.envs_per_actor == 16


def test_the_small_section_is_the_full_ones_shape():
    import json

    with open("config.json") as f:
        data = json.load(f)
    full, small = data["nemotron_h_moe"], data["nemotron_h_moe_small"]
    assert set(full) == set(small)
    same = ("algorithm", "mlp_hidden_act", "n_group", "norm_topk_prob",
            "routed_scaling_factor", "layer_norm_epsilon", "conv_kernel",
            "n_shared_experts", "tie_word_embeddings", "bias_update_speed")
    assert all(full[k] == small[k] for k in same)


@pytest.mark.parametrize("changes, message", [
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
    ({"n_group": 2}, "n_group"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"hybrid_override_pattern": "ME-ME"}, "not computed"),
    ({"hybrid_override_pattern": "ME*M"}, "num_hidden_layers"),
    ({"norm_eps": 1e-6}, "norm_eps"),
    ({"rms_norm_eps": 1e-5}, "layer_norm_epsilon"),
    ({"use_conv_bias": False}, "use_conv_bias")])
def test_load_config_refuses_what_is_not_computed(tmp_path, changes, message):
    import json

    with open("config.json") as f:
        section = {**json.load(f)["nemotron_h_moe_small"], **changes}
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"nemotron_h_moe_small": section}))
    with pytest.raises(ValueError, match=message):
        load_config(str(path), "nemotron_h_moe_small")


@pytest.mark.parametrize("key", ["n_groups", "mamba_head_dim", "hybrid_override_pattern",
                                 "moe_shared_expert_intermediate_size", "router_width"])
def test_load_config_refuses_a_missing_width(tmp_path, key):
    import json

    with open("config.json") as f:
        section = json.load(f)["nemotron_h_moe_small"]
    del section[key]
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"nemotron_h_moe_small": section}))
    with pytest.raises(KeyError, match=key):
        load_config(str(path), "nemotron_h_moe_small")


def test_a_share_past_the_routers_width_is_refused():
    with pytest.raises(ValueError, match="of a router 16 wide"):
        SSMoELMAgent(dataclasses.replace(CFG, first_expert=13))
    with pytest.raises(ValueError, match="heads over"):
        SSMoELMAgent(dataclasses.replace(CFG, n_groups=3))


@pytest.fixture(scope="module")
def chunk():
    agent = SSMoELMAgent(CFG)
    anakin = AnakinTokens(agent, N, TokenRecall(V, T, CFG.recall_distance))
    anakin.decode_spans = (12, 20, T)
    state = anakin.init(jax.random.PRNGKey(0))
    params, metrics = [jax.device_get(state.train.params)], []
    with jax.default_matmul_precision("highest"):
        for _ in range(2):  # two chunks of one update: one compile
            state, m = anakin.train_chunk(state, 1)
            params.append(jax.device_get(state.train.params))
            metrics.append(jax.device_get(m))
    return agent, params[0], params[1], params[2], jax.tree.map(
        lambda *xs: np.concatenate(xs), *metrics)


def test_fused_chunk_losses_are_finite_and_every_leaf_moves(chunk):
    _, before, _, after, m = chunk
    assert np.all(np.isfinite(m["total_loss"])) and np.all(m["grad_norm"] > 0)
    assert all(np.any(a != b) for a, b in zip(jax.tree.leaves(before),
                                              jax.tree.leaves(after)))
    assert np.all(m["dropped_pairs"] == 0)
    assert m["act_routes"].shape == (2, N, T, 2, 3)
    for k in ("held_experts_touched_mean", "relu2_zero_share", "dt_mean",
              "held_pair_share", "pair_slabs_mean", "state_norm_mean", "bias_abs_max"):
        assert m[k].shape == (2,) and np.all(m[k] > 0), k
    np.testing.assert_allclose(m["bias_abs_max"], [1e-3, 2e-3], rtol=1e-5)


def test_collect_logp_is_the_reference_forward_on_the_decode_steps_sets(chunk):
    agent, before, _, _, m = chunk
    rollout = {k: v[0] for k, v in m["rollout"].items()}
    routes = np.moveaxis(m["act_routes"][0], 2, 0)
    want = ref.taken_logp(before, rollout["tokens"], rollout["action"],
                          rollout["done"], hyper(CFG), routes=routes)
    np.testing.assert_allclose(rollout["behaviour_logp"], want, atol=2e-4)


def test_the_chunks_first_update_is_the_references_step(chunk):
    """Loss terms, counters and counts of the first update, the state the
    episode ended with, and the parameters and the BIAS after one update
    against the reference's own RMSProp step and bias step."""
    agent, before, after_one, _, m = chunk
    rollout = {k: v[0] for k, v in m["rollout"].items()}
    hp = hyper(CFG)
    out = ref.evaluate(before, rollout, hp, routes=np.asarray(m["routes"][0]))
    for term in ("total_loss", "pi_loss", "baseline_loss", "entropy", "grad_norm",
                 "held_pair_share", "relu2_zero_share", "dt_mean"):
        assert abs(float(m[term][0]) - float(out[term])) \
            <= 5e-5 * max(1.0, abs(float(out[term]))), term
    np.testing.assert_array_equal(m["router_load"][0], out["router_load"])
    acted = ref.forward(before, rollout["tokens"], rollout["done"], hp,
                        routes=np.moveaxis(m["act_routes"][0], 2, 0))["states"]
    flat = np.concatenate([np.asarray(s).reshape(-1) for s in acted])
    every = max(1, flat.size // 16384)
    sample = np.concatenate([np.asarray(s).reshape(-1)[::every] for s in acted])
    np.testing.assert_allclose(m["state_sample"][0], sample,
                               atol=1e-4 * np.abs(sample).max())
    theirs = ref.rekey(before, ORDER)
    new, _ = ref.rmsprop_step(theirs, None, out["grads"], hp, 0)
    new = ref.stacked(ref.bias_step(new, out["router_load"], hp))
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(after_one),
            jax.tree.leaves(new)):
        if path[-1].key == "router_bias":
            np.testing.assert_array_equal(got, want, err_msg=str(path))
        else:
            np.testing.assert_allclose(got, want, atol=1e-6, err_msg=str(path))

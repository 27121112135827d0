"""The `joyai_flash` configuration at a small size on the CPU: the
latent-attention sparse-expert model (`models/latent_moe_lm.py`), its two
forms of one attention (`ops/latent_attention.py`), the sigmoid-scored
bias-corrected router (`ops/expert_share.py`), the prediction module's
loss and the bias's update (`agents/mlalm.py`) and the fused loop
(`runtime/anakin_tokens.py`) against the plain reference
(`reference/joyai_flash.py`), which imports nothing of the program.

Sizes: hidden 32, 4 heads of 8 + 4 (rotary on the 4) with values of 8,
a query latent of 24 and a key/value latent of 16 (a cache of 20 a
token a layer), one dense layer 48 wide then two expert layers: a router
16 wide with 3 experts a token of which experts 4..7 are held here,
experts 16 wide; V 64, T 32, N 4; float32 so that the agreement is the
arithmetic's. The reference is given the sets the PROGRAM chose
(`routes`): at float32 they are its own.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.agents.looplm import LoopLMBatch
from distributed_reinforcement_learning_tpu.agents.mlalm import (
    MLALMAgent, MLALMConfig)
from distributed_reinforcement_learning_tpu.envs.token_recall_jax import TokenRecall
from distributed_reinforcement_learning_tpu.models import latent_moe_lm, looped_lm
from distributed_reinforcement_learning_tpu.ops import (
    attention, expert_share, latent_attention)
from distributed_reinforcement_learning_tpu.ops.pallas import attention as flash
from distributed_reinforcement_learning_tpu.ops.pallas.attention import (
    flash_attention_bhtd)
from distributed_reinforcement_learning_tpu.reference import joyai_flash as ref
from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import AnakinTokens
from distributed_reinforcement_learning_tpu.utils.config import load_config

V, T, N = 64, 32, 4
CFG = MLALMConfig(
    vocab_size=V, hidden_size=32, num_hidden_layers=3, num_attention_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, rope_theta=1e4, intermediate_size=48, n_routed_experts=4,
    router_width=16, first_expert=4, num_experts_per_tok=3,
    moe_intermediate_size=16, trajectory=T, dtype=jnp.float32,
    attention_backend="reference", row_block=2, head_block=32,
    start_learning_rate=1e-3, init_std=0.3)  # wide enough to see


def hyper(cfg: MLALMConfig) -> dict:
    return dict(num_heads=cfg.num_attention_heads, kv_rank=cfg.kv_lora_rank,
                nope_dim=cfg.qk_nope_head_dim, rope_dim=cfg.qk_rope_head_dim,
                rope_theta=cfg.rope_theta, top_k=cfg.num_experts_per_tok,
                first_expert=cfg.first_expert, experts_held=cfg.n_routed_experts,
                route_scale=cfg.routed_scaling_factor, rms_eps=cfg.rms_norm_eps,
                layer_order=tuple(cfg.layer_types), discount=cfg.discount_factor,
                baseline_loss_coef=cfg.baseline_loss_coef,
                entropy_coef=cfg.entropy_coef, reward_clipping=cfg.reward_clipping,
                gradient_clip_norm=cfg.gradient_clip_norm,
                learning_rate=cfg.start_learning_rate,
                end_learning_rate=cfg.end_learning_rate,
                learning_frame=cfg.learning_frame,
                bias_update_speed=cfg.bias_update_speed,
                mtp_loss_coef=cfg.mtp_loss_coef)


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
             "kv_norm": 0.2, "norm_h": 0.2, "norm_e": 0.2, "norm_out": 0.2,
             "router_bias": 0.1}
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
    return MLALMAgent(CFG)


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


# -- the attention: one function, two computations --------------------------


def _pieces(seed, b=2, t=16, h=4, n=8, r=4, rank=16, v=8):
    rng = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    done = np.zeros((b, t), bool)
    done[0, 5] = True
    seg, pos = ref.episode_positions(jnp.asarray(done))
    return dict(q_n=f(b, t, h, n), q_r=f(b, t, h, r), c=f(b, t, rank),
                k_r=f(b, t, r), w_kvb=0.3 * f(rank, h * (n + v)), seg=seg, pos=pos)


def _dense_expanded(x, theta=1e4, scale=None):
    """The equations as written: per-head keys and values, a dense softmax."""
    b, t, h, n = x["q_n"].shape
    r = x["q_r"].shape[-1]
    kv = (x["c"] @ x["w_kvb"]).reshape(b, t, h, -1)
    k_n, v = kv[..., :n], kv[..., n:]
    q_r = ref.rotary(x["q_r"], x["pos"], theta)
    k_r = ref.rotary(x["k_r"][:, :, None], x["pos"], theta)[:, :, 0]
    s = (jnp.einsum("bqhd,bkhd->bhqk", x["q_n"], k_n)
         + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r)) * (scale or (n + r) ** -0.5)
    steps = jnp.arange(t)
    mask = ((steps[:, None] >= steps[None])[None, None]
            & (x["seg"][:, None, :, None] == x["seg"][:, None, None, :]))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_expanded_form_is_the_equations(seed):
    x = _pieces(seed)
    with jax.default_matmul_precision("highest"):
        got = latent_attention.expanded(**x, theta=1e4, dtype=jnp.float32,
                                        backend="reference")
        assert _rel(got, _dense_expanded(x)) < 1e-5


@pytest.mark.parametrize("span", [16, 12, 8])
def test_absorbed_step_on_the_cache_is_the_expanded_form(span):
    """Step by step from t = 0 on one episode: the cache holds the normed
    latent and the ONE rotated key part; the absorbed scores and weighted
    sum on it, up-projected after, are the expanded form's rows."""
    x = _pieces(3)
    x["seg"], x["pos"] = jnp.zeros_like(x["seg"]), jnp.arange(16)[None] + 0 * x["pos"]
    b, t = x["c"].shape[:2]
    with jax.default_matmul_precision("highest"):
        want = _dense_expanded(x)
        cache = jnp.zeros((b, t, 20), jnp.float32)
        for step in range(span):
            cache = jax.lax.dynamic_update_slice(cache, latent_attention.cache_entry(
                x["c"][:, step], x["k_r"][:, step], step, 1e4, jnp.float32),
                (0, step, 0))
            got = latent_attention.absorbed_step(
                x["q_n"][:, step], x["q_r"][:, step], cache, x["w_kvb"],
                jnp.int32(step), span, 1e4, jnp.float32)
            assert _rel(got, want[:, step]) < 1e-5, step


def test_rotary_turns_neighbouring_pairs_and_keeps_relative_positions():
    x = jnp.asarray(np.random.RandomState(0).normal(size=(1, 6, 1, 8)), jnp.float32)
    turned = latent_attention.rotary_interleaved(x, jnp.arange(6)[None, :, None], 1e4)
    assert _rel(turned, ref.rotary(x, jnp.arange(6)[None], 1e4)) < 1e-6
    assert _rel(turned[0, 0], x[0, 0]) < 1e-7  # position 0 turns nothing
    # the pair (0, 1) turns by the angle `pos`: a rotation of the plane
    angle = 3.0
    assert _rel(turned[0, 3, 0, :2], [x[0, 3, 0, 0] * np.cos(angle) - x[0, 3, 0, 1] * np.sin(angle),
                                      x[0, 3, 0, 0] * np.sin(angle) + x[0, 3, 0, 1] * np.cos(angle)]) < 1e-5
    # q . k depends on the distance alone
    q, k = x[0, 0, 0], x[0, 1, 0]
    rot = lambda v, p: latent_attention.rotary_interleaved(v, jnp.float32(p), 1e4)
    assert abs(float(rot(q, 5) @ rot(k, 3) - rot(q, 9) @ rot(k, 7))) < 1e-4


@pytest.mark.parametrize("fault", ["scale_of_the_nope_width", "rotary_on_the_key_only",
                                   "key_part_per_head_not_shared"])
def test_a_wrong_attention_is_seen(fault):
    x = _pieces(4)
    with jax.default_matmul_precision("highest"):
        want = _dense_expanded(x)
        if fault == "scale_of_the_nope_width":
            got = _dense_expanded(x, scale=8 ** -0.5)
        elif fault == "rotary_on_the_key_only":
            got = latent_attention.expanded(
                **{**x, "q_r": ref.rotary(x["q_r"], -x["pos"], 1e4)}, theta=1e4,
                dtype=jnp.float32, backend="reference")
        else:
            got = latent_attention.expanded(
                **{**x, "k_r": jnp.roll(x["k_r"], 1, axis=-1)}, theta=1e4,
                dtype=jnp.float32, backend="reference")
    assert _rel(got, want) > 0.01


# -- the flash kernels with a value width of their own -------------------------


def _flash_bthd(q, k, v, seg, bq, bkv):
    """The kernels (interpret mode) on `[B, T, H, D]` with `[B, T]` ids."""
    b, t, h, _ = q.shape
    flat = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, x.shape[-1])
    out = flash_attention_bhtd(flat(q), flat(k), flat(v), jnp.repeat(seg, h, 0),
                               jnp.repeat(seg, h, 0), block_q=bq, block_kv=bkv,
                               interpret=True)
    return out.reshape(b, h, t, v.shape[-1]).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("d,dv", [(24, 16), (24, 8), (16, 16), (32, 8)])
def test_flash_attention_with_its_own_value_width_matches_dense(d, dv):
    """Interpret mode: forward and all three gradients against the dense
    softmax, with an episode end inside a block; `(16, 16)` is today's
    call."""
    r = np.random.RandomState(0)
    b, t, h = 2, 32, 2
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    q, k, v = f(b, t, h, d), f(b, t, h, d), f(b, t, h, dv)
    seg = jnp.asarray(np.cumsum(r.rand(b, t) < 0.1, axis=1), jnp.int32)
    kernel = lambda q, k, v: _flash_bthd(q, k, v, seg, 8, 8)
    dense = lambda q, k, v: attention.dense_attention(
        q, k, v, causal=True, q_seg=seg, k_seg=seg)
    w = f(b, t, h, dv)
    with jax.default_matmul_precision("highest"):
        assert _rel(kernel(q, k, v), dense(q, k, v)) < 1e-5
        got = jax.grad(lambda *a: jnp.sum(kernel(*a) * w), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for g, wnt in zip(got, want):
        assert g.shape == wnt.shape and _rel(g, wnt) < 1e-4


# Rows of 64 in tiles that differ a side, both orders, and more than one a
# side; episodes end inside a tile (5, 41) and on a tile's edge (32, 16).
_SEG = np.zeros((2, 64), np.int32)
_SEG[0, 5:] += 1
_SEG[0, 32:] += 1
_SEG[1, 16:] += 1
_SEG[1, 41:] += 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d,dv", [(192, 128), (32, 32)])
@pytest.mark.parametrize("bq,bkv", [(16, 32), (32, 16), (16, 16)])
def test_flash_tiles_match_dense_in_the_callers_dtype(bq, bkv, d, dv, dtype):
    """Interpret mode: values and all three gradients against the dense
    softmax. Float32 inputs agree to float32 rounding (no product rounds
    what the caller sent); bfloat16 inputs agree with the float32 answer
    as closely as `dense_attention` does with its own bfloat16 `probs`."""
    r = np.random.RandomState(0)
    b, t, h = 2, 64, 2
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32).astype(dtype)
    q, k, v, w = f(b, t, h, d), f(b, t, h, d), f(b, t, h, dv), f(b, t, h, dv)
    seg = jnp.asarray(_SEG)
    kernel = lambda q, k, v: _flash_bthd(q, k, v, seg, bq, bkv)
    dense = lambda q, k, v: attention.dense_attention(
        q, k, v, causal=True, q_seg=seg, k_seg=seg)

    def both(fn, *x):
        loss = lambda *a: jnp.sum((fn(*a) * w).astype(jnp.float32))
        return (fn(*x), *jax.grad(loss, argnums=(0, 1, 2))(*x))

    with jax.default_matmul_precision("highest"):
        got = both(kernel, q, k, v)
        same = both(dense, q, k, v)
        exact = both(dense, *(x.astype(jnp.float32) for x in (q, k, v)))
    for g, s, e, limit in zip(got, same, exact, (1e-5, 1e-4, 1e-4, 1e-4)):
        assert g.shape == e.shape and g.dtype == dtype
        if dtype == jnp.float32:
            assert _rel(g, e) < limit
        else:
            assert _rel(g, e) < 1.5 * _rel(s, e)


def test_the_tile_follows_t_the_widths_and_the_itemsize():
    """The four token cells' learner shapes (PERF.md section 6, PR 41), a
    row of one tile, a T that only 8 divides, none; and the working set the
    rule reckons stays under a kernel's scoped VMEM."""
    assert flash.flash_blocks(2048, 192, 128, 2) == (512, 512)   # joyai_flash
    assert flash.flash_blocks(1024, 256, 256, 2) == (512, 512)   # qwen3_next
    assert flash.flash_blocks(1024, 64, 64, 2) == (512, 512)     # granite_hybrid
    assert flash.flash_blocks(128, 128, 128, 2) == (128, 128)    # ouro_looplm
    assert flash.flash_blocks(32, 64, 64, 4) == (32, 32)
    assert flash.flash_blocks(1000, 64, 64, 4) == (8, 1000)  # kv: 128s or the row
    assert flash.flash_blocks(1001, 64, 64, 4) == (0, 0)
    for t, d, dv, itemsize in [(2048, 192, 128, 2), (1024, 256, 256, 4),
                               (4096, 512, 512, 4), (8192, 1024, 1024, 4)]:
        bq, bkv = flash.flash_blocks(t, d, dv, itemsize)
        assert bq >= 8 and t % bq == 0 and t % bkv == 0 and bkv % 128 == 0
        assert flash.flash_working_set_bytes(bq, bkv, d, dv, itemsize) \
            <= flash._SCOPED_VMEM_BYTES
    # wide float32 rows: the longer side gives way first
    assert flash.flash_blocks(8192, 1024, 1024, 4) != (512, 512)


def test_the_kernel_microbenchmark_prints_one_line_a_tile():
    import pathlib
    import subprocess
    import sys

    script = pathlib.Path(__file__).parent.parent / "scripts" / "flash_kernel_bench.py"
    done = subprocess.run(
        [sys.executable, str(script), "--bh", "2", "--t", "32", "--d", "24",
         "--dv", "16", "--dtype", "float32", "--block-q", "8", "16",
         "--block-kv", "16", "--iters", "1", "--interpret"],
        capture_output=True, text=True, timeout=300, check=True)
    lines = [json.loads(l) for l in done.stdout.splitlines()]
    assert [(l["block_q"], l["block_kv"]) for l in lines] == [(8, 16), (16, 16)]
    assert all(l["platform"] == "cpu" and l["fwd_ms"] > 0 and l["fwd_bwd_ms"] > 0
               and l["rule"] == [32, 32] for l in lines)


@pytest.mark.parametrize("backend", ["reference", "pallas_interpret"])
def test_causal_attention_takes_a_value_width_of_its_own(backend):
    r = np.random.RandomState(1)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    q, k, v = f(2, 16, 2, 12), f(2, 16, 2, 12), f(2, 16, 2, 8)
    seg = jnp.zeros((2, 16), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = attention.causal_attention(q, k, v, q_seg=seg, k_seg=seg,
                                         backend=backend)
        want = attention.dense_attention(q, k, v, causal=True)
        assert got.shape == (2, 16, 2, 8) and _rel(got, want) < 1e-5
        long = attention.blockwise_attention(q, k, v, block_size=4)
        assert _rel(long, want) < 1e-5


# -- the router: sigmoid scores, a bias that selects and does not weigh --------


def _expert_layer(seed, tokens=24, d=32, width=16, experts=16):
    r = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(r.normal(size=shape) * 0.3, jnp.float32)
    return {"x": f(1, tokens, d) * 3.0, "router": f(d, experts),
            "router_bias": jnp.zeros((experts,), jnp.float32),
            "expert_wgu": f(experts, d, 2 * width), "expert_wd": f(experts, width, d),
            "shared_wgu": f(d, 2 * width), "shared_wd": f(width, d)}


def _route(layer, top_k=3, scale=2.5):
    return expert_share.route(layer["x"][0], layer["router"], top_k, "sigmoid",
                              layer["router_bias"], scale)


def test_sigmoid_router_scores_selects_and_weighs_as_published():
    layer = _expert_layer(0)
    scores, chosen, weight, load = _route(layer)
    logits = np.asarray(layer["x"][0] @ layer["router"], np.float64)
    want = 1 / (1 + np.exp(-logits))
    assert _rel(scores, want) < 1e-5
    top = np.argsort(-want, -1)[:, :3]
    assert np.array_equal(np.sort(chosen, -1), np.sort(top, -1))
    picked = np.take_along_axis(want, np.asarray(chosen), -1)
    assert _rel(weight, 2.5 * picked / picked.sum(-1, keepdims=True)) < 1e-5
    assert _rel(weight.sum(-1), np.full(24, 2.5)) < 1e-5
    assert np.array_equal(load, np.bincount(np.asarray(chosen).reshape(-1), minlength=16))
    assert int(load.sum()) == 24 * 3


def test_a_bias_changes_the_set_and_not_the_weights():
    layer = _expert_layer(1)
    scores, chosen, weight, _ = _route(layer)
    bias = jnp.zeros((16,)).at[7].set(10.0)  # expert 7 now wins every token
    scores_b, chosen_b, weight_b, load_b = _route({**layer, "router_bias": bias})
    assert np.array_equal(scores, scores_b)  # the scores are unbiased
    assert np.all(np.any(np.asarray(chosen_b) == 7, -1)) and int(load_b[7]) == 24
    assert not np.array_equal(np.sort(chosen, -1), np.sort(chosen_b, -1))
    picked = np.take_along_axis(np.asarray(scores), np.asarray(chosen_b), -1)
    assert _rel(weight_b, 2.5 * picked / picked.sum(-1, keepdims=True)) < 1e-5
    # and the reference does the same, on its own sets
    _, _, facts = ref.moe(layer["x"], {**layer, "router_bias": bias},
                          dict(top_k=3, first_expert=0, experts_held=16, route_scale=2.5))
    assert np.array_equal(np.sort(facts["chosen"][0], -1), np.sort(chosen_b, -1))
    assert np.array_equal(facts["load"], load_b)


def test_no_gradient_reaches_the_bias_and_softmax_routing_is_as_it_was():
    layer = _expert_layer(2)

    def out(bias, router):
        _, _, weight, _ = expert_share.route(layer["x"][0], router, 3, "sigmoid",
                                             bias, 2.5)
        return jnp.sum(weight * jnp.arange(3.0))

    g_bias, g_router = jax.grad(out, argnums=(0, 1))(0.01 * jnp.arange(16.0),
                                                     layer["router"])
    assert float(jnp.max(jnp.abs(g_bias))) == 0.0 < float(jnp.max(jnp.abs(g_router)))
    probs, chosen, weight = expert_share.route(layer["x"][0], layer["router"], 3)
    assert _rel(probs.sum(-1), np.ones(24)) < 1e-6 and _rel(weight.sum(-1), np.ones(24)) < 1e-6
    with pytest.raises(ValueError, match="scoring"):
        expert_share.route(layer["x"][0], layer["router"], 3, "tanh")


@pytest.mark.parametrize("shares", [1, 2, 4, 16])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """The share test of the `model-configs` guide, section 4: the routed
    parts that all the shares give, plus the shared expert counted once,
    are what the uncut reference gives for the whole layer."""
    layer = _expert_layer(3)
    layer["router_bias"] = 0.05 * jnp.asarray(
        np.random.RandomState(9).normal(size=16), jnp.float32)
    held = 16 // shares
    hp = dict(top_k=3, first_expert=0, experts_held=16, route_scale=2.5)
    with jax.default_matmul_precision("highest"):
        _, chosen, weight, _ = _route(layer)
        parts = [expert_share.held_experts(
            layer["x"][0], chosen, weight, layer["expert_wgu"][first:first + held],
            layer["expert_wd"][first:first + held], first, 16, jnp.float32)
            for first in range(0, 16, held)]
        routed, shared, _ = ref.moe(layer["x"], layer, hp)
        assert _rel(sum(p[0] for p in parts), routed[0]) < 1e-5
        assert sum(int(p[1]["held_pairs"]) for p in parts) == 24 * 3
        assert all(int(p[1]["dropped_pairs"]) == 0 for p in parts)
        one, _, _ = ref.moe(layer["x"], {**layer, "expert_wgu": layer["expert_wgu"][:held],
                                         "expert_wd": layer["expert_wd"][:held]},
                            {**hp, "experts_held": held})
        assert _rel(parts[0][0], one[0]) < 1e-5
        whole = ref.swiglu(layer["x"], layer["shared_wgu"], layer["shared_wd"])
    assert _rel(shared, whole) < 1e-6 and float(jnp.max(jnp.abs(shared))) > 0


# -- the model against the reference -----------------------------------------


@pytest.mark.parametrize("what", ["logits", "value", "logp"])
def test_forward_matches_reference(program_out, reference_out, what):
    assert _rel(program_out[what], reference_out[what]) < 2e-4
    assert _rel(program_out["stats_logp"], reference_out["logp"]) < 2e-4


def test_the_program_chose_the_references_sets_under_a_bias(params, reference_out):
    assert np.all(reference_out["routing"]["same_set"])
    assert float(np.max(np.abs(np.concatenate(
        [np.ravel(b) for b in MLALMAgent.router_biases(params)])))) > 0.05


@pytest.mark.parametrize("term", ["total_loss", "pi_loss", "baseline_loss", "entropy",
                                  "mtp_loss", "held_pair_share", "router_score_mean"])
def test_loss_terms_and_counters_match_reference(program_out, reference_out, term):
    assert _rel(program_out[term], reference_out[term]) < 2e-4


def test_the_counters_of_the_share(program_out, reference_out):
    assert float(program_out["dropped_pairs"]) == 0
    assert 0 < float(program_out["held_pair_share"]) < 1
    assert np.array_equal(program_out["router_load"], reference_out["router_load"])
    assert program_out["router_load"].shape == (3, 16)  # two layers and the module
    assert np.all(program_out["router_load"].sum(-1) == N * T * 3)
    assert float(program_out["router_load_max_over_mean"]) >= 1
    agreed = float(reference_out["mtp_agreed"]) / float(reference_out["mtp_count"])
    assert abs(float(program_out["mtp_agreement"]) - agreed) < 1e-6
    assert int(reference_out["mtp_count"]) == N * (T - 1) - 2  # two episode ends inside


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


def test_mtp_loss_alone_reaches_trunk_embedding_head_and_module(agent, params):
    """L_mtp's own gradient: into every layer of the trunk, the embedding
    (both through h and through E[x_{t+1}]), the head and the module; not
    into the value head or the final norm, which it does not read."""
    batch = _batch(seeded_batch(0))
    grads = jax.grad(lambda p: agent._stats(p, batch)["counters"]["mtp_loss"])(params)
    p = grads["params"]
    for name in ("embed", "head"):
        assert np.any(p[name]), name
    for run in ("run0", "run1"):
        for k in ("wqa", "wkvb", "wo"):
            assert np.any(p[run][k]), (run, k)
    assert np.any(p["mtp"]["proj"]) and np.any(p["mtp"]["layer"]["expert_wgu"])
    assert not np.any(p["w_value"]) and not np.any(p["final_norm"])


def test_an_episode_end_cuts_attention_positions_and_the_modules_targets(
        agent, params):
    """Row 0 ends an episode at step 11: what comes before it reaches
    nothing after it (logits of steps 12.. do not move when tokens 0..11
    change), positions restart, and step 11 counts in no L_mtp."""
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
    assert _rel(run(shifted)[0, 14:], a[0, 14:]) > 1e-3
    # the module's loss does not read a_12 through step 11
    moved = {**nb, "action": nb["action"].copy()}
    moved["action"][0, 12] = (moved["action"][0, 12] + 1) % V
    loss = lambda b: float(agent._stats(params, _batch(b))["counters"]["mtp_loss"])
    assert loss(moved) == loss(nb)
    moved["action"][0, 11] = (moved["action"][0, 11] + 1) % V  # a_11 is step 10's target
    assert loss(moved) != loss(nb)


def test_rekey_and_stacked_are_inverses(params):
    back = ref.stacked(ref.rekey(params, CFG.layer_types))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params),
                            jax.tree.leaves(back)):
        assert np.array_equal(a, b), jax.tree_util.keystr(path)
    with pytest.raises(ValueError, match="configuration"):
        ref.rekey(params, ("moe", "dense", "moe"))


# -- the bias's update ----------------------------------------------------------


def test_learn_moves_the_bias_by_gamma_from_the_counts_and_not_by_the_optimizer(
        agent, params):
    state = common.TrainState.create(params, agent.tx)
    new, metrics = jax.jit(agent._learn)(state, _batch(seeded_batch(0)))
    load = np.asarray(metrics["router_load"], np.float64)
    want = CFG.bias_update_speed * np.sign(load.mean(-1, keepdims=True) - load)
    before = np.concatenate([np.asarray(b) for b in MLALMAgent.router_biases(params)])
    after = np.concatenate([np.asarray(b) for b in MLALMAgent.router_biases(new.params)])
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


def test_a_starved_expert_is_chosen_more_as_its_bias_rises():
    """What the update is FOR: an expert no token chose gains gamma a
    step until its biased score enters some token's top-k."""
    layer = _expert_layer(5)
    layer["router"] = layer["router"].at[:, 3].set(0.0)  # score 0.5 for every token
    layer["x"] = layer["x"] * 3
    bias = jnp.zeros((16,), jnp.float32)
    loads = []
    for _ in range(400):
        _, _, _, load = _route({**layer, "router_bias": bias})
        loads.append(int(load[3]))
        load = load.astype(jnp.float32)
        bias = bias + 1e-3 * jnp.sign(jnp.mean(load) - load)
    assert loads[0] < loads[-1] and float(bias[3]) > 0


# -- each mechanism planted wrong ------------------------------------------------


class _Wrong(latent_moe_lm.LatentMoELM):
    fault = ""

    def _latents(self, y, lp):
        q_n, q_r, c, k_r = super()._latents(y, lp)
        if self.fault == "latent_not_normed":
            c = jnp.split(self._mm(y, lp["wkva"]), [self.kv_rank], -1)[0]
        if self.fault == "query_latent_not_normed":
            q = self._mm(self._mm(y, lp["wqa"]), lp["wqb"]).reshape(
                *y.shape[:-1], self.num_heads, -1)
            q_n, q_r = q[..., :self.nope_dim], q[..., self.nope_dim:]
        return q_n, q_r, c, k_r

    def _ffn(self, kind, u, lp, scope):
        if kind == "moe" and self.fault == "shared_expert_left_out":
            lp = {**lp, "shared_wd": 0 * lp["shared_wd"]}
        if kind == "moe" and self.fault == "selected_by_unbiased_scores":
            lp = {**lp, "router_bias": 0 * lp["router_bias"]}
        if kind == "dense" and self.fault == "dense_layer_routed":
            lp = {**lp, "wd": 0 * lp["wd"]}
        return super()._ffn(kind, u, lp, scope)


MECHANISMS = ("latent_not_normed", "query_latent_not_normed", "shared_expert_left_out",
              "selected_by_unbiased_scores", "dense_layer_routed", "scale_missing",
              "theta_of_another_model", "mtp_fed_the_token_on_show",
              "mtp_targets_not_shifted")


@pytest.mark.parametrize("fault", MECHANISMS)
def test_each_mechanism_planted_wrong_is_seen(agent, params, program_out,
                                              reference_out, fault):
    """Each is a program that runs and is wrong; the distance that would
    refuse it on the chip is far over what float32 leaves."""
    fields = {f.name: getattr(agent.model, f.name)
              for f in dataclasses.fields(agent.model)}
    if fault == "scale_missing":
        fields["route_scale"] = 1.0
    if fault == "theta_of_another_model":
        fields["rope_theta"] = 10.0
    model = _Wrong(**fields)
    object.__setattr__(model, "fault", fault)
    wrong = MLALMAgent(CFG)
    wrong.model = model
    nb = seeded_batch(0)
    if fault == "mtp_fed_the_token_on_show":
        model.mtp = lambda p, h, tokens, done: latent_moe_lm.LatentMoELM.mtp(
            model, p, h, jnp.roll(tokens, 1, axis=1), done)
    if fault == "mtp_targets_not_shifted":
        stats = wrong._stats

        def unshifted(p, batch):
            return stats(p, batch._replace(action=jnp.roll(batch.action, 1, axis=1)))

        got = float(unshifted(params, _batch(nb))["counters"]["mtp_loss"])
        assert _rel(got, reference_out["mtp_loss"]) > 1e-3
        return
    out = _program(wrong, params, nb)
    if fault == "selected_by_unbiased_scores":
        theirs = ref.evaluate(params, nb, hyper(CFG), routes=np.asarray(out["routes"]))
        assert not np.all(theirs["routing"]["same_set"])
        return
    key = "mtp_loss" if fault.startswith("mtp") else "logits"
    assert _rel(out[key], reference_out[key]) > 1e-3, fault
    if not fault.startswith("mtp"):
        assert _rel(out["total_loss"], program_out["total_loss"]) > 1e-4


# -- acting as decode through the latent cache --------------------------------


def _decode_all(agent, params, tokens, spans=None, model=None):
    model = model or agent.model
    act = agent.for_acting(params)
    state = agent.init_cache(tokens.shape[0])
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
                                          hyper(CFG), routes=routes, mtp=False)


@pytest.mark.parametrize("segments", [1, 2, 4])
def test_absorbed_decode_through_the_cache_equals_the_expanded_forward(
        agent, params, whole_episode, segments):
    """Logits, not tokens, step by step from t = 0 with no prefill: the
    absorbed form on the latent cache, whatever prefix of it a step
    reads, is the reference's full expanded forward."""
    nb, _, _, want = whole_episode
    logits, state = _decode_all(agent, params, nb["tokens"],
                                looped_lm.decode_spans(T, segments))
    assert _rel(logits, want["logits"][0]) < 2e-4
    assert all(bool(np.all(r["same_set"])) for r in want["routing"])


def test_the_cache_holds_the_normed_latent_and_one_rotated_key(agent, params,
                                                               whole_episode):
    nb, _, state, _ = whole_episode
    assert len(state.cache) == 3 and state.cache[0].shape == (N, T, 20)
    assert state.routes.shape == (N, T, 2, 3) and state.routes.dtype == jnp.int16
    facts = agent.state_facts(N)
    assert facts["latent_cache_bytes"] == 3 * N * T * 20 * 4
    assert facts["cache_bytes_per_token"] == 3 * 20 * 4
    assert facts["expanded_cache_bytes_per_token"] == 3 * 4 * (8 + 4 + 8) * 4
    assert (facts["experts_held"], facts["router_width"], facts["first_expert"]) == (4, 16, 4)
    # layer 0's row: N(W_kva y)'s latent and the key part turned by t
    p = params["params"]
    lp = {k: v[0] for k, v in p["run0"].items()}
    y = looped_lm.rms_norm(p["embed"][nb["tokens"]], lp["norms"][0], 1e-6)
    ckr = y @ lp["wkva"]
    c = looped_lm.rms_norm(ckr[..., :16], lp["kv_norm"], 1e-6)
    k_r = ref.rotary(ckr[..., None, 16:], jnp.arange(T)[None] + jnp.zeros((N, 1)), 1e4)
    assert _rel(state.cache[0][..., :16], c) < 1e-5
    assert _rel(state.cache[0][..., 16:], k_r[:, :, 0]) < 1e-5


@pytest.mark.parametrize("fault", ["rotary_at_position_zero", "cache_in_bfloat16",
                                   "latent_cached_before_its_norm",
                                   "an_episode_not_reset"])
def test_a_wrong_decode_step_is_seen(agent, params, whole_episode, fault):
    nb, _, _, want = whole_episode
    model = agent.model
    if fault == "cache_in_bfloat16":
        class Rounded(latent_moe_lm.LatentMoELM):
            def init_state(self, rows, length):
                state = super().init_state(rows, length)
                return state._replace(cache=tuple(
                    c.astype(jnp.bfloat16) for c in state.cache))

            def _decode_mla(self, y, lp, cache, t, span):
                mix, cache = super()._decode_mla(y, lp, cache.astype(jnp.float32), t, span)
                return mix, cache.astype(jnp.bfloat16)
        wrong_cls = Rounded
    else:
        class Wrong(latent_moe_lm.LatentMoELM):
            def _decode_mla(self, y, lp, cache, t, span):
                if fault == "rotary_at_position_zero":
                    t_rot = 0 * t
                    q_n, q_r, c, k_r = self._latents(y, lp)
                    cache = jax.lax.dynamic_update_slice(
                        cache, latent_attention.cache_entry(c, k_r, t_rot, self.rope_theta,
                                                            self.dtype), (0, t, 0))
                    att = latent_attention.absorbed_step(
                        q_n, latent_attention.rotary_interleaved(q_r, -t, self.rope_theta),
                        cache, lp["wkvb"], t, span, self.rope_theta, self.dtype)
                    return self._mm(att.reshape(y.shape[0], -1), lp["wo"]), cache
                if fault == "latent_cached_before_its_norm":
                    lp = {**lp, "kv_norm": jnp.ones_like(lp["kv_norm"])}
                return super()._decode_mla(y, lp, cache, t, span)
        wrong_cls = Wrong
    wrong = wrong_cls(**{f.name: getattr(model, f.name)
                         for f in dataclasses.fields(model)})
    if fault == "an_episode_not_reset":
        # two episodes through one cache: the second sees the first
        first, state = _decode_all(agent, params, nb["tokens"])
        act = agent.for_acting(params)
        h, _ = model.apply(act, jnp.asarray(nb["tokens"][:, 0]), jnp.int32(0),
                           state, T, method=model.decode)
        h0, _ = model.apply(act, jnp.asarray(nb["tokens"][:, 0]), jnp.int32(0),
                            agent.init_cache(N), T, method=model.decode)
        assert _rel(h, h0) < 1e-6  # step 0 reads position 0 alone: the mask holds
        return
    agent_w = MLALMAgent(CFG)
    agent_w.model = wrong
    logits, _ = _decode_all(agent_w, params, nb["tokens"], model=wrong)
    assert _rel(logits, want["logits"][0]) > (1e-3 if fault == "cache_in_bfloat16"
                                              else 0.01)


def test_a_span_past_the_cache_is_refused(agent, params):
    with pytest.raises(ValueError, match="span"):
        agent.model.apply(agent.for_acting(params), jnp.zeros((N,), jnp.int32),
                          jnp.int32(0), agent.init_cache(N), T + 1,
                          method=agent.model.decode)


def test_acting_leaves_the_prediction_module_out(agent, params):
    act = agent.for_acting(params)["params"]
    assert "mtp" not in act and len(act["layers"]) == 3
    assert act["layers"][1]["router"].dtype == jnp.float32
    assert np.array_equal(act["layers"][1]["router_bias"],
                          params["params"]["run1"]["router_bias"][0])


# -- the section in `config.json` ---------------------------------------------


def _section(**changes):
    with open("config.json") as f:
        section = json.load(f)["joyai_flash"]
    section.update(changes)
    return section


def _load(tmp_path, section):
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"joyai_flash": section}))
    return load_config(str(path), "joyai_flash")


def test_load_config_reads_the_section(tmp_path):
    cfg, rt = _load(tmp_path, _section())
    assert isinstance(cfg, MLALMConfig)
    assert cfg.layer_types == ("dense",) + ("moe",) * 4
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (cfg.n_routed_experts, cfg.router_width, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.routed_scaling_factor) == (16, 256, 8, 768, 2.5)
    assert (cfg.vocab_size, cfg.trajectory, cfg.intermediate_size) == (16160, 2048, 7168)
    assert (cfg.bias_update_speed, cfg.mtp_loss_coef, cfg.rope_theta) == (1e-3, 0.3, 3.2e7)
    assert rt.num_actors * rt.envs_per_actor == 16
    agent = MLALMAgent(cfg)
    shapes = jax.eval_shape(agent.init_state, jax.random.PRNGKey(0)).params
    count = sum(x.size for x in jax.tree.leaves(shapes))
    assert count == 680_441_857 + 5 * 256  # ISSUE 40's count and the five biases
    facts = agent.state_facts(16)
    assert facts["latent_cache_bytes"] == 16 * 2048 * 5 * 576 * 2 == 188_743_680
    assert (facts["cache_bytes_per_token"],
            facts["expanded_cache_bytes_per_token"]) == (5_760, 102_400)
    with open("perfbench/configs/joyai_flash.json") as f:
        assert json.load(f)["joyai_flash"] == _section()


@pytest.mark.parametrize("changes, message", [
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"n_group": 8}, "n_group"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"rope_scaling": {"type": "yarn", "factor": 40}}, "rope_scaling"),
    ({"rope_interleave": False}, "rope_interleave"),
    ({"num_nextn_predict_layers": 2}, "num_nextn_predict_layers"),
    ({"qk_head_dim": 128}, "qk_head_dim"),
    ({"num_key_value_heads": 8}, "num_key_value_heads"),
])
def test_load_config_refuses_what_is_not_computed(tmp_path, changes, message):
    with pytest.raises(ValueError, match=message):
        _load(tmp_path, _section(**changes))


@pytest.mark.parametrize("key", ["kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
                                 "moe_intermediate_size", "router_width"])
def test_load_config_refuses_a_missing_width(tmp_path, key):
    section = _section()
    del section[key]
    with pytest.raises(KeyError, match=key):
        _load(tmp_path, section)


def test_a_share_past_the_routers_width_is_refused():
    with pytest.raises(ValueError, match="router"):
        MLALMAgent(dataclasses.replace(CFG, first_expert=13))
    with pytest.raises(ValueError, match="expert layer"):
        MLALMAgent(dataclasses.replace(CFG, first_k_dense_replace=3))


# -- the fused loop -----------------------------------------------------------


@pytest.fixture(scope="module")
def chunk():
    agent = MLALMAgent(CFG)
    anakin = AnakinTokens(agent, N, TokenRecall(V, T, 8))
    state = anakin.init(jax.random.PRNGKey(5))
    before = jax.device_get(state.train.params)
    state, metrics = anakin.train_chunk(state, 2)
    return anakin, before, jax.device_get(state), jax.device_get(metrics)


def test_fused_chunk_losses_are_finite_and_every_leaf_moves(chunk):
    anakin, before, state, metrics = chunk
    assert np.all(np.isfinite(metrics["total_loss"])) and np.all(metrics["grad_norm"] > 0)
    assert np.all(np.isfinite(metrics["mtp_loss"])) and np.all(metrics["mtp_loss"] > 0)
    assert np.all(metrics["dropped_pairs"] == 0)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(before),
                            jax.tree.leaves(state.train.params)):
        assert np.any(a != b), jax.tree_util.keystr(path)
    facts = anakin.static_facts
    assert facts["layer_order"] == CFG.layer_types and facts["experts_held"] == 4
    assert facts["decode_spans"] == (16, 32)
    assert metrics["act_routes"].shape == (2, N, T, 2, 3)
    assert metrics["routes"].shape == (2, 3, N, T, 3)
    assert metrics["router_load"].shape == (2, 3, 16)
    biases = np.concatenate([np.ravel(b) for b in
                             MLALMAgent.router_biases(state.train.params)])
    assert set(np.round(np.unique(np.abs(biases)) * 1e3).astype(int)) <= {0, 1, 2}
    assert float(metrics["bias_abs_max"][-1]) == float(np.max(np.abs(biases)))


def test_collect_logp_is_the_reference_forward_on_the_decode_steps_sets(chunk):
    """Update 0: the log mu(a_t) that collect wrote through the latent
    cache in the absorbed form is the reference's full expanded forward
    from the same parameters, on the sets the decode steps chose."""
    _, before, _, metrics = chunk
    rollout = {k: v[0] for k, v in metrics["rollout"].items()}
    routes = np.moveaxis(metrics["act_routes"][0], 2, 0)
    with jax.default_matmul_precision("highest"):
        out = ref.forward(before, rollout["tokens"], rollout["done"], hyper(CFG),
                          routes=routes, mtp=False)
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
    for k in ("total_loss", "pi_loss", "baseline_loss", "entropy", "mtp_loss"):
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

"""Plain reference of the `lfm2_moe` configuration: LiquidAI LFM2-24B-A2B
(`huggingface.co/LiquidAI/LFM2-24B-A2B` config.json, `model_type`
lfm2_moe: a double-gated short convolution in three mixers of four,
grouped-query attention in the fourth, a dense SwiGLU in the leading
layers, then a sigmoid-scored router with a selection bias over all of a
layer's experts with four per token and NO shared expert, a plain
RMSNorm, a tied vocabulary head) as the policy of a token-level V-trace
actor-critic, from the parameters up: forward, loss, gradients, RMSProp
steps, the bias's update; ONE CHIP'S SHARE of it, as the configuration
states the deployment: of each layer's `router_width` experts the
`experts_held` that start at `first_expert`.

`jax.numpy`, float32, `jax.default_matmul_precision("highest")`, a
Python loop over layers, the convolution as three shifted products, the
attention as a dense masked softmax with the key/value heads repeated,
the experts as a loop over those held, each applied to every token under
a mask; no window, no cache, no sorting, no kernels. It runs eagerly, one
jitted layer application and one head pass at a time. Imports nothing of
the program: what `models/conv_moe_lm.py`, `ops/expert_share.py`,
`agents/convlm.py` and `ops/vtrace.py` compute is held against this file
(tests/test_lfm2_moe.py at a small size on the CPU,
`perfbench/families/convlm.py` at the published widths on the chip), and
`perfbench/references/lfm2_moe.py` is its copy.

The equations (ISSUE 46, Tentpole). Tokens x_1..x_T, D wide:
    N(x; g) = g x / sqrt(mean(x^2) + eps)
    h_0 = E[x];  layer l:  u = h + Mix_l(N(h; g_op)),  h' = u + F_l(N(u; g_ffn))
    logits = N(h_L; g_f) E^T  (tied);  v = N(h_L; g_f) . w_v + b_v
    Mix of a `conv` layer, y = N(h; g_op), t = the step in the episode:
        [B | C | X] = y W_in  (D columns each, in that order);  u = B * X
        c_t = w[:, 0] u_{t-2} + w[:, 1] u_{t-1} + w[:, 2] u_t  (a u before the
        episode's first step is zero);  Mix = (C * c) W_out;  no activation, no bias
    Mix of a `full_attention` layer:
        q_i = N(y W_q; g_q) per head i (32 of 64);  k_j, v_j from y W_kv (8 of 64 each),
        k_j <- N(k_j; g_k);  q, k <- R_t q, R_t k:  R_t turns the pair (m, m + 32) of a
        head by t theta^(-m / 32)  (rotate-half over the whole head)
        s_i(t, j) = q_i(t) . k_{i // 4}(j) / 8,  causal AND same-episode;
        Mix = W_o [sum_j softmax_j(s_i) v_{i // 4}(j)]_i;  no gate, no bias
    F_l of a dense layer: W_d (silu(W_g x) * W_u x)
    F_l of an expert layer, x = N(u; g_ffn):
        s = sigmoid(W_r x) over ALL experts;  I = the top_k of s + b;
        w_i = c s_i / (sum_{j in I} s_j + 1e-6)  (the UNBIASED scores)
        MoE(x) = sum_{i in I, first <= i < first + held} w_i E_i(x);  NO shared expert
    the bias, after each optimizer step, from the tokens n_i that chose
    expert i in the step's forward: b_i <- b_i + gamma sign(mean_j(n_j) - n_i)
Loss: V-trace actor-critic per position (rho-bar = c-bar = 1; IMPALA's
double evaluation over the first / middle views of the unroll),
sum-reduced (`reference/qwen3_next.py`'s).

Layout of the fused matrices, as `models/conv_moe_lm.py` writes it down:
`in_proj` columns B | C | X; `wkv` per key/value head, keys then values;
`wgu`, `expert_wgu` gate | up; `conv_w [D, 3]` oldest tap first.

Departures from the published model, each in
`perfbench/configs/lfm2_moe.json`: what the experts this chip does not
hold would have added is LEFT OUT; a value head; the initialisation.
Every layer is rematerialised: the same arithmetic in the same order.

ROUTING IS DISCONTINUOUS (`reference/qwen3_next.py` says why). `routes`
(`[expert layers, B, T, top_k]` expert ids, the expert layers in order)
makes this file compute on the sets THE PROGRAM chose, with the weights
w_i from its OWN scores; it still says which sets it would have chosen
and by what margin of s + b.

`precision="bfloat16"` computes the same in the nearest precision below
the one the configuration states (bfloat16 parameters, activations,
router, softmax and loss): what the comparison's limits have to refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
TOP_KEYS = ("embed", "final_norm", "w_value", "b_value")
WEIGHT_EPS = 1e-6


def _runs(p) -> list:
    return sorted((k for k in p if k.startswith("run")), key=lambda k: int(k[3:]))


def _unstack(run) -> list:
    return [{k: v[i] for k, v in run.items()} for i in range(run["norms"].shape[0])]


def rekey(program_params, layer_order=None) -> dict:
    """The program's parameters (one `[n, ...]`-stacked dict per run of
    equal layers, `run0`, `run1`, ...) as this file's: one dict per layer,
    in the published order."""
    if "layers" in program_params:  # already this file's
        return program_params
    p = program_params["params"] if "params" in program_params else program_params
    layers = [lp for name in _runs(p) for lp in _unstack(p[name])]
    if layer_order is not None:
        kinds = [layer_kind(lp) for lp in layers]
        if kinds != list(layer_order):
            raise ValueError(f"the parameters hold {kinds}, the configuration "
                             f"says {list(layer_order)}")
    return {"layers": layers, **{k: p[k] for k in TOP_KEYS}}


def stacked(params) -> dict:
    """`rekey`'s inverse: this file's parameters in the program's layout,
    so that the two can be compared leaf by leaf."""
    runs: list = []
    for lp in params["layers"]:
        if runs and layer_kind(runs[-1][0]) == layer_kind(lp):
            runs[-1].append(lp)
        else:
            runs.append([lp])
    p = {f"run{i}": {k: jnp.stack([lp[k] for lp in run]) for k in run[0]}
         for i, run in enumerate(runs)}
    p.update({k: params[k] for k in TOP_KEYS})
    return {"params": p}


def layer_kind(lp) -> str:
    """`mixer+mlp`, read from the leaves the layer holds."""
    return (("conv" if "in_proj" in lp else "full_attention") + "+"
            + ("moe" if "router" in lp else "dense"))


def norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + jnp.asarray(eps, x.dtype)) * g


@jax.jit
def episode_positions(done):
    """([B, T] episode ids, [B, T] positions inside the episode):
    `done[t]` ends an episode AT step t, so t + 1 starts the next."""
    def step(carry, new):
        seg, pos = carry
        seg, pos = seg + new.astype(jnp.int32), jnp.where(new, 0, pos + 1)
        return (seg, pos), (seg, pos)

    zero = jnp.zeros(done.shape[:1], jnp.int32)
    _, (seg, pos) = jax.lax.scan(step, (zero, zero), done[:, :-1].T)
    first = jnp.zeros((done.shape[0], 1), jnp.int32)
    return (jnp.concatenate([first, seg.T], axis=1),
            jnp.concatenate([first, pos.T], axis=1))


def rotary(x, pos, theta):
    """`x [B, T, H, d]`, `pos [B, T]`: the pair (m, m + d / 2) turns by
    pos x theta^(-2m / d) (rotate-half, over the whole head)."""
    half = x.shape[-1] // 2
    m = jnp.arange(half, dtype=F32)
    angle = pos.astype(F32)[..., None, None] * jnp.asarray(theta, F32) ** (-m / half)
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos], axis=-1)


def short_conv(y, lp, pos):
    """The double-gated short convolution on `y [B, T, D]`: three shifted
    products, each tap zero where it would reach before the episode's
    first step -> (Mix, mean |B| + mean |C|)."""
    d = y.shape[-1]
    bcx = y @ lp["in_proj"]
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    u = b * x
    width = lp["conv_w"].shape[-1]
    out = jnp.zeros_like(u)
    for j in range(width):
        back = width - 1 - j  # this tap reads u_{t - back}
        shifted = jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :u.shape[1]]
        out = out + lp["conv_w"][:, j] * jnp.where((pos >= back)[..., None], shifted, 0)
    return (c * out) @ lp["out_proj"], jnp.mean(jnp.abs(b)) + jnp.mean(jnp.abs(c))


def attention(y, lp, seg, pos, hp):
    """Grouped-query attention as a dense masked softmax, the key/value
    heads repeated to the query heads."""
    b, t, _ = y.shape
    heads, kv, hd = hp["num_heads"], hp["num_kv_heads"], hp["head_dim"]
    q = norm((y @ lp["wq"]).reshape(b, t, heads, hd), lp["q_norm"], hp["rms_eps"])
    both = (y @ lp["wkv"]).reshape(b, t, 2 * kv, hd)
    k = norm(both[:, :, :kv], lp["k_norm"], hp["rms_eps"])
    v = both[:, :, kv:]
    q, k = rotary(q, pos, hp["rope_theta"]), rotary(k, pos, hp["rope_theta"])
    k, v = jnp.repeat(k, heads // kv, axis=2), jnp.repeat(v, heads // kv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.asarray(hd ** 0.5, y.dtype)
    steps = jnp.arange(t)
    mask = ((steps[:, None] >= steps[None, :])[None, None]
            & (seg[:, None, :, None] == seg[:, None, None, :]))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, -1) @ lp["wo"]


def swiglu(x, wgu, wd):
    gate, up = jnp.split(x @ wgu, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ wd


def moe(x, lp, hp, routes=None):
    """The expert MLP on `x [B, T, D]` (after its norm) -> (the held
    experts' part, facts). The held experts in a loop, each applied to
    every token and weighted by w_i where the token chose it, by 0 where
    it did not. `routes [B, T, top_k]`: the chosen sets to compute on
    (this file's own where None); the weights are always from this
    file's scores. There is no shared expert."""
    top_k, first, held = hp["top_k"], hp["first_expert"], hp["experts_held"]
    scores = jax.nn.sigmoid(x @ lp["router"])
    biased = scores + jax.lax.stop_gradient(lp["router_bias"])
    ranked = jnp.sort(biased, axis=-1)[..., ::-1]
    _, own = jax.lax.top_k(biased, top_k)
    chosen = own if routes is None else routes.astype(jnp.int32)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = (jnp.asarray(hp["route_scale"], x.dtype) * picked
              / (jnp.sum(picked, axis=-1, keepdims=True)
                 + jnp.asarray(WEIGHT_EPS, x.dtype)))

    def one_expert(acc, xs):
        index, wgu, wd = xs
        w = jnp.sum(jnp.where(chosen == index, weight, 0), axis=-1)  # [B, T]
        return acc + w[..., None] * swiglu(x, wgu, wd), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (first + jnp.arange(held), lp["expert_wgu"], lp["expert_wd"]))
    here = (chosen >= first) & (chosen < first + held)
    experts = scores.shape[-1]
    facts = {"probs": scores, "chosen": own,
             "margin": ranked[..., top_k - 1] - ranked[..., top_k],
             "edge": ranked[..., top_k - 1],
             "same_set": jnp.all(jnp.sort(own, -1) == jnp.sort(chosen, -1), axis=-1),
             "held_pairs": jnp.sum(here),
             "load": jnp.sum(chosen[..., None] == jnp.arange(experts),
                             axis=tuple(range(chosen.ndim)), dtype=jnp.int32)}
    return routed, facts


MODEL_KEYS = ("num_heads", "num_kv_heads", "head_dim", "rope_theta", "top_k",
              "first_expert", "experts_held", "route_scale", "rms_eps")


def _hp_static(hp) -> tuple:
    """What a layer reads of the hyperparameters, hashable for `jax.jit`."""
    return tuple(sorted((k, v) for k, v in hp.items() if k in MODEL_KEYS))


@functools.partial(jax.jit, static_argnames=("hp",))
def _layer(h, lp, seg, pos, routes, *, hp):
    hp = dict(hp)
    mixer, mlp = layer_kind(lp).split("+")
    with jax.default_matmul_precision("highest"):
        y = norm(h, lp["norms"][0], hp["rms_eps"])
        if mixer == "conv":
            mix, gate_abs = short_conv(y, lp, pos)
        else:
            mix, gate_abs = attention(y, lp, seg, pos, hp), None
        u = h + mix
        x = norm(u, lp["norms"][1], hp["rms_eps"])
        if mlp == "dense":
            return u + swiglu(x, lp["wgu"], lp["wd"]), None, gate_abs
        routed, facts = moe(x, lp, hp, routes)
        return u + routed, facts, gate_abs


def layer(h, lp, seg, pos, hp, routes=None):
    """One layer, rematerialised: the backward keeps its input and works
    through one layer's float32 intermediates at a time -> (h', the
    routing facts of an expert layer or None, mean |B| + mean |C| of a
    convolution layer or None)."""
    return jax.checkpoint(functools.partial(_layer, hp=_hp_static(hp)))(
        h, lp, seg, pos, routes)


@functools.partial(jax.jit, static_argnames=("eps",))
def heads(h, p, *, eps):
    """(logits, value) from the last hidden state; the head is the
    embedding, transposed."""
    with jax.default_matmul_precision("highest"):
        z = norm(h, p["final_norm"], eps)
        return z @ p["embed"].T, z @ p["w_value"] + p["b_value"]


def _cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def forward(params, tokens, done, hp, precision="highest", routes=None) -> dict:
    """-> `logits [1, B, T, V]`, `value [1, B, T]` (a leading axis of one
    pass, as `reference/ouro_looplm.py` has R), per expert layer the
    routing facts of `moe`, and `gate_abs`: per convolution layer mean
    |B| + mean |C|. `routes [expert layers, B, T, top_k]`: the sets to
    compute on."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else F32
    p = _cast(rekey(params, hp.get("layer_order")), dtype)
    tokens = jnp.asarray(tokens)
    seg, pos = episode_positions(jnp.asarray(done).astype(bool))
    h = p["embed"][tokens]
    routing, gates = [], []
    for lp in p["layers"]:
        given = None if routes is None or "router" not in lp else jnp.asarray(
            routes[len(routing)])
        h, facts, gate_abs = layer(h, lp, seg, pos, hp, given)
        if facts is not None:
            routing.append(facts)
        if gate_abs is not None:
            gates.append(gate_abs)
    logits, value = heads(
        h, {k: p[k] for k in ("final_norm", "embed", "w_value", "b_value")},
        eps=hp["rms_eps"])
    return {"logits": logits[None], "value": value[None], "routing": routing,
            "gate_abs": gates}


@jax.jit
def vtrace(log_rho, discount, reward, value, bootstrap):
    """`[B, T]` V-trace targets and clipped rhos, rho-bar = c-bar = 1; a
    plain reverse loop (Espeholt et al. 2018, eq. 1)."""
    rho = jnp.minimum(1.0, jnp.exp(log_rho))
    nxt = jnp.concatenate([value[:, 1:], bootstrap[:, None]], axis=1)
    delta = rho * (reward + discount * nxt - value)

    def back(acc, xs):
        d, c = xs
        acc = d + c * acc
        return acc, acc

    _, out = jax.lax.scan(back, jnp.zeros_like(bootstrap),
                          (delta.T, (discount * rho).T), reverse=True)
    return out.T + value, rho


def loss(params, batch: dict, hp, precision="highest", routes=None):
    """V-trace actor-critic per position, sum-reduced -> (total, terms).
    `batch`: `tokens, action [B, T]` int, `behaviour_logp, reward [B, T]`
    float, `done [B, T]` bool."""
    sg = jax.lax.stop_gradient
    out = forward(params, batch["tokens"], batch["done"], hp, precision, routes)
    logp_all = jax.nn.log_softmax(out["logits"][0], axis=-1)  # in `precision`'s dtype
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1).astype(F32)
    logp = jnp.take_along_axis(
        logp_all, batch["action"][..., None], axis=-1)[..., 0].astype(F32)
    v = out["value"][0].astype(F32)
    reward = batch["reward"].astype(F32)
    if hp["reward_clipping"] == "abs_one":
        reward = jnp.clip(reward, -1.0, 1.0)
    disc = (~batch["done"].astype(bool)).astype(F32) * hp["discount"]
    first = lambda x: x[..., :-2]
    middle = lambda x: x[..., 1:-1]
    last = lambda x: x[..., 2:]
    mu = batch["behaviour_logp"].astype(F32)
    vs, rho = vtrace(sg(first(logp) - first(mu)), first(disc), first(reward),
                     sg(first(v)), sg(middle(v)[:, -1]))
    vs1, _ = vtrace(sg(middle(logp) - middle(mu)), middle(disc), middle(reward),
                    sg(middle(v)), sg(last(v)[:, -1]))
    adv = sg(rho * (first(reward) + first(disc) * vs1 - first(v)))
    pi = -adv * first(logp)
    vl = 0.5 * jnp.square(sg(vs) - first(v))
    total = jnp.sum(pi + hp["baseline_loss_coef"] * vl
                    - hp["entropy_coef"] * first(entropy))
    routing = sg({k: jnp.stack([r[k] for r in out["routing"]]).astype(
        F32 if k in ("probs", "margin", "edge") else jnp.int32)
        for k in out["routing"][0]})  # every leaf [expert layers, ...]
    probs = routing["probs"]
    gates = sg(jnp.stack(out["gate_abs"]).astype(F32)) if out["gate_abs"] else None
    terms = {"total_loss": total, "pi_loss": jnp.sum(pi),
             "baseline_loss": jnp.sum(vl), "entropy": jnp.sum(first(entropy)),
             "pi_scale": jnp.sum(jnp.abs(pi)),
             "logits": out["logits"], "value": out["value"].astype(F32),
             "logp": logp[None],
             "router_score_mean": jnp.mean(probs),
             "held_pair_share": jnp.sum(routing["held_pairs"])
             / (probs.shape[0] * probs.shape[1] * probs.shape[2] * hp["top_k"]),
             "conv_gate_abs_mean": jnp.zeros(()) if gates is None
             else jnp.mean(gates) / 2,
             "router_load": routing["load"], "routing": routing}
    return total, terms


def clip_scale(grads, hp):
    """(global norm of `grads`, the factor that clips it to the
    configuration's `gradient_clip_norm`)."""
    norm_ = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    return norm_, jnp.minimum(1.0, hp["gradient_clip_norm"]
                              / jnp.maximum(norm_, 1e-30))


def learning_rate(step: int, hp) -> float:
    """The configuration's schedule: linear from `learning_rate` to
    `end_learning_rate` over `learning_frame` steps."""
    left = 1.0 - min(step, hp["learning_frame"]) / hp["learning_frame"]
    return ((hp["learning_rate"] - hp["end_learning_rate"]) * left
            + hp["end_learning_rate"])


def rmsprop_leaf(p, nu, g, lr):
    """RMSProp on one leaf (decay 0.99, eps 0.1 inside the root, no
    momentum) -> (parameter as its OWN precision keeps it, second
    moment)."""
    nu = 0.99 * nu + 0.01 * jnp.square(g)
    return (p.astype(F32) - lr * g / jnp.sqrt(nu + 0.1)).astype(p.dtype), nu


def step_over_last_bit(p, nu, g, lr) -> jax.Array:
    """The largest step of `rmsprop_leaf` on this leaf in units of the
    spacing of float32 at the parameter it moves: under 1/2 everywhere,
    the step is rounded away and the leaf stays where it is."""
    nu = 0.99 * nu + 0.01 * jnp.square(g)
    p = jnp.abs(p.astype(F32))
    return jnp.max(jnp.abs(lr * g / jnp.sqrt(nu + 0.1))
                   / (jnp.nextafter(p, jnp.inf) - p))


def rmsprop_step(params, nu, grads, hp, step: int):
    """Optimizer step number `step` (from 0) of the configuration: clip
    by global norm, RMSProp, times the schedule's learning rate ->
    (params, nu). `nu` starts at 1 (`nu=None`). The selection bias has
    no gradient and stays: `bias_step` moves it."""
    _, scale = clip_scale(grads, hp)
    lr = learning_rate(step, hp)
    leaves, tree = jax.tree.flatten(params)
    nus = jax.tree.leaves(nu) if nu is not None else [1.0] * len(leaves)
    out = [rmsprop_leaf(p, n, g * scale, lr)
           for p, n, g in zip(leaves, nus, jax.tree.leaves(grads))]
    return (jax.tree.unflatten(tree, [o[0] for o in out]),
            jax.tree.unflatten(tree, [o[1] for o in out]))


def bias_step(params, load, hp) -> dict:
    """The selection bias after a step whose forward counted `load
    [expert layers, E]` tokens an expert: b_i + gamma sign(mean_j(n_j) -
    n_i), layer by layer. `params` in this file's layout."""
    load = jnp.asarray(load, F32)
    move = hp["bias_update_speed"] * jnp.sign(
        jnp.mean(load, axis=-1, keepdims=True) - load)
    layers, at = [], 0
    for lp in params["layers"]:
        if "router" in lp:
            lp = {**lp, "router_bias": lp["router_bias"] + move[at]}
            at += 1
        layers.append(lp)
    return {**params, "layers": layers}


def biases(params) -> list:
    """Every selection bias `[E]`, in `bias_step`'s order."""
    return [lp["router_bias"] for lp in params["layers"] if "router" in lp]


def rmsprop_update_norm(params, grads, hp) -> jax.Array:
    """Global norm of the parameters' change in the FIRST step of the
    configuration's optimizer (second moment started at 1), leaf by leaf:
    the change is what the parameters' OWN precision keeps of it."""
    _, scale = clip_scale(grads, hp)
    sq = 0.0
    for p, g in zip(jax.tree.leaves(params), jax.tree.leaves(grads)):
        moved = rmsprop_leaf(p, 1.0, g * scale, learning_rate(0, hp))[0] - p
        sq = sq + jnp.sum(jnp.square(moved.astype(F32)))
    return jnp.sqrt(sq)


def loss_and_grads(params, batch: dict, hp, precision="highest", routes=None):
    """-> (the terms of `loss`, float32 gradients in `params`' layout).
    The loss is a sum over rows and V-trace runs along a row, so the
    gradients of a batch are the sums of those of its blocks of rows."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else F32
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        (_, terms), grads = jax.value_and_grad(
            lambda q: loss(q, batch, hp, precision, routes),
            has_aux=True)(_cast(rekey(params, hp.get("layer_order")), dtype))
    return terms, _cast(grads, F32)


def evaluate(params, batch: dict, hp, precision="highest", routes=None) -> dict:
    """Logits, values, taken-action log-probability, the routing facts,
    the loss terms, the gradients' global norm, the norm of the first
    optimizer step's change and the biases after it. `params` in this
    file's layout or the program's."""
    p = _cast(rekey(params, hp.get("layer_order")),
              jnp.bfloat16 if precision == "bfloat16" else F32)
    terms, grads = loss_and_grads(p, batch, hp, precision, routes)
    out = {**terms, "grads": grads, "grad_norm": clip_scale(grads, hp)[0],
           "update_norm": rmsprop_update_norm(p, grads, hp),
           "biases": biases(bias_step(p, terms["router_load"], hp))}
    return jax.device_get(out)


def logp_of(logits, action) -> jax.Array:
    """Float32 log-softmax of `logits [..., V]` at `action [...]`."""
    return jnp.take_along_axis(
        jax.nn.log_softmax(jnp.asarray(logits, F32), axis=-1),
        jnp.asarray(action)[..., None], axis=-1)[..., 0]


def taken_logp(params, tokens, action, done, hp, routes=None) -> jax.Array:
    """log pi(a_t | x_<=t) `[B, T]` from the full forward: what acting
    through the windows and the cache must reproduce."""
    with jax.default_matmul_precision("highest"):
        return logp_of(forward(params, tokens, done, hp, routes=routes
                               )["logits"][0], action)

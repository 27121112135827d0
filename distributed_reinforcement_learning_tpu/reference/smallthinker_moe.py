"""Plain reference of the `smallthinker_moe` configuration: PowerInfer
SmallThinker-21BA3B-Instruct (`huggingface.co/PowerInfer/
SmallThinker-21BA3B-Instruct` config.json, `model_name`
smallthinker_21b_instruct; arXiv:2507.20984: one GLOBAL attention layer
with no positional encoding to three SLIDING-WINDOW layers with rotary
positions, grouped-query heads, in every layer a softmax router that reads
the layer's INPUT, before attention, over all of the layer's experts with
six per token, ReGLU experts and NO shared expert, a plain RMSNorm, an
untied vocabulary head) as the policy of a token-level V-trace
actor-critic, from the parameters up: forward, loss, gradients, RMSProp
steps; ONE CHIP'S SHARE of it, as the configuration states the
deployment: of each layer's `router_width` experts the `experts_held`
that start at `first_expert`.

`jax.numpy`, float32, `jax.default_matmul_precision("highest")`, a
Python loop over layers, the attention as a dense masked softmax under a
`[T, T]` boolean mask per layer kind with the rotary written out and the
key/value heads repeated, the experts as a loop over those held, each
applied to every token under a mask, with `relu`; no kernel, no cache, no
ring, no sorting, no row block. It runs eagerly, one jitted layer
application and one head pass at a time. Imports nothing of the program:
what `models/window_moe_lm.py`, `ops/expert_share.py`, `ops/attention.py`,
`ops/pallas/attention.py`, `agents/swalm.py` and `ops/vtrace.py` compute
is held against this file (tests/test_smallthinker_moe.py at a small size
on the CPU, `perfbench/families/swalm.py` at the published widths on the
chip), and `perfbench/references/smallthinker_moe.py` is its copy.

The equations (ISSUE 49, Tentpole). Tokens x_1..x_T, D wide:
    N(x; s) = s x / sqrt(mean(x^2) + eps)
    h_0 = E[x];  layer l, input h:
        r = h W_r                     the router's logits from the layer's INPUT,
                                      before attention and before any norm
        y = N(h; s_att);  q_i = y W_q per head i (28 of 128);  k_j, v_j from y W_kv
            (4 of 128 each); no bias, no per-head norm
        a window layer: q, k <- R_t q, R_t k: R_t turns the pair (m, m + 64) of a
            head by t theta^(-m / 64) (rotate-half over the whole head), t = the
            step in the episode; key j visible to query t iff j <= t, t - j < W
            and same episode
        a global layer: NO rotation; key j visible iff j <= t and same episode
        s_i(t, j) = q_i(t) . k_{i // 7}(j) / sqrt(128);
        u = h + W_o [sum_j softmax_j(s_i) v_{i // 7}(j)]_i
        x = N(u; s_ffn);  I = the top_k largest of r;  w = softmax(r_I)
        h' = u + sum_{i in I, first <= i < first + held} w_i W_d,i (relu(W_g,i x) * W_u,i x)
    logits = N(h_L; s_f) W_head  (untied);  v = N(h_L; s_f) . w_v + b_v
Loss: V-trace actor-critic per position (rho-bar = c-bar = 1; IMPALA's
double evaluation over the first / middle views of the unroll),
sum-reduced (`reference/qwen3_next.py`'s).

Layout of the fused matrices, as `models/window_moe_lm.py` writes it
down: `wkv` per key/value head, keys then values; `expert_wgu` gate | up.

Departures from the published description, each in
`perfbench/configs/smallthinker_moe.json`: what the experts this chip
does not hold would have added is LEFT OUT; one level of experts (the
row's `config` has no key for a second); a value head; the
initialisation. So that a row of 8,192 positions fits a chip beside
float32 parameters and gradients, the backward keeps little and makes the
rest again (the same arithmetic): every layer is rematerialised, a
layer's attention runs in blocks of `QUERY_BLOCK` queries (each against
all keys under its rows of the mask), an expert's gate and up are made
again in the loop's backward, and the head with its log-softmax runs in
blocks of `HEAD_BLOCK` positions.

ROUTING IS DISCONTINUOUS (`reference/qwen3_next.py` says why). `routes`
(`[layers, B, T, top_k]` expert ids) makes this file compute on the sets
THE PROGRAM chose, with the weights w_i from its OWN logits; it still says
which sets it would have chosen and by what margin of the probabilities.

`precision="bfloat16"` computes the same in the nearest precision below
the one the configuration states (bfloat16 parameters, activations,
router, softmax and loss): what the comparison's limits have to refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
TOP_KEYS = ("embed", "head", "final_norm", "w_value", "b_value")
QUERY_BLOCK = 256


def _runs(p) -> list:
    return sorted((k for k in p if k.startswith("run")), key=lambda k: int(k[3:]))


def _unstack(run) -> list:
    return [{k: v[i] for k, v in run.items()} for i in range(run["norms"].shape[0])]


def rekey(program_params, layer_order=None) -> dict:
    """The program's parameters (one `[n, ...]`-stacked dict per run of
    equal layers, `run0`, `run1`, ...) as this file's: one dict per layer,
    in the published order. A layer's kind is not in its leaves (both
    kinds hold the same matrices): it is `layer_order`'s, which `forward`
    reads from the hyperparameters."""
    if "layers" in program_params:  # already this file's
        return program_params
    p = program_params["params"] if "params" in program_params else program_params
    layers = [lp for name in _runs(p) for lp in _unstack(p[name])]
    if layer_order is not None and len(layers) != len(layer_order):
        raise ValueError(f"the parameters hold {len(layers)} layers, the "
                         f"configuration says {list(layer_order)}")
    return {"layers": layers, **{k: p[k] for k in TOP_KEYS}}


def stacked(params, layer_order) -> dict:
    """`rekey`'s inverse: this file's parameters in the program's layout
    (the runs of equal kinds of `layer_order`), so that the two can be
    compared leaf by leaf."""
    runs: list = []
    for kind, lp in zip(layer_order, params["layers"]):
        if runs and runs[-1][0] == kind:
            runs[-1][1].append(lp)
        else:
            runs.append((kind, [lp]))
    p = {f"run{i}": {k: jnp.stack([lp[k] for lp in run]) for k in run[0]}
         for i, (_, run) in enumerate(runs)}
    p.update({k: params[k] for k in TOP_KEYS})
    return {"params": p}


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


def attention(kind, y, lp, seg, pos, hp):
    """Grouped-query attention as a dense masked softmax, the key/value
    heads repeated to the query heads. `kind` `window`: rotary on q and
    k, and a key further back than `window - 1` steps is masked; `global`:
    no positions at all, every earlier key of the episode visible."""
    b, t, _ = y.shape
    heads, kv, hd = hp["num_heads"], hp["num_kv_heads"], hp["head_dim"]
    q = (y @ lp["wq"]).reshape(b, t, heads, hd)
    both = (y @ lp["wkv"]).reshape(b, t, 2 * kv, hd)
    k, v = both[:, :, :kv], both[:, :, kv:]
    if kind == "window":
        q, k = rotary(q, pos, hp["rope_theta"]), rotary(k, pos, hp["rope_theta"])
    k, v = jnp.repeat(k, heads // kv, axis=2), jnp.repeat(v, heads // kv, axis=2)
    steps = jnp.arange(t)

    @jax.checkpoint
    def block(q_blk, q_steps, q_seg):
        """`q_blk [B, Q, H, d]` against all keys."""
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / jnp.asarray(hd ** 0.5, y.dtype)
        mask = ((q_steps[:, None] >= steps[None, :])[None, None]
                & (q_seg[:, None, :, None] == seg[:, None, None, :]))
        if kind == "window":
            mask &= (q_steps[:, None] - steps[None, :] < hp["window"])[None, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    n = t // QUERY_BLOCK if t % QUERY_BLOCK == 0 else 1
    out = jax.lax.map(
        lambda xs: block(*xs),
        (jnp.moveaxis(q.reshape(b, n, t // n, heads, hd), 1, 0),
         steps.reshape(n, t // n), jnp.moveaxis(seg.reshape(b, n, t // n), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, -1) @ lp["wo"]


def reglu(x, wgu, wd):
    """-> (W_d (relu(W_g x) * W_u x), the gate values that ReLU zeroed, a row)."""
    gate, up = jnp.split(x @ wgu, 2, axis=-1)
    return (jax.nn.relu(gate) * up) @ wd, jnp.sum(gate <= 0, axis=-1)


def router(h, lp, hp, routes=None):
    """The router on the layer's INPUT `h [B, T, D]` -> (the sets to
    compute on `[B, T, top_k]`: `routes`, this file's own where None;
    their weights, always from this file's logits: the softmax over the
    chosen logits, which is the softmax over all renormalised over the
    chosen; facts)."""
    top_k = hp["top_k"]
    probs = jax.nn.softmax(h @ lp["router"], axis=-1)
    ranked = jnp.sort(probs, axis=-1)[..., ::-1]
    _, own = jax.lax.top_k(probs, top_k)
    chosen = own if routes is None else routes.astype(jnp.int32)
    picked = jnp.take_along_axis(probs, chosen, axis=-1)
    weight = picked / jnp.sum(picked, axis=-1, keepdims=True)
    experts = probs.shape[-1]
    facts = {"probs": probs, "chosen": own,
             "margin": ranked[..., top_k - 1] - ranked[..., top_k],
             "edge": ranked[..., top_k - 1],
             "same_set": jnp.all(jnp.sort(own, -1) == jnp.sort(chosen, -1), axis=-1),
             "load": jnp.sum(chosen[..., None] == jnp.arange(experts),
                             axis=tuple(range(chosen.ndim)), dtype=jnp.int32)}
    return chosen, weight, facts


def moe(x, chosen, weight, lp, hp):
    """The expert MLP on `x [B, T, D]` (after its norm) with the sets the
    router chose AHEAD of attention -> (the held experts' part, facts).
    The held experts in a loop, each applied to every token and weighted
    by w_i where the token chose it, by 0 where it did not. There is no
    shared expert."""
    first, held = hp["first_expert"], hp["experts_held"]

    def one_expert(acc, xs):
        routed, zeroed = acc
        index, wgu, wd = xs
        mine = jnp.any(chosen == index, axis=-1)  # [B, T]
        w = jnp.sum(jnp.where(chosen == index, weight, 0), axis=-1)
        y, zeros = reglu(x, wgu, wd)
        return (routed + w[..., None] * y,
                zeroed + jnp.sum(jnp.where(mine, zeros, 0))), None

    # an expert's gate and up (`[B, T, 2 F]` each of 16) are made again in the
    # backward, not kept: at T = 8,192 they are 2.3 GB a layer
    (routed, zeroed), _ = jax.lax.scan(
        jax.checkpoint(one_expert, prevent_cse=False),
        (jnp.zeros_like(x), jnp.zeros((), jnp.int32)),
        (first + jnp.arange(held), lp["expert_wgu"], lp["expert_wd"]))
    here = (chosen >= first) & (chosen < first + held)
    return routed, {"held_pairs": jnp.sum(here), "gate_zeroed": zeroed}


MODEL_KEYS = ("num_heads", "num_kv_heads", "head_dim", "rope_theta", "window",
              "top_k", "first_expert", "experts_held", "rms_eps")


def _hp_static(hp) -> tuple:
    """What a layer reads of the hyperparameters, hashable for `jax.jit`."""
    return tuple(sorted((k, v) for k, v in hp.items() if k in MODEL_KEYS))


@functools.partial(jax.jit, static_argnames=("kind", "hp"))
def _layer(h, lp, seg, pos, routes, *, kind, hp):
    hp = dict(hp)
    with jax.default_matmul_precision("highest"):
        chosen, weight, facts = router(h, lp, hp, routes)
        y = norm(h, lp["norms"][0], hp["rms_eps"])
        u = h + attention(kind, y, lp, seg, pos, hp)
        x = norm(u, lp["norms"][1], hp["rms_eps"])
        routed, counts = moe(x, chosen, weight, lp, hp)
        return u + routed, {**facts, **counts}


def layer(kind, h, lp, seg, pos, hp, routes=None):
    """One layer, rematerialised: the backward keeps its input and works
    through one layer's float32 intermediates at a time -> (h', the
    routing facts)."""
    return jax.checkpoint(functools.partial(_layer, kind=kind, hp=_hp_static(hp)))(
        h, lp, seg, pos, routes)


@functools.partial(jax.jit, static_argnames=("eps",))
def heads(h, p, *, eps):
    """(logits, value) from the last hidden state; the head is untied."""
    with jax.default_matmul_precision("highest"):
        z = norm(h, p["final_norm"], eps)
        return z @ p["head"].T, z @ p["w_value"] + p["b_value"]


HEAD_BLOCK = 1024


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_stats(h, p, action, *, eps):
    with jax.default_matmul_precision("highest"):
        z = norm(h, p["final_norm"], eps)
        logp_all = jax.nn.log_softmax(z @ p["head"].T, axis=-1)  # in `h`'s dtype
        entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1).astype(F32)
        logp = jnp.take_along_axis(logp_all, action[..., None], axis=-1)[..., 0]
        return logp.astype(F32), entropy, (z @ p["w_value"] + p["b_value"]).astype(F32)


def head_stats(h, p, action, eps):
    """(log pi(a_t), the policy's entropy, the value) `[B, T]` float32 from
    the last hidden state, the log-softmax in `h`'s dtype: `heads` and a
    log-softmax over the slice of the vocabulary, in blocks of
    `HEAD_BLOCK` positions, each rematerialised (the same arithmetic: at
    T = 8,192 a row's logits, their log-softmax and its exponential are
    1.24 GB each, and the backward would keep all three)."""
    t = h.shape[1]
    size = HEAD_BLOCK if t % HEAD_BLOCK == 0 else t
    block = jax.checkpoint(functools.partial(_head_stats, eps=eps))
    outs = [block(h[:, i:i + size], p, action[:, i:i + size])
            for i in range(0, t, size)]
    return tuple(jnp.concatenate(x, axis=1) for x in zip(*outs))


def _cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def forward(params, tokens, done, hp, precision="highest", routes=None,
            logits: bool = True) -> dict:
    """-> `logits [1, B, T, V]`, `value [1, B, T]` (a leading axis of one
    pass, as `reference/ouro_looplm.py` has R; only if `logits`), `hidden
    [B, T, D]` (the last hidden state, with `head`: its parameters) and
    per layer the routing facts of `router` and `moe`.
    `hp["layer_order"]`: every layer's kind (`global` | `window`). `routes
    [layers, B, T, top_k]`: the sets to compute on."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else F32
    p = _cast(rekey(params, hp["layer_order"]), dtype)
    tokens = jnp.asarray(tokens)
    seg, pos = episode_positions(jnp.asarray(done).astype(bool))
    h = p["embed"][tokens]
    routing = []
    for i, (kind, lp) in enumerate(zip(hp["layer_order"], p["layers"])):
        given = None if routes is None else jnp.asarray(routes[i])
        h, facts = layer(kind, h, lp, seg, pos, hp, given)
        routing.append(facts)
    head = {k: p[k] for k in ("final_norm", "head", "w_value", "b_value")}
    out = {"hidden": h, "head": head, "routing": routing}
    if logits:
        every, value = heads(h, head, eps=hp["rms_eps"])
        out.update(logits=every[None], value=value[None])
    return out


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


def loss(params, batch: dict, hp, precision="highest", routes=None,
         logits: bool = True):
    """V-trace actor-critic per position, sum-reduced -> (total, terms;
    `logits` among them only if asked). `batch`: `tokens, action [B, T]`
    int, `behaviour_logp, reward [B, T]` float, `done [B, T]` bool."""
    sg = jax.lax.stop_gradient
    out = forward(params, batch["tokens"], batch["done"], hp, precision, routes,
                  logits=False)
    # the log-softmax in `precision`'s dtype
    logp, entropy, v = head_stats(out["hidden"], out["head"], batch["action"],
                                  hp["rms_eps"])
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
        for k in out["routing"][0]})  # every leaf [layers, ...]
    probs = routing["probs"]
    held = jnp.sum(routing["held_pairs"])
    terms = {"total_loss": total, "pi_loss": jnp.sum(pi),
             "baseline_loss": jnp.sum(vl), "entropy": jnp.sum(first(entropy)),
             "pi_scale": jnp.sum(jnp.abs(pi)),
             "value": v[None], "logp": logp[None],
             "held_pair_share": held
             / (probs.shape[0] * probs.shape[1] * probs.shape[2] * hp["top_k"]),
             "relu_gate_zero_share": jnp.sum(routing["gate_zeroed"]).astype(F32)
             / jnp.maximum(held.astype(F32) * params_width(params), 1.0),
             "router_load": routing["load"], "routing": routing}
    if logits:  # beside the loss, not under its gradient
        terms["logits"] = heads(sg(out["hidden"]), sg(out["head"]),
                                eps=hp["rms_eps"])[0][None]
    return total, terms


def params_width(params) -> int:
    """F, the experts' width, read from the parameters."""
    return rekey(params)["layers"][0]["expert_wd"].shape[-2]


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
    (params, nu). `nu` starts at 1 (`nu=None`)."""
    _, scale = clip_scale(grads, hp)
    lr = learning_rate(step, hp)
    leaves, tree = jax.tree.flatten(params)
    nus = jax.tree.leaves(nu) if nu is not None else [1.0] * len(leaves)
    out = [rmsprop_leaf(p, n, g * scale, lr)
           for p, n, g in zip(leaves, nus, jax.tree.leaves(grads))]
    return (jax.tree.unflatten(tree, [o[0] for o in out]),
            jax.tree.unflatten(tree, [o[1] for o in out]))


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


def loss_and_grads(params, batch: dict, hp, precision="highest", routes=None,
                   logits: bool = True):
    """-> (the terms of `loss`, float32 gradients in `params`' layout).
    The loss is a sum over rows and V-trace runs along a row, so the
    gradients of a batch are the sums of those of its blocks of rows."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else F32
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        (_, terms), grads = jax.value_and_grad(
            lambda q: loss(q, batch, hp, precision, routes, logits),
            has_aux=True)(_cast(rekey(params, hp["layer_order"]), dtype))
    return terms, _cast(grads, F32)


def evaluate(params, batch: dict, hp, precision="highest", routes=None) -> dict:
    """Logits, values, taken-action log-probability, the routing facts,
    the loss terms, the gradients' global norm and the norm of the first
    optimizer step's change. `params` in this file's layout or the
    program's."""
    p = _cast(rekey(params, hp["layer_order"]),
              jnp.bfloat16 if precision == "bfloat16" else F32)
    terms, grads = loss_and_grads(p, batch, hp, precision, routes)
    out = {**terms, "grads": grads, "grad_norm": clip_scale(grads, hp)[0],
           "update_norm": rmsprop_update_norm(p, grads, hp)}
    return jax.device_get(out)


def logp_of(logits, action) -> jax.Array:
    """Float32 log-softmax of `logits [..., V]` at `action [...]`."""
    return jnp.take_along_axis(
        jax.nn.log_softmax(jnp.asarray(logits, F32), axis=-1),
        jnp.asarray(action)[..., None], axis=-1)[..., 0]


def taken_logp(params, tokens, action, done, hp, routes=None) -> jax.Array:
    """log pi(a_t | x_<=t) `[B, T]` from the full forward: what acting
    through the rings and the global cache must reproduce."""
    with jax.default_matmul_precision("highest"):
        return logp_of(forward(params, tokens, done, hp, routes=routes
                               )["logits"][0], action)

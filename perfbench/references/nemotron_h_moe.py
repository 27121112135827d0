"""Plain reference of the `nemotron_h_moe` configuration: NVIDIA
Nemotron-3-Nano-30B-A3B (`huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`
config.json, `model_type` nemotron_h; the family is Nemotron-H,
arXiv:2504.03624: layers that are ONE sublayer each, of three kinds in a
published string, a Mamba-2 mixer with eight B/C groups, a sigmoid-scored
router with a selection bias over all of a layer's UNGATED relu^2 experts
beside one shared expert, grouped-query attention WITHOUT positions, a
plain RMSNorm, an untied vocabulary head) as the policy of a token-level
V-trace actor-critic, from the parameters up: forward, loss, gradients,
RMSProp steps, the bias's update; ONE CHIP'S SHARE of it, as the
configuration states the deployment: of each layer's `router_width`
experts the `experts_held` that start at `first_expert`.

`jax.numpy`, float32, `jax.default_matmul_precision("highest")`, a
Python loop over layers, the state-space layer as the STEP-BY-STEP
recurrence with B and C indexed by group (a `lax.scan` over t: the
chunked form is the code under test), the grouped norm written out, the
convolution as four shifted multiplies, attention as a dense masked
softmax with the key/value heads repeated, the experts as a loop over
those held with `relu(.) ** 2`, each applied to every token under a mask,
the shared expert; no cache, no chunks, no sorting, no kernels. It runs
eagerly, one jitted layer application and one head pass at a time.
Imports nothing of the program: what `models/ssm_moe_lm.py`,
`ops/ssd.py`, `ops/expert_share.py`, `agents/ssmoelm.py` and
`ops/vtrace.py` compute is held against this file
(tests/test_nemotron_h_moe.py at a small size on the CPU,
`perfbench/families/ssmoelm.py` at the published widths on the chip), and
`perfbench/references/nemotron_h_moe.py` is its copy.

The equations (ISSUE 53, Tentpole). Tokens x_1..x_T, D wide, layer l of
the kind the l-th character of `hybrid_override_pattern` says:
    N(x; g) = g x / sqrt(mean(x^2) + eps)
    h_0 = E[x];   h_{l+1} = h_l + Mix_k(N(h_l; g_l));   z = N(h_L; g_f)
    logits = z W_head  (untied);   v = z . w_v + b_v
    `M`, Mamba-2, y the normed input, H heads of P, G groups, state N:
        [z | xBC | dt] = y W_in;   xBC_t = silu(b_c + sum_j w_c[:, j] xBC_{t-3+j}),
        zeros before the episode's first step;   [x | B | C] = xBC, B and C [G, N]
        dt_t = softplus(dt_t + dt_bias)  (NO clamp);   A = -exp(A_log);   g(h) = h // (H / G)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t[g(h)], S = 0 before the
        episode's first step;   y_t = S_t C_t[g(h)] + D_h x_t
        Mix = W_out (g_n * u / rms_group(u)),  u = y * silu(z): the mean
        square over each group's d_inner / G channels, the gate BEFORE the norm
    `*`, attention: q of `num_heads`, k and v of `num_kv_heads` heads of d,
        query head i reads key/value head i // (heads / KV),
        softmax(q k^T / sqrt(d)) under the causal AND same-episode mask,
        NO position term;  Mix = W_o [...]
    `E`, sparse experts on y:  s = sigmoid(y W_r) over ALL experts;
        I = the top_k of s + b;   w_i = c s_i / (sum_{j in I} s_j + 1e-20)
        E_i(y) = W_d,i relu(W_u,i y)^2  (UNGATED);   E_s the shared expert, the
        same form at its own width, under no gate
        Mix = sum_{i in I, first <= i < first + held} w_i E_i(y) + E_s(y)
    the bias, after each optimizer step, from the tokens n_i that chose
    expert i in the step's forward: b_i <- b_i + gamma sign(mean_j(n_j) - n_i)
Loss: V-trace actor-critic per position (rho-bar = c-bar = 1; IMPALA's
double evaluation over the first / middle views of the unroll),
sum-reduced (`reference/qwen3_next.py`'s).

Layout of the fused matrices, as `models/ssm_moe_lm.py` writes it down:
`in_proj` columns z | x | B (group-major) | C (group-major) | dt; `wkv`
per key/value head, keys then values; `conv_w [C, 4]` oldest tap first.

Departures from the published model, each in
`perfbench/configs/nemotron_h_moe.json`: what the experts this chip does
not hold would have added is LEFT OUT; a value head; the initialisation.
The scan over t is a scan of blocks of steps whose body is rematerialised
(`SCAN_BLOCK`), and so is every layer: the same arithmetic in the same
order.

ROUTING IS DISCONTINUOUS (`reference/qwen3_next.py` says why). `routes`
(`[expert layers, B, T, top_k]` expert ids, the expert layers in order)
makes this file compute on the sets THE PROGRAM chose, with the weights
w_i from its OWN scores; it still says which sets it would have chosen
and by what margin of s + b.

`precision="bfloat16"` computes the same in the nearest precision below
the one the configuration states (bfloat16 parameters, activations,
recurrent state, router, softmax and loss): what the comparison's limits
have to refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
TOP_KEYS = ("embed", "head", "final_norm", "w_value", "b_value")
WEIGHT_EPS = 1e-20
SCAN_BLOCK = 32  # steps of the recurrence whose states the backward keeps


def _runs(p) -> list:
    return sorted((k for k in p if k.startswith("run")), key=lambda k: int(k[3:]))


def _unstack(run) -> list:
    return [{k: v[i] for k, v in run.items()} for i in range(run["norms"].shape[0])]


def layer_kind(lp) -> str:
    """The layer's character in `hybrid_override_pattern`, read from the
    leaves it holds."""
    return "M" if "in_proj" in lp else "E" if "router" in lp else "*"


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


def causal_conv(xbc, w, b, pos):
    """Depthwise causal convolution of width K as K shifted multiplies:
    out_t = b + sum_j w[:, j] x_{t-(K-1)+j}, a tap before the episode's
    first step reads zero."""
    width = w.shape[1]
    out = jnp.broadcast_to(b, xbc.shape)
    for j in range(width):
        back = width - 1 - j
        shifted = jnp.pad(xbc, ((0, 0), (back, 0), (0, 0)))[:, :xbc.shape[1]]
        out = out + w[:, j] * jnp.where((pos >= back)[..., None], shifted, 0)
    return out


def recurrence(x, dt, a, bmat, cmat, start):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t[g(h)] (S_{t-1} = 0
    where `start[t]`), y_t = S_t C_t[g(h)], one step at a time, head h
    reading group g(h) = h // (H / G). `x [B, T, H, P]`, `dt [B, T, H]`,
    `a [H]`, `bmat, cmat [B, T, G, N]`, `start [B, T]` -> (`y [B, T, H,
    P]`, the state after the last step `[B, H, P, N]`); everything in
    `x`'s dtype."""
    b, t, h, p = x.shape
    per_group = h // bmat.shape[2]
    block = max(d for d in range(1, min(SCAN_BLOCK, t) + 1) if t % d == 0)

    def step(state, xs):
        x_t, dt_t, b_t, c_t, start_t = xs
        b_h = jnp.repeat(b_t, per_group, axis=1)  # [B, H, N]: head h <- group h // (H / G)
        c_h = jnp.repeat(c_t, per_group, axis=1)
        state = jnp.where(start_t[:, None, None, None], 0, state)
        decay = jnp.exp(dt_t * a)  # [B, H]
        state = (decay[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        return state.astype(x.dtype), jnp.einsum("bhpn,bhn->bhp", state, c_h)

    @jax.checkpoint
    def steps(state, xs):
        return jax.lax.scan(step, state, xs)

    blocks = lambda v: jnp.moveaxis(v, 1, 0).reshape(t // block, block,
                                                     *v.shape[:1], *v.shape[2:])
    state, y = jax.lax.scan(
        steps, jnp.zeros((b, h, p, bmat.shape[-1]), x.dtype),
        tuple(blocks(v) for v in (x, dt, bmat, cmat, start)))
    return jnp.moveaxis(y.reshape(t, b, h, p), 0, 1), state


def mamba(y, lp, pos, hp):
    """-> (the mixer's output `[B, T, D]`, the state after the last step
    `[B, H, P, N]`, dt `[B, T, H]`)."""
    b, t, _ = y.shape
    h, p = hp["mamba_heads"], hp["mamba_head_dim"]
    g, n = hp["mamba_groups"], hp["mamba_state"]
    z, xbc, dt = jnp.split(y @ lp["in_proj"], [h * p, 2 * h * p + 2 * g * n], -1)
    xbc = jax.nn.silu(causal_conv(xbc, lp["conv_w"], lp["conv_b"], pos))
    x, bmat, cmat = jnp.split(xbc, [h * p, h * p + g * n], -1)
    x = x.reshape(b, t, h, p)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    ssm, state = recurrence(x, dt, -jnp.exp(lp["A_log"]), bmat.reshape(b, t, g, n),
                            cmat.reshape(b, t, g, n), pos == 0)
    u = ((ssm + lp["D"][:, None] * x).reshape(b, t, h * p) * jax.nn.silu(z)
         ).reshape(b, t, g, h * p // g)
    u = u / jnp.sqrt(jnp.mean(jnp.square(u), axis=-1, keepdims=True)
                     + jnp.asarray(hp["rms_eps"], u.dtype))
    return (u.reshape(b, t, h * p) * lp["gate_norm"]) @ lp["out_proj"], state, dt


def attention(y, lp, seg, hp):
    """Grouped-query attention without positions as a dense masked
    softmax, the key/value heads repeated to the query heads."""
    b, t, _ = y.shape
    heads, kv, hd = hp["num_heads"], hp["num_kv_heads"], hp["head_dim"]
    q = (y @ lp["wq"]).reshape(b, t, heads, hd)
    both = (y @ lp["wkv"]).reshape(b, t, 2 * kv, hd)
    k = jnp.repeat(both[:, :, :kv], heads // kv, axis=2)
    v = jnp.repeat(both[:, :, kv:], heads // kv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.asarray(hd ** 0.5, y.dtype)
    steps = jnp.arange(t)
    mask = ((steps[:, None] >= steps[None, :])[None, None]
            & (seg[:, None, :, None] == seg[:, None, None, :]))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, -1) @ lp["wo"]


def relu2(x, wu, wd):
    """The ungated expert: W_d relu(W_u x)^2 -> (result, W_u x)."""
    up = x @ wu
    return jnp.square(jax.nn.relu(up)) @ wd, up


def moe(x, lp, hp, routes=None):
    """The expert layer on the normed `x [B, T, D]` -> (the held experts'
    part + the shared expert, facts). The held experts in a loop, each
    applied to every token and weighted by w_i where the token chose it,
    by 0 where it did not. `routes [B, T, top_k]`: the chosen sets to
    compute on (this file's own where None); the weights are always from
    this file's scores."""
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

    def one_expert(carry, xs):
        acc, zeroed = carry
        index, wu, wd = xs
        paired = chosen == index  # [B, T, top_k]
        w = jnp.sum(jnp.where(paired, weight, 0), axis=-1)  # [B, T]
        out, up = relu2(x, wu, wd)
        zeroed = zeroed + jnp.sum(jnp.any(paired, -1)[..., None] & (up <= 0),
                                  dtype=jnp.int32)
        return (acc + w[..., None] * out, zeroed), None

    (routed, zeroed), _ = jax.lax.scan(
        one_expert, (jnp.zeros_like(x), jnp.int32(0)),
        (first + jnp.arange(held), lp["expert_wu"], lp["expert_wd"]))
    here = (chosen >= first) & (chosen < first + held)
    experts = scores.shape[-1]
    facts = {"probs": scores, "chosen": own,
             "margin": ranked[..., top_k - 1] - ranked[..., top_k],
             "edge": ranked[..., top_k - 1],
             "same_set": jnp.all(jnp.sort(own, -1) == jnp.sort(chosen, -1), axis=-1),
             "held_pairs": jnp.sum(here), "zeroed": zeroed,
             "load": jnp.sum(chosen[..., None] == jnp.arange(experts),
                             axis=tuple(range(chosen.ndim)), dtype=jnp.int32)}
    return routed + relu2(x, lp["shared_wu"], lp["shared_wd"])[0], facts


MODEL_KEYS = ("num_heads", "num_kv_heads", "head_dim", "mamba_heads",
              "mamba_head_dim", "mamba_groups", "mamba_state", "top_k",
              "first_expert", "experts_held", "route_scale", "rms_eps")


def _hp_static(hp) -> tuple:
    """What a layer reads of the hyperparameters, hashable for `jax.jit`."""
    return tuple(sorted((k, v) for k, v in hp.items() if k in MODEL_KEYS))


@functools.partial(jax.jit, static_argnames=("hp",))
def _layer(h, lp, seg, pos, routes, *, hp):
    hp = dict(hp)
    kind = layer_kind(lp)
    with jax.default_matmul_precision("highest"):
        y = norm(h, lp["norms"][0], hp["rms_eps"])
        facts = state = dt = None
        if kind == "M":
            mix, state, dt = mamba(y, lp, pos, hp)
        elif kind == "*":
            mix = attention(y, lp, seg, hp)
        else:
            mix, facts = moe(y, lp, hp, routes)
        return h + mix, facts, state, dt


def layer(h, lp, seg, pos, hp, routes=None):
    """One layer (one sublayer under one norm), rematerialised: the
    backward keeps its input and works through one layer's float32
    intermediates at a time -> (h', the routing facts of an expert layer
    or None, a state-space layer's last state and step sizes or None)."""
    return jax.checkpoint(functools.partial(_layer, hp=_hp_static(hp)))(
        h, lp, seg, pos, routes)


@functools.partial(jax.jit, static_argnames=("eps",))
def heads(h, p, *, eps):
    """(logits, value) from the last hidden state; the head is untied."""
    with jax.default_matmul_precision("highest"):
        z = norm(h, p["final_norm"], eps)
        return z @ p["head"].T, z @ p["w_value"] + p["b_value"]


def _cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def forward(params, tokens, done, hp, precision="highest", routes=None) -> dict:
    """-> `logits [1, B, T, V]`, `value [1, B, T]` (a leading axis of one
    pass, as `reference/ouro_looplm.py` has R), per expert layer the
    routing facts of `moe`, `states`: the recurrent state of every
    state-space layer after the last step, `dt`: every such layer's step
    sizes. `routes [expert layers, B, T, top_k]`: the sets to compute on."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else F32
    p = _cast(rekey(params, hp.get("layer_order")), dtype)
    tokens = jnp.asarray(tokens)
    seg, pos = episode_positions(jnp.asarray(done).astype(bool))
    h = p["embed"][tokens]
    routing, states, dts = [], [], []
    for lp in p["layers"]:
        given = None if routes is None or "router" not in lp else jnp.asarray(
            routes[len(routing)])
        h, facts, state, dt = layer(h, lp, seg, pos, hp, given)
        if facts is not None:
            routing.append(facts)
        if state is not None:
            states.append(state)
            dts.append(dt)
    logits, value = heads(
        h, {k: p[k] for k in ("final_norm", "head", "w_value", "b_value")},
        eps=hp["rms_eps"])
    return {"logits": logits[None], "value": value[None], "routing": routing,
            "states": states, "dt": dts}


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
         keep_logits: bool = True):
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
    held = jnp.sum(routing["held_pairs"])
    terms = {"total_loss": total, "pi_loss": jnp.sum(pi),
             "baseline_loss": jnp.sum(vl), "entropy": jnp.sum(first(entropy)),
             "pi_scale": jnp.sum(jnp.abs(pi)),
             "value": out["value"].astype(F32), "logp": logp[None],
             "router_score_mean": jnp.mean(probs),
             "held_pair_share": held
             / (probs.shape[0] * probs.shape[1] * probs.shape[2] * hp["top_k"]),
             "relu2_zero_share": jnp.sum(routing["zeroed"]).astype(F32)
             / jnp.maximum(held.astype(F32) * params_width(params), 1.0),
             "dt_mean": sg(jnp.mean(jnp.stack([jnp.mean(d.astype(F32))
                                               for d in out["dt"]]))),
             "states": sg([s.astype(F32) for s in out["states"]]),
             "router_load": routing["load"], "routing": routing}
    if keep_logits:
        terms["logits"] = out["logits"]
    return total, terms


def params_width(params) -> int:
    """The routed experts' width F, read from the parameters."""
    p = rekey(params)
    return next(lp["expert_wu"].shape[-1] for lp in p["layers"] if "router" in lp)


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


def loss_and_grads(params, batch: dict, hp, precision="highest", routes=None,
                   keep_logits: bool = True):
    """-> (the terms of `loss`, float32 gradients in `params`' layout).
    The loss is a sum over rows and V-trace runs along a row, so the
    gradients of a batch are the sums of those of its blocks of rows."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else F32
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        (_, terms), grads = jax.value_and_grad(
            lambda q: loss(q, batch, hp, precision, routes, keep_logits),
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
    through the recurrent states, the windows and the cache must
    reproduce."""
    with jax.default_matmul_precision("highest"):
        return logp_of(forward(params, tokens, done, hp, routes=routes
                               )["logits"][0], action)

"""Plain reference of the `joyai_flash` configuration: JoyAI-LLM-Flash
(`huggingface.co/jdopensource/JoyAI-LLM-Flash` config.json, `model_type`
joyai_llm_flash; its keys are DeepSeek-V3's, arXiv:2412.19437 sections
2.1-2.2: multi-head latent attention in every layer, a dense leading
layer, then a sigmoid-scored router with a selection bias over all of a
layer's experts with eight per token beside an ungated shared expert, a
multi-token-prediction module, a plain RMSNorm, an untied vocabulary
head) as the policy of a token-level V-trace actor-critic, from the
parameters up: forward, loss, gradients, RMSProp steps, the bias's
update; ONE CHIP'S SHARE of it, as the configuration states the
deployment: of each layer's `router_width` experts the `experts_held`
that start at `first_expert`.

`jax.numpy`, float32, `jax.default_matmul_precision("highest")`, a
Python loop over layers, the attention EXPANDED (per-head keys and
values rebuilt from the latent) as a dense masked softmax, the experts
as a loop over those held, each applied to every token under a mask; no
cache, no absorbed form, no sorting, no kernels. It runs eagerly, one
jitted layer application and one head pass at a time. Imports nothing
of the program: what `models/latent_moe_lm.py`,
`ops/latent_attention.py`, `ops/expert_share.py`, `agents/mlalm.py` and
`ops/vtrace.py` compute is held against this file
(tests/test_joyai_flash.py at a small size on the CPU,
`perfbench/families/mlalm.py` at the published widths on the chip), and
`perfbench/references/joyai_flash.py` is its copy.

The equations (ISSUE 40, Tentpole). Tokens x_1..x_T, D wide:
    N(x; g) = x rsqrt(mean(x^2) + eps) g
    h_0 = E[x];  layer l:  u = h + MLA(N(h; g_1)),  h' = u + F_l(N(u; g_2))
    logits = N(h_L; g_f) W_head^T;  v = N(h_L; g_f) . w_v + b_v
    MLA, y = N(h; g_1), position t = the step in the episode:
        c_q = N(W_qa y; g_q);  [q_n_i | q_r_i] = W_qb c_q for head i
        [c | k_r] = W_kva y;  c <- N(c; g_kv);  [k_n_i | v_i] = W_kvb c
        q_r_i <- R_t q_r_i,  k_r <- R_t k_r:  R_t turns the pair (2j, 2j + 1)
        by t theta^(-2j / r);  ONE k_r serves every head
        s_i(t, j) = (q_n_i(t) . k_n_i(j) + q_r_i(t) . k_r(j)) / sqrt(n + r),
        causal AND same-episode;  MLA = W_o [sum_j softmax_j(s_i) v_i(j)]_i
    F_l of a dense layer: W_d (silu(W_g x) * W_u x)
    F_l of an expert layer, x = N(u; g_2):
        s = sigmoid(W_r x) over ALL experts;  I = the top_k of s + b;
        w_i = c s_i / (sum_{j in I} s_j + 1e-20)  (the UNBIASED scores)
        MoE(x) = sum_{i in I, first <= i < first + held} w_i E_i(x) + E_shared(x)
    the bias, after each optimizer step, from the tokens n_i that chose
    expert i in the step's forward: b_i <- b_i + gamma sign(mean_j(n_j) - n_i)
    multi-token prediction, x_{t+1} the token shown at t + 1, a_{t+1} the
    action taken there:
        h' = W_p [N(h_L; g_h) ; N(E[x_{t+1}]; g_e)];  h'' = Layer_mtp(h')
        logits' = N(h''; g_m) W_head^T
        L_mtp = mean over t with t + 1 in t's episode of -log softmax(logits'_t)[a_{t+1}]
Loss: V-trace actor-critic per position (rho-bar = c-bar = 1; IMPALA's
double evaluation over the first / middle views of the unroll),
sum-reduced (`reference/qwen3_next.py`'s), + lambda n L_mtp, n the
positions L_mtp is a mean over: the module's loss SUMMED, the reduction
of the loss beside it (the configuration's file says why).

Layout of the fused matrices, as `models/latent_moe_lm.py` writes it
down: `wqb` columns per head q_n | q_r, heads contiguous; `wkva` c |
k_r; `wkvb` per head k_n | v; `wgu`, `expert_wgu`, `shared_wgu` gate |
up.

Departures from the published model, each in
`perfbench/configs/joyai_flash.json`: what the experts this chip does
not hold would have added is LEFT OUT; the prediction module is trained
on the rollout's own next action and is absent at act time; a value
head; the initialisation. Every layer is rematerialised: the same
arithmetic in the same order.

ROUTING IS DISCONTINUOUS (`reference/qwen3_next.py` says why). `routes`
(`[expert layers + 1, B, T, top_k]` expert ids: the trunk's expert
layers in order, then the prediction module's) makes this file compute
on the sets THE PROGRAM chose, with the weights w_i from its OWN scores;
it still says which sets it would have chosen and by what margin of s + b.

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
MTP_KEYS = ("norm_h", "norm_e", "norm_out", "proj")


def _runs(p) -> list:
    return sorted((k for k in p if k.startswith("run")), key=lambda k: int(k[3:]))


def _unstack(run) -> list:
    return [{k: v[i] for k, v in run.items()} for i in range(run["norms"].shape[0])]


def rekey(program_params, layer_order=None) -> dict:
    """The program's parameters (one `[n, ...]`-stacked dict per run of
    equal layers, `run0`, `run1`, ..., and the prediction module's one
    layer under `mtp`) as this file's: one dict per layer, in the
    published order."""
    if "layers" in program_params:  # already this file's
        return program_params
    p = program_params["params"] if "params" in program_params else program_params
    layers = [lp for name in _runs(p) for lp in _unstack(p[name])]
    if layer_order is not None:
        kinds = [layer_kind(lp) for lp in layers]
        if kinds != list(layer_order):
            raise ValueError(f"the parameters hold {kinds}, the configuration "
                             f"says {list(layer_order)}")
    mtp = {**{k: p["mtp"][k] for k in MTP_KEYS},
           "layer": _unstack(p["mtp"]["layer"])[0]}
    return {"layers": layers, "mtp": mtp, **{k: p[k] for k in TOP_KEYS}}


def stacked(params) -> dict:
    """`rekey`'s inverse: this file's parameters in the program's layout,
    so that the two can be compared leaf by leaf."""
    runs: list = []
    for lp in params["layers"]:
        if runs and layer_kind(runs[-1][0]) == layer_kind(lp):
            runs[-1].append(lp)
        else:
            runs.append([lp])
    stack = lambda run: {k: jnp.stack([lp[k] for lp in run]) for k in run[0]}
    p = {f"run{i}": stack(run) for i, run in enumerate(runs)}
    p["mtp"] = {**{k: params["mtp"][k] for k in MTP_KEYS},
                "layer": stack([params["mtp"]["layer"]])}
    p.update({k: params[k] for k in TOP_KEYS})
    return {"params": p}


def layer_kind(lp) -> str:
    return "moe" if "router" in lp else "dense"


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
    """`x [B, T, H, r]`, `pos [B, T]`: the pair (2j, 2j + 1) turns by
    pos x theta^(-2j / r) (`rope_interleave`: the pairs are neighbours)."""
    r = x.shape[-1]
    j = jnp.arange(r // 2, dtype=F32)
    angle = pos.astype(F32)[..., None, None] * jnp.asarray(theta, F32) ** (-2 * j / r)
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return turned.reshape(x.shape)


def latent_attention(y, lp, seg, pos, hp):
    """Multi-head latent attention, EXPANDED: every head's keys and
    values rebuilt from the normed latent, the one rotated key part
    repeated to every head, a dense masked softmax."""
    b, t, _ = y.shape
    heads, n, r, kv_rank = (hp["num_heads"], hp["nope_dim"], hp["rope_dim"],
                            hp["kv_rank"])
    c_q = norm(y @ lp["wqa"], lp["q_norm"], hp["rms_eps"])
    q = (c_q @ lp["wqb"]).reshape(b, t, heads, n + r)
    q_n, q_r = q[..., :n], q[..., n:]
    ckr = y @ lp["wkva"]
    c = norm(ckr[..., :kv_rank], lp["kv_norm"], hp["rms_eps"])
    k_r = ckr[..., kv_rank:].reshape(b, t, 1, r)
    kv = (c @ lp["wkvb"]).reshape(b, t, heads, -1)
    k_n, v = kv[..., :n], kv[..., n:]
    q_r, k_r = rotary(q_r, pos, hp["rope_theta"]), rotary(k_r, pos, hp["rope_theta"])
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_n, k_n)
         + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r[:, :, 0])
         ) / jnp.asarray((n + r) ** 0.5, y.dtype)
    steps = jnp.arange(t)
    mask = ((steps[:, None] >= steps[None, :])[None, None]
            & (seg[:, None, :, None] == seg[:, None, None, :]))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return att.reshape(b, t, -1) @ lp["wo"]


def swiglu(x, wgu, wd):
    gate, up = jnp.split(x @ wgu, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ wd


def moe(x, lp, hp, routes=None):
    """The expert MLP on `x [B, T, D]` (after its norm) -> (routed part,
    shared part, facts). The held experts in a loop, each applied to
    every token and weighted by w_i where the token chose it, by 0 where
    it did not. `routes [B, T, top_k]`: the chosen sets to compute on
    (this file's own where None); the weights are always from this
    file's scores."""
    top_k, first, held = hp["top_k"], hp["first_expert"], hp["experts_held"]
    scores = jax.nn.sigmoid(x @ lp["router"])
    biased = scores + jax.lax.stop_gradient(lp["router_bias"])
    ranked = jnp.sort(biased, axis=-1)[..., ::-1]
    _, own = jax.lax.top_k(biased, top_k)
    chosen = own if routes is None else routes.astype(jnp.int32)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = (jnp.asarray(hp["route_scale"], x.dtype) * picked
              / (jnp.sum(picked, axis=-1, keepdims=True) + jnp.asarray(1e-20, x.dtype)))

    def one_expert(acc, xs):
        index, wgu, wd = xs
        w = jnp.sum(jnp.where(chosen == index, weight, 0), axis=-1)  # [B, T]
        return acc + w[..., None] * swiglu(x, wgu, wd), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (first + jnp.arange(held), lp["expert_wgu"], lp["expert_wd"]))
    shared = swiglu(x, lp["shared_wgu"], lp["shared_wd"])
    here = (chosen >= first) & (chosen < first + held)
    experts = scores.shape[-1]
    facts = {"probs": scores, "chosen": own,
             "margin": ranked[..., top_k - 1] - ranked[..., top_k],
             "edge": ranked[..., top_k - 1],
             "same_set": jnp.all(jnp.sort(own, -1) == jnp.sort(chosen, -1), axis=-1),
             "held_pairs": jnp.sum(here),
             "load": jnp.sum(chosen[..., None] == jnp.arange(experts),
                             axis=tuple(range(chosen.ndim)), dtype=jnp.int32)}
    return routed, shared, facts


MODEL_KEYS = ("num_heads", "kv_rank", "nope_dim", "rope_dim", "rope_theta",
              "top_k", "first_expert", "experts_held", "route_scale", "rms_eps")


def _hp_static(hp) -> tuple:
    """What a layer reads of the hyperparameters, hashable for `jax.jit`."""
    return tuple(sorted((k, v) for k, v in hp.items() if k in MODEL_KEYS))


@functools.partial(jax.jit, static_argnames=("hp",))
def _layer(h, lp, seg, pos, routes, *, hp):
    hp = dict(hp)
    with jax.default_matmul_precision("highest"):
        u = h + latent_attention(norm(h, lp["norms"][0], hp["rms_eps"]), lp,
                                 seg, pos, hp)
        x = norm(u, lp["norms"][1], hp["rms_eps"])
        if layer_kind(lp) == "dense":
            return u + swiglu(x, lp["wgu"], lp["wd"]), None
        routed, shared, facts = moe(x, lp, hp, routes)
        return u + routed + shared, facts


def layer(h, lp, seg, pos, hp, routes=None):
    """One layer, rematerialised: the backward keeps its input and works
    through one layer's float32 intermediates at a time."""
    return jax.checkpoint(functools.partial(_layer, hp=_hp_static(hp)))(
        h, lp, seg, pos, routes)


@functools.partial(jax.jit, static_argnames=("eps",))
def heads(h, p, *, eps):
    """(logits, value) from the last hidden state; the head is untied."""
    with jax.default_matmul_precision("highest"):
        z = norm(h, p["final_norm"], eps)
        return z @ p["head"].T, z @ p["w_value"] + p["b_value"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _mtp_join(h, nxt, p, *, eps):
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate([norm(h, p["norm_h"], eps),
                                norm(nxt, p["norm_e"], eps)], axis=-1) @ p["proj"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _mtp_head(h2, g, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return norm(h2, g, eps) @ head.T


def _cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def forward(params, tokens, done, hp, precision="highest", routes=None,
            mtp: bool = True) -> dict:
    """-> `logits [1, B, T, V]`, `value [1, B, T]` (a leading axis of one
    pass, as `reference/ouro_looplm.py` has R), `mtp_logits [B, T, V]`:
    the prediction module's at every position (the caller leaves out
    those whose t + 1 is not in t's episode; None without `mtp`), and
    per expert layer, the module's last, the routing facts of `moe`.
    `routes [expert layers + 1, B, T, top_k]`: the sets to compute on."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else F32
    p = _cast(rekey(params, hp.get("layer_order")), dtype)
    tokens = jnp.asarray(tokens)
    seg, pos = episode_positions(jnp.asarray(done).astype(bool))
    h = p["embed"][tokens]
    routing, at = [], 0
    given = lambda: None if routes is None else jnp.asarray(routes[at])
    for lp in p["layers"]:
        h, facts = layer(h, lp, seg, pos, hp, given())
        if facts is not None:
            routing.append(facts)
            at += 1
    logits, value = heads(
        h, {k: p[k] for k in ("final_norm", "head", "w_value", "b_value")},
        eps=hp["rms_eps"])
    out = {"logits": logits[None], "value": value[None], "mtp_logits": None}
    if mtp:
        m = p["mtp"]
        # x_{t+1} at t; the last step wraps, and is one of those left out
        h2 = _mtp_join(h, p["embed"][jnp.roll(tokens, -1, axis=1)],
                       {k: m[k] for k in ("norm_h", "norm_e", "proj")},
                       eps=hp["rms_eps"])
        h2, facts = layer(h2, m["layer"], seg, pos, hp, given())
        routing.append(facts)
        out["mtp_logits"] = _mtp_head(h2, m["norm_out"], p["head"],
                                      eps=hp["rms_eps"])
    return {**out, "routing": routing}


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


def mtp_terms(mtp_logits, logits, action, done):
    """(sum over the positions that count of -log softmax(logits'_t)[a_{t+1}],
    the count, the positions among them where the module's argmax is the
    main head's at t + 1): position t counts if t + 1 is in t's episode."""
    t = action.shape[1]
    counts = ~done.astype(bool) & (jnp.arange(t) < t - 1)
    ahead = lambda x: jnp.roll(x, -1, axis=1)
    logp = jnp.take_along_axis(jax.nn.log_softmax(mtp_logits, axis=-1),
                               ahead(action)[..., None], axis=-1)[..., 0].astype(F32)
    agree = jnp.argmax(mtp_logits, -1) == ahead(jnp.argmax(logits, -1))
    return (-jnp.sum(jnp.where(counts, logp, 0.0)), jnp.sum(counts),
            jnp.sum(counts & agree))


def loss(params, batch: dict, hp, precision="highest", routes=None,
         mtp_count=None):
    """V-trace actor-critic per position + lambda x the module's loss
    summed over its positions -> (total, terms). `batch`: `tokens,
    action [B, T]` int, `behaviour_logp, reward [B, T]` float, `done [B,
    T]` bool. `mtp_count`: the positions the REPORTED `mtp_loss` is a
    mean over (this batch's own where None; a caller that sums the terms
    of blocks of rows gives the whole batch's)."""
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
    mtp_sum, counted, agreed = mtp_terms(out["mtp_logits"], out["logits"][0],
                                         batch["action"], batch["done"])
    mtp_loss = mtp_sum / jnp.maximum(counted if mtp_count is None else mtp_count, 1)
    total = (jnp.sum(pi + hp["baseline_loss_coef"] * vl
                     - hp["entropy_coef"] * first(entropy))
             + hp["mtp_loss_coef"] * mtp_sum)
    routing = sg({k: jnp.stack([r[k] for r in out["routing"]]).astype(
        F32 if k in ("probs", "margin", "edge") else jnp.int32)
        for k in out["routing"][0]})  # every leaf [expert layers + 1, ...]
    probs = routing["probs"]
    terms = {"total_loss": total, "pi_loss": jnp.sum(pi),
             "baseline_loss": jnp.sum(vl), "entropy": jnp.sum(first(entropy)),
             "pi_scale": jnp.sum(jnp.abs(pi)), "mtp_loss": mtp_loss,
             "mtp_count": counted, "mtp_agreed": agreed,
             "logits": out["logits"], "value": out["value"].astype(F32),
             "logp": logp[None], "mtp_logits": out["mtp_logits"],
             "router_score_mean": jnp.mean(probs),
             "held_pair_share": jnp.sum(routing["held_pairs"])
             / (probs.shape[0] * probs.shape[1] * probs.shape[2] * hp["top_k"]),
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
    [expert layers + 1, E]` tokens an expert: b_i + gamma sign(mean_j(n_j)
    - n_i), layer by layer (the trunk's expert layers in order, then the
    prediction module's). `params` in this file's layout."""
    load = jnp.asarray(load, F32)
    move = hp["bias_update_speed"] * jnp.sign(
        jnp.mean(load, axis=-1, keepdims=True) - load)
    layers, at = [], 0
    for lp in params["layers"]:
        if layer_kind(lp) == "moe":
            lp = {**lp, "router_bias": lp["router_bias"] + move[at]}
            at += 1
        layers.append(lp)
    layer_ = params["mtp"]["layer"]
    mtp = {**params["mtp"], "layer": {
        **layer_, "router_bias": layer_["router_bias"] + move[at]}}
    return {**params, "layers": layers, "mtp": mtp}


def biases(params) -> list:
    """Every selection bias `[E]`, in `bias_step`'s order."""
    return [lp["router_bias"] for lp in params["layers"]
            if layer_kind(lp) == "moe"] + [params["mtp"]["layer"]["router_bias"]]


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
                   mtp_count=None):
    """-> (the terms of `loss`, float32 gradients in `params`' layout).
    The loss is a sum over rows and V-trace runs along a row, so the
    gradients of a batch are the sums of those of its blocks of rows,
    and with the whole batch's `mtp_count` so are the terms."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else F32
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        (_, terms), grads = jax.value_and_grad(
            lambda q: loss(q, batch, hp, precision, routes, mtp_count),
            has_aux=True)(_cast(rekey(params, hp.get("layer_order")), dtype))
    return terms, _cast(grads, F32)


def evaluate(params, batch: dict, hp, precision="highest", routes=None) -> dict:
    """Logits, values, taken-action log-probability, the prediction
    module's logits and loss, the routing facts, the loss terms, the
    gradients' global norm, the norm of the first optimizer step's change
    and the biases after it. `params` in this file's layout or the
    program's."""
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
    """log pi(a_t | x_<=t) `[B, T]` from the full expanded forward: what
    acting through the latent cache in the absorbed form must reproduce."""
    with jax.default_matmul_precision("highest"):
        return logp_of(forward(params, tokens, done, hp, routes=routes,
                               mtp=False)["logits"][0], action)

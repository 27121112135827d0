"""Plain reference of the `ouro_looplm` configuration: Ouro's looped
decoder (ByteDance Seed, "Scaling Latent Reasoning via Looped Language
Models", 2025-10; `huggingface.co/ByteDance/Ouro-2.6B` config.json) as
the policy of a token-level V-trace actor-critic, from the parameters
up: forward, loss, gradients, RMSProp steps.

`jax.numpy`, float32, `jax.default_matmul_precision("highest")`, Python
loops over passes and layers, dense masked attention; no cache, no
scan, no remat, no kernel. It runs EAGERLY, one layer application and
one head pass at a time (`layer` and `heads` alone are jitted, so that
the 4 x L layer applications compile once and not as one unrolled
program: that one took 178 s to compile for a v5e at the published
widths, PR 30; and the two loops over T, `episode_positions` and
`vtrace`, whose few thousand one-element operations took 7 of the 8.5 s
of an evaluation when dispatched one by one). Imports nothing of the
program: what
`models/looped_lm.py`, `agents/looplm.py` and `ops/vtrace.py` compute is
held against this file (tests/test_ouro_looplm.py at a small size on the
CPU, `perfbench/families/looplm.py` at the published widths on the
chip), and `perfbench/references/ouro_looplm.py` is its copy.

The equations (ISSUE 30, Tentpole 1 and 2). Tokens x_1..x_T; R passes of
the SAME L layers:
    h^(0) = E[x];  h^(r) = Layer_L(... Layer_1(h^(r-1)))
    Layer: u = h + N2(Attn(N1(h)));  h' = u + N4(W_d(silu(W_g N3 u) * W_u N3 u))
    Attn: 16 heads of 128, rotate-half RoPE (theta 1e6) at the position
          inside the episode, causal AND same-episode mask
    z^(r) = RMSNorm(h^(r); g_f); logits^(r) = z^(r) W_out;
    lambda^(r) = sigmoid(z^(r) . w_e + b_e);  v^(r) = z^(r) . w_v + b_v
    p(1) = lambda^(1); p(r) = lambda^(r) prod_{j<r}(1 - lambda^(j)) (r < R);
    p(R) = prod_{j<R}(1 - lambda^(j))
Loss: per pass V-trace actor-critic (rho-bar = c-bar = 1; IMPALA's double
evaluation over the first / middle views of the unroll), weighted by the
exit distribution, less beta times the exit distribution's entropy.

Departures from the published model, each under `assumed` in
`perfbench/configs/ouro_looplm.json`: sandwich norm placement, no
biases, the value head, normal(0.02) initialisation.

`precision="bfloat16"` computes the same in the nearest precision below
the one the configuration states (bfloat16 parameters, activations,
softmax and loss): what the comparison's limits have to refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rekey(program_params) -> dict:
    """The program's `[L, ...]`-stacked parameters as this file's: one
    dict per layer, the fused projections split."""
    if "layers" in program_params:  # already this file's
        return program_params
    p = program_params["params"] if "params" in program_params else program_params
    layers = []
    for i in range(p["wqkv"].shape[0]):
        wq, wk, wv = jnp.split(p["wqkv"][i], 3, axis=-1)
        wg, wu = jnp.split(p["wgu"][i], 2, axis=-1)
        layers.append({"wq": wq, "wk": wk, "wv": wv, "wo": p["wo"][i],
                       "wg": wg, "wu": wu, "wd": p["wd"][i],
                       "n1": p["norms"][i, 0], "n2": p["norms"][i, 1],
                       "n3": p["norms"][i, 2], "n4": p["norms"][i, 3]})
    return {"embed": p["embed"], "layers": layers,
            "final_norm": p["final_norm"], "w_out": p["w_out"],
            "w_exit": p["w_exit"], "b_exit": p["b_exit"],
            "w_value": p["w_value"], "b_value": p["b_value"]}


def stacked(params) -> dict:
    """`rekey`'s inverse: this file's parameters in the program's
    `[L, ...]`-stacked layout, so that the two can be compared leaf by
    leaf."""
    layers = params["layers"]
    over = lambda f: jnp.stack([f(lp) for lp in layers])
    p = {"wqkv": over(lambda lp: jnp.concatenate(
             [lp["wq"], lp["wk"], lp["wv"]], axis=-1)),
         "wgu": over(lambda lp: jnp.concatenate([lp["wg"], lp["wu"]], axis=-1)),
         "wo": over(lambda lp: lp["wo"]), "wd": over(lambda lp: lp["wd"]),
         "norms": over(lambda lp: jnp.stack([lp[f"n{i}"] for i in (1, 2, 3, 4)]))}
    p.update({k: v for k, v in params.items() if k != "layers"})
    return {"params": p}


def rms_norm(x, g, eps):
    return g * x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                            + eps)


@jax.jit
def episode_positions(done):
    """([B, T] episode ids, [B, T] positions inside the episode):
    `done[t]` ends an episode AT step t, so t + 1 starts the next."""
    b, t = done.shape
    seg = jnp.zeros((b, t), jnp.int32)
    pos = jnp.zeros((b, t), jnp.int32)
    for i in range(1, t):
        new = done[:, i - 1]
        seg = seg.at[:, i].set(seg[:, i - 1] + new.astype(jnp.int32))
        pos = pos.at[:, i].set(jnp.where(new, 0, pos[:, i - 1] + 1))
    return seg, pos


def rope(x, pos, theta):
    """Rotate-half RoPE on `[B, T, H, d]` at positions `[B, T]`."""
    d2 = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(d2, dtype=F32) / d2)
    ang = pos.astype(F32)[..., None] * freqs  # [B, T, d2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(h, lp, seg, pos, num_heads, head_dim, rope_theta):
    b, t, _ = h.shape
    split = lambda y: y.reshape(b, t, num_heads, head_dim)
    q = rope(split(h @ lp["wq"]), pos, rope_theta)
    k = rope(split(h @ lp["wk"]), pos, rope_theta)
    v = split(h @ lp["wv"])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(head_dim)).astype(q.dtype)
    steps = jnp.arange(t)
    mask = ((steps[:, None] >= steps[None, :])[None, None]
            & (seg[:, None, :, None] == seg[:, None, None, :]))
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return (jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, num_heads * head_dim)
            @ lp["wo"])


@functools.partial(jax.jit, static_argnames=("num_heads", "head_dim",
                                             "rope_theta", "eps"))
def layer(h, lp, seg, pos, *, num_heads, head_dim, rope_theta, eps):
    with jax.default_matmul_precision("highest"):
        att = attention(rms_norm(h, lp["n1"], eps), lp, seg, pos, num_heads,
                        head_dim, rope_theta)
        u = h + rms_norm(att, lp["n2"], eps)
        y = rms_norm(u, lp["n3"], eps)
        mlp = (jax.nn.silu(y @ lp["wg"]) * (y @ lp["wu"])) @ lp["wd"]
        return u + rms_norm(mlp, lp["n4"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def heads(h, p, *, eps):
    """(logits, gate, value) of one pass from its last hidden state."""
    with jax.default_matmul_precision("highest"):
        z = rms_norm(h, p["final_norm"], eps)
        return (z @ p["w_out"], jax.nn.sigmoid(z @ p["w_exit"] + p["b_exit"]),
                z @ p["w_value"] + p["b_value"])


def _cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def forward(params, tokens, done, hp, precision="highest") -> dict:
    """-> `logits [R, B, T, V]`, `gate [R, B, T]`, `value [R, B, T]` of
    every pass."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else F32
    p = _cast(params, dtype)
    seg, pos = episode_positions(jnp.asarray(done).astype(bool))
    head_params = {k: p[k] for k in ("final_norm", "w_out", "w_exit", "b_exit",
                                     "w_value", "b_value")}
    h = p["embed"][jnp.asarray(tokens)]
    logits, gate, value = [], [], []
    for _ in range(hp["loop_passes"]):
        for lp in p["layers"]:
            h = layer(h, lp, seg, pos, num_heads=hp["num_heads"],
                      head_dim=hp["head_dim"], rope_theta=hp["rope_theta"],
                      eps=hp["rms_eps"])
        out = heads(h, head_params, eps=hp["rms_eps"])
        logits.append(out[0])
        gate.append(out[1])
        value.append(out[2])
    return {"logits": jnp.stack(logits), "gate": jnp.stack(gate),
            "value": jnp.stack(value)}


def exit_distribution(gate):
    """`gate [R, ...]` -> p `[R, ...]` summing to 1 over passes."""
    r = gate.shape[0]
    stay = jnp.ones_like(gate[0])
    out = []
    for i in range(r - 1):
        out.append(gate[i] * stay)
        stay = stay * (1 - gate[i])
    out.append(stay)
    return jnp.stack(out)


@jax.jit
def vtrace(log_rho, discount, reward, value, bootstrap):
    """`[B, T]` V-trace targets and clipped rhos, rho-bar = c-bar = 1; a
    plain reverse loop (Espeholt et al. 2018, eq. 1)."""
    rho = jnp.minimum(1.0, jnp.exp(log_rho))
    t = value.shape[1]
    nxt = jnp.concatenate([value[:, 1:], bootstrap[:, None]], axis=1)
    delta = rho * (reward + discount * nxt - value)
    acc = jnp.zeros_like(bootstrap)
    out = [None] * t
    for i in reversed(range(t)):
        acc = delta[:, i] + discount[:, i] * rho[:, i] * acc
        out[i] = acc
    return jnp.stack(out, axis=1) + value, rho


def loss(params, batch: dict, hp, precision="highest"):
    """Ouro's stage-I objective with V-trace actor-critic as the loss of
    a pass -> (total, terms). `batch`: `tokens, action [B, T]` int,
    `behaviour_logp, reward [B, T]` float, `done [B, T]` bool."""
    sg = jax.lax.stop_gradient
    out = forward(params, batch["tokens"], batch["done"], hp, precision)
    logp_all = jax.nn.log_softmax(out["logits"], axis=-1)  # in `precision`'s dtype
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1).astype(F32)
    logp = jnp.take_along_axis(
        logp_all, batch["action"][None, ..., None], axis=-1)[..., 0].astype(F32)
    value, gate = out["value"].astype(F32), out["gate"].astype(F32)
    reward = batch["reward"].astype(F32)
    if hp["reward_clipping"] == "abs_one":
        reward = jnp.clip(reward, -1.0, 1.0)
    disc = (~batch["done"].astype(bool)).astype(F32) * hp["discount"]
    first = lambda x: x[..., :-2]
    middle = lambda x: x[..., 1:-1]
    last = lambda x: x[..., 2:]
    mu = batch["behaviour_logp"].astype(F32)
    per_pass, pi_terms, v_terms, rhos = [], [], [], []
    for r in range(hp["loop_passes"]):
        v = value[r]
        vs, rho = vtrace(sg(first(logp[r]) - first(mu)), first(disc),
                         first(reward), sg(first(v)), sg(middle(v)[:, -1]))
        vs1, _ = vtrace(sg(middle(logp[r]) - middle(mu)), middle(disc),
                        middle(reward), sg(middle(v)), sg(last(v)[:, -1]))
        adv = sg(rho * (first(reward) + first(disc) * vs1 - first(v)))
        pi = -adv * first(logp[r])
        vl = 0.5 * jnp.square(sg(vs) - first(v))
        per_pass.append(pi + hp["baseline_loss_coef"] * vl
                        - hp["entropy_coef"] * first(entropy[r]))
        pi_terms.append(pi)
        v_terms.append(vl)
        rhos.append(rho)
    p_exit = first(exit_distribution(gate))  # [R, B, T-2]
    exit_entropy = -jnp.sum(jnp.where(p_exit > 0, p_exit * jnp.log(
        jnp.where(p_exit > 0, p_exit, 1.0)), 0.0), axis=0)
    total = (jnp.sum(p_exit * jnp.stack(per_pass))
             - hp["exit_entropy_coef"] * jnp.sum(exit_entropy))
    terms = {"total_loss": total,
             "pi_loss": jnp.sum(p_exit * jnp.stack(pi_terms)),
             "baseline_loss": jnp.sum(p_exit * jnp.stack(v_terms)),
             "entropy": jnp.sum(p_exit * first(entropy)),
             "exit_entropy": jnp.sum(exit_entropy),
             "pi_scale": jnp.sum(jnp.abs(p_exit * jnp.stack(pi_terms))),
             "logits": out["logits"], "gate": gate, "value": value,
             "logp": logp}
    return total, terms


def clip_scale(grads, hp):
    """(global norm of `grads`, the factor that clips it to the
    configuration's `gradient_clip_norm`)."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    return norm, jnp.minimum(1.0, hp["gradient_clip_norm"]
                             / jnp.maximum(norm, 1e-30))


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


def loss_and_grads(params, batch: dict, hp, precision="highest"):
    """-> (the terms of `loss`, float32 gradients in `params`' layout).
    The loss is a sum over rows and V-trace runs along a row, so the
    terms and gradients of a batch are the sums of those of its blocks
    of rows."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else F32
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        (_, terms), grads = jax.value_and_grad(
            lambda q: loss(q, batch, hp, precision), has_aux=True)(
                _cast(rekey(params), dtype))
    return terms, _cast(grads, F32)


def evaluate(params, batch: dict, hp, precision="highest") -> dict:
    """Every pass's logits, gates, values, taken-action log-probability,
    the loss terms, the gradients' global norm and the norm of the first
    optimizer step's change. `params` in this file's layout or the
    program's."""
    p = _cast(rekey(params), jnp.bfloat16 if precision == "bfloat16" else F32)
    terms, grads = loss_and_grads(p, batch, hp, precision)
    out = {**terms, "grad_norm": clip_scale(grads, hp)[0],
           "update_norm": rmsprop_update_norm(p, grads, hp)}
    return jax.device_get(out)


def logp_of(logits, action) -> jax.Array:
    """Float32 log-softmax of `logits [..., V]` at `action [...]`."""
    return jnp.take_along_axis(
        jax.nn.log_softmax(jnp.asarray(logits, F32), axis=-1),
        jnp.asarray(action)[..., None], axis=-1)[..., 0]


def taken_logp(params, tokens, action, done, hp) -> jax.Array:
    """log pi^(R)(a_t | x_<=t) `[B, T]` of the LAST pass from the full
    forward: what acting through a per-pass cache must reproduce."""
    return logp_of(forward(params, tokens, done, hp)["logits"][-1], action)

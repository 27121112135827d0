"""Plain reference of the `granite_hybrid` configuration: IBM's
granite-4.0-h-micro (`huggingface.co/ibm-granite/granite-4.0-h-micro`
config.json, `model_type` granitemoehybrid: nine Mamba-2 state-space
layers to one grouped-query attention layer, a SwiGLU MLP in every
layer, four scalar multipliers, a tied vocabulary head) as the policy of
a token-level V-trace actor-critic, from the parameters up: forward,
loss, gradients, RMSProp steps.

`jax.numpy`, float32, `jax.default_matmul_precision("highest")`, a
Python loop over layers, the state-space layer as the STEP-BY-STEP
recurrence (a `lax.scan` over t: the chunked form is the code under
test), the convolution as four shifted multiplies, attention as a dense
masked softmax with repeated key/value heads; no cache, no chunks, no
kernels. It runs eagerly, one jitted layer application and one head
pass at a time. Imports nothing of the program: what
`models/hybrid_lm.py`, `ops/ssd.py`, `agents/hybridlm.py` and
`ops/vtrace.py` compute is held against this file
(tests/test_granite_hybrid.py at a small size on the CPU,
`perfbench/families/hybridlm.py` at the published widths on the chip),
and `perfbench/references/granite_hybrid.py` is its copy.

The equations (ISSUE 32, Tentpole 1 and 2). Tokens x_1..x_T, D wide:
    h_0 = m_e E[x];  per layer, in the published order:
    u = h + m_r Mix(N1(h));  h' = u + m_r W_out(silu(a) * b), [a, b] = W_in N2(u)
    z = RMSNorm(h_L; g_f);  logits = z E^T / s_l;  v = z . w_v + b_v
    Mix = attention: q, k, v of 32 / 8 / 8 heads, query head i reads
          key/value head i // 4, NO position term, softmax(q k^T m_a)
          under the causal AND same-episode mask
    Mix = Mamba-2:  [z, xBC, dt] = W_in y;
          xBC_t = silu(b_c + sum_j w_c[:, j] xBC_{t-3+j}), zeros before
          the episode's first step;  [x, B, C] = xBC;
          dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
          S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t, S = 0 before the
          episode's first step;  y_t = S_t C_t + D x_t
          out = W_out (g_n * g / rms(g)),  g = y * silu(z)
Loss: V-trace actor-critic per position (rho-bar = c-bar = 1; IMPALA's
double evaluation over the first / middle views of the unroll),
sum-reduced: `reference/ouro_looplm.py`'s loss of one pass with no gate.

Departures from the published model, each under `assumed` in
`perfbench/configs/granite_hybrid.json`: the value head, the
initialisation. The scan over t is a scan of blocks of steps whose body
is rematerialised (`SCAN_BLOCK`), and so is every layer: the same
arithmetic in the same order, with the backward's memory the square root
of a plain scan's (1,024 states of 2.1 MB a row a layer at the published
widths) and one layer's intermediates, not ten layers'.

`precision="bfloat16"` computes the same in the nearest precision below
the one the configuration states (bfloat16 parameters, activations,
recurrent state, softmax and loss): what the comparison's limits have
to refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
SCAN_BLOCK = 32  # steps of the recurrence whose states the backward keeps


def rekey(program_params, layer_order=None) -> dict:
    """The program's parameters (one `[n, ...]`-stacked dict per run of
    equal layers, `run0`, `run1`, ...) as this file's: one dict per
    layer, in the published order, the fused projections split."""
    if "layers" in program_params:  # already this file's
        return program_params
    p = program_params["params"] if "params" in program_params else program_params
    layers = []
    for name in sorted((k for k in p if k.startswith("run")),
                       key=lambda k: int(k[3:])):
        run = p[name]
        for i in range(run["norms"].shape[0]):
            wg, wu = jnp.split(run["wgu"][i], 2, axis=-1)
            lp = {"n1": run["norms"][i, 0], "n2": run["norms"][i, 1],
                  "wg": wg, "wu": wu, "wd": run["wd"][i]}
            if "in_proj" in run:
                lp.update({k: run[k][i] for k in (
                    "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                    "gate_norm", "out_proj")})
            else:
                kv = run["wkv"].shape[-1] // 2
                lp.update(wq=run["wq"][i], wk=run["wkv"][i][:, :kv],
                          wv=run["wkv"][i][:, kv:], wo=run["wo"][i])
            layers.append(lp)
    out = {"embed": p["embed"], "layers": layers, "final_norm": p["final_norm"],
           "w_value": p["w_value"], "b_value": p["b_value"]}
    if layer_order is not None:
        kinds = ["mamba" if "in_proj" in lp else "attention" for lp in layers]
        if kinds != list(layer_order):
            raise ValueError(f"the parameters hold {kinds}, the configuration "
                             f"says {list(layer_order)}")
    return out


def stacked(params) -> dict:
    """`rekey`'s inverse: this file's parameters in the program's layout
    (a stacked dict per run of equal layers), so that the two can be
    compared leaf by leaf."""
    runs, kinds = [], []
    for lp in params["layers"]:
        kind = "in_proj" in lp
        if not kinds or kinds[-1] != kind:
            runs.append([])
            kinds.append(kind)
        runs[-1].append(lp)
    p = {}
    for i, (run, mamba) in enumerate(zip(runs, kinds)):
        over = lambda f: jnp.stack([f(lp) for lp in run])
        out = {"norms": over(lambda lp: jnp.stack([lp["n1"], lp["n2"]])),
               "wgu": over(lambda lp: jnp.concatenate([lp["wg"], lp["wu"]], -1)),
               "wd": over(lambda lp: lp["wd"])}
        if mamba:
            out.update({k: over(lambda lp: lp[k]) for k in (
                "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                "gate_norm", "out_proj")})
        else:
            out.update(wq=over(lambda lp: lp["wq"]), wo=over(lambda lp: lp["wo"]),
                       wkv=over(lambda lp: jnp.concatenate(
                           [lp["wk"], lp["wv"]], -1)))
        p[f"run{i}"] = out
    p.update({k: v for k, v in params.items() if k != "layers"})
    return {"params": p}


def rms_norm(x, g, eps):
    return g * x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                            + eps)


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


def attention(y, lp, seg, hp):
    """Grouped-query attention without a position term: the key/value
    heads repeated, the published scale in place of 1 / sqrt(d)."""
    b, t, _ = y.shape
    heads, kv_heads, d = hp["num_heads"], hp["num_kv_heads"], hp["head_dim"]
    q = (y @ lp["wq"]).reshape(b, t, heads, d)
    k = jnp.repeat((y @ lp["wk"]).reshape(b, t, kv_heads, d), heads // kv_heads, 2)
    v = jnp.repeat((y @ lp["wv"]).reshape(b, t, kv_heads, d), heads // kv_heads, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * jnp.asarray(
        hp["attention_multiplier"], q.dtype)
    steps = jnp.arange(t)
    mask = ((steps[:, None] >= steps[None, :])[None, None]
            & (seg[:, None, :, None] == seg[:, None, None, :]))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, heads * d) @ lp["wo"]


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
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t (S_{t-1} = 0 where
    `start[t]`), y_t = S_t C_t, one step at a time. `x [B, T, H, P]`,
    `dt [B, T, H]`, `a [H]`, `bmat, cmat [B, T, N]`, `start [B, T]` ->
    (`y [B, T, H, P]`, the state after the last step `[B, H, P, N]`);
    everything in `x`'s dtype."""
    b, t, h, p = x.shape
    block = max(d for d in range(1, min(SCAN_BLOCK, t) + 1) if t % d == 0)

    def step(state, xs):
        x_t, dt_t, b_t, c_t, start_t = xs
        state = jnp.where(start_t[:, None, None, None], 0, state)
        decay = jnp.exp(dt_t * a)  # [B, H]
        state = (decay[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return state.astype(x.dtype), jnp.einsum("bhpn,bn->bhp", state, c_t)

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
    """-> (the mixer's output `[B, T, D]`, the state after the last
    step `[B, H, P, N]`, dt `[B, T, H]`)."""
    b, t, _ = y.shape
    h, p, n = hp["mamba_n_heads"], hp["mamba_d_head"], hp["mamba_d_state"]
    z, xbc, dt = jnp.split(y @ lp["in_proj"], [h * p, 2 * h * p + 2 * n], -1)
    xbc = jax.nn.silu(causal_conv(xbc, lp["conv_w"], lp["conv_b"], pos))
    x, bmat, cmat = jnp.split(xbc, [h * p, h * p + n], -1)
    x = x.reshape(b, t, h, p)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    ssm, state = recurrence(x, dt, -jnp.exp(lp["A_log"]), bmat, cmat, pos == 0)
    g = (ssm + lp["D"][:, None] * x).reshape(b, t, h * p) * jax.nn.silu(z)
    return rms_norm(g, lp["gate_norm"], hp["rms_eps"]) @ lp["out_proj"], state, dt


MODEL_KEYS = ("num_heads", "num_kv_heads", "head_dim", "attention_multiplier",
              "residual_multiplier", "mamba_n_heads", "mamba_d_head",
              "mamba_d_state", "rms_eps")


def _hp_static(hp) -> tuple:
    """What a layer reads of the hyperparameters, hashable for `jax.jit`."""
    return tuple(sorted((k, v) for k, v in hp.items() if k in MODEL_KEYS))


@functools.partial(jax.jit, static_argnames=("hp",))
def _layer(h, lp, seg, pos, *, hp):
    hp = dict(hp)
    with jax.default_matmul_precision("highest"):
        y = rms_norm(h, lp["n1"], hp["rms_eps"])
        if "in_proj" in lp:
            mix, state, dt = mamba(y, lp, pos, hp)
        else:
            mix, state, dt = attention(y, lp, seg, hp), None, None
        m_r = jnp.asarray(hp["residual_multiplier"], h.dtype)
        u = h + m_r * mix
        y = rms_norm(u, lp["n2"], hp["rms_eps"])
        mlp = (jax.nn.silu(y @ lp["wg"]) * (y @ lp["wu"])) @ lp["wd"]
        return u + m_r * mlp, state, dt


def layer(h, lp, seg, pos, hp):
    """One layer, rematerialised: the backward keeps its input and works
    through one layer's float32 intermediates at a time (0.6 GB a row at
    the published widths, where all ten were 6 GB)."""
    return jax.checkpoint(functools.partial(_layer, hp=_hp_static(hp)))(
        h, lp, seg, pos)


@functools.partial(jax.jit, static_argnames=("eps", "logits_scaling"))
def heads(h, p, *, eps, logits_scaling):
    """(logits, value) from the last hidden state; the head is the
    embedding, transposed (tied)."""
    with jax.default_matmul_precision("highest"):
        z = rms_norm(h, p["final_norm"], eps)
        return ((z @ p["embed"].T) / jnp.asarray(logits_scaling, z.dtype),
                z @ p["w_value"] + p["b_value"])


def _cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def forward(params, tokens, done, hp, precision="highest") -> dict:
    """-> `logits [1, B, T, V]`, `value [1, B, T]` (a leading axis of one
    pass, as `reference/ouro_looplm.py` has R), `states`: the recurrent
    state of every state-space layer after the last step, `dt`: every
    such layer's step sizes."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else F32
    p = _cast(rekey(params, hp.get("layer_order")), dtype)
    seg, pos = episode_positions(jnp.asarray(done).astype(bool))
    h = jnp.asarray(hp["embedding_multiplier"], dtype) * p["embed"][jnp.asarray(tokens)]
    states, dts = [], []
    for lp in p["layers"]:
        h, state, dt = layer(h, lp, seg, pos, hp)
        if state is not None:
            states.append(state)
            dts.append(dt)
    logits, value = heads(
        h, {k: p[k] for k in ("final_norm", "embed", "w_value", "b_value")},
        eps=hp["rms_eps"], logits_scaling=hp["logits_scaling"])
    return {"logits": logits[None], "value": value[None], "states": states,
            "dt": dts}


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


def loss(params, batch: dict, hp, precision="highest"):
    """V-trace actor-critic per position -> (total, terms). `batch`:
    `tokens, action [B, T]` int, `behaviour_logp, reward [B, T]` float,
    `done [B, T]` bool."""
    sg = jax.lax.stop_gradient
    out = forward(params, batch["tokens"], batch["done"], hp, precision)
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
    dt = jnp.stack(out["dt"]).astype(F32)  # [layers, B, T, H]
    decay = jnp.exp(-dt * jnp.exp(jnp.stack(
        [lp["A_log"] for lp in rekey(params)["layers"] if "A_log" in lp]
    ).astype(F32))[:, None, None, :])
    terms = {"total_loss": total, "pi_loss": jnp.sum(pi),
             "baseline_loss": jnp.sum(vl), "entropy": jnp.sum(first(entropy)),
             "pi_scale": jnp.sum(jnp.abs(pi)),
             "logits": out["logits"], "value": out["value"].astype(F32),
             "logp": logp[None], "states": out["states"],
             "dt_mean": jnp.mean(dt), "decay_min": jnp.min(decay)}
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
                _cast(rekey(params, hp.get("layer_order")), dtype))
    return terms, _cast(grads, F32)


def evaluate(params, batch: dict, hp, precision="highest") -> dict:
    """Logits, values, taken-action log-probability, the final recurrent
    states, the loss terms, the gradients' global norm and the norm of
    the first optimizer step's change. `params` in this file's layout or
    the program's."""
    p = _cast(rekey(params, hp.get("layer_order")),
              jnp.bfloat16 if precision == "bfloat16" else F32)
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
    """log pi(a_t | x_<=t) `[B, T]` from the full forward: what acting
    through the recurrent state, the convolution window and the
    key/value cache must reproduce."""
    return logp_of(forward(params, tokens, done, hp)["logits"][0], action)

"""Plain reference of the `qwen3_next` configuration: Qwen3-Next-80B-A3B
(`huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct` config.json,
`model_type` qwen3_next: three gated-delta-rule linear-attention layers
to one gated softmax-attention layer, in every layer a router over all
of the layer's experts with ten per token beside a gated shared expert,
a zero-centred RMSNorm, an untied vocabulary head) as the policy of a
token-level V-trace actor-critic, from the parameters up: forward, loss,
gradients, RMSProp steps; ONE CHIP'S SHARE of it, as the configuration
states the deployment: of each layer's `router_width` experts the
`experts_held` from `first_expert` on.

`jax.numpy`, float32, `jax.default_matmul_precision("highest")`, a
Python loop over layers, the delta rule STEP BY STEP (a `lax.scan` over
t: the chunked form is the code under test), the convolution as four
shifted multiplies, attention as a dense masked softmax with repeated
key/value heads, the experts as a loop over those held, each applied to
every token under a mask; no cache, no chunks, no sorting, no kernels.
It runs eagerly, one jitted layer application and one head pass at a
time. Imports nothing of the program: what `models/moe_lm.py`,
`ops/gated_delta.py`, `ops/expert_share.py`, `agents/moelm.py` and
`ops/vtrace.py` compute is held against this file
(tests/test_qwen3_next.py at a small size on the CPU,
`perfbench/families/moelm.py` at the published widths on the chip), and
`perfbench/references/qwen3_next.py` is its copy.

The equations (ISSUE 36, Tentpole). Tokens x_1..x_T, D wide:
    N(x; g) = x rsqrt(mean(x^2) + eps) (1 + g)      (zero-centred scale)
    h_0 = E[x];  per layer:  u = h + Mix(N(h; g_1)),  h' = u + MoE(N(u; g_2))
    logits = N(h_L; g_f) W_head^T;  v = N(h_L; g_f) . w_v + b_v
    Mix = gated delta rule, y = N(h; g_1):
          [q, k, v, z] = W_qkvz y;  [b, a] = W_ba y
          [q, k, v]_t <- silu(sum_j w_c[:, j] [q, k, v]_{t-3+j}), zeros
          before the episode's first step
          beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias);  alpha = exp(g)
          q <- q / sqrt(|q|^2 + 1e-6) / sqrt(K),  k <- k / sqrt(|k|^2 + 1e-6)
          per value head (key head j serves value heads 2j, 2j + 1), S = 0
          before the episode's first step:
          S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t S_{t-1})^T k_t)^T,  o_t = S_t^T q_t
          Mix = W_o [ o_t rsqrt(mean(o_t^2) + eps) g_n * silu(z_t) ]   (g_n plain)
    Mix = gated attention:  [q | gate] = W_q y per head, [k | v] = W_kv y;
          q <- N(q; g_q), k <- N(k; g_k) per head; rotate-half rotary on
          the first `rotary_dim` of each head at the step in the episode;
          softmax(q k^T / sqrt(d)) causal AND same-episode, query head i
          reads key/value head i // (heads / KV);
          Mix = W_o [ attn * sigmoid(gate) ]
    MoE(x) = sum_{i in I, first <= i < first + held} w_i E_i(x)
             + sigmoid(w_s . x) E_shared(x),   E(x) = W_d (silu(W_g x) * W_u x)
          p = softmax(W_r x) over ALL experts;  I = the top_k largest;
          w_i = p_i / sum_{j in I} p_j
Loss: V-trace actor-critic per position (rho-bar = c-bar = 1; IMPALA's
double evaluation over the first / middle views of the unroll),
sum-reduced: `reference/ouro_looplm.py`'s loss of one pass with no gate.

Layout of the fused matrices, as `models/moe_lm.py` writes it down
(the source interleaves them by key-head group; any fixed layout is the
same model): `in_proj` columns q (key heads x K) | k | v (value heads x
V) | z; `in_ba` b | a; `wq` per head q | gate; `wkv` k | v;
`expert_wgu`, `shared_wgu` gate | up.

Departures from the published model, each in
`perfbench/configs/qwen3_next.json`: what the experts this chip does not
hold would have added is LEFT OUT (their chips add it, in a deployment;
the partial result goes on to the next layer); no multi-token prediction
module and no auxiliary balance loss (the catalog row's `config` has no
key for either); a value head; the initialisation. The scan over t is a
scan of blocks of steps whose body is rematerialised (`SCAN_BLOCK`), and
so is every layer: the same arithmetic in the same order.

ROUTING IS DISCONTINUOUS: a program whose residual stream is bfloat16
can take, for a token whose tenth and eleventh probabilities nearly tie,
the other one. `routes` (`[layers, B, T, top_k]` expert ids) makes this
file compute on the sets THE PROGRAM chose, with the weights w_i from
its OWN probabilities; it still says which sets it would have chosen
(`chosen`) and by what margin (`margin` = p_(k) - p_(k+1)), so a caller
can hold the program's choices wherever the margin is not a tie.

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
SCAN_BLOCK = 32  # steps of the recurrence whose states the backward keeps
TOP_KEYS = ("embed", "head", "final_norm", "w_value", "b_value")


def _runs(p) -> list:
    return sorted((k for k in p if k.startswith("run")), key=lambda k: int(k[3:]))


def rekey(program_params, layer_order=None) -> dict:
    """The program's parameters (one `[n, ...]`-stacked dict per run of
    equal layers, `run0`, `run1`, ...) as this file's: one dict per
    layer, in the published order."""
    if "layers" in program_params:  # already this file's
        return program_params
    p = program_params["params"] if "params" in program_params else program_params
    layers = [{k: v[i] for k, v in p[name].items()}
              for name in _runs(p) for i in range(p[name]["norms"].shape[0])]
    if layer_order is not None:
        kinds = [layer_kind(lp) for lp in layers]
        if kinds != list(layer_order):
            raise ValueError(f"the parameters hold {kinds}, the configuration "
                             f"says {list(layer_order)}")
    return {"layers": layers, **{k: p[k] for k in TOP_KEYS}}


def stacked(params) -> dict:
    """`rekey`'s inverse: this file's parameters in the program's layout
    (a stacked dict per run of equal layers), so that the two can be
    compared leaf by leaf."""
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
    return "linear_attention" if "in_proj" in lp else "full_attention"


def norm(x, g, eps):
    """The zero-centred RMSNorm: the scale is 1 + g."""
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * (1 + g)


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


def rotary(x, pos, hp):
    """Rotate-half on the first `rotary_dim` of each head: pairs (i, i +
    rotary_dim / 2) turn by pos x theta^(-2 i / rotary_dim); the rest of
    the head passes through. `x [B, T, H, d]`, `pos [B, T]`."""
    r = hp["rotary_dim"]
    half = r // 2
    freq = jnp.asarray(hp["rope_theta"], F32) ** (-jnp.arange(half, dtype=F32) / half)
    angle = pos.astype(F32)[..., None, None] * freq  # [B, T, 1, half]
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    x1, x2, rest = x[..., :half], x[..., half:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], -1)


def attention(y, lp, seg, pos, hp):
    """Gated grouped-query attention: per-head q/k norms, partial
    rotary, the key/value heads repeated, a sigmoid gate on the output."""
    b, t, _ = y.shape
    heads, kv_heads, d = hp["num_heads"], hp["num_kv_heads"], hp["head_dim"]
    qg = (y @ lp["wq"]).reshape(b, t, heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    kv = (y @ lp["wkv"]).reshape(b, t, 2 * kv_heads, d)
    k, v = kv[:, :, :kv_heads], kv[:, :, kv_heads:]
    q = rotary(norm(q, lp["q_norm"], hp["rms_eps"]), pos, hp)
    k = rotary(norm(k, lp["k_norm"], hp["rms_eps"]), pos, hp)
    k, v = (jnp.repeat(x, heads // kv_heads, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.asarray(d ** 0.5, q.dtype)
    steps = jnp.arange(t)
    mask = ((steps[:, None] >= steps[None, :])[None, None]
            & (seg[:, None, :, None] == seg[:, None, None, :]))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", p, v) * jax.nn.sigmoid(gate)
    return att.reshape(b, t, heads * d) @ lp["wo"]


def causal_conv(x, w, pos):
    """Depthwise causal convolution of width K, no bias, as K shifted
    multiplies: out_t = sum_j w[:, j] x_{t-(K-1)+j}, a tap before the
    episode's first step reads zero."""
    width = w.shape[1]
    out = jnp.zeros_like(x)
    for j in range(width):
        back = width - 1 - j
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :x.shape[1]]
        out = out + w[:, j] * jnp.where((pos >= back)[..., None], shifted, 0)
    return out


def delta_recurrence(q, k, v, alpha, beta, start):
    """S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t S_{t-1})^T k_t)^T
    (S_{t-1} = 0 where `start[t]`), o_t = S_t^T q_t, one step at a time.
    `q, k [B, T, H, K]`, `v [B, T, H, V]`, `alpha, beta [B, T, H]`,
    `start [B, T]` -> (`o [B, T, H, V]`, the state after the last step
    `[B, H, K, V]`); everything in `v`'s dtype."""
    b, t, h, dk = q.shape
    block = max(d for d in range(1, min(SCAN_BLOCK, t) + 1) if t % d == 0)

    def step(state, xs):
        q_t, k_t, v_t, a_t, b_t, start_t = xs
        state = jnp.where(start_t[:, None, None, None], 0, state)
        decayed = a_t[..., None, None] * state
        seen = jnp.einsum("bhkv,bhk->bhv", decayed, k_t)  # what k_t returns today
        state = decayed + k_t[..., None] * (b_t[..., None] * (v_t - seen))[..., None, :]
        state = state.astype(v.dtype)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    @jax.checkpoint
    def steps(state, xs):
        return jax.lax.scan(step, state, xs)

    blocks = lambda x: jnp.moveaxis(x, 1, 0).reshape(t // block, block,
                                                     *x.shape[:1], *x.shape[2:])
    state, o = jax.lax.scan(
        steps, jnp.zeros((b, h, dk, v.shape[-1]), v.dtype),
        tuple(blocks(x) for x in (q, k, v, alpha, beta, start)))
    return jnp.moveaxis(o.reshape(t, b, h, -1), 0, 1), state


def l2_normalize(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + jnp.asarray(1e-6, x.dtype))


def delta_rule(y, lp, pos, hp):
    """-> (the mixer's output `[B, T, D]`, the state after the last step
    `[B, H, K, V]`, alpha and beta `[B, T, H]`)."""
    b, t, _ = y.shape
    hk, hv = hp["gdn_key_heads"], hp["gdn_value_heads"]
    dk, dv = hp["gdn_key_dim"], hp["gdn_value_dim"]
    qkv, z = jnp.split(y @ lp["in_proj"], [2 * hk * dk + hv * dv], axis=-1)
    ba = y @ lp["in_ba"]
    beta = jax.nn.sigmoid(ba[..., :hv])
    alpha = jnp.exp(-jnp.exp(lp["A_log"])
                    * jax.nn.softplus(ba[..., hv:] + lp["dt_bias"]))
    qkv = jax.nn.silu(causal_conv(qkv, lp["conv_w"], pos))
    q, k, v = jnp.split(qkv, [hk * dk, 2 * hk * dk], axis=-1)
    q = l2_normalize(q.reshape(b, t, hk, dk)) / jnp.asarray(dk ** 0.5, y.dtype)
    k = l2_normalize(k.reshape(b, t, hk, dk))
    q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))
    o, state = delta_recurrence(q, k, v.reshape(b, t, hv, dv), alpha, beta, pos == 0)
    o = (o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                      + jnp.asarray(hp["rms_eps"], o.dtype)) * lp["gate_norm"])
    gated = o * jax.nn.silu(z.reshape(b, t, hv, dv))
    return gated.reshape(b, t, hv * dv) @ lp["out_proj"], state, alpha, beta


def swiglu(x, wgu, wd):
    gate, up = jnp.split(x @ wgu, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ wd


def moe(x, lp, hp, routes=None):
    """The expert MLP on `x [B, T, D]` (after its norm) -> (routed part,
    shared part, facts). The held experts in a loop, each applied to
    every token and weighted by w_i where the token chose it, by 0 where
    it did not. `routes [B, T, top_k]`: the chosen sets to compute on
    (this file's own where None); the weights are always from this
    file's probabilities."""
    top_k, first, held = hp["top_k"], hp["first_expert"], hp["experts_held"]
    probs = jax.nn.softmax(x @ lp["router"], axis=-1)
    ranked = jnp.sort(probs, axis=-1)[..., ::-1]
    _, own = jax.lax.top_k(probs, top_k)
    chosen = own if routes is None else routes.astype(jnp.int32)
    picked = jnp.take_along_axis(probs, chosen, axis=-1)
    weight = picked / jnp.sum(picked, axis=-1, keepdims=True)

    def one_expert(acc, xs):
        index, wgu, wd = xs
        w = jnp.sum(jnp.where(chosen == index, weight, 0), axis=-1)  # [B, T]
        return acc + w[..., None] * swiglu(x, wgu, wd), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (first + jnp.arange(held), lp["expert_wgu"], lp["expert_wd"]))
    share = jax.nn.sigmoid(x @ lp["shared_gate"])
    shared = share[..., None] * swiglu(x, lp["shared_wgu"], lp["shared_wd"])
    here = (chosen >= first) & (chosen < first + held)
    facts = {"probs": probs, "chosen": own,
             "margin": ranked[..., top_k - 1] - ranked[..., top_k],
             "same_set": jnp.all(jnp.sort(own, -1) == jnp.sort(chosen, -1), axis=-1),
             "held_pairs": jnp.sum(here), "share": share}
    return routed, shared, facts


MODEL_KEYS = ("num_heads", "num_kv_heads", "head_dim", "rotary_dim", "rope_theta",
              "gdn_key_heads", "gdn_value_heads", "gdn_key_dim", "gdn_value_dim",
              "top_k", "first_expert", "experts_held", "rms_eps")


def _hp_static(hp) -> tuple:
    """What a layer reads of the hyperparameters, hashable for `jax.jit`."""
    return tuple(sorted((k, v) for k, v in hp.items() if k in MODEL_KEYS))


@functools.partial(jax.jit, static_argnames=("hp",))
def _layer(h, lp, seg, pos, routes, *, hp):
    hp = dict(hp)
    with jax.default_matmul_precision("highest"):
        y = norm(h, lp["norms"][0], hp["rms_eps"])
        if layer_kind(lp) == "linear_attention":
            mix, state, alpha, beta = delta_rule(y, lp, pos, hp)
        else:
            mix, state, alpha, beta = attention(y, lp, seg, pos, hp), None, None, None
        u = h + mix
        routed, shared, facts = moe(norm(u, lp["norms"][1], hp["rms_eps"]), lp,
                                    hp, routes)
        return u + routed + shared, state, alpha, beta, facts


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


def _cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def forward(params, tokens, done, hp, precision="highest", routes=None) -> dict:
    """-> `logits [1, B, T, V]`, `value [1, B, T]` (a leading axis of one
    pass, as `reference/ouro_looplm.py` has R), `states`: the recurrent
    state of every linear-attention layer after the last step, `alpha`,
    `beta`: every such layer's decays and write strengths, and per layer
    the routing facts of `moe` (`probs`, `chosen`, `margin`, `same_set`,
    `held_pairs`, `share`). `routes [layers, B, T, top_k]`: the sets to
    compute on."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else F32
    p = _cast(rekey(params, hp.get("layer_order")), dtype)
    seg, pos = episode_positions(jnp.asarray(done).astype(bool))
    h = p["embed"][jnp.asarray(tokens)]
    states, alphas, betas, routing = [], [], [], []
    for i, lp in enumerate(p["layers"]):
        h, state, alpha, beta, facts = layer(
            h, lp, seg, pos, hp, None if routes is None else jnp.asarray(routes[i]))
        routing.append(facts)
        if state is not None:
            states.append(state)
            alphas.append(alpha)
            betas.append(beta)
    logits, value = heads(
        h, {k: p[k] for k in ("final_norm", "head", "w_value", "b_value")},
        eps=hp["rms_eps"])
    return {"logits": logits[None], "value": value[None], "states": states,
            "alpha": alphas, "beta": betas, "routing": routing}


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
    """V-trace actor-critic per position -> (total, terms). `batch`:
    `tokens, action [B, T]` int, `behaviour_logp, reward [B, T]` float,
    `done [B, T]` bool."""
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
        F32 if k in ("probs", "margin", "share") else jnp.int32)
        for k in out["routing"][0]})  # every leaf [layers, B, T, ...]
    probs = routing["probs"]
    terms = {"total_loss": total, "pi_loss": jnp.sum(pi),
             "baseline_loss": jnp.sum(vl), "entropy": jnp.sum(first(entropy)),
             "pi_scale": jnp.sum(jnp.abs(pi)),
             "logits": out["logits"], "value": out["value"].astype(F32),
             "logp": logp[None], "states": out["states"],
             "beta_mean": jnp.mean(jnp.stack(out["beta"]).astype(F32)),
             "decay_min": jnp.min(jnp.stack(out["alpha"]).astype(F32)),
             "router_entropy": -jnp.mean(jnp.sum(
                 jnp.where(probs > 0, probs * jnp.log(jnp.where(probs > 0, probs, 1)),
                           0), axis=-1)),
             "shared_gate_mean": jnp.mean(routing["share"]),
             "held_pair_share": jnp.sum(routing["held_pairs"])
             / (probs.shape[0] * probs.shape[1] * probs.shape[2] * hp["top_k"]),
             "routing": routing}
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


def loss_and_grads(params, batch: dict, hp, precision="highest", routes=None):
    """-> (the terms of `loss`, float32 gradients in `params`' layout).
    The loss is a sum over rows and V-trace runs along a row, so the
    terms and gradients of a batch are the sums of those of its blocks
    of rows."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else F32
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        (_, terms), grads = jax.value_and_grad(
            lambda q: loss(q, batch, hp, precision, routes), has_aux=True)(
                _cast(rekey(params, hp.get("layer_order")), dtype))
    return terms, _cast(grads, F32)


def evaluate(params, batch: dict, hp, precision="highest", routes=None) -> dict:
    """Logits, values, taken-action log-probability, the final recurrent
    states, the routing facts, the loss terms, the gradients' global norm
    and the norm of the first optimizer step's change. `params` in this
    file's layout or the program's."""
    p = _cast(rekey(params, hp.get("layer_order")),
              jnp.bfloat16 if precision == "bfloat16" else F32)
    terms, grads = loss_and_grads(p, batch, hp, precision, routes)
    out = {**terms, "grad_norm": clip_scale(grads, hp)[0],
           "update_norm": rmsprop_update_norm(p, grads, hp)}
    return jax.device_get(out)


def logp_of(logits, action) -> jax.Array:
    """Float32 log-softmax of `logits [..., V]` at `action [...]`."""
    return jnp.take_along_axis(
        jax.nn.log_softmax(jnp.asarray(logits, F32), axis=-1),
        jnp.asarray(action)[..., None], axis=-1)[..., 0]


def taken_logp(params, tokens, action, done, hp, routes=None) -> jax.Array:
    """log pi(a_t | x_<=t) `[B, T]` from the full forward: what acting
    through the recurrent state, the convolution window and the
    key/value cache must reproduce."""
    with jax.default_matmul_precision("highest"):
        return logp_of(forward(params, tokens, done, hp, routes=routes)["logits"][0],
                       action)

"""One chip's share of a sparse expert layer: the router keeps its
published width (every expert of the layer, on whatever chip), each token
takes its `top_k` experts out of all of them, and this chip computes the
part of the result that the experts it HOLDS give, for the (token,
expert) pairs routed to them:

    p = softmax(x W_r) over all E;   I = the top_k largest;   w_i = p_i / sum_{j in I} p_j
    (or, `scoring="sigmoid"`: s = sigmoid(x W_r), I = the top_k of s + b, w_i = c s_i / sum_{j in I} s_j)
    out(x) = sum_{i in I, first <= i < first + held} w_i E_i(x),
    E(x) = W_d (silu(W_g x) * W_u x)

What the absent experts would have added is left out (their chips add
it, in a deployment, through an exchange this file does not stand in
for). `ops/moe.py` is the other expert layer of the repo: all experts
here, a one-hot `[N, E, C]` dispatch with a fixed capacity that DROPS
what overflows. This one is dropless with static shapes: the pairs are
sorted by expert into a buffer of the worst case's size (`N x top_k`
rows: every choice of every token held here), the held experts run as
two grouped products over it (`jax.lax.ragged_dot`: rows past the last
group are not computed, so the work follows the pairs that are really
here, about `held / E` of the buffer), and the results are added back to
their tokens. No array has an expert AND a capacity axis.

Router logits, softmax, top-k and the weights w are float32 (the product
at `highest` precision: a choice between two experts is discontinuous,
and it is made from float32 logits as the configuration states); the
grouped products take operands in `dtype` with float32 accumulation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def route(x: jax.Array, w_router: jax.Array, top_k: int,
          scoring: str = "softmax", select_bias: jax.Array | None = None,
          scale: float = 1.0):
    """`x [N, D]`, `w_router [D, E]` -> (`probs [N, E]`, `chosen [N,
    top_k]` int32 expert ids in order of decreasing probability, `weight
    [N, top_k]` renormalised over the chosen), float32.

    `scoring="sigmoid"` (the auxiliary-loss-free router of DeepSeek-V3,
    arXiv:2412.19437 section 2.1.2): `probs` are the sigmoid scores of
    ALL experts, the set is CHOSEN by score + `select_bias [E]` (a bias
    no gradient reaches), the weights are the UNBIASED scores of the
    chosen, renormalised and times `scale`; a fourth result, `load [E]`
    int32, counts the tokens that chose each expert (what moves the bias
    after the step)."""
    logits = jnp.dot(x.astype(F32), w_router.astype(F32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        top, chosen = jax.lax.top_k(probs, top_k)
        return probs, chosen.astype(jnp.int32), top / jnp.sum(top, -1, keepdims=True)
    if scoring != "sigmoid":
        raise ValueError(f"unknown scoring {scoring!r}: softmax or sigmoid")
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(select_bias.astype(F32)), top_k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    load = jnp.sum(chosen[..., None] == jnp.arange(scores.shape[-1]),
                   axis=(0, 1), dtype=jnp.int32)
    return (scores, chosen.astype(jnp.int32),
            scale * top / (jnp.sum(top, -1, keepdims=True) + 1e-20), load)


def held_pairs(chosen: jax.Array, first_expert: int, held: int):
    """The (token, choice) pairs whose expert lies in `[first_expert,
    first_expert + held)`, sorted by expert -> (`order [N * top_k]`: the
    flat pair index at every row of the buffer, held pairs first, by
    expert; `sizes [held]` int32 pairs an expert; `here [N, top_k]`
    bool)."""
    local = chosen - first_expert
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held).reshape(-1)  # `held`: an absent expert
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    return order, sizes, here


def held_experts(x: jax.Array, chosen: jax.Array, weight: jax.Array,
                 wgu: jax.Array, wd: jax.Array, first_expert: int,
                 dtype=jnp.bfloat16):
    """`x [N, D]`, `chosen, weight [N, top_k]` (`route`'s), `wgu [held,
    D, 2 F]` (gate and up side by side), `wd [held, F, D]` -> (`out [N,
    D]` float32: the held experts' weighted part of the layer's result;
    counters). Dropless: the buffer has a row for every pair."""
    n, top_k = chosen.shape
    held = wgu.shape[0]
    order, sizes, here = held_pairs(chosen, first_expert, held)
    token = order // top_k
    # A row past the last group belongs to no held expert, and the grouped
    # product neither reads nor WRITES it: on the chip it holds whatever
    # the buffer held (my chip run, PR 36: finite garbage; nothing says it
    # is), forward and in the backward's products alike. So the buffer is
    # masked where it is filled and where it is read: no such row reaches
    # the result, and no cotangent of one reaches `x`.
    count = jnp.sum(sizes)
    live = (jnp.arange(n * top_k) < count)[:, None]
    rows = jnp.where(live, x.astype(dtype)[token], 0)  # the pair buffer [N * top_k, D]
    gate, up = jnp.split(jax.lax.ragged_dot(
        rows, wgu.astype(dtype), sizes, preferred_element_type=F32), 2, -1)
    y = jax.lax.ragged_dot((jax.nn.silu(gate) * up).astype(dtype),
                           wd.astype(dtype), sizes, preferred_element_type=F32)
    y = jnp.where(live, y, 0.0) * weight.reshape(-1)[order][:, None]
    out = jnp.zeros((n, x.shape[-1]), F32).at[token].add(y)
    counters = {"held_pairs": count, "expert_pairs": sizes,
                "dropped_pairs": jnp.sum(here) - jnp.sum(live)}
    return out, counters

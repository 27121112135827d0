"""One chip's share of a sparse expert layer: the router keeps its
published width (every expert of the layer, on whatever chip), each token
takes its `top_k` experts out of all of them, and this chip computes the
part of the result that the experts it HOLDS give, for the (token,
expert) pairs routed to them:

    p = softmax(x W_r) over all E;   I = the top_k largest;   w_i = p_i / sum_{j in I} p_j
    (or, `scoring="sigmoid"`: s = sigmoid(x W_r), I = the top_k of s + b, w_i = c s_i / sum_{j in I} s_j)
    out(x) = sum_{i in I, first <= i < first + held} w_i E_i(x),
    E(x) = W_d (silu(W_g x) * W_u x)      (`activation="relu"`: relu(W_g x) * W_u x, ReGLU)
    or, UNGATED (`activation="relu2"`):   E(x) = W_d relu(W_u x)^2, one up matrix `[E, D, F]`

What the absent experts would have added is left out (their chips add
it, in a deployment, through an exchange this file does not stand in
for). `ops/moe.py` is the other expert layer of the repo: all experts
here, a one-hot `[N, E, C]` dispatch with a fixed capacity that DROPS
what overflows. This one is dropless with static shapes, and takes one of
two forms of the same sum, chosen when it is traced from the shapes alone
(`slab_rows`, `one_slab_form`; `call_form` says which, and `runtime/
launch.py` prints it once at start-up):

SORTED. The pairs are sorted, held first and by expert, into a list of the
worst case's length (`N x top_k`: every choice of every token held here),
and the SORTED LIST is worked a slab at a time, as many slabs as hold a
held pair (`ceil(held pairs / slab)`, a trip count read from the data:
nothing is compiled again when it changes). A slab gathers its rows of
`x`, runs the held experts as two grouped products (`jax.lax.ragged_dot`),
weights the result and adds it to its tokens. A slab is the held pairs a
uniform router would send here and a quarter more, `1.25 N x top_k x held
/ E` rounded up to 512 rows (`slab_rows`); where that is the whole list (a
decode step; a layer that holds every expert) there is one slab and no
loop. No array has an expert AND a capacity axis, and none has the list's
length and a model width. Every learner runs this form (4,096 rows a call
and more), and the decode steps that send a held expert under one pair a
call (`qwen3_next`: 32 rows x 10 of 512, `joyai_flash`: 16 x 8 of 256,
`smallthinker_moe`: 8 x 6 of 64, 0.75 pair an expert and 16 held of width
768), where the grouped product reads the touched experts' weights alone
(about 8.7 of 16 a layer a step there).

DENSE (PR 47). Where the list is one slab, the router sends every held
expert more than one pair a call and the rows are few (`lfm2_moe`'s decode
step: 64 rows x 4 of 64, 16 held, four pairs an expert), every held
expert's weights are read whatever the routing, and the sort, the gather,
the scatter-add and the grouped products buy nothing: the held experts run
as ONE batched product over all rows, `[held, N, 2 F]` then `[held, N,
D]`, the weights contracted as they are stored, and each row's results
are weighted by what the router gave the expert, 0 for one it did not
choose. The same sum in another order of float32 additions, 429 us a layer
a step where the sorted form takes 623 (`one_slab_form` has the table).
Since PR 53 also where a width of the products is no whole number of the
grouped product's tiles (`nemotron_h_moe`'s decode step: D 2,688, F 1,856;
219 us where the sorted form takes 738 and follows the routing).

Which work follows the pairs that are here. Until PR 42 the list was ONE
buffer `[N x top_k, D]`: the grouped products skipped the rows past the
last group, and every gather, mask, weighting and scatter-add, forward
and backward, passed over all of it, sixteen times the held pairs at a
sixteenth of the experts. Now only the sort and the counts (`[N x top_k]`
and `[N x top_k, held]`, no model width) are as long as the list; the
pairs' weights are gathered, and everything `D`, `F` or `2 F` wide is, a
slab at a time. What a trip costs whatever its rows: in the backward,
passes over arrays as large as the weights (their two transposes, their
gradients' float32 sums). (The sorted form's.)

Router logits, softmax, top-k and the weights w are float32 (the product
at `highest` precision: a choice between two experts is discontinuous,
and it is made from float32 logits as the configuration states); the
products of both forms take operands in `dtype` with float32 accumulation.
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp

F32 = jnp.float32


def route(x: jax.Array, w_router: jax.Array, top_k: int,
          scoring: str = "softmax", select_bias: jax.Array | None = None,
          scale: float = 1.0, weight_eps: float = 1e-20):
    """`x [N, D]`, `w_router [D, E]` -> (`probs [N, E]`, `chosen [N,
    top_k]` int32 expert ids in order of decreasing probability, `weight
    [N, top_k]` renormalised over the chosen), float32.

    `scoring="sigmoid"` (the auxiliary-loss-free router of DeepSeek-V3,
    arXiv:2412.19437 section 2.1.2): `probs` are the sigmoid scores of
    ALL experts, the set is CHOSEN by score + `select_bias [E]` (a bias
    no gradient reaches), the weights are the UNBIASED scores of the
    chosen, renormalised (over their sum + `weight_eps`, the source
    family's own constant) and times `scale`; a fourth result, `load [E]`
    int32, counts the tokens that chose each expert (what moves the bias
    after the step: `rebias`)."""
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
            scale * top / (jnp.sum(top, -1, keepdims=True) + weight_eps), load)


def rebias(before: dict, after: dict, load: jax.Array, gamma: float,
           holders) -> dict:
    """`after` (the parameters an optimizer step made of `before`; both
    the tree under `"params"`) with every router's selection bias set to
    `before`'s moved by `gamma sign(mean_j(n_j) - n_i)` (arXiv:2412.19437
    section 2.1.2), `load [rows, E]` the tokens that chose each expert in
    the step's forward: whatever the optimizer did to the bias is
    dropped. `holders`: the key path of every dict that holds a
    `router_bias [n, E]`, in the order of `load`'s rows (a stacked run of
    n layers takes n of them)."""
    load = load.astype(F32)
    move = gamma * jnp.sign(jnp.mean(load, -1, keepdims=True) - load)

    def replaced(old: dict, new: dict, path: tuple, rows: jax.Array) -> dict:
        if not path:
            return {**new, "router_bias": old["router_bias"] + rows}
        return {**new, path[0]: replaced(old[path[0]], new[path[0]], path[1:], rows)}

    new, at = after, 0
    for path in holders:
        n = functools.reduce(operator.getitem, path, before)["router_bias"].shape[0]
        new = replaced(before, new, tuple(path), move[at:at + n])
        at += n
    return new


def held_pairs(chosen: jax.Array, first_expert: int, held: int):
    """The (token, choice) pairs whose expert lies in `[first_expert,
    first_expert + held)` -> (`key [N * top_k]` int32: every pair's expert
    among the held, `held` for an absent one; `here [N, top_k]` bool).
    Both forms of `held_experts` take their pairs from here and nowhere
    else."""
    local = chosen - first_expert
    here = (local >= 0) & (local < held)
    return jnp.where(here, local, held).reshape(-1), here


def slab_rows(pairs: int, held: int, num_experts: int) -> int:
    """Rows of one slab of the sorted pair list, from shapes alone: the
    held pairs a uniform router would send here (`pairs x held /
    num_experts`) and a quarter more, up to the next multiple of 512, and
    never more than the list. The quarter: whatever its rows, a trip costs
    the backward several passes over arrays as large as the weights (4.2 ms
    a call at the sixth cell's shape, where 512 more rows cost 0.15; my
    chip run, PR 42), and a slab AT the expectation would run a second one
    for every other call."""
    rows = -(-5 * pairs * held // (4 * num_experts))
    return min(pairs, -(-rows // 512) * 512)


def _gated(activation: str, gate: jax.Array, counted: jax.Array):
    """The expert's gate under its activation (`"silu"`: SwiGLU;
    `"relu"`: ReGLU) and, for `"relu"`, how many of the gate values of the
    pairs `counted` (a mask that broadcasts against `gate`) it zeroes
    (None for `"silu"`, which zeroes none: no op is added to its callers)."""
    if activation == "silu":
        return jax.nn.silu(gate), None
    if activation != "relu":
        raise ValueError(f"unknown activation {activation!r}: silu, relu or relu2")
    return jax.nn.relu(gate), jnp.sum(counted & (gate <= 0), dtype=jnp.int32)


def _inner(activation: str, up: jax.Array, counted: jax.Array):
    """What the down-projection reads, from the up-projection's result: a
    GATED expert's (`"silu"`, `"relu"`: `up` is gate and up side by side,
    `[..., 2 F]`) `act(gate) * up`, the UNGATED `"relu2"`'s (`[..., F]`)
    `relu(up)^2`; and `_gated`'s count (for `"relu2"`: of the
    up-projections that ReLU zeroed)."""
    if activation == "relu2":
        return (jnp.square(jax.nn.relu(up)),
                jnp.sum(counted & (up <= 0), dtype=jnp.int32))
    gate, up = jnp.split(up, 2, -1)
    gate, zeroed = _gated(activation, gate, counted)
    return gate * up, zeroed


def _slab(rows: jax.Array, weight: jax.Array, wgu: jax.Array, wd: jax.Array,
          sizes: jax.Array, live_rows: jax.Array, activation: str = "silu"):
    """The held experts on one slab of the sorted pairs: `rows [S, D]`
    (the pairs' tokens, operands' dtype), `weight [S]`, `sizes [held]` (the
    rows of each expert inside the slab, in order), `live_rows`: how many
    of the slab's rows are held pairs -> (`[S, D]` float32, each pair's
    weighted expert output and 0 on the rest; `_inner`'s count)."""
    # A row past the last group belongs to no held expert, and the grouped
    # product neither reads nor WRITES it: on the chip it holds whatever
    # the buffer held (my chip run, PR 36: finite garbage; nothing says it
    # is), forward and in the backward's products alike. So the slab is
    # masked where it is filled and where it is read: no such row reaches
    # the result, and no cotangent of one reaches `x`.
    live = (jnp.arange(rows.shape[0]) < live_rows)[:, None]
    inner, zeroed = _inner(activation, jax.lax.ragged_dot(
        jnp.where(live, rows, 0), wgu, sizes, preferred_element_type=F32), live)
    y = jax.lax.ragged_dot(inner.astype(rows.dtype), wd, sizes,
                           preferred_element_type=F32)
    return jnp.where(live, y, 0.0) * weight[:, None], zeroed


TILE = 256  # lanes of the grouped product's tile, by the readings of PR 53 below


def one_slab_form(n: int, top_k: int, num_experts: int, widths=()) -> str:
    """`"dense"` or `"sorted"`: the form of a call of `held_experts` on `n`
    rows whose pair list is one slab, from shapes alone. Dense where (i) a
    uniform router sends every held expert MORE than one pair a call (`n x
    top_k > num_experts`), so every held expert's weights are read in
    either form, OR (PR 53) one of the products' `widths` (D, the
    up-projection's, F) is over a tile and no whole number of tiles of
    `TILE`, where the compiler's grouped product falls to a quarter of the
    batched product's rate, and (ii) `n <= 256`, where `n x held` rows of
    product still take less than the weights' read (a bfloat16 weight gives
    `n` operations a byte, the chip's ridge is about 240). The readings, us a
    layer a step in a scan of 64 decode steps of 4 layers, bfloat16, D
    2,048, sorted | dense (my chip run, PR 47; `scripts/expert_share_bench.
    py --rows`; 302 MB of weights take 369 us at HBM's peak, 151 MB 184):

        64 rows, top 4 of 64, 16 held, F 1,536 (`lfm2_moe`'s decode)  623 | 429
        the same at 16 / 32 / 128 rows          334 | 437, 470 | 439, 1,016 | 470
        the same at F 768, 32 / 64 / 128 rows   263 | 217, 358 | 225, 556 | 241
        32 rows, top 10 of 512, 32 held, F 512 (`qwen3_next`'s)       234 | 294
        16 rows, top 8 of 256, 16 held, F 768 (`joyai_flash`'s)       156 | 214
        the same at 32 / 64 rows                          249 | 217, 492 | 225
        8 rows, top 6 of 64, 16 held, F 768 (`smallthinker_moe`'s): 48 pairs
          for 64 experts, UNDER one pair an expert: sorted by the rule
                                                          190 | 259 (PR 53)
        UNGATED (`relu2`), 16 rows, top 6 of 128, 8 held (my chip run, PR 53;
          the batched product streams its weights at 89 % of HBM's peak
          whatever the widths and the routing, the grouped one does not):
          D 2,688, F 1,856 (`nemotron_h_moe`'s: 10.5 and 7.25 tiles)  738 | 219
            the same under a skewed router (5.3 held pairs for 6.1)  188 | 219
            the same at 32 rows 1,132 | 220; with 16 held 1,540 | 430
          D 2,688, F 1,792   370 | 210        D 2,688, F 2,048   289 | 241
          D 2,560, F 1,856   275 | 208        D 2,048, F 1,536   136 | 146
        all 16 of 16 held, F 1,536, 128 / 256 / 384 / 512 rows
                              1,016 | 470, 1,171 | 482, 1,271 | 745, 1,400 | 958

    Under one pair an expert the sorted form wins (it reads the touched
    experts alone: 39-46 % of them at the two other cells' decode steps),
    over one the dense form does; AT one the two shapes measured disagree
    (16 rows of 64: sorted by 24 %; 32 rows of 256: dense by 13 %) and the
    rule says sorted. The dense product is bound by the weights' read up
    to 256 rows (482 us) and by the matrix unit past it (745 at 384); it
    still beat the sorted form there with every pair held, which no caller
    does, so the bound is the ridge and not the last win. With widths that
    are whole tiles the sorted form is at best level with the dense one at
    these rows (136 | 146) unless the experts are narrow (F 768: 190 |
    259); with a width that is not, it is 1.2 to 3.4 times slower AND its
    time follows the routing (738 | 188 us by the router's skew alone: an
    update's time then swings with the seed), so such shapes go dense."""
    ragged = any(w > TILE and w % TILE for w in widths)
    return "dense" if (n * top_k > num_experts or ragged) and n <= 256 else "sorted"


def _form(n: int, top_k: int, held: int, num_experts: int,
          widths=()) -> tuple[str, int]:
    """(`"dense"`, `"sorted"` (one slab) or `"slabs"`; the rows of a slab) of
    a call of `held_experts` on `n` rows with products `widths` wide."""
    slab = slab_rows(n * top_k, held, num_experts)
    if slab < n * top_k:
        return "slabs", slab
    return one_slab_form(n, top_k, num_experts, widths), slab


def call_form(n: int, top_k: int, held: int, num_experts: int, widths=()) -> str:
    """The form and the shape of a call of `held_experts` on `n` rows, as a
    start-up line says it (`runtime/launch.py`): static, as compiled."""
    form, slab = _form(n, top_k, held, num_experts, widths)
    return {"dense": f"dense, {n} rows x {held} held",
            "sorted": f"sorted, one slab of {slab} pairs",
            "slabs": f"sorted, {n * top_k} pairs in slabs of {slab}"}[form]


def _dense(x: jax.Array, key: jax.Array, weight: jax.Array, wgu: jax.Array,
           wd: jax.Array, activation: str = "silu"):
    """Every held expert on every row, one batched product over the
    experts, a row's results weighted by what the router gave each (0: an
    expert the row did not choose): `x [N, D]`, `key, weight [N x top_k]`
    (`held_pairs`' experts, the router's weights) -> (`[N, D]` float32, the
    sorted form's sum in another order of float32 additions; `_inner`'s
    count over the (expert, row) the router paired)."""
    held, pairs = wgu.shape[0], (x.shape[0], -1)
    w = jnp.sum(jnp.where(
        key.reshape(pairs)[None] == jnp.arange(held)[:, None, None],
        weight.reshape(pairs)[None], 0.0), axis=-1)[..., None]  # [held, N, 1]
    inner, zeroed = _inner(activation, jnp.einsum(
        "end,edf->enf", jnp.broadcast_to(x, (held, *x.shape)), wgu,
        preferred_element_type=F32), w != 0)
    y = jnp.einsum("enf,efd->end", inner.astype(x.dtype), wd,
                   preferred_element_type=F32)
    # 0 x a non-finite result of an expert the row did not choose would be NaN
    return jnp.sum(jnp.where(w != 0, y, 0.0) * w, axis=0), zeroed


def _trips(sizes, slab: int):
    """The slabs that hold a held pair."""
    return (jnp.sum(sizes) + slab - 1) // slab


def _slab_of(i, slab: int, top_k: int, order, sizes):
    """Slab `i` of the sorted list: the flat pair index and the token of
    each of its rows, the overlap of every expert's `[begin, end)` with it,
    its live rows."""
    lo = i * slab
    end = jnp.cumsum(sizes)
    inside = jnp.clip(end, lo, lo + slab) - jnp.clip(end - sizes, lo, lo + slab)
    pair = jax.lax.dynamic_slice(order, (lo,), (slab,))
    return pair, pair // top_k, inside, end[-1] - lo


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _slabs(x, weight, wgu, wd, order, sizes, slab: int, top_k: int,
           activation: str = "silu"):
    """`sum_i scatter(_slab(slab i))` over the slabs that hold a held pair:
    `x [N, D]`, `wgu`, `wd` in the operands' dtype, `weight [N x top_k]`
    by flat pair index, `order [trips_max x slab]` -> (`[N, D]` float32,
    the slabs' `_inner` counts summed: None for `"silu"`)."""
    return _slabs_fwd(x, weight, wgu, wd, order, sizes, slab, top_k, activation)[0]


def _slabs_fwd(x, weight, wgu, wd, order, sizes, slab, top_k, activation):
    def trip(i, sums):
        out, zeroed = sums
        pair, tok, inside, live_rows = _slab_of(i, slab, top_k, order, sizes)
        y, z = _slab(x[tok], weight[pair], wgu, wd, inside, live_rows, activation)
        return out.at[tok].add(y), None if z is None else zeroed + z

    sums = jax.lax.fori_loop(
        0, _trips(sizes, slab), trip,
        (jnp.zeros(x.shape, F32), None if activation == "silu" else jnp.int32(0)))
    return sums, (x, weight, wgu, wd, order, sizes)


def _slabs_bwd(slab, top_k, activation, saved, g):
    """The same loop backwards: a trip takes the slab's own VJP and adds
    into float32 sums (reverse mode does not pass a loop whose trip count
    is traced, and the caller rematerialises the layer anyway, so only the
    inputs were kept)."""
    x, weight, wgu, wd, order, sizes = saved
    g = g[0]  # the count is an integer: no cotangent

    def trip(i, sums):
        dx, dweight, dwgu, dwd = sums
        pair, tok, inside, live_rows = _slab_of(i, slab, top_k, order, sizes)
        _, back = jax.vjp(
            lambda rows, w, wgu, wd: _slab(rows, w, wgu, wd, inside, live_rows,
                                           activation)[0],
            x[tok], weight[pair], wgu, wd)
        rows_bar, w_bar, wgu_bar, wd_bar = back(g[tok])
        return (dx.at[tok].add(rows_bar.astype(F32)), dweight.at[pair].add(w_bar),
                dwgu + wgu_bar.astype(F32), dwd + wd_bar.astype(F32))

    zeros = lambda a: jnp.zeros(a.shape, F32)
    dx, dweight, dwgu, dwd = jax.lax.fori_loop(
        0, _trips(sizes, slab), trip, (zeros(x), zeros(weight), zeros(wgu), zeros(wd)))
    return (dx.astype(x.dtype), dweight, dwgu.astype(wgu.dtype),
            dwd.astype(wd.dtype), None, None)


_slabs.defvjp(_slabs_fwd, _slabs_bwd)


def held_experts(x: jax.Array, chosen: jax.Array, weight: jax.Array,
                 wgu: jax.Array, wd: jax.Array, first_expert: int,
                 num_experts: int, dtype=jnp.bfloat16, activation: str = "silu"):
    """`x [N, D]`, `chosen, weight [N, top_k]` (`route`'s), `wgu [held,
    D, 2 F]` (gate and up side by side; `[held, D, F]`, the one up matrix,
    for the ungated `"relu2"`), `wd [held, F, D]`, `num_experts`:
    the router's width -> (`out [N, D]` float32: the held experts'
    weighted part of the layer's result; counters). Dropless: every held
    pair lies in exactly one slab, and every slab with one is run; the
    dense form (`one_slab_form`) runs every held expert on every row.
    `activation`: the gate's, `"silu"` or `"relu"` (ReGLU, `relu(W_g x) *
    W_u x`), or `"relu2"` (ungated, `relu(W_u x)^2`); the two ReLU forms
    add the counter `gate_zeroed`: the held pairs' gate values (`"relu2"`:
    up-projections; F a pair) that ReLU zeroed."""
    n, top_k = chosen.shape
    held = wgu.shape[0]
    form, slab = _form(n, top_k, held, num_experts,
                       (wgu.shape[1], wgu.shape[2], wd.shape[1]))
    key, here = held_pairs(chosen, first_expert, held)
    if form != "dense":  # held pairs first, by expert: the flat pair index a row
        order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)  # pairs an expert
    count, trips = jnp.sum(sizes), _trips(sizes, slab)
    x, wgu, wd = x.astype(dtype), wgu.astype(dtype), wd.astype(dtype)
    weight = weight.reshape(-1)
    if form == "dense":  # every held expert is read anyway: no sort, no grouped product
        out, zeroed = _dense(x, key, weight, wgu, wd, activation)
    elif form == "sorted":  # the list is one slab: no loop, autodiff's own backward
        token, out = order // top_k, jnp.zeros(x.shape, F32)
        y, zeroed = _slab(x[token], weight[order], wgu, wd, sizes, count, activation)
        out = out.at[token].add(y)
    else:  # the list's last slab is a whole one too: rows of pair 0, past `count`
        out, zeroed = _slabs(x, weight, wgu, wd,
                             jnp.pad(order, (0, -order.size % slab)), sizes, slab,
                             top_k, activation)
    counters = {"held_pairs": count, "expert_pairs": sizes, "pair_slabs": trips,
                "dropped_pairs": jnp.sum(here) - jnp.minimum(count, trips * slab),
                "dense_rows": jnp.int32(n * held if form == "dense" else 0)}
    if zeroed is not None:
        counters["gate_zeroed"] = jax.lax.stop_gradient(zeroed)
    return out, counters

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
three forms of the same sum, chosen when it is traced from the shapes alone
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
and more); of the one-slab calls those past 256 rows do, and those under
one pair a held expert whose experts are too small for the touched form
(`qwen3_next`'s decode step: 32 rows x 10 of 512, 32 held of width 512).

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

TOUCHED (PR 54). Where the list is one slab and the router sends a held
expert AT MOST one pair a call, a good part of the held experts is chosen
by no row (`smallthinker_moe`'s decode step, 8 rows x 6 of 64 with 16
held: 8.7 touched of 16; `nemotron_h_moe`, 16 x 6 of 128 with 8 held: 4.4
of 8; `joyai_flash`, 16 x 8 of 256, 16 held: 6.5), and where an expert is
large enough for a trip's fixed cost (`qwen3_next`'s, 32 x 10 of 512 with
32 held of 6.3 MB each, is not and stays sorted: `one_slab_form`). A loop
runs the experts some row chose and no other
(its trip count is their number, read from the data), each as two PLAIN
products over ALL rows of the call, `x [N, D] @ wgu[e]`, the activation,
`@ wd[e]`, the result weighted by the expert's column of the router's
weights (0 on a row that did not choose it) and added into a float32 sum
in expert order: the dense form's sum without its terms that are exactly
0, and without the read of their weights. No sort, no gather, no
scatter-add, no grouped product: the weights of one expert stream through
a plain product at 74-83 % of HBM's peak whatever the widths, where the
grouped product reads the same bytes slower (and at a quarter of its rate
at widths that are no whole number of its tiles) and the dense form reads
every held expert. A trip reads its expert's two matrices from the
stacked arrays inside the products' own fusions (no copy of them); it
costs about 6 us beside the read. The backward is the same loop with each
touched expert's own VJP; an expert no row chose gets zeros and is never
read.

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
products of all forms take operands in `dtype` with float32 accumulation.
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
    Every form of `held_experts` takes its pairs from here and nowhere
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


def _inner_zeroed(activation: str, up: jax.Array, counted: jax.Array):
    """What the down-projection reads, from the up-projection's result: a
    GATED expert's (`"silu"`: SwiGLU, `"relu"`: ReGLU; `up` is gate and up
    side by side, `[..., 2 F]`) `act(gate) * up`, the UNGATED `"relu2"`'s
    (`[..., F]`) `relu(up)^2`; and, `[..., F]` bool, which of the gate
    values (`"relu2"`: up-projections) of the pairs `counted` (a mask that
    broadcasts against them) ReLU zeroed (None for `"silu"`, which zeroes
    none: no op is added to its callers)."""
    if activation == "relu2":
        return jnp.square(jax.nn.relu(up)), counted & (up <= 0)
    gate, up = jnp.split(up, 2, -1)
    if activation == "silu":
        return jax.nn.silu(gate) * up, None
    if activation != "relu":
        raise ValueError(f"unknown activation {activation!r}: silu, relu or relu2")
    return jax.nn.relu(gate) * up, counted & (gate <= 0)


def _inner(activation: str, up: jax.Array, counted: jax.Array):
    """`_inner_zeroed` with the zeroed values COUNTED (int32; None for
    `"silu"`)."""
    inner, zeroed = _inner_zeroed(activation, up, counted)
    return inner, None if zeroed is None else jnp.sum(zeroed, dtype=jnp.int32)


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


TRIP_WEIGHTS = 5 << 18  # D x F of the narrowest expert worth a trip: the cells below


def one_slab_form(n: int, top_k: int, num_experts: int, widths=()) -> str:
    """`"touched"`, `"dense"` or `"sorted"`: the form of a call of
    `held_experts` on `n` rows whose pair list is one slab, from shapes
    alone. Up to 256 rows (where `n x held` rows of product still take less
    than the weights' read: a bfloat16 weight gives `n` operations a byte,
    the chip's ridge is about 240): DENSE where a uniform router sends a
    held expert MORE than one pair a call (`n x top_k > num_experts`), so
    every held expert's weights are read in any form; TOUCHED where it
    sends at most one, so a good part of the held experts is chosen by no
    row and is not read, AND an expert is large enough for a trip's fixed
    cost: `widths` (D, ..., F: the products') with `D x F >= TRIP_WEIGHTS`
    (1.31 M), or none given. A trip costs about 6 us beside its expert's
    read, whatever the widths. In the CELLS (my chip runs, PR 54, three pairs
    in the first and the third, one in the others; `frames_learned_per_s`):
    `nemotron_h_moe` (D x F 4.99 M, 19.96 MB an expert: the 6 us beside a
    read of 24.4) + 18 %,
    `joyai_flash` (1.57 M, 9.4 MB: 11.5) + 14.6 %, `smallthinker_moe` (1.97
    M, 11.8 MB: 14.4) + 1.2 %, `qwen3_next` (1.05 M, 6.3 MB: 7.7, and the
    longest loop, 14.8 trips) - 8.6 %, THOUGH the table's run of that shape
    alone reads 234 | 293 | 202 below: in its cell the compiler stages the
    grouped product's weights into fast memory under the step's other
    work (169 us a layer a step there, PRs 36-37) and a loop's trips hide
    behind nothing. So that shape keeps the sorted form, by its experts'
    size. Past 256 rows: sorted. The readings, us a layer a step in a
    scan of 64 decode steps of 4 layers, bfloat16, sorted | dense | touched
    (my chip run, PR 54, every row in one call; `scripts/expert_share_bench.
    py --rows`; the first two columns as PRs 47 and 53 read them, within 1
    %), `t`: the held experts some row chose, a call:

        UNDER one pair an expert (the rule: touched, but `qwen3_next`'s)
        8 rows, top 6 of 64, 16 held, D 2,560, F 768 ReGLU
          (`smallthinker_moe`'s decode), t 8.7            190 | 259 | 184
          (the table's runs left the ReLU forms' count unread; read, as a
          cell reads it: 189 | - | 188; in the cell 188 -> 183)
        16 rows, top 8 of 256, 16 held, D 2,048, F 768
          (`joyai_flash`'s), t 6.5                        160 | 215 | 117
        32 rows, top 10 of 512, 32 held, D 2,048, F 512
          (`qwen3_next`'s), t 14.8; at 16 rows, t 8.6
                                            234 | 293 | 202, 171 | 289 | 117
        UNGATED (`relu2`), 16 rows, top 6 of 128, 8 held, t 4.4:
          D 2,688, F 1,856 (`nemotron_h_moe`'s: 10.5 and 7.25 of the
            grouped product's tiles of 256)               740 | 219 | 135
            the same where the router all but avoids the held experts
            (0.3 held pairs a call, t 0.2)                189 | 219 |  38
          D 2,688, F 1,792   344 | 210 | 140    D 2,688, F 2,048   291 | 241 | 155
          D 2,560, F 1,856   275 | 208 | 130    D 2,048, F 1,536   138 | 146 |  95
        AT one pair an expert (the rule: touched)
        16 rows, top 4 of 64, 16 held, D 2,048, F 1,536, t 10.6
                                                          344 | 437 | 326
        32 rows, top 8 of 256, 16 held, F 768, t 10.7     254 | 216 | 184
        OVER one pair an expert (the rule: dense)
        64 rows, top 4 of 64, 16 held, F 1,536 (`lfm2_moe`'s decode),
          t 15.9; at 32 / 128 rows, t 14.1 / 16
                        625 | 429 | 511, 474 | 440 | 445, 1,016 | 470 | 549
        the same at F 768, 32 / 64 / 128 rows
                          264 | 217 | 249, 358 | 224 | 303, 556 | 241 | 331
        `smallthinker_moe`'s at 16 / 32 rows, t 12.9 / 15.3
                                          280 | 268 | 267, 343 | 269 | 321
        `joyai_flash`'s at 64 rows, t 13.9                492 | 225 | 269
        `nemotron_h_moe`'s at 32 rows (1.5 pairs an expert), t 6.2
                                                        1,132 | 219 | 189
        all 16 of 16 held, F 1,536, 128 / 256 / 384 / 512 rows
                        1,017 | 470 | 547, 1,171 | 482 | 633,
                        1,271 | 745 | 828, 1,400 | 956 | 1,040

    A trip of the touched form costs its expert's read at HBM's peak and
    about 6 us more, whatever the widths (11.8 MB: 21.1 us; 19.96 MB: 30.4;
    9.4 MB: 17.8; 6.3 MB: 13.7: one expert's product streams at 74-83 % of
    the peak where the dense form's ONE product over all of them streams
    at 89, and a trip has 2 us of control), so the form's time is `t`
    trips: it wins wherever a good part of the held experts is untouched,
    by 1-3 % where the grouped product is at its best (8 rows of narrow
    experts at whole tiles: the sorted call there moves the touched up
    matrices and ALL the down matrices, staged by the compiler, at 86 % of
    the peak) and by 38 % where it is at its worst, and its time follows
    the routing as the sorted form's does (219 -> 38 us by the router's
    skew alone). At one pair an expert it wins both shapes measured, which the
    two-form rule got wrong one way or the other (PR 47). Over one pair
    nearly every held expert is touched and the dense form's ONE batched
    product beats `held` trips by their fixed cost (429 | 511); the one
    shape measured where touched still wins there (wide experts, 6.2 of 8
    touched: 219 | 189) has no caller, and the rule stays with the count of
    pairs. The dense product is bound by the weights' read up to 256 rows
    (482 us) and by the matrix unit past it (745 at 384); it still beat the
    sorted form there with every pair held, which no caller does, so the
    bound is the ridge and not the last win."""
    if n > 256:
        return "sorted"
    if n * top_k > num_experts:
        return "dense"
    wide = not widths or widths[0] * widths[-1] >= TRIP_WEIGHTS
    return "touched" if wide else "sorted"


def _form(n: int, top_k: int, held: int, num_experts: int,
          widths=()) -> tuple[str, int]:
    """(`"touched"`, `"dense"`, `"sorted"` (one slab) or `"slabs"`; the rows
    of a slab) of a call of `held_experts` on `n` rows with products
    `widths` wide."""
    slab = slab_rows(n * top_k, held, num_experts)
    if slab < n * top_k:
        return "slabs", slab
    return one_slab_form(n, top_k, num_experts, widths), slab


def call_form(n: int, top_k: int, held: int, num_experts: int, widths=()) -> str:
    """The form and the shape of a call of `held_experts` on `n` rows, as a
    start-up line says it (`runtime/launch.py`): static, as compiled."""
    form, slab = _form(n, top_k, held, num_experts, widths)
    return {"touched": f"touched, {n} rows x up to {held} held",
            "dense": f"dense, {n} rows x {held} held",
            "sorted": f"sorted, one slab of {slab} pairs",
            "slabs": f"sorted, {n * top_k} pairs in slabs of {slab}"}[form]


def _columns(key: jax.Array, weight: jax.Array, held: int, n: int) -> jax.Array:
    """`[held, n]`: what the router gave each held expert on each row (`key,
    weight [n x top_k]`: `held_pairs`' experts, the router's weights), 0
    where the row did not choose it."""
    pairs = (n, -1)
    return jnp.sum(jnp.where(
        key.reshape(pairs)[None] == jnp.arange(held)[:, None, None],
        weight.reshape(pairs)[None], 0.0), axis=-1)


def _dense(x: jax.Array, key: jax.Array, weight: jax.Array, wgu: jax.Array,
           wd: jax.Array, activation: str = "silu"):
    """Every held expert on every row, one batched product over the
    experts, a row's results weighted by what the router gave each (0: an
    expert the row did not choose): `x [N, D]`, `key, weight [N x top_k]`
    (`held_pairs`' experts, the router's weights) -> (`[N, D]` float32, the
    sorted form's sum in another order of float32 additions; `_inner`'s
    count over the (expert, row) the router paired)."""
    held = wgu.shape[0]
    w = _columns(key, weight, held, x.shape[0])[..., None]  # [held, N, 1]
    inner, zeroed = _inner(activation, jnp.einsum(
        "end,edf->enf", jnp.broadcast_to(x, (held, *x.shape)), wgu,
        preferred_element_type=F32), w != 0)
    y = jnp.einsum("enf,efd->end", inner.astype(x.dtype), wd,
                   preferred_element_type=F32)
    # 0 x a non-finite result of an expert the row did not choose would be NaN
    return jnp.sum(jnp.where(w != 0, y, 0.0) * w, axis=0), zeroed


def _expert(x: jax.Array, w: jax.Array, wgu: jax.Array, wd: jax.Array,
            activation: str):
    """ONE expert on every row, two plain products: `x [N, D]`, `w [N]` (its
    column of `_columns`), `wgu [D, 2 F]` (`[D, F]` ungated), `wd [F, D]` ->
    (`[N, D]` float32, the rows' weighted results and exactly 0 on a row
    that did not choose it, as `_dense` guards them; `_inner_zeroed`'s mask
    `[N, F]`)."""
    chose = (w != 0)[:, None]
    inner, zeroed = _inner_zeroed(
        activation, jnp.dot(x, wgu, preferred_element_type=F32), chose)
    y = jnp.dot(inner.astype(x.dtype), wd, preferred_element_type=F32)
    return jnp.where(chose, y, 0.0) * w[:, None], zeroed


def _touched_ids(sizes: jax.Array):
    """(the held experts some row chose, compacted in expert order into the
    head of `[held]`; how many they are): entry `i` is the number of experts
    before the `i + 1`-th touched one. No sort."""
    seen = jnp.cumsum(sizes > 0)
    return (jnp.sum(seen[None, :] <= jnp.arange(sizes.size)[:, None], axis=1,
                    dtype=jnp.int32), seen[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _touched(x, w, wgu, wd, sizes, activation: str = "silu"):
    """`sum_e _expert(e)` over the held experts with `sizes[e] > 0` and no
    other, in expert order: `_dense`'s sum without its terms that are
    exactly 0, their weights not read. `x [N, D]`, `wgu`, `wd` in the
    operands' dtype, `w [held, N]` (`_columns`) -> (`[N, D]` float32,
    `_inner`'s counts summed: None for `"silu"`). The trip count is read
    from the data: nothing compiles again when it changes."""
    return _touched_fwd(x, w, wgu, wd, sizes, activation)[0]


def _touched_fwd(x, w, wgu, wd, sizes, activation):
    ids, trips = _touched_ids(sizes)

    def trip(i, sums):
        out, zeroed = sums
        e = ids[i]
        y, z = _expert(x, w[e], wgu[e], wd[e], activation)
        return out + y, None if z is None else zeroed + z

    # The zeroed values are summed `[N, F]` wide and counted once after the
    # loop: a count a trip is one more op a trip (6 us of 194 a call at
    # `smallthinker_moe`'s shape; my chip run, PR 54).
    out, zeroed = jax.lax.fori_loop(
        0, trips, trip,
        (jnp.zeros(x.shape, F32),
         None if activation == "silu" else jnp.zeros((x.shape[0], wd.shape[1]), jnp.int32)))
    return ((out, None if zeroed is None else jnp.sum(zeroed)),
            (x, w, wgu, wd, ids, trips))


def _touched_bwd(activation, saved, g):
    """The same loop backwards, as `_slabs_bwd`: a trip takes its expert's
    own VJP; an expert no row chose is not read, and its gradients are 0."""
    x, w, wgu, wd, ids, trips = saved
    g = g[0]  # the count is an integer: no cotangent

    def trip(i, sums):
        dx, dw, dwgu, dwd = sums
        e = ids[i]
        _, back = jax.vjp(lambda *a: _expert(*a, activation)[0],
                          x, w[e], wgu[e], wd[e])
        x_bar, w_bar, wgu_bar, wd_bar = back(g)
        return (dx + x_bar.astype(F32), dw.at[e].set(w_bar),
                dwgu.at[e].set(wgu_bar), dwd.at[e].set(wd_bar))

    dx, dw, dwgu, dwd = jax.lax.fori_loop(
        0, trips, trip, (jnp.zeros(x.shape, F32), *map(jnp.zeros_like, (w, wgu, wd))))
    return dx.astype(x.dtype), dw, dwgu, dwd, None


_touched.defvjp(_touched_fwd, _touched_bwd)


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
    if form in ("sorted", "slabs"):  # held pairs first, by expert: the flat pair index a row
        order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)  # pairs an expert
    count, trips = jnp.sum(sizes), _trips(sizes, slab)
    x, wgu, wd = x.astype(dtype), wgu.astype(dtype), wd.astype(dtype)
    weight = weight.reshape(-1)
    if form == "touched":  # the experts some row chose, each a plain product over all rows
        out, zeroed = _touched(x, _columns(key, weight, held, n), wgu, wd, sizes,
                               activation)
    elif form == "dense":  # every held expert is read anyway: no sort, no grouped product
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
                "dense_rows": jnp.int32(n * held if form == "dense" else 0),
                "touched_experts": jnp.sum(sizes > 0, dtype=jnp.int32)}
    if zeroed is not None:
        counters["gate_zeroed"] = jax.lax.stop_gradient(zeroed)
    return out, counters

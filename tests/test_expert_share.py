"""`ops/expert_share.held_experts`: the held experts run over slabs of the
sorted pair list, as many as hold a held pair. Its value and its four
gradients are held against the whole-buffer computation it replaced
(written out here), at every count the loop's edges make special; the
loop is one compilation for all of them, is absent where the list is one
slab, and leaves no pair-list-long wide array behind.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.ops import expert_share

F32 = jnp.float32
# 256 tokens x 4 choices of 16 experts, 2 held: 1,024 pairs, slabs of 512
# (the expectation, 128, and a quarter more, rounded up).
N, TOP_K, E, HELD, D, WIDTH = 256, 4, 16, 2, 32, 16
SLAB = 512


def whole_buffer(x, chosen, weight, wgu, wd, first_expert, dtype):
    """The layer over the worst case's buffer, a row for every pair."""
    n, top_k = chosen.shape
    held = wgu.shape[0]
    key, _ = expert_share.held_pairs(chosen, first_expert, held)
    order = jnp.argsort(key, stable=True)  # held pairs first, by expert
    sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32)
    token = order // top_k
    live = (jnp.arange(n * top_k) < jnp.sum(sizes))[:, None]
    rows = jnp.where(live, x.astype(dtype)[token], 0)
    gate, up = jnp.split(jax.lax.ragged_dot(
        rows, wgu.astype(dtype), sizes, preferred_element_type=F32), 2, -1)
    y = jax.lax.ragged_dot((jax.nn.silu(gate) * up).astype(dtype),
                           wd.astype(dtype), sizes, preferred_element_type=F32)
    y = jnp.where(live, y, 0.0) * weight.reshape(-1)[order][:, None]
    return jnp.zeros((n, x.shape[-1]), F32).at[token].add(y)


def layer(seed=0, n=N, top_k=TOP_K, experts=E, held=HELD, d=D, width=WIDTH):
    r = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(r.normal(size=shape) * 0.3, F32)
    return {"x": f(n, d) * 3.0, "router": f(d, experts),
            "wgu": f(held, d, 2 * width), "wd": f(held, width, d)}


def choices(count, n=N, top_k=TOP_K, experts=E, held=HELD, first=4, seed=0):
    """`chosen [n, top_k]` with exactly `count` pairs on the held experts
    `[first, first + held)`, spread over tokens and choices, the rest on
    the absent ones."""
    r = np.random.RandomState(seed)
    absent = np.setdiff1d(np.arange(experts), np.arange(first, first + held))
    flat = r.choice(absent, size=n * top_k)
    where = r.choice(n * top_k, size=count, replace=False)
    flat[where] = first + r.randint(held, size=count)
    return jnp.asarray(flat.reshape(n, top_k), jnp.int32)


def weights(lay, chosen):
    """The pairs' weights as a differentiable function of the router."""
    def of(router):
        probs = jax.nn.softmax(lay["x"] @ router, axis=-1)
        top = jnp.take_along_axis(probs, chosen, axis=-1)
        return top / jnp.sum(top, -1, keepdims=True)
    return of


def losses(lay, chosen, dtype, first=4, experts=E):
    """(the slab version's loss, the whole buffer's) of (x, router, wgu, wd)."""
    def loss(fn, x, router, wgu, wd):
        out = fn(x, chosen, weights({**lay, "x": x}, chosen)(router), wgu, wd)
        return jnp.sum(out * jnp.cos(jnp.arange(out.size, dtype=F32).reshape(out.shape)))

    slabs = lambda *a: expert_share.held_experts(*a, first, experts, dtype)[0]
    whole = lambda *a: whole_buffer(*a, first, dtype)
    return functools.partial(loss, slabs), functools.partial(loss, whole)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


COUNTS = {"none": 0, "one": 1, "a_slab": SLAB, "a_slab_and_one": SLAB + 1,
          "every_pair": N * TOP_K}


@pytest.fixture(scope="module")
def graded():
    """One jitted value-and-gradients of each version, `chosen` an
    argument: every count below goes through the same two programs."""
    lay = layer()

    def both(dtype):
        def run(which, chosen, x, router, wgu, wd):
            return jax.value_and_grad(
                losses(lay, chosen, dtype)[which], argnums=(0, 1, 2, 3))(
                    x, router, wgu, wd)
        return jax.jit(functools.partial(run, 0)), jax.jit(functools.partial(run, 1))

    return lay, {F32: both(F32), jnp.bfloat16: both(jnp.bfloat16)}


@pytest.mark.parametrize("count", COUNTS.values(), ids=COUNTS.keys())
def test_value_and_gradients_are_the_whole_buffers_in_float32(graded, count):
    lay, fns = graded
    slabs, whole = fns[F32]
    args = (choices(count), lay["x"], lay["router"], lay["wgu"], lay["wd"])
    with jax.default_matmul_precision("highest"):
        got, want = slabs(*args), whole(*args)
    assert abs(float(got[0]) - float(want[0])) <= 1e-6 * max(1.0, abs(float(want[0])))
    for name, a, b in zip(("x", "router", "wgu", "wd"), got[1], want[1]):
        assert bool(jnp.all(jnp.isfinite(a))), name
        if count == 0:
            assert not np.any(np.asarray(a)) and not np.any(np.asarray(b)), name
        else:
            assert rel(a, b) < 1e-6, (name, rel(a, b))


@pytest.mark.parametrize("count", COUNTS.values(), ids=COUNTS.keys())
def test_value_and_gradients_in_bfloat16_are_inside_the_cells_limits(graded, count):
    """The cells hold a bfloat16 program to 2e-2 of its float32 reference
    (`perfbench/families/moelm.py`, `GRAD_REL`'s order); the slab version
    stays well inside that of the whole buffer in the SAME dtype: the
    products are the same, the sums over slabs are float32."""
    lay, fns = graded
    slabs, whole = fns[jnp.bfloat16]
    args = (choices(count, seed=1), lay["x"], lay["router"], lay["wgu"], lay["wd"])
    got, want = slabs(*args), whole(*args)
    assert abs(float(got[0]) - float(want[0])) <= 5e-3 * max(1.0, abs(float(want[0])))
    for name, a, b in zip(("x", "router", "wgu", "wd"), got[1], want[1]):
        assert bool(jnp.all(jnp.isfinite(a))), name
        if count:
            assert rel(a, b) < 5e-3, (name, rel(a, b))


def test_every_count_is_one_compilation(graded):
    lay, fns = graded
    slabs, _ = fns[F32]
    with jax.default_matmul_precision("highest"):  # as the value test's calls: one key
        for count in (0, 1, 300, SLAB, SLAB + 1, 700, N * TOP_K):
            slabs(choices(count, seed=2), lay["x"], lay["router"], lay["wgu"], lay["wd"])
    assert slabs._cache_size() == 1


@pytest.mark.parametrize("count", [0, 1, SLAB - 1, SLAB, SLAB + 1, 2 * SLAB])
def test_pair_slabs_is_the_count_over_the_slab_rounded_up(count):
    lay = layer()
    chosen = choices(count, seed=3)
    weight = weights(lay, chosen)(lay["router"])
    _, counters = expert_share.held_experts(
        lay["x"], chosen, weight, lay["wgu"], lay["wd"], 4, E, F32)
    assert expert_share.slab_rows(N * TOP_K, HELD, E) == SLAB
    assert int(counters["held_pairs"]) == count
    assert int(counters["pair_slabs"]) == math.ceil(count / SLAB)
    assert int(counters["dropped_pairs"]) == 0
    assert int(jnp.sum(counters["expert_pairs"])) == count


def test_every_pair_held_runs_every_slab_and_drops_none():
    """1,024 x 4 of 8 experts, 1 held, and the router sends every choice
    to it: 4,096 pairs in slabs of 1,024 (the expectation's 512 and a
    quarter more, rounded up), 4 trips where the expectation is an eighth."""
    lay = layer(4, n=1024, experts=8, held=1)
    chosen = jnp.full((1024, TOP_K), 5, jnp.int32)
    weight = jnp.full((1024, TOP_K), 0.25, F32)
    with jax.default_matmul_precision("highest"):
        out, counters = expert_share.held_experts(
            lay["x"], chosen, weight, lay["wgu"], lay["wd"], 5, 8, F32)
        want = whole_buffer(lay["x"], chosen, weight, lay["wgu"], lay["wd"], 5, F32)
    assert expert_share.slab_rows(4096, 1, 8) == 1024
    assert int(counters["pair_slabs"]) == 4 and int(counters["dropped_pairs"]) == 0
    assert rel(out, want) < 1e-6


@pytest.mark.parametrize("pairs,held,experts,want", [
    (4096 * 8, 16, 256, 2560), (4096 * 10, 32, 512, 3584),  # the two cells' learners
    (16 * 8, 16, 256, 128), (32 * 10, 32, 512, 320),  # their decode steps: the list
    (1024, 2, 16, 512), (1000, 2, 16, 512), (4096, 8, 8, 4096), (600, 16, 16, 600),
    (4096 * 8, 17, 256, 3072),
    # a QUARTER of the experts (16 of 64, 4 a token: `lfm2_moe`): a learner's
    # row block of 4 x 1,024 tokens, a decode step at 64 rows, one row
    (4096 * 4, 16, 64, 5120), (64 * 4, 16, 64, 256), (1024 * 4, 16, 64, 1536)])
def test_slab_rows_is_the_expectation_and_a_quarter_rounded_up_and_capped(pairs, held, experts, want):
    assert expert_share.slab_rows(pairs, held, experts) == want


def test_a_list_that_is_no_multiple_of_the_slab_keeps_its_tail():
    """250 x 4 = 1,000 pairs in slabs of 512: the second slab is padded,
    and the pairs in rows 512..999 are all there."""
    lay = layer(5, n=250)
    chosen = choices(900, n=250, seed=5)
    weight = weights(lay, chosen)(lay["router"])
    with jax.default_matmul_precision("highest"):
        out, counters = expert_share.held_experts(
            lay["x"], chosen, weight, lay["wgu"], lay["wd"], 4, E, F32)
        want = whole_buffer(lay["x"], chosen, weight, lay["wgu"], lay["wd"], 4, F32)
    assert int(counters["pair_slabs"]) == 2 and int(counters["dropped_pairs"]) == 0
    assert rel(out, want) < 1e-6


@pytest.mark.parametrize("count", [1, SLAB + 1])
def test_under_checkpoint_in_a_scan_as_the_models_call_it(count):
    """A stack of 3 layers' weights scanned over, each layer a
    `jax.checkpoint` mapped over 2 row blocks: gradients through the
    loop's own backward equal the whole buffer's through autodiff."""
    lay = layer(6)
    r = np.random.RandomState(6)
    stack = {k: jnp.stack([lay[k] * s for s in (1.0, 0.5, 0.25)])
             for k in ("router", "wgu", "wd")}
    xs = jnp.stack([lay["x"], jnp.asarray(r.normal(size=lay["x"].shape), F32)])
    chosen = jnp.stack([choices(count, seed=6), choices(count, seed=7)])

    def total(fn, xs, stack):
        @jax.checkpoint
        def block(x, chosen, lp):
            weight = weights({"x": x}, chosen)(lp["router"])
            return x + 0.1 * fn(x, chosen, weight, lp["wgu"], lp["wd"])

        def one(h, lp):
            return jax.lax.map(lambda a: block(*a, lp), (h, chosen)), None

        h, _ = jax.lax.scan(one, xs, stack)
        return jnp.sum(h * jnp.sin(jnp.arange(h.size, dtype=F32).reshape(h.shape)))

    slabs = lambda *a: expert_share.held_experts(*a, 4, E, F32)[0]
    whole = lambda *a: whole_buffer(*a, 4, F32)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(functools.partial(total, slabs), (0, 1)))(xs, stack)
        want = jax.jit(jax.value_and_grad(functools.partial(total, whole), (0, 1)))(xs, stack)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert rel(a, b) < 1e-6


def test_rows_the_grouped_product_leaves_unwritten_reach_nothing(monkeypatch):
    """On the chip a row past the last group holds whatever the buffer
    held. With a grouped product that leaves NaN there, forward and in
    both of its gradients, the loop's value and gradients stay finite and
    equal: each slab masks where it fills and where it reads."""
    clean = jax.lax.ragged_dot

    def poison(out, sizes):
        return jnp.where((jnp.arange(out.shape[0]) < jnp.sum(sizes))[:, None], out, jnp.nan)

    @jax.custom_vjp
    def dirty(lhs, rhs, sizes):
        return poison(clean(lhs, rhs, sizes, preferred_element_type=F32), sizes)

    def fwd(lhs, rhs, sizes):
        return dirty(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        d_lhs, d_rhs = jax.vjp(lambda a, b: clean(
            a, b, sizes, preferred_element_type=F32), lhs, rhs)[1](g)
        return poison(d_lhs, sizes), d_rhs, None

    dirty.defvjp(fwd, bwd)
    lay = layer(8)
    chosen = choices(SLAB + 37, seed=8)
    loss = losses(lay, chosen, F32)[0]
    args = (lay["x"], lay["router"], lay["wgu"], lay["wd"])
    want = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(*args)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        lambda lhs, rhs, sizes, **_: dirty(lhs, rhs, sizes))
    got = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(*args)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a))) and rel(a, b) < 1e-5


def _primitives(jaxpr, found=None):
    """Every (primitive name, result shapes) of a jaxpr and the jaxprs
    inside it."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append((eqn.primitive.name, [v.aval.shape for v in eqn.outvars
                                           if hasattr(v.aval, "shape")]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


def _graded_jaxpr(n, top_k, experts, held, d, width):
    shapes = [jax.ShapeDtypeStruct(s, F32) for s in (
        (n, d), (n, top_k), (held, d, 2 * width), (held, width, d))]
    chosen = jax.ShapeDtypeStruct((n, top_k), jnp.int32)

    def graded(x, weight, wgu, wd, chosen):
        return jax.value_and_grad(lambda *a: jnp.sum(expert_share.held_experts(
            a[0], chosen, *a[1:], 0, experts, jnp.bfloat16)[0] ** 2),
            argnums=(0, 1, 2, 3))(x, weight, wgu, wd)

    return jax.make_jaxpr(graded)(*shapes, chosen).jaxpr


@pytest.mark.parametrize("n,top_k,experts,held", [(16, 8, 256, 16), (32, 10, 512, 32)])
def test_a_decode_steps_list_is_one_slab_and_no_loop(n, top_k, experts, held):
    """At experts 64 x 32 wide: the sorted one-slab form."""
    names = {name for name, _ in _primitives(_graded_jaxpr(n, top_k, experts, held, 64, 32))}
    assert "while" not in names and "ragged_dot_general" in names
    assert not any("custom_vjp" in name for name in names)


# The rule's table (ISSUE 47; the touched form: ISSUE 54): the callers of
# `held_experts` in the six expert cells, (rows, top_k, router width, held,
# (D, F): the experts' widths) -> the form of the call.
RULE = {"lfm2_decode": ((64, 4, 64, 16, (2048, 1536)), "dense", "dense, 64 rows x 16 held"),
        # under one pair an expert, but 1.05 M weights a block: too small for a trip
        "qwen3_decode": ((32, 10, 512, 32, (2048, 512)), "sorted",
                         "sorted, one slab of 320 pairs"),
        "joyai_decode": ((16, 8, 256, 16, (2048, 768)), "touched",
                         "touched, 16 rows x up to 16 held"),
        "lfm2_learner": ((4096, 4, 64, 16, (2048, 1536)), "slabs",
                         "sorted, 16384 pairs in slabs of 5120"),
        "qwen3_learner": ((4096, 10, 512, 32, (2048, 512)), "slabs",
                          "sorted, 40960 pairs in slabs of 3584"),
        "joyai_learner": ((4096, 8, 256, 16, (2048, 768)), "slabs",
                          "sorted, 32768 pairs in slabs of 2560"),
        # ISSUE 49: 48 pairs for 64 experts, under one pair an expert
        "smallthinker_decode": ((8, 6, 64, 16, (2560, 768)), "touched",
                                "touched, 8 rows x up to 16 held"),
        "smallthinker_learner": ((8192, 6, 64, 16, (2560, 768)), "slabs",
                                 "sorted, 49152 pairs in slabs of 15360"),
        # ISSUE 53: 96 pairs for 128 experts (dense until ISSUE 54, by its widths)
        "nemotron_decode": ((16, 6, 128, 8, (2688, 1856)), "touched",
                            "touched, 16 rows x up to 8 held"),
        "nemotron_learner": ((8192, 6, 128, 8, (2688, 1856)), "slabs",
                             "sorted, 49152 pairs in slabs of 4096"),
        # AT one pair an expert: touched; one row more: dense; past 256 rows: sorted;
        # widths not given: as if large; the tests' own small experts: sorted
        "at_one_pair": ((16, 4, 64, 16, (2048, 1536)), "touched",
                        "touched, 16 rows x up to 16 held"),
        "over_one_pair": ((17, 4, 64, 16, (2048, 1536)), "dense", "dense, 17 rows x 16 held"),
        "past_the_row_bound": ((257, 1, 1024, 512, (2048, 1536)), "sorted",
                               "sorted, one slab of 257 pairs"),
        "widths_not_given": ((8, 6, 64, 16, ()), "touched", "touched, 8 rows x up to 16 held"),
        "small_experts": ((8, 6, 64, 16, (64, 32)), "sorted", "sorted, one slab of 48 pairs")}


@pytest.mark.parametrize("shape,form,said", RULE.values(), ids=RULE.keys())
def test_the_form_follows_the_shapes_as_the_rules_table_says(monkeypatch, shape, form, said):
    """Forward and backward of a call at each caller's rows (64 x 32 wide
    here, so the rule is asked with the caller's widths and the call made in
    its answer): the dense form has no grouped product, no sort and no
    scatter-add (and no loop); the touched form none of the three either,
    and a loop (over the experts some row chose); the sorted one-slab form
    all three and no loop; a learner's call the loop over slabs."""
    n, top_k, experts, held, widths = shape
    assert expert_share.call_form(n, top_k, held, experts, widths) == said
    one_slab = expert_share.slab_rows(n * top_k, held, experts) == n * top_k
    assert one_slab == (form != "slabs")
    if one_slab:
        assert expert_share.one_slab_form(n, top_k, experts, widths) == form
        monkeypatch.setattr(expert_share, "one_slab_form", lambda *_: form)
    names = {name for name, _ in _primitives(_graded_jaxpr(n, top_k, experts, held, 64, 32))}
    sorted_forms = {"ragged_dot_general", "sort", "scatter-add"}
    if form in ("dense", "touched"):
        assert not names & sorted_forms, names
        assert "dot_general" in names
        assert ("while" in names) == (form == "touched")
    else:
        assert sorted_forms <= names
        assert ("while" in names) == (form == "slabs")


@pytest.mark.parametrize("n,top_k,experts,held", [(512, 8, 256, 16), (512, 10, 512, 32),
                                                  (256, 4, 64, 16)])
def test_at_a_learners_shape_nothing_wide_is_as_long_as_the_pair_list(
        n, top_k, experts, held):
    """Forward and backward: the `[P]` index vectors and the `[P, held]`
    count stay, no array has `P` rows and `D`, `F` or `2 F` columns."""
    d, width = 64, 48  # no width is `held`: the `[P, held]` count stays
    pairs = n * top_k
    assert expert_share.slab_rows(pairs, held, experts) == 512 < pairs
    found = _primitives(_graded_jaxpr(n, top_k, experts, held, d, width))
    assert any(name == "while" for name, _ in found)
    wide = [(name, s) for name, shapes in found for s in shapes
            if len(s) == 2 and s[0] >= pairs and s[1] in (d, width, 2 * width)]
    assert not wide, wide
    assert any(s == (pairs, held) for _, shapes in found for s in shapes)


# -- a QUARTER of the experts and no shared expert (ISSUE 46) --------------------


@pytest.mark.parametrize("count", [0, 1, 255, 256, 640, 1024])
def test_pair_slabs_at_a_quarter_share(count):
    """256 tokens x 4 choices of 16 experts, 4 held from expert 4 on: the
    expectation is 256 pairs, the slab 320 rounded up to 512, so a uniform
    router's call is ONE slab where a sixteenth's list of the same length
    would be one too, and a call with every pair here is two."""
    lay = layer(11, held=4)
    assert expert_share.slab_rows(N * TOP_K, 4, E) == SLAB
    chosen = choices(count, held=4, seed=11)
    weight = weights(lay, chosen)(lay["router"])
    with jax.default_matmul_precision("highest"):
        out, counters = expert_share.held_experts(
            lay["x"], chosen, weight, lay["wgu"], lay["wd"], 4, E, F32)
        want = whole_buffer(lay["x"], chosen, weight, lay["wgu"], lay["wd"], 4, F32)
    assert int(counters["pair_slabs"]) == math.ceil(count / SLAB)
    assert int(counters["held_pairs"]) == count == int(jnp.sum(counters["expert_pairs"]))
    assert int(counters["dropped_pairs"]) == 0
    assert rel(out, want) < 1e-6


def test_a_layer_without_a_shared_expert_is_the_sum_of_its_shares():
    """Four shares of four experts: with a sigmoid router over all 16 and
    nothing computed alike on every chip, the shares' parts add up to the
    layer over all 16 held at once, and a token none of whose choices is
    held here gets exactly zero from this share."""
    lay = layer(12, held=E)
    bias = 0.05 * jnp.asarray(np.random.RandomState(12).normal(size=E), F32)
    with jax.default_matmul_precision("highest"):
        _, chosen, weight, load = expert_share.route(
            lay["x"], lay["router"], TOP_K, "sigmoid", bias, 1.0, 1e-6)
        whole, counters = expert_share.held_experts(
            lay["x"], chosen, weight, lay["wgu"], lay["wd"], 0, E, F32)
        parts = [expert_share.held_experts(
            lay["x"], chosen, weight, lay["wgu"][first:first + 4],
            lay["wd"][first:first + 4], first, E, F32) for first in (0, 4, 8, 12)]
    assert rel(sum(p[0] for p in parts), whole) < 1e-6
    assert sum(int(p[1]["held_pairs"]) for p in parts) == N * TOP_K == int(jnp.sum(load))
    assert int(counters["pair_slabs"]) == 1  # every expert held: the list is one slab
    first_share = np.asarray(parts[0][0])
    nobody_here = ~np.any(np.asarray(chosen) < 4, axis=-1)
    assert nobody_here.any() and not np.any(first_share[nobody_here])
    assert np.all(np.any(first_share[~nobody_here] != 0, axis=-1))


# -- the one-slab path's dense form (ISSUE 47) -----------------------------------
# 32 rows x 4 choices of 16 experts, 4 held from expert 4 on: 128 pairs, one
# slab, and 8 pairs an expert from a uniform router: the rule takes the dense
# form, and `sorted_form` is the same call with the rule answering "sorted".
DN, DE, DHELD, DFIRST = 32, 16, 4, 4


def by_experts(x, chosen, weight, wgu, wd, first_expert):
    """The layer as its equation reads, float32: a loop over the held
    experts, each on every row, weighted where the row chose it."""
    out = jnp.zeros(x.shape, F32)
    for e in range(wgu.shape[0]):
        gate, up = jnp.split(x @ wgu[e], 2, -1)
        w = jnp.sum(jnp.where(chosen == first_expert + e, weight, 0.0), -1)
        out = out + w[:, None] * ((jax.nn.silu(gate) * up) @ wd[e])
    return out


def forced(monkeypatch, form, fn, *args):
    """`fn(*args)` with the rule answering `form`."""
    with monkeypatch.context() as m:
        m.setattr(expert_share, "one_slab_form", lambda *_: form)
        return fn(*args)


def sorted_form(monkeypatch, fn, *args):
    return forced(monkeypatch, "sorted", fn, *args)


def dense_case(seed, count=None):
    lay = layer(seed, n=DN, experts=DE, held=DHELD)
    if count is None:  # the router's own choice
        chosen = jax.lax.top_k(lay["x"] @ lay["router"], TOP_K)[1].astype(jnp.int32)
    else:
        chosen = choices(count, n=DN, experts=DE, held=DHELD, first=DFIRST, seed=seed)
    return lay, chosen


def graded_of(lay, chosen, dtype, first, experts, activation="silu"):
    """(value and four gradients of a version, the program, the equation:
    a plain loop over the held experts, `EQUATIONS`)."""
    def loss(fn, x, router, wgu, wd):
        out = fn(x, chosen, weights({**lay, "x": x}, chosen)(router), wgu, wd)
        return jnp.sum(out * jnp.cos(jnp.arange(out.size, dtype=F32).reshape(out.shape)))

    program = lambda *a: expert_share.held_experts(*a, first, experts, dtype, activation)[0]
    equation = lambda *a: EQUATIONS[activation](*a, first)[0]
    args = (lay["x"], lay["router"], lay["wgu"], lay["wd"])
    graded = lambda fn: jax.value_and_grad(functools.partial(loss, fn),
                                           argnums=(0, 1, 2, 3))(*args)
    return graded, program, equation


def dense_graded(lay, chosen, dtype):
    return graded_of(lay, chosen, dtype, DFIRST, DE)


def test_the_rule_takes_the_dense_form_at_the_dense_tests_shape():
    assert expert_share.slab_rows(DN * TOP_K, DHELD, DE) == DN * TOP_K
    assert expert_share.one_slab_form(DN, TOP_K, DE) == "dense"
    assert expert_share.one_slab_form(4, TOP_K, DE) == "touched"  # ONE pair an expert
    assert expert_share.one_slab_form(4, TOP_K, DE, (D, 2 * WIDTH, WIDTH)) == "sorted"  # small
    assert expert_share.one_slab_form(5, TOP_K, DE) == "dense"
    assert expert_share.one_slab_form(256, TOP_K, DE) == "dense"  # the row bound
    assert expert_share.one_slab_form(257, TOP_K, DE) == "sorted"


@pytest.mark.parametrize("count", [None, 0, 1, DN * TOP_K], ids=[
    "routed", "none", "one", "every_pair"])
def test_the_dense_form_is_the_sorted_form_and_the_equation_in_float32(monkeypatch, count):
    """Value and all four gradients (x, the router through the pairs'
    weights, wgu, wd)."""
    lay, chosen = dense_case(20, count)
    graded, program, equation = dense_graded(lay, chosen, F32)
    with jax.default_matmul_precision("highest"):
        dense = graded(program)
        by_sort = sorted_form(monkeypatch, graded, program)
        want = graded(equation)
    for got in (dense, by_sort):
        assert abs(float(got[0]) - float(want[0])) <= 1e-5 * max(1.0, abs(float(want[0])))
        for name, a, b in zip(("x", "router", "wgu", "wd"), got[1], want[1]):
            assert bool(jnp.all(jnp.isfinite(a))), name
            if count == 0:
                assert not np.any(np.asarray(a)), name
            else:
                assert rel(a, b) < 1e-5, (name, rel(a, b))


@pytest.mark.parametrize("count", [None, 1, DN * TOP_K], ids=["routed", "one", "every_pair"])
def test_the_dense_form_in_bfloat16_is_inside_the_cells_limits(monkeypatch, count):
    """Against the sorted form in the SAME dtype (the same products, other
    float32 sums) and against the float32 equation under 2e-2, the order
    of the cells' limit on a bfloat16 program."""
    lay, chosen = dense_case(21, count)
    graded, program, equation = dense_graded(lay, chosen, jnp.bfloat16)
    dense = graded(program)
    by_sort = sorted_form(monkeypatch, graded, program)
    with jax.default_matmul_precision("highest"):
        want = graded(equation)
    assert abs(float(dense[0]) - float(by_sort[0])) <= 5e-3 * max(1.0, abs(float(by_sort[0])))
    for name, a, b, c in zip(("x", "router", "wgu", "wd"), dense[1], by_sort[1], want[1]):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert rel(a, b) < 5e-3, (name, rel(a, b))
        assert rel(a, c) < 2e-2, (name, rel(a, c))


def test_in_the_dense_form_a_token_that_chose_no_held_expert_gets_exactly_zero():
    """And an expert that no row chose may hold anything: its results,
    infinite here, reach no row."""
    lay, _ = dense_case(22)
    chosen = choices(40, n=DN, experts=DE, held=DHELD, first=DFIRST, seed=22)
    chosen = jnp.where(chosen == DFIRST, 0, chosen)  # nobody chose the first held
    weight = weights(lay, chosen)(lay["router"])
    out, counters = expert_share.held_experts(
        lay["x"], chosen, weight, lay["wgu"].at[0].set(jnp.inf), lay["wd"],
        DFIRST, DE, F32)
    out, chosen = np.asarray(out), np.asarray(chosen)
    nobody_here = ~np.any((chosen >= DFIRST) & (chosen < DFIRST + DHELD), axis=-1)
    assert nobody_here.any() and not np.any(out[nobody_here])
    assert np.all(np.isfinite(out))
    assert np.all(np.any(out[~nobody_here] != 0, axis=-1))
    assert int(counters["dense_rows"]) == DN * DHELD and int(counters["expert_pairs"][0]) == 0


@pytest.mark.parametrize("count", [None, 0, 1, 77, DN * TOP_K])
def test_the_counters_are_the_same_in_both_forms(monkeypatch, count):
    lay, chosen = dense_case(23, count)
    weight = weights(lay, chosen)(lay["router"])
    call = lambda: expert_share.held_experts(
        lay["x"], chosen, weight, lay["wgu"], lay["wd"], DFIRST, DE, F32)[1]
    dense, by_sort = call(), sorted_form(monkeypatch, call)
    for key in ("held_pairs", "expert_pairs", "dropped_pairs", "pair_slabs"):
        assert np.array_equal(np.asarray(dense[key]), np.asarray(by_sort[key])), key
    assert int(dense["dropped_pairs"]) == 0
    if count is not None:
        assert int(dense["held_pairs"]) == count
    assert int(dense["dense_rows"]) == DN * DHELD and int(by_sort["dense_rows"]) == 0


# -- the gate's activation (ISSUE 49): ReGLU beside SwiGLU --------------------------
# The three forms at one shape each: the dense and the sorted one-slab form at
# the dense tests' shape (the rule's and the rule answered "sorted"), the loop
# over slabs at the module's (1,024 pairs in slabs of 512).

def relu_by_experts(x, chosen, weight, wgu, wd, first_expert):
    """`by_experts` with ReGLU, and the gate values that ReLU zeroed of the
    pairs routed to the held experts."""
    out, zeroed = jnp.zeros(x.shape, F32), 0
    for e in range(wgu.shape[0]):
        gate, up = jnp.split(x @ wgu[e], 2, -1)
        pairs = jnp.sum(chosen == first_expert + e, -1)  # a router's sets: 0 or 1
        w = jnp.sum(jnp.where(chosen == first_expert + e, weight, 0.0), -1)
        out = out + w[:, None] * ((jax.nn.relu(gate) * up) @ wd[e])
        zeroed = zeroed + jnp.sum(pairs[:, None] * (gate <= 0))
    return out, zeroed


def relu_case(form, monkeypatch):
    """(layer, chosen, first, experts, the program's call in `form`)."""
    if form == "slabs":
        lay, first, experts = layer(30), 4, E
        chosen = choices(SLAB + 77, seed=30)
    else:
        (lay, chosen), first, experts = dense_case(30), DFIRST, DE  # the router's sets
    if form == "sorted":
        monkeypatch.setattr(expert_share, "one_slab_form", lambda *_: "sorted")
    n, top_k = chosen.shape
    said = {"dense": "dense", "sorted": "one slab", "slabs": "in slabs of"}[form]
    assert said in expert_share.call_form(n, top_k, lay["wgu"].shape[0], experts)
    return lay, chosen, first, experts


@pytest.mark.parametrize("form", ["dense", "sorted", "slabs"])
def test_relu_experts_are_the_equation_in_every_form(monkeypatch, form):
    """Value, all four gradients and the count of zeroed gate values of
    `held_experts(activation="relu")` against a loop over the held experts
    with `relu`, float32."""
    lay, chosen, first, experts = relu_case(form, monkeypatch)

    def loss(fn, x, router, wgu, wd):
        out, aux = fn(x, chosen, weights({**lay, "x": x}, chosen)(router), wgu, wd)
        return jnp.sum(out * jnp.cos(
            jnp.arange(out.size, dtype=F32).reshape(out.shape))), aux

    def program(*a):
        out, counters = expert_share.held_experts(*a, first, experts, F32, "relu")
        return out, counters["gate_zeroed"]

    args = (lay["x"], lay["router"], lay["wgu"], lay["wd"])
    graded = lambda fn: jax.value_and_grad(
        functools.partial(loss, fn), argnums=(0, 1, 2, 3), has_aux=True)(*args)
    with jax.default_matmul_precision("highest"):
        (got, zeroed), grads = graded(program)
        (want, by_hand), want_grads = graded(
            lambda *a: relu_by_experts(*a, first))
    assert abs(float(got) - float(want)) <= 1e-5 * max(1.0, abs(float(want)))
    assert int(zeroed) == int(by_hand) > 0
    for name, a, b in zip(("x", "router", "wgu", "wd"), grads, want_grads):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert rel(a, b) < 1e-5, (name, rel(a, b))


@pytest.mark.parametrize("form", ["dense", "sorted", "slabs"])
def test_silu_is_the_default_and_is_what_it_was(monkeypatch, form):
    """`activation="silu"` and no argument are the same program: the same
    jaxpr, no `gate_zeroed` counter, no op beside what was there; and ReGLU
    is another result."""
    lay, chosen, first, experts = relu_case(form, monkeypatch)
    w = weights(lay, chosen)(lay["router"])
    call = lambda *activation: expert_share.held_experts(
        lay["x"], chosen, w, lay["wgu"], lay["wd"], first, experts, jnp.bfloat16,
        *activation)
    (default, counters), (named, _), (relu, relu_counters) = \
        call(), call("silu"), call("relu")
    np.testing.assert_array_equal(default, named)
    assert "gate_zeroed" not in counters and "gate_zeroed" in relu_counters
    assert set(relu_counters) - set(counters) == {"gate_zeroed"}
    assert float(jnp.max(jnp.abs(relu - default))) > 1e-3
    text = lambda *activation: str(jax.make_jaxpr(lambda: call(*activation)[0])())
    assert text() == text("silu") and "logistic" in text()
    assert "logistic" not in text("relu")
    with pytest.raises(ValueError, match="unknown activation"):
        call("gelu")


# -- the UNGATED expert (ISSUE 53): W_d relu(W_u x)^2, one up matrix ------------------

def relu2_by_experts(x, chosen, weight, wu, wd, first_expert):
    """A loop over the held experts with `relu(.) ** 2`, and the
    up-projections that ReLU zeroed of the pairs routed to them."""
    out, zeroed = jnp.zeros(x.shape, F32), 0
    for e in range(wu.shape[0]):
        up = x @ wu[e]
        pairs = jnp.sum(chosen == first_expert + e, -1)  # a router's sets: 0 or 1
        w = jnp.sum(jnp.where(chosen == first_expert + e, weight, 0.0), -1)
        out = out + w[:, None] * (jax.nn.relu(up) ** 2 @ wd[e])
        zeroed = zeroed + jnp.sum(pairs[:, None] * (up <= 0))
    return out, zeroed


@pytest.mark.parametrize("form", ["dense", "sorted", "slabs"])
def test_ungated_relu2_experts_are_the_equation_in_every_form(monkeypatch, form):
    """Value, all four gradients and the count of zeroed up-projections of
    `held_experts(activation="relu2")` with ONE up matrix `[held, D, F]`
    (the gate's half of the layer's `wgu`) against a loop over the held
    experts, float32; and a gated activation on the same call is another
    result."""
    lay, chosen, first, experts = relu_case(form, monkeypatch)
    wu = lay["wgu"][..., :lay["wgu"].shape[-1] // 2]
    assert wu.shape[-1] == lay["wd"].shape[1]

    def loss(fn, x, router, wu, wd):
        out, aux = fn(x, chosen, weights({**lay, "x": x}, chosen)(router), wu, wd)
        return jnp.sum(out * jnp.cos(
            jnp.arange(out.size, dtype=F32).reshape(out.shape))), aux

    def program(*a):
        out, counters = expert_share.held_experts(*a, first, experts, F32, "relu2")
        return out, counters["gate_zeroed"]

    args = (lay["x"], lay["router"], wu, lay["wd"])
    graded = lambda fn: jax.value_and_grad(
        functools.partial(loss, fn), argnums=(0, 1, 2, 3), has_aux=True)(*args)
    with jax.default_matmul_precision("highest"):
        (got, zeroed), grads = graded(program)
        (want, by_hand), want_grads = graded(
            lambda *a: relu2_by_experts(*a, first))
    assert abs(float(got) - float(want)) <= 1e-5 * max(1.0, abs(float(want)))
    assert int(zeroed) == int(by_hand) > 0
    for name, a, b in zip(("x", "router", "wu", "wd"), grads, want_grads):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert rel(a, b) < 1e-5, (name, rel(a, b))
    w = weights(lay, chosen)(lay["router"])
    gated, _ = expert_share.held_experts(lay["x"], chosen, w, lay["wgu"], lay["wd"],
                                         first, experts, F32, "relu")
    ungated, _ = expert_share.held_experts(lay["x"], chosen, w, wu, lay["wd"],
                                           first, experts, F32, "relu2")
    assert float(jnp.max(jnp.abs(gated - ungated))) > 1e-3


# -- the one-slab path's touched form (ISSUE 54) ------------------------------------
# 8 rows x 4 choices of 32 experts, 8 held from expert 4 on: 32 pairs, one
# slab, ONE pair an expert from a uniform router: the rule takes the touched
# form for experts of a cell's size and the sorted one for these (32 x 16
# wide), so every test below runs with the rule answering "touched"
# (`touched_form`); `forced` is a call with it answering another form.
TN, TE, THELD, TFIRST = 8, 32, 8, 4


@pytest.fixture
def touched_form(monkeypatch):
    monkeypatch.setattr(expert_share, "one_slab_form", lambda *_: "touched")
EQUATIONS = {"silu": lambda *a: (by_experts(*a), None), "relu": relu_by_experts,
             "relu2": relu2_by_experts}


def touched_case(seed, activation="silu", count=None):
    """(layer, chosen): the router's own sets (`count` None: some held
    experts touched and some not) or exactly `count` held pairs."""
    lay = layer(seed, n=TN, experts=TE, held=THELD)
    if activation == "relu2":  # one up matrix: the gate's half
        lay["wgu"] = lay["wgu"][..., :WIDTH]
    if count is None:
        chosen = jax.lax.top_k(lay["x"] @ lay["router"], TOP_K)[1].astype(jnp.int32)
    else:
        chosen = choices(count, n=TN, experts=TE, held=THELD, first=TFIRST, seed=seed)
    return lay, chosen


def touched_graded(lay, chosen, dtype, activation):
    return graded_of(lay, chosen, dtype, TFIRST, TE, activation)


def test_the_rule_takes_the_touched_form_at_the_touched_tests_rows():
    """By the rows and the pairs; and by the experts' size: `D x F` of
    `TRIP_WEIGHTS` (1.31 M) and more, or widths not given."""
    assert expert_share.slab_rows(TN * TOP_K, THELD, TE) == TN * TOP_K
    assert expert_share.one_slab_form(TN, TOP_K, TE) == "touched"
    assert expert_share.one_slab_form(TN + 1, TOP_K, TE) == "dense"
    assert expert_share.call_form(TN, TOP_K, THELD, TE) == "touched, 8 rows x up to 8 held"
    assert expert_share.one_slab_form(256, 4, 1024) == "touched"  # the row bound
    assert expert_share.one_slab_form(257, 4, 2048) == "sorted"
    assert expert_share.TRIP_WEIGHTS == 1_310_720
    for widths, form in (((2048, 1536, 768), "touched"), ((2688, 1856, 1856), "touched"),
                         ((2560, 768), "touched"), ((2048, 640), "touched"),
                         ((2048, 1024, 512), "sorted"), ((2048, 512), "sorted"),
                         ((D, 2 * WIDTH, WIDTH), "sorted")):
        assert expert_share.one_slab_form(TN, TOP_K, TE, widths) == form, widths
        assert expert_share.one_slab_form(TN + 1, TOP_K, TE, widths) == "dense"


@pytest.mark.parametrize("activation", ["silu", "relu", "relu2"])
@pytest.mark.parametrize("count", [None, 1, TN * TOP_K], ids=["routed", "one", "every_pair"])
def test_the_touched_form_is_the_equation_and_the_sorted_form_in_float32(
        monkeypatch, count, activation, touched_form):
    """Value and all four gradients (x, the router through the pairs'
    weights, wgu, wd) against a plain loop over the held experts."""
    lay, chosen = touched_case(40, activation, count)
    graded, program, equation = touched_graded(lay, chosen, F32, activation)
    with jax.default_matmul_precision("highest"):
        touched = graded(program)
        by_sort = forced(monkeypatch, "sorted", graded, program)
        want = graded(equation)
    for got in (touched, by_sort):
        assert abs(float(got[0]) - float(want[0])) <= 1e-5 * max(1.0, abs(float(want[0])))
        for name, a, b in zip(("x", "router", "wgu", "wd"), got[1], want[1]):
            assert bool(jnp.all(jnp.isfinite(a))), name
            assert rel(a, b) < 1e-5, (name, rel(a, b))


@pytest.mark.parametrize("activation", ["silu", "relu", "relu2"])
def test_the_touched_form_in_bfloat16_is_inside_the_cells_limits(monkeypatch, activation, touched_form):
    """Against the dense form in the SAME dtype (the same products of the
    same operands; the touched form leaves out terms that are exactly 0)
    under the dense form's own tolerance, and against the float32 equation
    under 2e-2."""
    lay, chosen = touched_case(41, activation)
    graded, program, equation = touched_graded(lay, chosen, jnp.bfloat16, activation)
    touched = graded(program)
    dense = forced(monkeypatch, "dense", graded, program)
    with jax.default_matmul_precision("highest"):
        want = graded(equation)
    assert abs(float(touched[0]) - float(dense[0])) <= 5e-3 * max(1.0, abs(float(dense[0])))
    for name, a, b, c in zip(("x", "router", "wgu", "wd"), touched[1], dense[1], want[1]):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert rel(a, b) < 5e-3, (name, rel(a, b))
        assert rel(a, c) < 2e-2, (name, rel(a, c))


@pytest.mark.parametrize("activation", ["silu", "relu2"])
def test_the_touched_form_with_no_held_pair_makes_no_trip(activation, touched_form):
    """No row chose a held expert: zeros out, zero gradients, all finite,
    and no expert counted."""
    lay, chosen = touched_case(42, activation, 0)
    graded, program, _ = touched_graded(lay, chosen, F32, activation)
    value, grads = graded(program)
    assert float(value) == 0.0
    for name, g in zip(("x", "router", "wgu", "wd"), grads):
        assert bool(jnp.all(jnp.isfinite(g))) and not np.any(np.asarray(g)), name
    _, counters = expert_share.held_experts(
        lay["x"], chosen, weights(lay, chosen)(lay["router"]), lay["wgu"], lay["wd"],
        TFIRST, TE, F32, activation)
    assert int(counters["touched_experts"]) == 0 == int(counters["held_pairs"])


def test_the_touched_form_with_every_pair_on_one_expert_makes_one_trip(touched_form):
    """Every choice of every row is held expert 2: one trip; the gradients
    of the seven experts no row chose are exactly 0, and the result is the
    equation's."""
    lay, _ = touched_case(43)
    chosen = jnp.full((TN, TOP_K), TFIRST + 2, jnp.int32)
    graded, program, equation = touched_graded(lay, chosen, F32, "silu")
    with jax.default_matmul_precision("highest"):
        got, want = graded(program), graded(equation)
    assert abs(float(got[0]) - float(want[0])) <= 1e-5 * max(1.0, abs(float(want[0])))
    for name, a, b in zip(("x", "router", "wgu", "wd"), got[1], want[1]):
        if name == "router":  # a row's four weights are 1/4 whatever the router says
            assert float(jnp.max(jnp.abs(a))) < 1e-6 > float(jnp.max(jnp.abs(b)))
        else:
            assert rel(a, b) < 1e-5, (name, rel(a, b))
    others = np.arange(THELD) != 2
    assert not np.any(np.asarray(got[1][2])[others]) and np.any(np.asarray(got[1][2])[2])
    assert not np.any(np.asarray(got[1][3])[others]) and np.any(np.asarray(got[1][3])[2])
    _, counters = expert_share.held_experts(
        lay["x"], chosen, jnp.full((TN, TOP_K), 0.25, F32), lay["wgu"], lay["wd"],
        TFIRST, TE, F32)
    assert int(counters["touched_experts"]) == 1
    assert int(counters["held_pairs"]) == TN * TOP_K == int(counters["expert_pairs"][2])


def test_in_the_touched_form_an_expert_no_row_chose_is_never_read(touched_form):
    """Its weights may hold anything, infinite and NaN here: the value and
    every gradient stay finite, its own gradients are exactly 0, and a row
    none of whose choices is held gets exactly zero."""
    lay, _ = touched_case(44)
    chosen = choices(12, n=TN, experts=TE, held=THELD, first=TFIRST, seed=44)
    chosen = jnp.where((chosen == TFIRST) | (chosen == TFIRST + 5), 0, chosen)
    graded, program, equation = touched_graded(lay, chosen, F32, "silu")
    with jax.default_matmul_precision("highest"):
        want = graded(equation)
        lay["wgu"] = lay["wgu"].at[0].set(jnp.inf).at[5].set(jnp.nan)
        lay["wd"] = lay["wd"].at[0].set(jnp.nan).at[5].set(-jnp.inf)
        got = touched_graded(lay, chosen, F32, "silu")[0](program)
        out, counters = expert_share.held_experts(
            lay["x"], chosen, weights(lay, chosen)(lay["router"]), lay["wgu"],
            lay["wd"], TFIRST, TE, F32)
    assert abs(float(got[0]) - float(want[0])) <= 1e-5 * max(1.0, abs(float(want[0])))
    for name, a, b in zip(("x", "router", "wgu", "wd"), got[1], want[1]):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert rel(a, b) < 1e-5, (name, rel(a, b))
    for g in got[1][2:]:
        assert not np.any(np.asarray(g)[[0, 5]])
    out, chosen = np.asarray(out), np.asarray(chosen)
    nobody_here = ~np.any((chosen >= TFIRST) & (chosen < TFIRST + THELD), axis=-1)
    assert nobody_here.any() and not np.any(out[nobody_here])
    assert np.all(np.isfinite(out)) and np.all(np.any(out[~nobody_here] != 0, axis=-1))
    assert int(counters["expert_pairs"][0]) == 0 == int(counters["expert_pairs"][5])


@pytest.mark.parametrize("count", [None, 0, 1, 9, TN * TOP_K])
def test_touched_experts_is_the_count_by_hand_in_every_form(monkeypatch, count, touched_form):
    """The held experts some row chose: the touched form's trips, and the
    same number from the two other forms; `dense_rows` reads 0 in it."""
    lay, chosen = touched_case(45, count=count)
    weight = weights(lay, chosen)(lay["router"])
    call = lambda: expert_share.held_experts(
        lay["x"], chosen, weight, lay["wgu"], lay["wd"], TFIRST, TE, F32)[1]
    local = np.asarray(chosen) - TFIRST
    by_hand = len(np.unique(local[(local >= 0) & (local < THELD)]))
    touched = call()
    assert int(touched["touched_experts"]) == by_hand
    assert int(touched["touched_experts"]) == int(np.sum(np.asarray(touched["expert_pairs"]) > 0))
    assert int(touched["dense_rows"]) == 0 and int(touched["dropped_pairs"]) == 0
    if count is None:
        assert 0 < by_hand < THELD  # the router's sets leave some held expert alone
    else:
        assert int(touched["held_pairs"]) == count
    for form in ("sorted", "dense"):
        other = forced(monkeypatch, form, call)
        for key in ("held_pairs", "expert_pairs", "dropped_pairs", "pair_slabs",
                    "touched_experts"):
            assert np.array_equal(np.asarray(touched[key]), np.asarray(other[key])), key
        assert int(other["dense_rows"]) == (TN * THELD if form == "dense" else 0)


@pytest.mark.parametrize("activation", ["silu", "relu", "relu2"])
def test_a_touched_calls_lowered_text_holds_no_grouped_product_and_no_sort(activation, touched_form):
    """A decode step's call as the models make it (bfloat16, forward): a
    `while` whose body holds the two plain products, and neither
    `ragged_dot`, `sort`, `gather` nor `scatter`; every count is one
    compilation."""
    lay, chosen = touched_case(46, activation)
    weight = weights(lay, chosen)(lay["router"])

    @jax.jit
    def call(x, chosen, weight, wgu, wd):
        return expert_share.held_experts(x, chosen, weight, wgu, wd, TFIRST, TE,
                                         jnp.bfloat16, activation)

    text = call.lower(lay["x"], chosen, weight, lay["wgu"], lay["wd"]).as_text()
    assert "stablehlo.while" in text and text.count("stablehlo.dot_general") == 2
    for absent in ("ragged_dot", "stablehlo.sort", "stablehlo.gather", "stablehlo.scatter",
                   "chlo.top_k"):
        assert absent not in text, absent
    for count in (0, 1, 5, TN * TOP_K):
        call(lay["x"], choices(count, n=TN, experts=TE, held=THELD, first=TFIRST),
             weight, lay["wgu"], lay["wd"])
    assert call._cache_size() == 1

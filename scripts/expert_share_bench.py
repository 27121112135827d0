#!/usr/bin/env python
"""Time the held experts' layer alone, at a learner's shape or a decode step's.

Forward, and forward + backward (all four gradients: x, the pairs' weights,
wgu, wd), of `ops/expert_share.held_experts` on `--tokens` rows with
exactly `share x tokens x top_k` pairs on the held experts, for every
`--share` of the pair list (default 1/32, 1/16, 1/8, 1/2 and all of it):
the median of `--iters` timed calls after a warm one, one JSON line a point
on stdout. `--cell` names the shape: `joyai` (4,096 x 8 of 256, 16 held, D
2,048, F 768), `qwen3` (4,096 x 10 of 512, 32 held, D 2,048, F 512),
`lfm2` (4,096 x 4 of 64, 16 held, D 2,048, F 1,536), `smallthinker` (x 6 of
64, 16 held, D 2,560, F 768, ReGLU) or `nemotron` (x 6 of 128, 8 held, D
2,688, F 1,856, ungated relu^2: one up matrix).
`--slab` times other slab sizes than the rule's (`slab_rows`), each
compiled in turn, which is how the rule was chosen (PERF.md section 6, PR
42):

    python scripts/expert_share_bench.py --cell joyai --slab 1024 2048 4096

`--rows` is the decode mode, forward only: a decode step's expert layers
(`--layers` of them, each with weights of its own, so that every call
reads its weights from HBM as a step does) on `--rows` rows a call, routed
by a uniform router over all the experts (`--skew`: a layer's rows prefer
the same experts by that much), `--steps` steps in one compiled scan; a
line gives the microseconds a layer a step of ALL THREE forms of the
one-slab path side by side (`sorted_us`, `dense_us`, `touched_us`: each
forced in turn through `one_slab_form`, which says the rule's own choice
under `form`; `touched_experts_a_call`: the touched form's trips), which is
how the rule was fixed (PERF.md section 6, PRs 47, 54). `--width`, `--d`
and `--router` replace the cell's F, its D and its router's width:

    python scripts/expert_share_bench.py --cell lfm2 --rows 16 32 64 128
    python scripts/expert_share_bench.py --cell lfm2 --rows 64 --width 768
    python scripts/expert_share_bench.py --cell smallthinker nemotron --rows 8 16

A time is the chip's only there: on the CPU pass a tiny `--tokens`, and the
line says `"platform": "cpu"`. No cell of the benchmark runs this. It runs
on a checkout from before the slabs too (no `slab_rows` there: the line
says `"slab": null`), which is how the parent's column was made.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELLS = {  # top_k, router width, held, D, F, the experts' activation
    "joyai": (8, 256, 16, 2048, 768, "silu"),
    "qwen3": (10, 512, 32, 2048, 512, "silu"),
    "lfm2": (4, 64, 16, 2048, 1536, "silu"),
    "smallthinker": (6, 64, 16, 2560, 768, "relu"),
    "nemotron": (6, 128, 8, 2688, 1856, "relu2"),
}


def up_width(activation: str, width: int) -> int:
    """Columns of an expert's up matrix: gate and up side by side, or the
    ungated `relu2`'s one."""
    return width if activation == "relu2" else 2 * width


def measure(cell: str, tokens: int, share: float, slab: int | None, iters: int,
            dtype: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_reinforcement_learning_tpu.ops import expert_share

    top_k, experts, held, d, width, activation = CELLS[cell]
    up = up_width(activation, width)
    pairs = tokens * top_k
    count = min(pairs, round(share * pairs))
    sliced = hasattr(expert_share, "slab_rows")
    if slab is not None:
        if not sliced:
            raise SystemExit("--slab: this checkout has no slabs")
        expert_share.slab_rows = lambda *_: min(slab, pairs)
    r = np.random.RandomState(0)
    flat = r.randint(held, experts, size=pairs)  # the absent experts
    flat[r.choice(pairs, size=count, replace=False)] = r.randint(held, size=count)
    chosen = jnp.asarray(flat.reshape(tokens, top_k), jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (tokens, d), jnp.float32)
    wgu = 0.02 * jax.random.normal(keys[1], (held, d, up), jnp.float32)
    wd = 0.02 * jax.random.normal(keys[2], (held, width, d), jnp.float32)
    weight = jnp.full((tokens, top_k), 1.0 / top_k, jnp.float32)
    width_of_router = (experts,) if sliced else ()
    gate = () if activation == "silu" else (activation,)  # an old checkout has none

    def layer(x, weight, wgu, wd):
        return expert_share.held_experts(x, chosen, weight, wgu, wd, 0,
                                         *width_of_router, jnp.dtype(dtype), *gate)

    def loss(x, weight, wgu, wd):
        return jnp.sum(layer(x, weight, wgu, wd)[0] ** 2)

    def median_ms(fn):
        jax.block_until_ready(fn(x, weight, wgu, wd))
        times = []
        for _ in range(iters):
            start = time.perf_counter()
            jax.block_until_ready(fn(x, weight, wgu, wd))
            times.append(time.perf_counter() - start)
        return statistics.median(times) * 1e3

    forward = jax.jit(layer)
    counters = forward(x, weight, wgu, wd)[1]
    device = jax.devices()[0]
    return {
        "cell": cell, "tokens": tokens, "pairs": pairs, "held_pairs": count,
        "share": share, "dtype": jnp.dtype(dtype).name,
        "slab": expert_share.slab_rows(pairs, held, experts) if sliced else None,
        "pair_slabs": int(counters["pair_slabs"]) if sliced else None,
        "dropped_pairs": int(counters["dropped_pairs"]), "iters": iters,
        "fwd_ms": median_ms(forward),
        "fwd_bwd_ms": median_ms(jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))),
        "platform": device.platform, "device_kind": device.device_kind,
    }


def measure_decode(cell: str, rows: int, width: int | None, router: int | None,
                   layers: int, steps: int, iters: int, dtype: str,
                   d_model: int | None = None, skew: float = 0.0) -> dict:
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.ops import expert_share

    top_k, experts, held, d, f, activation = CELLS[cell]
    width, experts, d = width or f, router or experts, d_model or d
    up = up_width(activation, width)
    gate = () if activation == "silu" else (activation,)  # an old checkout has none
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    # every row's top_k distinct experts of a uniform router, a set a step a
    # layer; `skew`: a layer's rows prefer the same experts by that much
    prefer = skew * jax.random.normal(keys[4], (layers, 1, experts))
    chosen = jnp.argsort(-(jax.random.uniform(
        keys[0], (steps, layers, rows, experts)) + prefer))[..., :top_k].astype(jnp.int32)
    weight = jnp.full((rows, top_k), 1.0 / top_k, jnp.float32)
    x = jax.random.normal(keys[1], (rows, d), jnp.float32).astype(dtype)
    # a tuple of each layer's own arrays, as the decode bodies hold them: a
    # slice of one stacked array would be a weight-sized copy a step
    made = lambda key, *shape: tuple(
        (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
        for k in jax.random.split(key, layers))
    wgu, wd = made(keys[2], held, d, up), made(keys[3], held, width, d)

    def us_a_layer(form):
        if form is not None:
            expert_share.one_slab_form = lambda *_: form

        def run(x, wgu, wd, chosen):  # a function of its own a form: traced anew
            def step(h, chosen):
                for i in range(layers):
                    out, counters = expert_share.held_experts(
                        h, chosen[i], weight, wgu[i], wd[i], 0, experts,
                        jnp.dtype(dtype), *gate)
                    h = (h.astype(jnp.float32) + out).astype(h.dtype)
                # every counter a cell's chunk reads is read here: it is work
                return h * 0.5, (counters["held_pairs"], counters.get("dense_rows", 0),
                                 counters.get("touched_experts", 0),
                                 counters.get("gate_zeroed", 0))
            return jax.lax.scan(step, x, chosen)

        fn = jax.jit(run)
        grouped = "ragged_dot" in str(jax.make_jaxpr(run)(x, wgu, wd, chosen))
        _, (pairs, dense_rows, touched, _) = jax.block_until_ready(
            fn(x, wgu, wd, chosen))
        if form is not None and ((form == "dense") != bool(dense_rows[0])
                                 or (form == "sorted") != grouped):
            raise SystemExit(f"asked for the {form} form, dense_rows {dense_rows[0]}, "
                             f"a grouped product: {grouped}")
        times = []
        for _ in range(iters):
            start = time.perf_counter()
            jax.block_until_ready(fn(x, wgu, wd, chosen))
            times.append(time.perf_counter() - start)
        return (statistics.median(times) * 1e6 / (steps * layers), float(pairs.mean()),
                float(touched.mean()))

    rule = getattr(expert_share, "one_slab_form", None)  # None: a checkout before it
    device = jax.devices()[0]
    itemsize = jnp.dtype(dtype).itemsize
    line = {"cell": cell, "rows": rows, "top_k": top_k, "router": experts,
            "held": held, "d": d, "width": width, "skew": skew, "activation": activation,
            "layers": layers, "steps": steps,
            "dtype": jnp.dtype(dtype).name, "iters": iters,
            "weight_bytes_a_layer": held * (up + width) * d * itemsize,
            "form": rule(rows, top_k, experts, (d, up, width)) if rule else "sorted",
            "one_slab": expert_share.slab_rows(rows * top_k, held, experts) == rows * top_k}
    try:
        if rule is None or not line["one_slab"]:  # a list in slabs has one form
            line["sorted_us"], line["held_pairs_a_call"], _ = us_a_layer(None)
        else:
            line["sorted_us"], line["held_pairs_a_call"], _ = us_a_layer("sorted")
            line["dense_us"], _, _ = us_a_layer("dense")
            if hasattr(expert_share, "_touched"):  # since PR 54
                line["touched_us"], _, touched = us_a_layer("touched")
                line["touched_experts_a_call"] = touched
                # the least a call can take: the touched experts' weights at HBM's peak
                line["touched_bytes_a_call"] = touched * (up + width) * d * itemsize
    finally:
        if rule is not None:
            expert_share.one_slab_form = rule
    return {**line, "platform": device.platform, "device_kind": device.device_kind}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cell", nargs="*", choices=sorted(CELLS), default=sorted(CELLS))
    ap.add_argument("--tokens", type=int, default=4096,
                    help="rows a call (a learner's row block x T)")
    ap.add_argument("--share", type=float, nargs="*",
                    default=[1 / 32, 1 / 16, 1 / 8, 1 / 2, 1.0],
                    help="held pairs over the pair list")
    ap.add_argument("--slab", type=int, nargs="*", default=[],
                    help="slab sizes to time in place of the rule's")
    ap.add_argument("--rows", type=int, nargs="*", default=[],
                    help="decode mode: rows a call, forward only, every one-slab form")
    ap.add_argument("--width", type=int, help="decode mode: F in place of the cell's")
    ap.add_argument("--router", type=int,
                    help="decode mode: the router's width in place of the cell's")
    ap.add_argument("--d", type=int, help="decode mode: D in place of the cell's")
    ap.add_argument("--skew", type=float, default=0.0,
                    help="decode mode: how far a layer's rows prefer the same experts")
    ap.add_argument("--layers", type=int, default=4,
                    help="decode mode: expert layers a step, each its own weights")
    ap.add_argument("--steps", type=int, default=64, help="decode mode: steps a call")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args(argv)
    for cell in args.cell:
        for rows in args.rows:
            print(json.dumps(measure_decode(
                cell, rows, args.width, args.router, args.layers, args.steps,
                args.iters, args.dtype, args.d, args.skew)), flush=True)
        for slab in [] if args.rows else args.slab or [None]:
            for share in args.share:
                print(json.dumps(measure(cell, args.tokens, share, slab, args.iters,
                                         args.dtype)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Time the held experts' layer alone at a learner's shape.

Forward, and forward + backward (all four gradients: x, the pairs' weights,
wgu, wd), of `ops/expert_share.held_experts` on `--tokens` rows with
exactly `share x tokens x top_k` pairs on the held experts, for every
`--share` of the pair list (default 1/32, 1/16, 1/8, 1/2 and all of it):
the median of `--iters` timed calls after a warm one, one JSON line a point
on stdout. `--cell` names the shape: `joyai` (4,096 x 8 of 256, 16 held, D
2,048, F 768) or `qwen3` (4,096 x 10 of 512, 32 held, D 2,048, F 512).
`--slab` times other slab sizes than the rule's (`slab_rows`), each
compiled in turn, which is how the rule was chosen (PERF.md section 6, PR
42):

    python scripts/expert_share_bench.py --cell joyai --slab 1024 2048 4096

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

CELLS = {  # top_k, router width, held, D, F
    "joyai": (8, 256, 16, 2048, 768),
    "qwen3": (10, 512, 32, 2048, 512),
}


def measure(cell: str, tokens: int, share: float, slab: int | None, iters: int,
            dtype: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_reinforcement_learning_tpu.ops import expert_share

    top_k, experts, held, d, width = CELLS[cell]
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
    wgu = 0.02 * jax.random.normal(keys[1], (held, d, 2 * width), jnp.float32)
    wd = 0.02 * jax.random.normal(keys[2], (held, width, d), jnp.float32)
    weight = jnp.full((tokens, top_k), 1.0 / top_k, jnp.float32)
    width_of_router = (experts,) if sliced else ()

    def layer(x, weight, wgu, wd):
        return expert_share.held_experts(x, chosen, weight, wgu, wd, 0,
                                         *width_of_router, jnp.dtype(dtype))

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
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args(argv)
    for cell in args.cell:
        for slab in args.slab or [None]:
            for share in args.share:
                print(json.dumps(measure(cell, args.tokens, share, slab, args.iters,
                                         args.dtype)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Time the flash-attention kernels alone at one shape and tile.

Forward, and forward + backward (all three gradients), of
`ops/pallas/attention.flash_attention_bhtd` on `[BH, T, D]` x `[BH, T, DV]`
random inputs with one episode a row: the median of `--iters` timed calls
after a warm one, one JSON line a tile on stdout. With no `--block-q` /
`--block-kv` the tile is the rule's own (`flash_blocks`); several values of
either give every pair, each compiled and timed in turn, which is how the
table of PERF.md section 6 (PR 41) was made:

    python scripts/flash_kernel_bench.py --bh 64 --t 2048 --d 192 --dv 128 \
        --block-q 128 256 512 --block-kv 128 256 512 1024

A time is the chip's only there: on the CPU pass `--interpret` and a tiny
shape, and the line says `"platform": "cpu"`. No cell of the benchmark runs
this; it is the instrument for choosing the tile.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure(bh: int, t: int, d: int, dv: int, dtype: str, block_q: int | None,
            block_kv: int | None, iters: int, interpret: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.ops.pallas.attention import (
        flash_attention_bhtd, flash_blocks)

    dt = jnp.dtype(dtype)
    rule = flash_blocks(t, d, dv, dt.itemsize)
    bq, bkv = block_q or rule[0], block_kv or rule[1]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (bh, t, d), dt)
    k = jax.random.normal(kk, (bh, t, d), dt)
    v = jax.random.normal(kv, (bh, t, dv), dt)
    seg = jnp.zeros((bh, t), jnp.int32)

    def attend(q, k, v):
        return flash_attention_bhtd(q, k, v, seg, seg, block_q=bq, block_kv=bkv,
                                    interpret=interpret)

    def loss(q, k, v):
        return jnp.sum(attend(q, k, v).astype(jnp.float32) ** 2)

    def median_ms(fn):
        jax.block_until_ready(fn(q, k, v))
        times = []
        for _ in range(iters):
            start = time.perf_counter()
            jax.block_until_ready(fn(q, k, v))
            times.append(time.perf_counter() - start)
        return statistics.median(times) * 1e3

    device = jax.devices()[0]
    return {
        "bh": bh, "t": t, "d": d, "dv": dv, "dtype": dt.name,
        "block_q": bq, "block_kv": bkv, "rule": list(rule), "iters": iters,
        "fwd_ms": median_ms(jax.jit(attend)),
        "fwd_bwd_ms": median_ms(jax.jit(jax.grad(loss, argnums=(0, 1, 2)))),
        "platform": device.platform, "device_kind": device.device_kind,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--bh", type=int, default=64, help="batch rows x heads")
    ap.add_argument("--t", type=int, default=2048)
    ap.add_argument("--d", type=int, default=192, help="q/k width")
    ap.add_argument("--dv", type=int, default=None, help="value width (default: --d)")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--block-q", type=int, nargs="*", default=[])
    ap.add_argument("--block-kv", type=int, nargs="*", default=[])
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args(argv)
    dv = args.d if args.dv is None else args.dv
    for bq, bkv in itertools.product(args.block_q or [None], args.block_kv or [None]):
        if (bq and args.t % bq) or (bkv and args.t % bkv):
            continue
        try:
            line = measure(args.bh, args.t, args.d, dv, args.dtype, bq, bkv,
                           args.iters, args.interpret)
        except Exception as e:  # a tile the compiler refuses must not end a sweep
            line = {"block_q": bq, "block_kv": bkv, "error": str(e)[:300]}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Pallas TPU kernels for the sequential hot ops.

The two ops XLA cannot fuse well on its own are the framework's only
truly sequential recursions (SURVEY §7 "hard parts" b):

- the V-trace backward recursion (`pallas/vtrace.py`) — the reference
  serialized a `tf.scan(parallel_iterations=1)` over it
  (`/root/reference/optimizer/vtrace.py:86-100`),
- the LSTM sequence unroll with done-masking (`pallas/lstm.py`) — the
  reference replicated the whole network per timestep in Python
  (`/root/reference/model/r2d2_lstm.py:65-112`).

Both kernels keep the entire time loop in VMEM (one kernel launch per
batch instead of T dependent HLO while-loop iterations bouncing carries
through HBM) and are numerically validated against the `lax.scan`
reference implementations in interpret mode on CPU.

Backend selection: `resolve_backend("auto")` picks pallas on TPU and the
lax.scan reference elsewhere; `DRL_TPU_PALLAS=0` force-disables.
"""

from __future__ import annotations

import os

import jax


# Per-kernel scoped VMEM is 16MB on current TPUs; leave slack for the
# compiler's own scratch and the replicated (non-tiled) operands.
_VMEM_BUDGET = 11 << 20


def pick_block(
    b: int, block: int, per_row_bytes: int = 0, fixed_bytes: int = 0
) -> int:
    """Batch-tile size for a 1-D grid over B.

    Tile by `block` when it divides B, otherwise one program owns the
    whole (padded) batch. When `per_row_bytes` (total bytes of all tiled
    refs per batch row) is given, the tile is instead the largest DIVISOR
    of B, at most `block`, whose VMEM footprint — double-buffered tiles +
    `fixed_bytes` of replicated operands — fits the scoped budget, so big
    [T, B, 4H] workloads don't hit the 16MB scoped-vmem stack limit (seen
    at B=256, T=20, H=256) even when B is not a power of two (a
    whole-batch fallback here would reintroduce exactly that failure).
    """
    if per_row_bytes:
        n = min(block, b)
        while n > 1 and (
            b % n != 0 or fixed_bytes + 2 * n * per_row_bytes > _VMEM_BUDGET
        ):
            n -= 1
        return n
    return b if b < block or b % block != 0 else block


def batch_partitioned(call, in_dims: tuple[int, ...], out_dims: tuple[int, ...]):
    """`call` (arrays -> tuple of arrays, ending in a `pallas_call`),
    made to run per device when it is traced for a multi-device mesh.

    A Mosaic kernel inside a program jitted over a mesh does not lower
    ("Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map") — which is every kernel in a
    `ShardedLearner` step on a multi-chip host. Each kernel here is
    independent along one batch dim per operand (`in_dims`) and result
    (`out_dims`), so under a context mesh (`ShardedLearner` traces its
    step inside `jax.sharding.use_abstract_mesh`) the call is wrapped in
    a `shard_map` over every mesh axis: batch dims split over the
    `data` axis, everything else replicated, each device running the
    kernel on its own rows. With no context mesh (one device), or
    already inside a `shard_map` (Anakin mesh, ring attention), `call`
    is returned unchanged.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return call
    # Imported here: `parallel` imports `ops` at module level.
    from distributed_reinforcement_learning_tpu.parallel.mesh import DATA_AXIS, P

    batch_axis = DATA_AXIS if DATA_AXIS in mesh.axis_names else None

    def specs(dims):
        return tuple(P(*([None] * d), batch_axis) for d in dims)

    return jax.shard_map(call, in_specs=specs(in_dims),
                         out_specs=specs(out_dims), check_vma=False)


def resolve_backend(backend: str = "auto", opt_in_env: str | None = None) -> str:
    """-> 'pallas' | 'pallas_interpret' | 'reference'.

    `opt_in_env`: name of an env var that must be "1" for `auto` to pick
    the kernel — used by ops whose measured advantage is not (or not
    yet) established, e.g. the fused LSTM (DRL_LSTM_PALLAS). Ops with a
    stable margin (V-trace) pass None and auto-enable on TPU.
    """
    if backend == "auto":
        if os.environ.get("DRL_TPU_PALLAS", "1") == "0":
            return "reference"
        if opt_in_env is not None and os.environ.get(opt_in_env, "0") != "1":
            return "reference"
        return "pallas" if jax.default_backend() == "tpu" else "reference"
    if backend not in ("pallas", "pallas_interpret", "reference"):
        raise ValueError(f"unknown backend {backend!r}")
    return backend

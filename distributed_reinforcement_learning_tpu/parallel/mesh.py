"""Device mesh construction and named shardings.

The reference has no multi-device learner at all — one `/job:learner/task:0`
process owns the weights (`train_impala.py:33,37`), and "distributed" means
actor processes over gRPC. The TPU-native generalization (SURVEY §2.3, §5.8)
is a learner spanning a `jax.sharding.Mesh` of chips:

- `data` axis: batch-dimension data parallelism. Params replicated (or
  model-sharded, below), batch split; XLA inserts the gradient `psum` over
  ICI automatically because the output params must be consistent.
- `model` axis: optional tensor parallelism for large kernels (LSTM and
  head matmuls sharded on their output feature dim, Megatron column style).
  Size 1 by default — the reference-parity configs are small enough that
  DP is the only axis that pays.
- `seq` axis: optional sequence/context parallelism for long-context
  attention (`parallel/sequence.py` ring / all-to-all). Size 1 by
  default; sized >1 it sits between `data` and `model` so neighboring
  devices carry adjacent sequence shards and the ring's `ppermute`
  rides nearest ICI links.
- `pipe` axis: optional pipeline parallelism (`parallel/pipeline.py`) —
  one stage per device, GPipe microbatch schedule. OUTERMOST: pipeline
  hops move one activation microbatch per tick, the lightest traffic of
  any axis, so it can ride the slowest links (incl. DCN on multi-host
  meshes).
- `expert` axis: optional expert parallelism for MoE layers
  (`ops/moe.py`) — expert weights and the dispatched token buffer shard
  over it; GSPMD inserts the all-to-alls.

Everything here is plain `jax.sharding`; no torch-style process groups.
"""

from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PIPE_AXIS = "pipe"
DATA_AXIS = "data"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"
MODEL_AXIS = "model"


def make_mesh(
    n_devices: int | None = None,
    model_parallel: int = 1,
    seq_parallel: int = 1,
    pipe_parallel: int = 1,
    expert_parallel: int = 1,
    devices: list | None = None,
) -> Mesh:
    """Build a `(pipe, data, seq, expert, model)` mesh over the first
    `n_devices` devices.

    `model_parallel` chips are adjacent in device order so the model axis
    rides the fastest ICI links on real TPU topologies; `expert` and
    `seq` are next-innermost for the same reason, and `pipe` is
    outermost (lightest traffic on the slowest links).
    """
    devices = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(
                f"requested {n_devices} devices but only {len(devices)} visible; "
                "set XLA_FLAGS=--xla_force_host_platform_device_count=N for CPU simulation"
            )
        devices = devices[:n_devices]
    n = len(devices)
    inner = model_parallel * seq_parallel * expert_parallel
    if n % (inner * pipe_parallel) != 0:
        raise ValueError(
            f"{n} devices not divisible by pipe*seq*expert*model="
            f"{inner * pipe_parallel}"
        )
    arr = np.array(devices).reshape(
        pipe_parallel, n // (inner * pipe_parallel), seq_parallel, expert_parallel,
        model_parallel,
    )
    return Mesh(arr, (PIPE_AXIS, DATA_AXIS, SEQ_AXIS, EXPERT_AXIS, MODEL_AXIS))


def pcast_varying(x, axes: tuple[str, ...]):
    """`lax.pcast(..., to="varying")` over exactly the axes `x` is not
    already varying on (pcast rejects already-varying axes). The shared
    idiom for typing shard_map carries whose loop bodies write
    shard-dependent values into an invarying init — used by the ring
    attention accumulators and the pipeline schedule."""
    have = set(getattr(jax.typeof(x), "vma", ()))
    need = tuple(a for a in axes if a not in have)
    return jax.lax.pcast(x, need, to="varying") if need else x


def traced_on(mesh: Mesh, fn):
    """`fn`, traced with `mesh` as JAX's context mesh.

    GSPMD partitions everything in a step jitted over a mesh by itself
    except a Pallas kernel, which has to be told the mesh to wrap itself
    in a `shard_map` (`ops/pallas.batch_partitioned` asks
    `jax.sharding.get_abstract_mesh()`); without this the step does not
    lower on a multi-chip TPU host."""

    @functools.wraps(fn)
    def traced(*args):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args)

    return traced


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch) dim over the `data` axis."""
    return NamedSharding(mesh, P(DATA_AXIS))


def model_kernel_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """Shard a kernel's last (output-feature) dim over the `model` axis."""
    return NamedSharding(mesh, P(*([None] * (ndim - 1)), MODEL_AXIS))


def place_local_batch(tree, sharding: NamedSharding | None):
    """Put this process's host batch onto the mesh as (its shard of) the
    global batch.

    Single-process: a plain `device_put` into the sharding. Multi-process
    (a mesh spanning hosts, after `parallel.distributed.initialize`): each
    process holds only its local rows, so the global array is assembled
    with `jax.make_array_from_process_local_data` — the per-host batch
    feed of the multi-host learner. Local row count follows the sharding:
    when the batch axis spans processes (the usual data-parallel feed),
    each process supplies `global_batch / process_count` rows; when the
    processes sit on an axis the batch is REPLICATED over (e.g. hosts on
    `pipe`, batch sharded over a within-host `data` axis), each process
    supplies the full, identical global batch (see the pipeline step in
    tests/multihost_worker.py).
    """
    if sharding is None:
        return jax.device_put(tree)
    if jax.process_count() == 1:
        return jax.device_put(tree, sharding)
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(sharding, np.asarray(x)),
        tree,
    )

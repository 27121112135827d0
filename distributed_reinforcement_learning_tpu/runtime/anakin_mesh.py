"""Shared multi-chip plumbing for the on-device replay families.

`runtime/anakin.py` (IMPALA) meshes via plain jit-with-shardings: its
state is envs + TrainState, all of whose collectives XLA infers. The
replay families (`anakin_apex.py`, `anakin_r2d2.py`) additionally carry
a prioritized RING — and a capacity-sharded ring under GSPMD would turn
every prioritized sample into a cross-chip gather of frame stacks
(cumsum over the sharded priority vector, then a global index gather),
serializing each learn batch behind ICI traffic that dwarfs the grads.

So the replay families shard over the `data` axis with shard_map and
PER-DEVICE REPLAY SHARDS: each device steps its env shard, ingests into
its own ring shard, and samples its learn sub-batch locally; only the
gradients cross the interconnect (one pmean per learn step, inserted in
the agents' `_learn(axis_name=...)`). This mirrors how distributed
replay deploys at scale (sharded Reverb-style servers, one per learner
shard) rather than a single logical prioritized heap; the semantic
deviation — stratified sampling within equal-size shards instead of one
global stratification — is documented on `data/device_replay.sample`,
which keeps the IS weights exact for the per-shard sampler and
batch-max-normalizes over the GLOBAL batch via pmax.

Scalar ring bookkeeping (ptr/size/beta) advances identically on every
device (same local write width, same schedule), so those leaves stay
replicated; NOTE the host-visible `replay.size` is therefore the
PER-DEVICE count — chunk metrics report the psum'd global `replay_size`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_reinforcement_learning_tpu.data.device_replay import DeviceReplay
from distributed_reinforcement_learning_tpu.observability import scopes
from distributed_reinforcement_learning_tpu.parallel.mesh import DATA_AXIS


def validate_data_mesh(mesh, **divisible_by_data) -> int:
    """Check a replay-family mesh (data axis only) and return its data
    size (1 when mesh is None). `divisible_by_data` entries must split
    evenly over the axis."""
    if mesh is None:
        return 1
    extra = {a: s for a, s in mesh.shape.items() if a != DATA_AXIS and s > 1}
    if extra:
        raise ValueError(
            "the on-device replay families shard over the data axis only "
            f"(per-device replay shards); mesh also has {extra}")
    d = mesh.shape.get(DATA_AXIS, 1)
    for name, val in divisible_by_data.items():
        if val % d != 0:
            raise ValueError(
                f"{name} ({val}) must divide over the data axis ({d})")
    return d


def replay_specs(storage_tree) -> DeviceReplay:
    """PartitionSpecs for a DeviceReplay: rings shard their capacity dim
    over `data` (per-device shards), bookkeeping scalars replicate."""
    return DeviceReplay(
        storage=jax.tree.map(lambda _: P(DATA_AXIS), storage_tree),
        priorities=P(DATA_AXIS),
        ptr=P(), size=P(), beta=P(),
    )


def batched_specs(abstract_tree):
    """P(data) for array leaves with a leading per-env dim, P() for
    scalars (env-state pytrees)."""
    return jax.tree.map(
        lambda l: P(DATA_AXIS) if l.ndim >= 1 else P(), abstract_tree)


def state_shardings(mesh, specs_tree):
    """Specs pytree -> NamedSharding pytree (for device_put at init)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs_tree,
                        is_leaf=lambda x: isinstance(x, P))


def own_buffers(state):
    """`state` with every leaf in a buffer of its own: a donated state
    cannot hold one buffer twice, and freshly made zeros may share one."""
    seen = set()

    def own(x):
        if isinstance(x, jax.core.Tracer):  # `jax.eval_shape(init)`
            return x
        buffer = x.addressable_shards[0].data.unsafe_buffer_pointer()
        if buffer in seen:
            return jnp.copy(x)
        seen.add(buffer)
        return x

    return jax.tree.map(own, state)


class DataMeshReplayMixin:
    """Shared ctor/init plumbing for the mesh-capable replay runtimes.

    Host class supplies `_state_specs() -> state-NamedTuple of P` plus
    `_train_chunk` / `_collect_chunk` bodies written in LOCAL sizes
    (`self.num_envs_local`, `self.batch_local`); this mixin wires the
    single-device jit vs shard_map dispatch, the per-device rng split at
    init, and the psum/pmean metric reducers.

    Both chunks DONATE their state: the ring is the state (6.9 GB at the
    `r2d2_atari` section's sizes), and an undonated chunk holds it twice,
    as input and as output. A caller rebinds (`state, m =
    train_chunk(state, n)`) and keeps no reference into the old state.
    """

    def _setup_mesh(self, mesh, *, num_envs: int, batch_size: int,
                    capacity: int) -> None:
        self.mesh = mesh
        self.dshard = validate_data_mesh(
            mesh, num_envs=num_envs, batch_size=batch_size, capacity=capacity)
        self.num_envs_local = num_envs // self.dshard
        self.batch_local = batch_size // self.dshard
        self._axis = DATA_AXIS if mesh is not None else None
        if mesh is None:
            self.train_chunk = jax.jit(scopes.tagged(self._train_chunk),
                                       static_argnums=(1,), donate_argnums=(0,))
            self.collect_chunk = jax.jit(scopes.tagged(self._collect_chunk),
                                         static_argnums=(1,), donate_argnums=(0,))
        else:
            self._specs = self._state_specs()
            self.train_chunk = shard_mapped_chunk(
                mesh, self._specs, self._train_chunk)
            self.collect_chunk = shard_mapped_chunk(
                mesh, self._specs, self._collect_chunk)

    def _place_init(self, state, k_run):
        """Mesh mode: one independent rng stream per device, state placed
        into its shardings. Either way every leaf ends in a buffer of its
        own (`initial_lstm_state` hands out one zero array as h and as c),
        since a donated state cannot hold one buffer twice."""
        if self.mesh is not None:
            state = state._replace(rng=jax.random.split(k_run, self.dshard))
            state = jax.device_put(state,
                                   state_shardings(self.mesh, self._specs))
        return own_buffers(state)

    def _psum(self, tree):
        return jax.lax.psum(tree, self._axis) if self._axis else tree

    def _pmean(self, x):
        return jax.lax.pmean(x, self._axis) if self._axis else x

    def _pmax(self, x):
        return jax.lax.pmax(x, self._axis) if self._axis else x


def shard_mapped_chunk(mesh, specs, body):
    """jit(shard_map) a `(state, num) -> (state, metrics)` chunk body.

    The global state carries one rng key PER DEVICE ([D, 2], sharded
    over `data` so every shard collects and samples an independent
    stream); the wrapper unwraps it to the body's scalar key and wraps
    it back. Metrics leave the body fully reduced (psum/pmean), so their
    out_spec is replicated.
    """

    def call(state, num: int):
        def local_body(s):
            s = s._replace(rng=s.rng[0])
            s, metrics = body(s, num)
            return s._replace(rng=s.rng[None]), metrics

        f = jax.shard_map(
            local_body, mesh=mesh, in_specs=(specs,), out_specs=(specs, P()))
        return f(state)

    call.__name__ = body.__name__  # the HLO module is named for the body
    return jax.jit(scopes.tagged(call), static_argnums=(1,),
                   donate_argnums=(0,))

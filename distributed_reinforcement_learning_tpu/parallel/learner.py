"""Multi-chip sharded learner: pjit any agent's learn step over a mesh.

Replaces nothing in the reference (its learner is a single process holding
TF variables, `train_impala.py:37-62`) — this is the capability the TPU
design adds: the same pure `learn(state, batch, ...)` function compiled
once over an N-chip mesh, with

- the batch sharded over the `data` axis (each chip grads its shard; XLA
  emits the `psum` over ICI because the returned params are consistent),
- params / optimizer moments either replicated or, when the mesh has a
  `model` axis > 1, sharded on their output-feature dim (tensor
  parallelism; XLA GSPMD inserts the activation collectives).

The sharding rule is structural — any ≥2-D leaf whose last dim divides the
model axis and is big enough to be worth splitting — so it applies to the
whole TrainState pytree (params *and* Adam/RMSProp moments) without
per-model annotations.
"""

from __future__ import annotations

from typing import Any

import jax

from distributed_reinforcement_learning_tpu.parallel import mesh as mesh_lib
from distributed_reinforcement_learning_tpu.parallel.mesh import (
    EXPERT_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    Mesh,
    NamedSharding,
    P,
)

# Leaves smaller than this stay replicated: splitting a 256-float bias over
# ICI costs more in collective latency than the shard saves.
_MIN_SHARD_SIZE = 4096


def _leaf_sharding(mesh: Mesh, leaf: jax.ShapeDtypeStruct) -> NamedSharding:
    m = mesh.shape.get(MODEL_AXIS, 1)
    if (
        m > 1
        and leaf.ndim >= 2
        and leaf.shape[-1] % m == 0
        and leaf.size >= _MIN_SHARD_SIZE
    ):
        return mesh_lib.model_kernel_sharding(mesh, leaf.ndim)
    return mesh_lib.replicated(mesh)


def train_state_sharding(mesh: Mesh, abstract_state: Any):
    """Sharding pytree for a TrainState, from its `jax.eval_shape` skeleton.

    Three rules, first match wins, applied to params AND optimizer
    moments (the moments mirror the params tree, so the same path keys
    appear):
    - leaves under a `blocks_stacked` key (the pipelined transformer
      body) shard their leading layer dim over `pipe`;
    - expert-stacked MoE leaves (`moe_w*`/`moe_b*`) shard their leading
      expert dim over `expert`;
    - any other big 2-D+ kernel shards its output-feature dim over
      `model` (Megatron column style); the rest replicate.
    """
    pipe = mesh.shape.get(PIPE_AXIS, 1)
    ep = mesh.shape.get(EXPERT_AXIS, 1)

    def rule(path, leaf):
        keys = [str(k) for k in path]
        if (
            pipe > 1
            and any("blocks_stacked" in k for k in keys)
            and leaf.ndim >= 1
            and leaf.shape[0] % pipe == 0
        ):
            # % not ==: with virtual stages the stored layout stays
            # [num_layers, ...] and each pipe shard holds its stage's
            # contiguous layers-per-stage group.
            return NamedSharding(mesh, P(PIPE_AXIS))
        if (
            ep > 1
            and any("moe_" in k and "moe_gate" not in k for k in keys)
            and leaf.ndim >= 2
            and leaf.shape[0] % ep == 0
        ):
            return NamedSharding(mesh, P(EXPERT_AXIS))
        return _leaf_sharding(mesh, leaf)

    return jax.tree_util.tree_map_with_path(rule, abstract_state)


class ShardedLearner:
    """Bind an agent's `_learn` to a mesh.

    `num_data_args`: learn-args after the state that carry a leading batch
    dim (IMPALA: 1 = batch; Ape-X/R2D2: 2 = batch + is_weight).
    `num_aux_outputs`: outputs after the new state (metrics, and for the
    replay agents the per-element TD/priority vector) — these are gathered
    to replicated form since the host consumes them.
    """

    def __init__(
        self,
        agent,
        mesh: Mesh,
        num_data_args: int = 1,
        num_aux_outputs: int = 1,
    ):
        self.agent = agent
        self.mesh = mesh
        abstract_state = jax.eval_shape(agent.init_state, jax.random.PRNGKey(0))
        self.state_sharding = train_state_sharding(mesh, abstract_state)
        self._data_sh = mesh_lib.data_sharding(mesh)
        self._repl = mesh_lib.replicated(mesh)
        in_shardings = (self.state_sharding,) + (self._data_sh,) * num_data_args
        out_shardings = (self.state_sharding,) + (self._repl,) * num_aux_outputs
        self.learn = jax.jit(
            self._on_mesh(agent._learn),
            in_shardings=in_shardings,
            out_shardings=out_shardings,
            donate_argnums=(0,),
        )
        # Split learn step on the mesh (learner-tier allreduce seam):
        # gradients come OUT in the params' sharding — the host-side
        # partition plan (parallel/partition.py) then exchanges each
        # spec class owner-scoped instead of ring-reducing the full
        # vector. apply_grads does NOT donate state, mirroring the
        # agents' own split jits (the tier holds state across the
        # exchange). Only the replay families' (state, batch,
        # is_weight) arity carries the seam.
        if (num_data_args == 2 and hasattr(agent, "_grads")
                and hasattr(agent, "_apply_grads")):
            params_sh = self.state_sharding.params
            self.grads = jax.jit(
                self._on_mesh(agent._grads),
                in_shardings=(self.state_sharding,) + (self._data_sh,) * 2,
                out_shardings=(params_sh, self._repl, self._repl),
            )
            self.apply_grads = jax.jit(
                agent._apply_grads,
                in_shardings=(self.state_sharding, params_sh, self._repl),
                out_shardings=(self.state_sharding, self._repl),
            )
        # K-step scanned learn over [K, B, ...] stacks (agents/common
        # scan_learn): the scan carries the sharded TrainState, each
        # iteration's batch slice shards its B dim over `data`. Only the
        # (state, batch) signature — replay agents' weighted learn stays
        # per-step at the runner level.
        if num_data_args == 1:
            from distributed_reinforcement_learning_tpu.agents.common import scan_learn

            self.stacked_data_sharding = NamedSharding(mesh, P(None, mesh_lib.DATA_AXIS))
            self.learn_many = jax.jit(
                self._on_mesh(scan_learn(agent._learn)),
                in_shardings=(self.state_sharding, self.stacked_data_sharding),
                out_shardings=(self.state_sharding, self._repl),
                donate_argnums=(0,),
            )

    def _on_mesh(self, fn):
        """`fn`, traced with this learner's mesh as JAX's context mesh
        (`mesh.traced_on`: what a Pallas kernel in the step needs)."""
        return mesh_lib.traced_on(self.mesh, fn)

    def init_state(self, rng: jax.Array):
        """Initialize the TrainState directly into its mesh sharding."""
        init = jax.jit(self.agent.init_state, out_shardings=self.state_sharding)
        return init(rng)

    def place_state(self, state):
        return jax.device_put(state, self.state_sharding)

    def shard_batch(self, tree):
        """Host batch -> device, leading dim split over the `data` axis."""
        return jax.device_put(tree, self._data_sh)

"""Ape-X dueling Q-network.

Re-design of `/root/reference/model/apex_value.py`. The reference's
"dueling" head is nonstandard: q = value_tower(num_action) - mean_tower(1),
two separate [256, 256] MLP towers (`model/apex_value.py:22-40`) — kept
for behavioral parity. `build_network`'s three scoped copies (main(s),
main(s') reused, target(s')) become two param trees (main/target) with the
main net applied to a stacked [s; s'] batch in one conv pass.
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.models.torso import (
    MLP, ActionEmbedding, NatureConv, frame_scale)


class DuelingQNetwork(nn.Module):
    """Conv torso + prev-action embedding -> value(num_action) - mean(1)."""

    num_actions: int
    hidden_sizes: Sequence[int] = (256, 256)
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, obs: jax.Array, prev_action: jax.Array) -> jax.Array:
        img = NatureConv(dtype=self.dtype, input_scale=frame_scale(obs), name="torso")(obs)
        act = ActionEmbedding(self.num_actions, dtype=self.dtype, name="action_embed")(prev_action)
        z = jnp.concatenate([img, act], axis=-1)
        value = MLP(self.hidden_sizes, self.num_actions, dtype=self.dtype, name="value")(z)
        mean = MLP(self.hidden_sizes, 1, dtype=self.dtype, name="mean")(z)
        return (value - mean).astype(jnp.float32)


class SimpleQNetwork(nn.Module):
    """MLP variant for vector observations (CartPole-class envs).

    Parity with `model/apex_value.py:67-100` (`build_simple_network`): state
    MLP 256-256, prev-action embed 256-256, concat -> 256 -> dueling head.
    """

    num_actions: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, obs: jax.Array, prev_action: jax.Array) -> jax.Array:
        obs = obs.astype(self.dtype)
        glorot = nn.initializers.xavier_uniform()
        x = nn.relu(nn.Dense(256, kernel_init=glorot, dtype=self.dtype)(obs))
        x = nn.relu(nn.Dense(256, kernel_init=glorot, dtype=self.dtype)(x))
        act = ActionEmbedding(self.num_actions, dtype=self.dtype, name="action_embed")(prev_action)
        z = jnp.concatenate([x, act], axis=-1)
        z = nn.relu(nn.Dense(256, kernel_init=glorot, dtype=self.dtype)(z))
        value = nn.Dense(self.num_actions, kernel_init=glorot, dtype=self.dtype)(z)
        mean = nn.Dense(1, kernel_init=glorot, dtype=self.dtype)(z)
        return (value - mean).astype(jnp.float32)

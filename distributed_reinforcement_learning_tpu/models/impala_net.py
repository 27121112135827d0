"""IMPALA conv-LSTM actor-critic network.

Re-design of `/root/reference/model/impala_actor_critic.py`. The reference
builds 1 inference copy plus 3*(T-2) replicated single-step copies of the
network under `AUTO_REUSE` (`model/impala_actor_critic.py:73-114`) because
every training timestep is re-seeded from the **actor-recorded** (h, c) —
stored-state semantics, no recurrence across learner timesteps.

On TPU that collapses to a single application: flatten `[B, T, ...]` to
`[B*T, ...]`, run the network once (big batched conv + one LSTM-cell
matmul), and reshape back. The first/middle/last V-trace views become
cheap slices of the one output (see `agents/impala.py`). With no
recurrence across the rows their order is free, so a `[T, B, ...]`
rollout flattens to `[T*B, ...]` just as well and nobody transposes it.
"""

from __future__ import annotations

from typing import NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.models.recurrent import LSTMCell
from distributed_reinforcement_learning_tpu.models.torso import (
    MLP, ActionEmbedding, NatureConv, ResNetTorso, frame_scale)


class ImpalaOutput(NamedTuple):
    policy: jax.Array  # [N, num_actions] softmax probabilities
    value: jax.Array  # [N]
    h: jax.Array  # [N, lstm]
    c: jax.Array  # [N, lstm]


class ImpalaActorCritic(nn.Module):
    """Single-step conv-LSTM actor-critic: obs+prev_action+(h,c) -> policy/value.

    Matches `model/impala_actor_critic.py:33-42`: conv torso + action
    embedding -> 1-step LSTM -> separate 256-256 policy/value heads.
    """

    num_actions: int
    lstm_size: int = 256
    dtype: jnp.dtype = jnp.float32
    # "nature" (reference parity) or "resnet" (the IMPALA paper's deep
    # torso, width-multiplied — the MXU-dense variant; models/torso.py).
    torso: str = "nature"
    torso_width: int = 1

    @nn.compact
    def __call__(self, obs: jax.Array, prev_action: jax.Array, h: jax.Array, c: jax.Array) -> ImpalaOutput:
        if obs.ndim == 2:  # vector observations (CartPole-class envs)
            img = MLP([256], 256, final_activation=nn.relu, dtype=self.dtype, name="torso")(
                obs.astype(self.dtype)
            )
        else:
            scale = frame_scale(obs)
            if self.torso == "resnet":
                img = ResNetTorso(dtype=self.dtype, width=self.torso_width,
                                  input_scale=scale, name="torso")(obs)
            else:
                img = NatureConv(dtype=self.dtype, input_scale=scale, name="torso")(obs)
        act = ActionEmbedding(self.num_actions, dtype=self.dtype, name="action_embed")(prev_action)
        z = jnp.concatenate([img, act], axis=-1)
        new_h, new_c = LSTMCell(self.lstm_size, dtype=self.dtype, name="lstm")(z, h, c)
        logits = MLP([256, 256], self.num_actions, dtype=self.dtype, name="policy_head")(new_h)
        policy = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        value = MLP([256, 256], 1, dtype=self.dtype, name="value_head")(new_h)[..., 0]
        return ImpalaOutput(policy, value.astype(jnp.float32), new_h, new_c)


def apply_stored_state(
    model: ImpalaActorCritic,
    params,
    obs_seq: jax.Array,  # [B, T, ...obs] or [T, B, ...obs]
    prev_action_seq: jax.Array,  # [B, T] or [T, B]
    h_seq: jax.Array,  # [B, T, lstm] actor-recorded per-step h, or [T, B, lstm]
    c_seq: jax.Array,  # as h_seq
) -> tuple[jax.Array, jax.Array]:
    """Policy/value for all (b, t) at once via stored-state flattening.

    Replaces the 3*(T-2) replicated graphs of
    `model/impala_actor_critic.py:73-114` with one batched forward over
    the two leading axes flattened AS THEY LIE: a batch-major `[B, T]`
    input gives (`policy` `[B, T, A]`, `value` `[B, T]`), a time-major
    `[T, B]` input gives (`[T, B, A]`, `[T, B]`). Either is a free
    reshape; the layout is the caller's and comes back unchanged.
    """
    lead = obs_seq.shape[:2]
    flat = lambda x: x.reshape((lead[0] * lead[1],) + x.shape[2:])
    out = model.apply(params, flat(obs_seq), flat(prev_action_seq), flat(h_seq), flat(c_seq))
    return out.policy.reshape(*lead, -1), out.value.reshape(lead)

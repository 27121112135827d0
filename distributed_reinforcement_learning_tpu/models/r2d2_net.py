"""R2D2 recurrent Q-network with scan-based sequence unroll.

Re-design of `/root/reference/model/r2d2_lstm.py`. The reference unrolls
main and target networks with Python loops, one full network copy per
timestep (`model/r2d2_lstm.py:65-112`), zero-resetting (h, c) *after* the
step whenever done[t] is set. Here the unroll is a `flax.linen.scan`
(=> one compiled `lax.scan`), same done-masking semantics, seeded from
the sequence-start stored state like the reference
(`agent/r2d2.py:110-111`).
"""

from __future__ import annotations

import contextlib

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.models.recurrent import LSTMCell
from distributed_reinforcement_learning_tpu.models.torso import (
    ActionEmbedding, NatureConv, ResNetTorso, frame_scale)

_glorot = nn.initializers.xavier_uniform()


class R2D2Net(nn.Module):
    """Torso + action embed -> LSTM -> dueling head.

    `dueling_hidden=None` is the reference's head: `Dense(128)` ->
    `Dense(A)` minus a LEARNED `Dense(1)` mean. An integer (paper: 512)
    is the published dueling head (Wang et al. 2016 as Kapturowski et
    al. 2019 use it): a value stream `Dense(n) -> Dense(1)` and an
    advantage stream `Dense(n) -> Dense(A)`, Q = V + A - mean_a A.

    Single-step signature matches `model/r2d2_lstm.py:26-47`: returns
    (q_value [N, A], h, c).

    `torso`: "mlp" is the reference's CartPole downscaling
    (`model/r2d2_lstm.py:26-47` — its R2D2 never sees pixels); "nature" /
    "resnet" are the conv torsos that make the family an Atari agent the
    way the R2D2 paper describes (Kapturowski et al. 2019 use exactly
    the Nature-DQN stack in front of the LSTM) — a deliberate
    beyond-parity extension for the on-device pixel envs.
    """

    num_actions: int
    lstm_size: int = 512
    dtype: jnp.dtype = jnp.float32
    cell_backend: str = "auto"  # LSTM recursion backend (pallas on TPU)
    torso: str = "mlp"  # "mlp" | "nature" | "resnet"
    torso_width: int = 1  # ResNet channel multiplier
    dueling_hidden: int | None = None

    def setup(self):
        if self.torso == "mlp":
            self.state_fc1 = nn.Dense(256, kernel_init=_glorot, dtype=self.dtype)
            self.state_fc2 = nn.Dense(256, kernel_init=_glorot, dtype=self.dtype)
        self.action_embed = ActionEmbedding(self.num_actions, dtype=self.dtype)
        self.cell = LSTMCell(self.lstm_size, dtype=self.dtype, backend=self.cell_backend)
        dense = lambda n: nn.Dense(n, kernel_init=_glorot, dtype=self.dtype)
        if self.dueling_hidden is None:
            self.head_fc = dense(128)
            self.value = dense(self.num_actions)
            self.mean = dense(1)
        else:
            self.value_fc = dense(self.dueling_hidden)
            self.value_out = dense(1)
            self.advantage_fc = dense(self.dueling_hidden)
            self.advantage_out = dense(self.num_actions)

    def _head(self, h: jax.Array) -> jax.Array:
        """LSTM outputs `[..., H]` -> Q-values `[..., A]`, float32."""
        if self.dueling_hidden is None:
            q = nn.relu(self.head_fc(h))
            q = self.value(q) - self.mean(q)
        else:
            v = self.value_out(nn.relu(self.value_fc(h)))
            adv = self.advantage_out(nn.relu(self.advantage_fc(h)))
            q = v + adv - jnp.mean(adv, axis=-1, keepdims=True)
        return q.astype(jnp.float32)

    @nn.compact
    def _torso(self, x: jax.Array) -> jax.Array:
        """[N, ...obs] -> [N, F] features.

        The conv torso is made here, not in `setup`, because what it is
        made with follows from the frames' dtype (`frame_scale`).
        """
        if self.torso == "mlp":
            x = nn.relu(self.state_fc1(x.astype(self.dtype)))
            return nn.relu(self.state_fc2(x))
        scale = frame_scale(x)
        if self.torso == "resnet":
            return ResNetTorso(dtype=self.dtype, width=self.torso_width,
                               input_scale=scale, name="torso")(x)
        return NatureConv(dtype=self.dtype, input_scale=scale, name="torso")(x)

    def step(self, obs: jax.Array, prev_action: jax.Array, h: jax.Array, c: jax.Array):
        x = self._torso(obs)
        a = self.action_embed(prev_action)
        z = jnp.concatenate([x, a], axis=-1)
        new_h, new_c = self.cell(z, h, c)
        return self._head(new_h), new_h, new_c

    def __call__(self, obs, prev_action, h, c):
        return self.step(obs, prev_action, h, c)

    def unroll(self, obs_seq, prev_action_seq, done_seq, h0, c0,
               scope: str | None = None):
        """Q-values over a `[B, T, ...]` sequence from stored start state.

        Batch-major: what everyone who holds an `R2D2Batch` calls (the
        learn step's `_loss`, `_td_error`, the host learner
        `runtime/r2d2_runner.py`, `scripts/`, the benchmark's reference
        check). `unroll_time_major` is the same net over `[T, B, ...]`.

        done-masked like `model/r2d2_lstm.py:78-80`: (h, c) are zeroed
        *after* the step at which done[t] is True. Returns `[B, T, A]`.

        Only the LSTM recursion is sequential: the torso, action
        embedding, and dueling head are h-independent, so they run
        time-parallel over the whole `[B, T]` batch (one MXU matmul /
        conv pass each) around the fused `cell.unroll` — vs the
        reference's per-timestep whole-network replicas
        (`model/r2d2_lstm.py:65-112`). Conv torsos flatten [B, T] into
        the batch dim for the pass (2-D feature maps keep their own
        trailing dims).

        `scope`: a `jax.named_scope` around the recurrence alone; the
        learn step names it (observability/scopes.py UNROLL), the
        scoring of new sequences does not. The fused loop scores with
        the TARGET net's unroll only: the online net's values over a new
        sequence are `step`'s, one env step at a time, under the same
        parameters, start state and resets (`runtime/anakin_r2d2.py`).
        """
        return _unroll(self, obs_seq, prev_action_seq, done_seq, h0, c0,
                       scope, time_major=False)

    def unroll_time_major(self, obs_seq, prev_action_seq, done_seq, h0, c0,
                          scope: str | None = None):
        """`unroll` over a `[T, B, ...]` sequence, as a `lax.scan` stacks
        a rollout: returns `[T, B, A]`. Same parameters, same sub-modules.

        What the fused loop's scoring pass calls
        (`R2D2Agent._td_error_time_major`, `runtime/anakin_r2d2.py`):
        the frames are flattened to `T*B` rows as they lie (the torso does
        not care about the order of its rows) and the recurrence, which
        runs over time, is entered with time outermost, so the frame batch
        is never transposed. The caller chooses the entry: the order of
        two leading axes cannot be read from their sizes.
        """
        return _unroll(self, obs_seq, prev_action_seq, done_seq, h0, c0,
                       scope, time_major=True)


def _unroll(net: R2D2Net, obs_seq, prev_action_seq, done_seq, h0, c0,
            scope: str | None, time_major: bool):
    """The body of both unrolls. Only the recurrence reads which of the
    two leading axes is time; everything around it runs row by row.
    A plain function: a method of the module would put a scope of its
    own into every op's name, and the profile's readers know the ops by
    `R2D2Net.unroll/...`."""
    lead = obs_seq.shape[:2]
    x = net._torso(obs_seq.reshape((lead[0] * lead[1],) + obs_seq.shape[2:]))
    x = x.reshape(lead + (-1,))
    a = net.action_embed(prev_action_seq)
    z = jnp.concatenate([x, a], axis=-1)
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        h_all, _ = net.cell.unroll(z, done_seq, h0, c0, time_major=time_major)
    return net._head(h_all)

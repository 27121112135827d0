"""LSTM cell and unroll strategies for the recurrent agents.

The reference uses TF1's `LSTMCell` + `dynamic_rnn` one step at a time
(`model/impala_actor_critic.py:18-25`, `model/r2d2_lstm.py:10-18`) and
unrolls sequences with Python loops that replicate the whole network per
timestep. Here:

- `LSTMCell` holds one fused `[x; h] @ W + b` gate projection (TF-style
  forget bias 1.0) and exposes `unroll` over a whole `[B, T]` sequence:
  the time-parallel input projection runs as one big MXU matmul, and the
  sequential recursion goes through `ops.lstm.lstm_scan` (or, for a
  caller that holds `[T, B]`, `lstm_scan_time_major`) — a `lax.scan`
  by default; the fused Pallas VMEM kernel (`ops/pallas/lstm.py`) is
  opt-in via DRL_LSTM_PALLAS=1 (its measured margin over the scan is
  not yet stable across artifacts — see ops/lstm.py).
- Stored-state training (IMPALA) needs **no unroll at all**: each timestep
  is seeded from the actor-recorded (h, c), so the learner applies the cell
  to a flattened `[B*T]` batch in one shot (see `agents/impala.py`).
- Sequential unrolls (R2D2) call `unroll` with done-masked state resets,
  replacing the reference's Python loop (`model/r2d2_lstm.py:67-112`).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.ops.lstm import (
    lstm_scan, lstm_scan_time_major)


class LSTMCell(nn.Module):
    """LSTM with the reference's fused gate projection and forget bias 1.0.

    The single parameter pair mirrors TF1's `LSTMCell`: one
    `[input+hidden, 4*hidden]` kernel over `[x; h]` plus a `[4*hidden]`
    bias. `unroll` splits the kernel into its input and recurrent halves
    so the input half runs time-parallel and only the recurrent half sits
    inside the sequential scan.
    """

    hidden_size: int
    dtype: jnp.dtype = jnp.float32
    backend: str = "auto"  # ops.pallas.resolve_backend: auto/pallas/reference

    @nn.compact
    def unroll(
        self,
        z_seq: jax.Array,  # [B, T, F]
        done_seq: jax.Array,  # [B, T] bool
        h: jax.Array,  # [B, hidden]
        c: jax.Array,
        backend: str | None = None,
        time_major: bool = False,
    ):
        """-> (h_all [B, T, hidden] pre-mask outputs, (hT, cT) masked carry).

        (h, c) are zeroed AFTER any step where done is set
        (`model/r2d2_lstm.py:78-80` semantics).

        `time_major`: `z_seq` `[T, B, F]`, `done_seq` `[T, B]`, `h_all`
        `[T, B, hidden]`, the recursion's own order with nothing swapped
        (`ops.lstm.lstm_scan_time_major`). The caller says which order it
        holds (shapes cannot: T and B may coincide): `R2D2Net.unroll_time_major`
        is the one that holds `[T, B]`.
        """
        feat = z_seq.shape[-1]
        hid = self.hidden_size
        kernel = self.param(
            "gates_kernel", nn.initializers.xavier_uniform(), (feat + hid, 4 * hid)
        )
        bias = self.param("gates_bias", nn.initializers.zeros_init(), (4 * hid,))
        xg = jnp.dot(z_seq.astype(self.dtype), kernel[:feat]) + bias
        keep = 1.0 - done_seq.astype(xg.dtype)
        scan = lstm_scan_time_major if time_major else lstm_scan
        return scan(
            xg, kernel[feat:], keep, h, c, backend=backend or self.backend
        )

    def __call__(self, x: jax.Array, h: jax.Array, c: jax.Array):
        """Single step on an `[N, F]` batch (act paths, stored-state IMPALA).

        One fused step is already a single XLA kernel — the Pallas path
        buys nothing at T=1, so this always takes the reference scan.
        """
        h_all, (new_h, new_c) = self.unroll(
            x[:, None, :],
            jnp.zeros(x.shape[:1] + (1,), bool),
            h,
            c,
            backend="reference",
        )
        del h_all  # == new_h (keep mask is all-ones at T=1)
        return new_h, new_c

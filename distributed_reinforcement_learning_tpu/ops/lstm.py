"""LSTM sequence recursion as a pure op, with a fused Pallas TPU path.

The recurrent models split one LSTM unroll into:

  (a) the input projection `xg = [x] @ Wx + b` for ALL timesteps — one big
      MXU matmul, embarrassingly parallel, left to XLA;
  (b) the sequential recursion over T carrying (h, c) with done-masking —
      this module.

The reference instead replicated the entire network per timestep in
Python graph-building loops (`/root/reference/model/r2d2_lstm.py:65-112`,
`model/impala_actor_critic.py:73-114`). Here (b) is a `lax.scan`
(reference backend, differentiable by autodiff) or a Pallas kernel pair
(`ops/pallas/lstm.py`) that keeps the carries in VMEM across a
time-gridded launch, wired up through `jax.custom_vjp` with a
hand-derived BPTT backward kernel.

The kernel is OPT-IN (`DRL_LSTM_PALLAS=1`, or backend="pallas"), not
auto: its margin over the XLA scan is not measured on the attached
chip, and the builders' earlier account had it within noise of the
scan. The kernel stays a documented, tested kernel
(`tests/test_pallas.py` keeps it numerically matched to the scan,
`tests/test_tpu_compile.py` keeps it compiling for the v5e); `auto`
resolves to the XLA scan. Under a multi-device mesh it does not lower
(its backward reduces dWh over the batch, so it is not wrapped by
`pallas.batch_partitioned` like the V-trace and attention kernels,
which are independent along the batch).

Gate math (TF1 `LSTMCell` parity, forget bias 1.0):

    i, f, g, o = split(gates, 4)
    c' = sigmoid(f + 1) * c + sigmoid(i) * tanh(g)
    h' = sigmoid(o) * tanh(c')

Done-masking: the carried (h, c) are zeroed AFTER the step at which
done[t] is set (`model/r2d2_lstm.py:78-80`); the emitted h_t is pre-mask.

Two entries, one recursion (the axis order is not observable from the
shapes, so WHO CALLS chooses):

`lstm_scan_time_major`, `[T, B, ...]`, is the recursion itself: a
`lax.scan` runs over the leading axis, so time-major is the order it
wants. Called by `LSTMCell.unroll(time_major=True)`, which the fused
R2D2 loop's scoring pass reaches through `R2D2Net.unroll_time_major`
(`runtime/anakin_r2d2.py`: the collect scan wrote the rollout that way).
    xg   [T, B, 4H]   input projection + bias
    wh   [H, 4H]      recurrent weights
    keep [T, B]       1.0 - done
    h0/c0 [B, H]      sequence-start stored state (`agent/r2d2.py:110-111`)
Returns (h_all [T, B, H], (hT [B, H], cT [B, H])).

`lstm_scan`, `[B, T, ...]`, is the batch-major adapter around it (two
`swapaxes` in, one out), matching the models: everyone who holds a
`[B, T]` batch calls it (`LSTMCell.unroll` / `__call__`: the learn steps,
the act steps, IMPALA, the host learners).
    xg [B, T, 4H], keep [B, T] -> h_all [B, T, H], same carries.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.ops.pallas import resolve_backend


def lstm_step(gates: jax.Array, c: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One cell update from pre-activation gates. Shared by every backend."""
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    new_c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    new_h = jax.nn.sigmoid(o) * jnp.tanh(new_c)
    return new_h, new_c


def _scan_reference(xg_tm, wh, keep_tm, h0, c0):
    """Time-major lax.scan recursion; autodiff provides its gradient."""

    def body(carry, xs):
        h, c = carry
        xg_t, keep_t = xs
        gates = xg_t + jnp.dot(h, wh)
        new_h, new_c = lstm_step(gates, c)
        k = keep_t[:, None]
        return (new_h * k, new_c * k), new_h

    (hT, cT), h_all = jax.lax.scan(body, (h0, c0), (xg_tm, keep_tm))
    return h_all, (hT, cT)


def lstm_scan_time_major(
    xg_tm: jax.Array,
    wh: jax.Array,
    keep_tm: jax.Array,
    h0: jax.Array,
    c0: jax.Array,
    backend: str = "auto",
):
    """Run the recursion over `[T, B, ...]`; see module docstring."""
    backend = resolve_backend(backend, opt_in_env="DRL_LSTM_PALLAS")
    keep_tm = keep_tm.astype(xg_tm.dtype)
    if backend == "reference":
        return _scan_reference(xg_tm, wh, keep_tm, h0, c0)
    from distributed_reinforcement_learning_tpu.ops.pallas.lstm import lstm_pallas

    h_all_tm, hT, cT = lstm_pallas(
        xg_tm, wh, keep_tm[..., None], h0, c0,
        interpret=(backend == "pallas_interpret"),
    )
    return h_all_tm, (hT, cT)


def lstm_scan(
    xg: jax.Array,
    wh: jax.Array,
    keep: jax.Array,
    h0: jax.Array,
    c0: jax.Array,
    backend: str = "auto",
):
    """The batch-major adapter: `[B, T, ...]` in and out around
    `lstm_scan_time_major`; see module docstring."""
    h_all_tm, carry = lstm_scan_time_major(
        jnp.swapaxes(xg, 0, 1), wh, jnp.swapaxes(keep, 0, 1), h0, c0, backend)
    return jnp.swapaxes(h_all_tm, 0, 1), carry

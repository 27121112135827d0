"""V-trace off-policy corrected returns and IMPALA losses.

TPU-native re-design of the reference's V-trace module
(`/root/reference/optimizer/vtrace.py:3-126`): the reference builds a TF1
graph with a serialized `tf.scan(parallel_iterations=1)`; here the
backward recursion is a `jax.lax.scan(reverse=True)` over time with the
delta computation fused in front of it, all inside one XLA compilation.

Conventions:
- Two layouts, one recursion. `split_data` / `from_softmax` take
  `[B, T, ...]` like the reference (`optimizer/vtrace.py:29-44`);
  `split_time_major` / `from_softmax_time_major` take `[T, B, ...]`, the
  order a rollout scan writes and `from_importance_weights` runs in.
- Loss reductions are **sums** over batch and time, matching the reference
  (`optimizer/vtrace.py:105-126`); IMPALA's gradient-clip/LR settings were
  tuned against sum-reduced losses.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.observability import scopes


class VTraceReturns(NamedTuple):
    """Outputs of the V-trace recursion (both stop-gradiented)."""

    vs: jax.Array  # V-trace value targets, same shape as `values`.
    clipped_rhos: jax.Array  # min(rho_bar, pi/mu), the pg-advantage weights.


def split_data(x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Time-shifted first/middle/last views of a `[B, T, ...]` tensor.

    Mirrors `optimizer/vtrace.py:3-14`: given a T-step unroll, returns the
    three `[B, T-2, ...]` slices `x[:, :-2]`, `x[:, 1:-1]`, `x[:, 2:]` used
    to form (s_t, s_{t+1}, s_{t+2}) aligned views for the double V-trace
    pass in the IMPALA loss.
    """
    return x[:, :-2], x[:, 1:-1], x[:, 2:]


def split_time_major(x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """`split_data` for a `[T, B, ...]` tensor: three `[T-2, B, ...]` views."""
    return x[:-2], x[1:-1], x[2:]


def action_log_probs(policy_probs: jax.Array, actions: jax.Array, eps: float = 0.0) -> jax.Array:
    """log pi(a_t | x_t) from softmax probabilities and taken actions.

    Parity with `optimizer/vtrace.py:16-27` (one-hot gather + log). `eps`
    guards the log for callers that need it; the rho computation uses
    eps=0 like the reference, the pg loss uses 1e-8
    (`optimizer/vtrace.py:109`).
    """
    taken = jnp.take_along_axis(policy_probs, actions[..., None].astype(jnp.int32), axis=-1)
    return jnp.log(taken[..., 0] + eps)


def from_importance_weights(
    log_rhos: jax.Array,
    discounts: jax.Array,
    rewards: jax.Array,
    values: jax.Array,
    bootstrap_value: jax.Array,
    clip_rho_threshold: float | None = 1.0,
    clip_c_threshold: float = 1.0,
    backend: str = "auto",
) -> VTraceReturns:
    """Time-major V-trace core: `[T, B]` inputs, `[T, B]` outputs.

    Implements the recursion of `optimizer/vtrace.py:71-103`:
        delta_t = clipped_rho_t * (r_t + gamma_t * V(x_{t+1}) - V(x_t))
        vs_t - V(x_t) = delta_t + gamma_t * c_t * (vs_{t+1} - V(x_{t+1}))
    computed with a reverse `lax.scan` (the reference serializes a TF scan
    with `parallel_iterations=1, back_prop=False`; here XLA compiles the
    whole thing and `stop_gradient` replaces `back_prop=False`).

    `backend="auto"` resolves to the fused Pallas kernel on TPU
    (`ops/pallas/vtrace.py`): the kernel runs the whole reverse
    recursion in one VMEM-resident launch, where this lax.scan's T
    while-loop iterations each round-trip their carries through HBM.
    Its margin over this scan is not measured on the attached chip
    (the benchmark's IMPALA cell runs the kernel only); chip_smoke.py
    asserts that the compiled learn step really holds it.
    """
    from distributed_reinforcement_learning_tpu.ops.pallas import resolve_backend

    resolved = resolve_backend(backend)
    if resolved != "reference" and _kernel_fits(*log_rhos.shape):
        from distributed_reinforcement_learning_tpu.ops.pallas.vtrace import vtrace_pallas

        # The whole V-trace target is stop-gradded (the reference's
        # `back_prop=False`), so cut the tape at the kernel's INPUTS too:
        # pallas_call has no jvp rule, and linearization would otherwise
        # fail inside value_and_grad even though no cotangent ever flows.
        sg = jax.lax.stop_gradient
        vs, clipped = vtrace_pallas(
            sg(log_rhos), sg(discounts), sg(rewards), sg(values), sg(bootstrap_value),
            clip_rho_threshold=clip_rho_threshold,
            clip_c_threshold=clip_c_threshold,
            interpret=(resolved == "pallas_interpret"),
        )
        return VTraceReturns(
            vs=jax.lax.stop_gradient(vs),
            clipped_rhos=jax.lax.stop_gradient(clipped),
        )
    rhos = jnp.exp(log_rhos)
    if clip_rho_threshold is not None:
        clipped_rhos = jnp.minimum(clip_rho_threshold, rhos)
    else:
        clipped_rhos = rhos
    cs = jnp.minimum(clip_c_threshold, rhos)

    values_t_plus_1 = jnp.concatenate([values[1:], bootstrap_value[None]], axis=0)
    deltas = clipped_rhos * (rewards + discounts * values_t_plus_1 - values)

    def body(acc, xs):
        discount_t, c_t, delta_t = xs
        acc = delta_t + discount_t * c_t * acc
        return acc, acc

    _, vs_minus_v = jax.lax.scan(
        body,
        jnp.zeros_like(bootstrap_value),
        (discounts, cs, deltas),
        reverse=True,
    )
    vs = vs_minus_v + values
    return VTraceReturns(
        vs=jax.lax.stop_gradient(vs),
        clipped_rhos=jax.lax.stop_gradient(clipped_rhos),
    )


@jax.named_scope(scopes.VTRACE)
def from_softmax(
    behavior_policy: jax.Array,
    target_policy: jax.Array,
    actions: jax.Array,
    discounts: jax.Array,
    rewards: jax.Array,
    values: jax.Array,
    next_values: jax.Array,
    clip_rho_threshold: float | None = 1.0,
    backend: str = "auto",
) -> VTraceReturns:
    """Batch-major V-trace from behavior/target softmax probabilities.

    Parity with `optimizer/vtrace.py:29-69`: inputs `[B, T, A]` policies and
    `[B, T]` trajectories; `next_values[:, -1]` supplies the bootstrap value.
    Returns `[B, T]` vs and clipped rhos. Swaps four `[B, T]` arrays to
    `[T, B]` for the recursion and two back: a caller that holds
    time-major data calls `from_softmax_time_major` instead.
    """
    log_rhos = action_log_probs(target_policy, actions) - action_log_probs(behavior_policy, actions)
    # Transpose to time-major for the scan, back to batch-major after.
    tm = lambda x: jnp.swapaxes(x, 0, 1)
    out = from_importance_weights(
        log_rhos=tm(log_rhos),
        discounts=tm(discounts),
        rewards=tm(rewards),
        values=tm(values),
        bootstrap_value=next_values[:, -1],
        clip_rho_threshold=clip_rho_threshold,
        backend=backend,
    )
    return VTraceReturns(vs=tm(out.vs), clipped_rhos=tm(out.clipped_rhos))


@jax.named_scope(scopes.VTRACE)
def from_softmax_time_major(
    behavior_policy: jax.Array,
    target_policy: jax.Array,
    actions: jax.Array,
    discounts: jax.Array,
    rewards: jax.Array,
    values: jax.Array,
    next_values: jax.Array,
    clip_rho_threshold: float | None = 1.0,
    backend: str = "auto",
) -> VTraceReturns:
    """`from_softmax` for `[T, B, A]` policies and `[T, B]` trajectories.

    `[T, B]` vs and clipped rhos out, nothing transposed on the way;
    `next_values[-1]` supplies the bootstrap value.
    """
    return from_importance_weights(
        log_rhos=action_log_probs(target_policy, actions) - action_log_probs(behavior_policy, actions),
        discounts=discounts,
        rewards=rewards,
        values=values,
        bootstrap_value=next_values[-1],
        clip_rho_threshold=clip_rho_threshold,
        backend=backend,
    )


def policy_gradient_loss(
    policy_probs: jax.Array, actions: jax.Array, advantages: jax.Array
) -> jax.Array:
    """-sum_t log pi(a_t|x_t) * adv_t, summed over batch and time.

    Parity with `optimizer/vtrace.py:105-112` (log has a 1e-8 guard there).
    """
    log_prob = action_log_probs(policy_probs, actions, eps=1e-8)
    return -jnp.sum(log_prob * jax.lax.stop_gradient(advantages))


def baseline_loss(vs: jax.Array, values: jax.Array) -> jax.Array:
    """0.5 * sum (stop_grad(vs) - V)^2, per `optimizer/vtrace.py:114-118`."""
    return 0.5 * jnp.sum(jnp.square(jax.lax.stop_gradient(vs) - values))


def entropy_loss(policy_probs: jax.Array) -> jax.Array:
    """Negative total entropy: sum_{b,t,a} p log p.

    Parity with `optimizer/vtrace.py:120-126` — the reference returns
    `-sum(-p*log(p))`, i.e. a *negative* quantity added to the loss with a
    positive coefficient, which acts as an entropy bonus. Uses the
    `p > 0 ? p*log(p) : 0` form so exact-zero probabilities contribute 0
    instead of NaN (the reference would NaN there).
    """
    plogp = jnp.where(policy_probs > 0, policy_probs * jnp.log(jnp.where(policy_probs > 0, policy_probs, 1.0)), 0.0)
    return jnp.sum(plogp)


def _kernel_fits(T: int, B: int) -> bool:
    """Whether the Pallas kernel's working set at `[T, B]` fits the 16 MiB
    of scoped VMEM: it unrolls T and holds about nine `[T, block]` float32
    arrays (four inputs, two outputs, `next_values`, `deltas`, `cs`), a
    column padded to the 128 lanes of a tile and a block of at most 256
    columns (the compiler's own count at [8190, 8]: 35.78 MB). Where it
    does not (an episode of thousands of steps), `from_importance_weights`
    takes its scan. Defined BELOW the call sites: a line shifted above
    them changes the kernel's serialized body (its call stack's lines) and
    with it every compile-cache key of the IMPALA cells."""
    return 9 * T * min(max(B, 128), 256) * 4 <= 16 * 2**20

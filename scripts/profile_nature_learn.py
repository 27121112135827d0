"""Decompose the Nature-CNN B=32 learn step.

A roofline says how far the flagship learn step is from its own
attainable time, not WHERE the rest goes. This script measures the
step's components independently on the device (lax.scan of K
data-dependently-coupled iterations, two-window marginal, completion
forced by materializing the carry), and
reports a breakdown that must sum to the measured step within ~10%.
Not measured on the attached chip yet:

  fwd        stored-state forward (conv tower + embed + LSTM cell + heads)
  conv       the NatureConv tower alone on the flat [B*T] frames
  post       everything after the forward (V-trace x2, reductions)
  grad       jax.grad of the full loss (fwd + bwd)
  opt        RMSProp transform + param update alone
  learn      the full learn step (grad + opt), scan-timed

Prints one JSON line (device named) and writes it to
chiprun_out/nature_cnn_profile.json.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent, ImpalaConfig
    from distributed_reinforcement_learning_tpu.models.impala_net import apply_stored_state
    from distributed_reinforcement_learning_tpu.models.torso import NatureConv
    from distributed_reinforcement_learning_tpu.ops import vtrace
    from distributed_reinforcement_learning_tpu.utils.device import (
        enable_compile_cache, open_devices)
    from distributed_reinforcement_learning_tpu.utils.synthetic import synthetic_impala_batch

    enable_compile_cache()
    device = open_devices("profile")
    B = int(sys.argv[1]) if len(sys.argv) > 1 else 32
    K = 16
    cfg = ImpalaConfig(dtype=jnp.bfloat16)
    agent = ImpalaAgent(cfg)
    state = agent.init_state(jax.random.PRNGKey(0))
    batch = jax.device_put(jax.tree.map(
        jnp.asarray, synthetic_impala_batch(
            B, cfg.trajectory, cfg.obs_shape, cfg.num_actions, cfg.lstm_size,
            uniform_behavior=False)))
    # Pre-normalized float frames so a scalar carry can be mixed in
    # (same math the model sees after _prep_obs).
    obs_f = batch.state.astype(jnp.float32) / 255.0

    def timed(name, fn, reps=5):
        """Two-window marginal over K-vs-2K scans of `fn` (carry-coupled)."""
        def scan_of(n):
            return jax.jit(
                lambda c: lax.scan(lambda c, _: (fn(c), None), c, None,
                                   length=n)[0])
        f1, f2 = scan_of(K), scan_of(2 * K)
        c0 = jnp.float32(1e-6)
        float(np.asarray(f1(c0)))  # compile + warm
        float(np.asarray(f2(c0)))
        samples = []
        for _ in range(reps):
            t0 = time.perf_counter()
            float(np.asarray(f1(c0)))
            t1 = time.perf_counter() - t0
            t0 = time.perf_counter()
            float(np.asarray(f2(c0)))
            t2 = time.perf_counter() - t0
            samples.append((t2 - t1) / K)
        ms = 1e3 * float(np.median(samples))
        iqr = float(np.subtract(*np.percentile(samples, [75, 25])))
        print(f"[profile] {name}: {ms:.3f} ms (iqr {1e3*iqr:.3f})",
              file=sys.stderr, flush=True)
        return round(ms, 4)

    params = state.params

    # Forward: the one [B*T] stored-state pass.
    def fwd(c):
        policy, value = apply_stored_state(
            agent.model, params, obs_f + c, batch.previous_action,
            batch.initial_h, batch.initial_c)
        return (policy.mean() + value.mean()).astype(jnp.float32)

    # Conv tower alone, flat [B*T, 84, 84, 4] (own params, same shapes).
    conv_mod = NatureConv(dtype=cfg.dtype)
    flat = obs_f.reshape((-1,) + tuple(cfg.obs_shape))
    conv_params = conv_mod.init(jax.random.PRNGKey(1), flat[:1])

    def conv(c):
        return conv_mod.apply(conv_params, flat + c).mean().astype(jnp.float32)

    # Post-forward: V-trace x2 + losses from fixed (policy, value).
    policy0, value0 = jax.jit(lambda: apply_stored_state(
        agent.model, params, obs_f, batch.previous_action,
        batch.initial_h, batch.initial_c))()

    def post(c):
        policy, value = policy0 + c, value0 + c
        clipped_r = jnp.clip(batch.reward, -1.0, 1.0)
        discounts = (~batch.done).astype(jnp.float32) * cfg.discount_factor
        first_p, middle_p, _ = vtrace.split_data(policy)
        first_v, middle_v, last_v = vtrace.split_data(value)
        first_a, middle_a, _ = vtrace.split_data(batch.action)
        first_r, middle_r, _ = vtrace.split_data(clipped_r)
        first_d, middle_d, _ = vtrace.split_data(discounts)
        first_b, middle_b, _ = vtrace.split_data(batch.behavior_policy)
        vs, rho = vtrace.from_softmax(
            behavior_policy=first_b, target_policy=first_p, actions=first_a,
            discounts=first_d, rewards=first_r, values=first_v,
            next_values=middle_v)
        vs1, _ = vtrace.from_softmax(
            behavior_policy=middle_b, target_policy=middle_p, actions=middle_a,
            discounts=middle_d, rewards=middle_r, values=middle_v,
            next_values=last_v)
        adv = lax.stop_gradient(rho * (first_r + first_d * vs1 - first_v))
        total = (vtrace.policy_gradient_loss(first_p, first_a, adv)
                 + cfg.baseline_loss_coef * vtrace.baseline_loss(vs, first_v)
                 + cfg.entropy_coef * vtrace.entropy_loss(first_p))
        return total.astype(jnp.float32)

    # Loss on a carry-shifted batch (fwd + post in one program).
    def loss(c):
        shifted = batch._replace(state=obs_f + c)
        total, _ = agent._loss(params, shifted)
        return total.astype(jnp.float32)

    # fwd + bwd.
    def grad(c):
        g, _ = jax.grad(agent._loss, has_aux=True)(
            params, batch._replace(state=obs_f + c))
        leaves = jax.tree.leaves(g)
        return sum(l.sum() for l in leaves).astype(jnp.float32) * 0 + leaves[0].mean().astype(jnp.float32)

    # Optimizer transform alone on fixed grads.
    grads0 = jax.jit(lambda: jax.grad(agent._loss, has_aux=True)(
        params, batch)[0])()

    def opt(c):
        g = jax.tree.map(lambda x: x * (1.0 + c * 1e-9), grads0)
        updates, _ = agent.tx.update(g, state.opt_state, params)
        return jax.tree.leaves(updates)[0].mean().astype(jnp.float32)

    results = {"B": B, "K": K, "dtype": "bfloat16"}
    for name, fn in [("conv", conv), ("fwd", fwd), ("post", post),
                     ("loss", loss), ("grad", grad), ("opt", opt)]:
        results[f"{name}_ms"] = timed(name, fn)

    # Full learn step, scan-timed with the real state carry (the honest
    # device time).
    def learn_scan(n):
        return jax.jit(lambda s: lax.scan(
            lambda s, _: (agent._learn(s, batch)[0], None), s, None,
            length=n)[0])
    l1, l2 = learn_scan(K), learn_scan(2 * K)
    s1 = l1(state)
    float(np.asarray(s1.step))
    s2 = l2(state)
    float(np.asarray(s2.step))
    samples = []
    for _ in range(5):
        t0 = time.perf_counter()
        float(np.asarray(l1(state).step))
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(np.asarray(l2(state).step))
        t2 = time.perf_counter() - t0
        samples.append((t2 - t1) / K)
    results["learn_ms"] = round(1e3 * float(np.median(samples)), 4)

    results["bwd_ms_derived"] = round(results["grad_ms"] - results["fwd_ms"], 4)
    results["sum_grad_opt_ms"] = round(results["grad_ms"] + results["opt_ms"], 4)
    results["sum_over_learn"] = round(
        results["sum_grad_opt_ms"] / results["learn_ms"], 3)
    results["fwd_minus_conv_ms"] = round(
        results["fwd_ms"] - results["conv_ms"], 4)
    results["loss_minus_fwd_ms"] = round(
        results["loss_ms"] - results["fwd_ms"], 4)

    results["device"] = device
    out = Path("chiprun_out")
    out.mkdir(parents=True, exist_ok=True)
    (out / "nature_cnn_profile.json").write_text(json.dumps(results, indent=2))
    print(json.dumps(results))


if __name__ == "__main__":
    main()

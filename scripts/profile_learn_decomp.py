"""Decompose the IMPALA learn step's pure device time by sub-module.

Each variant is repeated K times INSIDE one jit via `lax.scan`, with the
obs tensor threaded through the carry (a 1-byte in-place bump per
iteration) so XLA cannot hoist loop-invariant compute out of the loop.
Per-iteration time = (t(K2) - t(K1)) / (K2 - K1), median of R repeats,
so the per-dispatch constant cancels.

    python scripts/profile_learn_decomp.py [B]
"""
from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent, ImpalaConfig
from distributed_reinforcement_learning_tpu.models.torso import NatureConv
from distributed_reinforcement_learning_tpu.utils.device import enable_compile_cache
from distributed_reinforcement_learning_tpu.utils.synthetic import synthetic_impala_batch

K1, K2, REPS = 8, 32, 3


def bump(obs):
    """In-place-able 1-element perturbation keeping obs loop-variant."""
    return obs.at[(0,) * obs.ndim].add(1)


def timeit(make_step, carry0, label):
    """make_step: carry -> carry (all device work inside)."""

    def runner(k):
        def body(c, _):
            return make_step(c), None

        f = jax.jit(functools.partial(lambda c0, k: jax.lax.scan(body, c0, None, length=k)[0], k=k))
        f(carry0)  # compile
        times = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            out = f(carry0)
            # completion barrier: one host scalar
            jax.tree.leaves(out)[0].block_until_ready()
            float(jnp.asarray(jax.tree.leaves(out)[0]).ravel()[0].astype(jnp.float32))
            times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2]

    per = (runner(K2) - runner(K1)) / (K2 - K1)
    print(f"{label:32s}: {1e3 * per:8.3f} ms")
    return per


def main():
    enable_compile_cache()
    B = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    dtype = jnp.bfloat16
    cfg = ImpalaConfig(dtype=dtype)
    agent = ImpalaAgent(cfg)
    state = agent.init_state(jax.random.PRNGKey(0))
    batch = jax.device_put(jax.tree.map(jnp.asarray, synthetic_impala_batch(
        B, cfg.trajectory, cfg.obs_shape, cfg.num_actions, cfg.lstm_size,
        uniform_behavior=False)))
    N = B * cfg.trajectory
    d = jax.devices()[0]
    print(f"platform={d.platform} kind={d.device_kind} B={B} N={N} dtype={dtype.__name__}")

    # A. full learn step
    def learn_step(c):
        st, obs = c
        st, _ = agent.learn(st, batch._replace(state=obs))
        return st, bump(obs)
    t_full = timeit(learn_step, (state, batch.state), "A full learn step")
    print(f"{'':34s}-> {N / t_full:,.0f} frames/s")

    params = state.params

    # B. grad only (no optimizer)
    def grad_step(c):
        p, obs = c
        g = jax.grad(lambda pp: agent._loss(pp, batch._replace(state=obs))[0])(p)
        return g, bump(obs)
    timeit(grad_step, (params, batch.state), "B loss grad (no opt)")

    # C. loss forward only
    def loss_step(c):
        acc, obs = c
        l, _ = agent._loss(params, batch._replace(state=obs))
        return acc + l, bump(obs)
    timeit(loss_step, (jnp.float32(0), batch.state), "C loss forward only")

    # D. model forward only (no vtrace): stored-state apply, sum outputs
    from distributed_reinforcement_learning_tpu.models.impala_net import apply_stored_state
    from distributed_reinforcement_learning_tpu.agents import common

    def model_fwd(p, obs):
        pol, val = apply_stored_state(
            agent.model, p, common.normalize_obs(obs, dtype),
            batch.previous_action, batch.initial_h, batch.initial_c)
        return pol.sum() + val.sum()

    def modelf_step(c):
        acc, obs = c
        return acc + model_fwd(params, obs), bump(obs)
    timeit(modelf_step, (jnp.float32(0), batch.state), "D model fwd (no vtrace)")

    # E. model fwd+bwd (no vtrace)
    def modelg_step(c):
        p, obs = c
        g = jax.grad(model_fwd)(p, obs)
        return g, bump(obs)
    timeit(modelg_step, (params, batch.state), "E model fwd+bwd (no vtrace)")

    # F. conv torso only fwd
    conv = NatureConv(dtype=dtype)
    flat_obs = batch.state.reshape(N, *cfg.obs_shape)
    cparams = conv.init(jax.random.PRNGKey(0), jnp.zeros((1, *cfg.obs_shape), dtype))

    def conv_fwd(p, obs):
        return conv.apply(p, common.normalize_obs(obs, dtype)).astype(jnp.float32).sum()

    def convf_step(c):
        acc, obs = c
        return acc + conv_fwd(cparams, obs), bump(obs)
    timeit(convf_step, (jnp.float32(0), flat_obs), "F conv fwd (+normalize)")

    # G. conv torso fwd+bwd
    def convg_step(c):
        p, obs = c
        g = jax.grad(conv_fwd)(p, obs)
        return g, bump(obs)
    timeit(convg_step, (cparams, flat_obs), "G conv fwd+bwd (+normalize)")

    # H. normalize_obs alone
    def norm_step(c):
        acc, obs = c
        return acc + common.normalize_obs(obs, dtype).astype(jnp.float32).sum(), bump(obs)
    timeit(norm_step, (jnp.float32(0), flat_obs), "H normalize_obs alone")

    # I. vtrace both views fwd+bwd wrt (policy, value)
    from distributed_reinforcement_learning_tpu.ops import vtrace as V

    pol0 = jnp.asarray(batch.behavior_policy)
    val0 = jnp.zeros((B, cfg.trajectory), jnp.float32)

    def vt_loss(pol, val, obs_scalar):
        pol = pol + obs_scalar  # keep loop-variant
        clipped_r = common.clip_rewards(batch.reward, cfg.reward_clipping)
        discounts = (~batch.done).astype(jnp.float32) * cfg.discount_factor
        fp, mp, _ = V.split_data(pol)
        fv, mv, lv = V.split_data(val)
        fa, ma, _ = V.split_data(batch.action)
        fr, mr, _ = V.split_data(clipped_r)
        fd, md, _ = V.split_data(discounts)
        fb, mb, _ = V.split_data(jnp.asarray(batch.behavior_policy))
        vs, rho = V.from_softmax(behavior_policy=fb, target_policy=fp, actions=fa,
                                 discounts=fd, rewards=fr, values=fv, next_values=mv)
        vs1, _ = V.from_softmax(behavior_policy=mb, target_policy=mp, actions=ma,
                                discounts=md, rewards=mr, values=mv, next_values=lv)
        adv = jax.lax.stop_gradient(rho * (fr + fd * vs1 - fv))
        return (V.policy_gradient_loss(fp, fa, adv) + V.baseline_loss(vs, fv)
                + cfg.entropy_coef * V.entropy_loss(fp))

    def vt_step(c):
        acc, obs = c
        scalar = obs[(0,) * obs.ndim].astype(jnp.float32) * 1e-9
        g = jax.grad(vt_loss, argnums=(0, 1))(pol0, val0, scalar)
        return acc + g[1].sum(), bump(obs)
    timeit(vt_step, (jnp.float32(0), batch.state), "I vtrace 2 views fwd+bwd")


if __name__ == "__main__":
    main()

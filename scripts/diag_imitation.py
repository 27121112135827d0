"""Diagnostic: can the IMPALA net FIT a competent Breakout policy at all?

The 50M-frame Anakin run learned a state-INDEPENDENT policy (constant
[0.14, 0.44, 0.21, 0.21] across wildly different frames — the conv
torso contributes nothing to the action choice, only the action
marginal was learned). Before touching RL hyperparameters, this script
answers the structural question: given the exact observation pipeline
(`envs/breakout_jax.py` 84x84x4 uint8 stacks) and the exact model
(`models/impala_net.py`, stored-state LSTM path), can supervised
cross-entropy on a scripted expert's actions reach high accuracy?

- accuracy >> chance: the representation path is fine; the plateau is
  an RL-signal problem (exploration, credit assignment, scale).
- accuracy ~ chance: the obs/model path destroys the information.

The expert is the ball tracker from `tests/test_envs.py` re-expressed
on the jittable state (FIRE when the ball is dead, else steer the
paddle center toward the ball), which scores ~420 vs random ~14 on the
sim core (5-episode means, frameskip 4).

Usage: python scripts/diag_imitation.py [--steps 300] [--platform cpu]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--platform", default=None)
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--envs", type=int, default=64)
    p.add_argument("--rollout", type=int, default=256, help="steps of expert rollout")
    p.add_argument("--batch", type=int, default=512)
    p.add_argument("--lr", type=float, default=1e-3)
    args = p.parse_args()
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent, ImpalaConfig
    from distributed_reinforcement_learning_tpu.envs import breakout_jax as bj

    def tracker_actions(state) -> jax.Array:
        center = state.paddle_x + 8.0
        steer = jnp.where(state.ball_x > center + 2.0, 2,
                          jnp.where(state.ball_x < center - 2.0, 3, 0))
        return jnp.where(state.ball_dead, 1, steer).astype(jnp.int32)

    @jax.jit
    def expert_step(carry, _):
        est, rng = carry
        rng, k = jax.random.split(rng)
        a = tracker_actions(est)
        est, obs, r, d, er = bj.step(est, a, k)
        return (est, rng), (obs, a, d)

    rng = jax.random.PRNGKey(0)
    est, obs0 = bj.reset(rng, args.envs)
    (est, rng), (obs_t, act_t, done_t) = jax.lax.scan(
        expert_step, (est, rng), None, length=args.rollout)
    # [T, B, ...] -> flat [T*B, ...]; drop the first obs offset subtlety:
    # obs_t[t] is the observation AFTER action act_t[t]. The policy maps
    # obs -> next action, so pair obs_t[t] with act_t[t+1].
    X = np.asarray(obs_t[:-1]).reshape(-1, 84, 84, 4)
    Y = np.asarray(act_t[1:]).reshape(-1)
    print(f"dataset {X.shape[0]} pairs; action marginal "
          f"{np.bincount(Y, minlength=4) / len(Y)}", file=sys.stderr)

    cfg = ImpalaConfig(obs_shape=bj.OBS_SHAPE, num_actions=4, trajectory=20,
                       lstm_size=256, dtype=jnp.float32)
    agent = ImpalaAgent(cfg)
    params = agent.init_state(jax.random.PRNGKey(1)).params
    tx = optax.adam(args.lr)
    opt = tx.init(params)
    h0, c0 = agent.initial_lstm_state(args.batch)
    pa0 = jnp.zeros(args.batch, jnp.int32)

    def loss_fn(params, xb, yb):
        out = agent.model.apply(params, agent._prep_obs(xb), pa0, h0, c0)
        logp = jnp.log(out.policy + 1e-20)
        ce = -jnp.take_along_axis(logp, yb[:, None], axis=-1).mean()
        acc = (jnp.argmax(out.policy, -1) == yb).mean()
        return ce, acc

    @jax.jit
    def train_step(params, opt, xb, yb):
        (ce, acc), g = jax.value_and_grad(loss_fn, has_aux=True)(params, xb, yb)
        up, opt = tx.update(g, opt, params)
        params = jax.tree.map(lambda p, u: p + u, params, up)
        return params, opt, ce, acc

    nrng = np.random.default_rng(0)
    t0 = time.time()
    for i in range(args.steps):
        idx = nrng.integers(0, len(X), args.batch)
        params, opt, ce, acc = train_step(params, opt, jnp.asarray(X[idx]),
                                          jnp.asarray(Y[idx]))
        if i % 50 == 0 or i == args.steps - 1:
            print(f"step {i}: ce={float(ce):.4f} acc={float(acc):.3f}",
                  file=sys.stderr)
    marginal_acc = float(np.bincount(Y, minlength=4).max() / len(Y))
    print(json.dumps({
        "final_acc": round(float(acc), 4),
        "marginal_acc": round(marginal_acc, 4),
        "steps": args.steps,
        "pairs": int(X.shape[0]),
        "seconds": round(time.time() - t0, 1),
    }))


if __name__ == "__main__":
    main()

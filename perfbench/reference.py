"""Plain float32 numpy re-computation of the two families' losses.

The comparison that decides `correct` holds the program's loss on one
seeded batch against these functions, in set-up and outside the window.
They take the NETWORK'S OWN OUTPUTS (policy and value, or the online and
target Q-values) and redo everything after them: so a learn step that
dropped a V-trace pass, clipped differently, skipped the value
rescaling or computed the targets in a lower precision fails, while the
network's matmul precision on the chip (the same in both) does not
enter. Written from the published equations; no JAX, no kernels.

IMPALA (Espeholt et al. 2018, eq. 1 and sec. 4.2, with the reference
implementation's double pass over the first / middle / last views of a
T-step unroll and its sum-reduced losses):
    rho_t = min(1, pi/mu), c_t = min(1, pi/mu)
    delta_t = rho_t (r_t + g_t V(x_{t+1}) - V(x_t))
    vs_t - V(x_t) = delta_t + g_t c_t (vs_{t+1} - V(x_{t+1}))
R2D2 (Kapturowski et al. 2019, sec. 2.3 and table 2; 1-step double-Q
targets as this program computes them, value rescaling h of Pohlen et
al. 2018 with eps 1e-3, priorities eta max|d| + (1 - eta) mean|d|).
"""

from __future__ import annotations

import numpy as np

F = np.float32


def _vtrace(behavior, target, actions, discounts, rewards, values, next_values):
    """Batch-major `[B, T]` V-trace targets and clipped rhos."""
    idx = actions[..., None].astype(np.int64)
    pi = np.take_along_axis(target, idx, -1)[..., 0]
    mu = np.take_along_axis(behavior, idx, -1)[..., 0]
    rhos = np.exp(np.log(pi) - np.log(mu)).astype(F)
    clipped = np.minimum(F(1), rhos)
    cs = np.minimum(F(1), rhos)
    deltas = clipped * (rewards + discounts * next_values - values)
    acc = np.zeros_like(values[:, 0])
    vs_minus_v = np.zeros_like(values)
    for t in reversed(range(values.shape[1])):
        acc = deltas[:, t] + discounts[:, t] * cs[:, t] * acc
        vs_minus_v[:, t] = acc
    return (vs_minus_v + values).astype(F), clipped.astype(F)


def impala_losses(policy, value, batch: dict, *, discount: float,
                  baseline_coef: float, entropy_coef: float,
                  reward_clipping: str = "abs_one") -> dict:
    """`policy [B,T,A]`, `value [B,T]` from the network; `batch` holds
    numpy `reward, action, done, behavior_policy`. -> the three loss
    terms and their weighted total, as `agents/impala.py` logs them
    (and `pi_scale`, for the comparison's tolerance)."""
    policy, value = policy.astype(F), value.astype(F)
    reward = batch["reward"].astype(F)
    if reward_clipping == "abs_one":
        reward = np.clip(reward, -1, 1)
    elif reward_clipping != "none":
        raise ValueError(f"reward clipping {reward_clipping!r} not in the reference")
    disc = (~batch["done"].astype(bool)).astype(F) * F(discount)
    first = lambda x: x[:, :-2]
    middle = lambda x: x[:, 1:-1]
    last = lambda x: x[:, 2:]
    act, beh = batch["action"], batch["behavior_policy"].astype(F)
    vs, rho = _vtrace(first(beh), first(policy), first(act), first(disc),
                      first(reward), first(value), middle(value))
    vs1, _ = _vtrace(middle(beh), middle(policy), middle(act), middle(disc),
                     middle(reward), middle(value), last(value))
    adv = rho * (first(reward) + first(disc) * vs1 - first(value))
    idx = first(act)[..., None].astype(np.int64)
    logp = np.log(np.take_along_axis(first(policy), idx, -1)[..., 0] + F(1e-8))
    pi_loss = -np.sum(logp * adv, dtype=np.float64)
    pi_scale = np.sum(np.abs(logp * adv), dtype=np.float64)
    v_loss = 0.5 * np.sum(np.square(vs - first(value)), dtype=np.float64)
    p = first(policy)
    ent = np.sum(np.where(p > 0, p * np.log(np.where(p > 0, p, 1)), 0),
                 dtype=np.float64)
    total = pi_loss + baseline_coef * v_loss + entropy_coef * ent
    # `pi_scale`: the summed magnitude of the policy-gradient terms, which
    # cancel in `pi_loss`; a float32 sum's rounding follows it, not the sum.
    return {"pi_loss": float(pi_loss), "baseline_loss": float(v_loss),
            "entropy": float(ent), "total_loss": float(total),
            "pi_scale": float(pi_scale)}


def _h(x, eps):
    return np.sign(x) * (np.sqrt(np.abs(x) + 1) - 1) + eps * x


def _h_inv(x, eps):
    return np.sign(x) * (np.square(
        (np.sqrt(1 + 4 * eps * (np.abs(x) + 1 + eps)) - 1) / (2 * eps)) - 1)


def r2d2_loss(main_q, target_q, batch: dict, is_weight, *, burn_in: int,
              discount: float, eta: float | None,
              rescale_eps: float = 1e-3) -> dict:
    """`main_q, target_q [B,T,A]` from the two unrolls; `batch` holds
    numpy `action, reward, done`. -> loss and per-sequence priorities."""
    mq = main_q.astype(np.float64)[:, burn_in:]
    tq = target_q.astype(np.float64)[:, burn_in:]
    act = batch["action"][:, burn_in:].astype(np.int64)
    rew = batch["reward"][:, burn_in:].astype(np.float64)
    disc = ((~batch["done"].astype(bool)).astype(np.float64)
            * discount)[:, burn_in:]
    sav = np.take_along_axis(mq[:, :-1], act[:, :-1, None], -1)[..., 0]
    best = np.argmax(main_q.astype(F)[:, burn_in:][:, 1:], axis=-1)
    nxt = np.take_along_axis(tq[:, 1:], best[..., None], -1)[..., 0]
    target = _h(_h_inv(nxt, rescale_eps) * disc[:, :-1] + rew[:, :-1],
                rescale_eps)
    delta = target - sav
    per_seq = np.mean(np.square(delta), axis=1)
    loss = float(np.mean(per_seq * np.asarray(is_weight, np.float64)))
    ad = np.abs(delta)
    if eta is None:
        prio = np.abs(np.mean(delta, axis=1))
    else:
        prio = eta * ad.max(axis=1) + (1 - eta) * ad.mean(axis=1)
    return {"loss": loss, "priorities": prio.astype(F)}

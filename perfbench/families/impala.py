"""Family `impala`: what the harness needs to know of one algorithm,
found by the section's `algorithm` (or name prefix). A later PR brings
another family as a file like this one.

- `LAUNCHER`: the program's script whose `--mode actor` is an actor;
- `LOSS_TAG`: the tag of the loss in the learner's `metrics.jsonl`;
- `UPDATE_METHOD`: the learner's method that `transport._learner_loop`
  calls once per update (the observer of `modes/hostloop_learner.py`
  stamps its returns);
- `forward_flops_per_frame`, `learn_flops_per_update`: operations from
  shapes, given the torso's count;
- `seeded_batch`, `reference_check`, `learn_step_kernels`: the seeded
  batch, the comparison with the plain numpy reference, and the Mosaic
  kernels in the lowered learn step.
"""

from __future__ import annotations

import numpy as np

import flops

LAUNCHER = "train_impala.py"
LOSS_TAG = "learner/total_loss"
UPDATE_METHOD = "step"
REFERENCE_BATCH = 8


def forward_flops_per_frame(section: dict, torso: tuple[int, int]) -> int:
    """models/impala_net.py ImpalaActorCritic, one frame: torso, action
    embedding, one LSTM step, policy head MLP([256,256],A), value head
    MLP([256,256],1)."""
    a, hid = section["model_output"], section.get("lstm_size", 256)
    torso_macs, feat = torso
    heads = (hid * 256 + 256 * 256 + 256 * a) + (hid * 256 + 256 * 256 + 256)
    macs = (torso_macs + flops.embed_macs(a)
            + flops.lstm_macs(feat + flops.ACTION_EMBED_WIDTH, hid) + heads)
    return 2 * macs


def learn_flops_per_update(section: dict, torso: tuple[int, int],
                           batch: int | None = None) -> int:
    """Forward + backward over batch x trajectory frames (the
    stored-state forward covers every step of the unroll). `batch`: the
    unrolls one update learns from where that is not `batch_size` (the
    fused loop learns from every env's)."""
    b = batch or section["batch_size"]
    return (3 * forward_flops_per_frame(section, torso) * b
            * section.get("trajectory", 20))


def seeded_batch(section: dict, batch: int, seed: int) -> dict:
    """A seeded `[B, T]` batch as numpy arrays: off-policy behaviour
    probabilities (so the rho clip matters), rewards beyond +-1 (so the
    reward clip matters), a few episode ends."""
    r = np.random.RandomState(seed % (2 ** 32))
    t, a, h = section.get("trajectory", 20), section["model_output"], \
        section.get("lstm_size", 256)
    obs = tuple(section["model_input"])
    logits = r.normal(size=(batch, t, a)).astype(np.float32)
    beh = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    state = (r.randint(0, 256, size=(batch, t, *obs)).astype(np.uint8)
             if len(obs) == 3 else
             r.normal(size=(batch, t, *obs)).astype(np.float32))
    return {
        "state": state,
        "reward": r.choice([-2.0, -1.0, 0.0, 0.0, 0.5, 1.0, 3.0],
                           size=(batch, t)).astype(np.float32),
        "action": r.randint(0, a, size=(batch, t)).astype(np.int32),
        "done": r.uniform(size=(batch, t)) < 0.05,
        "behavior_policy": beh.astype(np.float32),
        "previous_action": r.randint(0, a, size=(batch, t)).astype(np.int32),
        "initial_h": (0.1 * r.normal(size=(batch, t, h))).astype(np.float32),
        "initial_c": (0.1 * r.normal(size=(batch, t, h))).astype(np.float32),
    }


def reference_check(agent, train_state, section: dict, seed: int) -> dict:
    """The agent's loss terms on a seeded batch against
    `reference.impala_losses` fed with the network's own outputs.

    Tolerance: `childlib.LOSS_RTOL` relative to max(1, |reference|). The
    policy-gradient loss is a sum of terms of both signs (and the total
    holds it), so there the error follows the summed MAGNITUDE of the
    terms, not the sum, and the tolerance is relative to that. Seen on
    the chip (PERF.md): 1e-5 to 2e-5 on the one-signed sums; 2.5e-3 to
    3.3e-3 absolute on pi_loss whatever its value (24 to 290), 1e-5 of
    the summed magnitude: the chip's log is that far from numpy's.
    Targets computed in bfloat16 would be several times outside."""
    import jax

    import childlib
    import reference
    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaBatch
    from distributed_reinforcement_learning_tpu.models.impala_net import (
        apply_stored_state)

    nb = seeded_batch(section, REFERENCE_BATCH, seed)
    jb = ImpalaBatch(**nb)

    def both(p, b):
        _, metrics = agent._loss(p, b)
        policy, value = apply_stored_state(
            agent.model, p, agent._prep_obs(b.state), b.previous_action,
            b.initial_h, b.initial_c)
        return metrics, policy, value

    metrics, policy, value = jax.device_get(
        jax.jit(both)(train_state.params, jb))
    want = reference.impala_losses(
        np.asarray(policy), np.asarray(value), nb,
        discount=agent.cfg.discount_factor,
        baseline_coef=agent.cfg.baseline_loss_coef,
        entropy_coef=agent.cfg.entropy_coef,
        reward_clipping=agent.cfg.reward_clipping)
    pi_scale = want.pop("pi_scale")
    got = {k: float(metrics[k]) for k in want}
    magnitude = {"pi_loss": pi_scale, "total_loss": (
        pi_scale + agent.cfg.baseline_loss_coef * abs(want["baseline_loss"])
        + agent.cfg.entropy_coef * abs(want["entropy"]))}
    ok = all(childlib.close(got[k], want[k], magnitude.get(k, 0.0))
             for k in want)
    return {"ok": ok, "program": got, "reference": want,
            "rtol": childlib.LOSS_RTOL, "pi_scale": pi_scale}


def learn_step_kernels(agent, train_state, section: dict) -> int:
    """`tpu_custom_call`s in the learn step lowered for the section's batch."""
    import childlib
    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaBatch

    nb = seeded_batch(section, section["batch_size"], 0)
    return childlib.kernels_in_lowered(agent.learn, train_state,
                                       ImpalaBatch(**nb))

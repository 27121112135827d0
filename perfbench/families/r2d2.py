"""Family `r2d2` (see `families/impala.py` for what a family file
holds). No cell of `BENCHMARK.json` uses it yet (PERF.md, Open question
1); the CPU rehearsal and the reference tests do."""

from __future__ import annotations

import numpy as np

import flops

LAUNCHER = "train_r2d2.py"
LOSS_TAG = "learner/loss"
UPDATE_METHOD = "train"  # returns None, and completes no update, until warm
REFERENCE_BATCH = 4


def forward_flops_per_frame(section: dict, torso: tuple[int, int]) -> int:
    """models/r2d2_net.py R2D2Net, one frame: torso, action embedding,
    one LSTM step, Dense(128), dueling value Dense(A) and mean Dense(1)."""
    a, hid = section["model_output"], section.get("lstm_size", 512)
    torso_macs, feat = torso
    head = hid * 128 + 128 * a + 128
    macs = (torso_macs + flops.embed_macs(a)
            + flops.lstm_macs(feat + flops.ACTION_EMBED_WIDTH, hid) + head)
    return 2 * macs


def learn_flops_per_update(section: dict, torso: tuple[int, int],
                           batch: int | None = None) -> int:
    """The online net is unrolled forward + backward over the WHOLE
    stored sequence (burn-in is cut from the loss, not from the unroll:
    agents/r2d2.py), the target net forward over the same."""
    b = batch or section["batch_size"]
    return ((3 + 1) * forward_flops_per_frame(section, torso) * b
            * section["seq_len"])


def seeded_batch(section: dict, batch: int, seed: int) -> dict:
    r = np.random.RandomState(seed % (2 ** 32))
    t, a, h = section["seq_len"], section["model_output"], \
        section.get("lstm_size", 512)
    obs = tuple(section["model_input"])
    state = (r.randint(0, 256, size=(batch, t, *obs)).astype(np.uint8)
             if len(obs) == 3 else
             r.normal(size=(batch, t, *obs)).astype(np.float32))
    return {
        "state": state,
        "previous_action": r.randint(0, a, size=(batch, t)).astype(np.int32),
        "action": r.randint(0, a, size=(batch, t)).astype(np.int32),
        "reward": r.choice([-1.0, 0.0, 0.0, 0.0, 1.0],
                           size=(batch, t)).astype(np.float32),
        "done": r.uniform(size=(batch, t)) < 0.02,
        "initial_h": (0.1 * r.normal(size=(batch, h))).astype(np.float32),
        "initial_c": (0.1 * r.normal(size=(batch, h))).astype(np.float32),
    }


def reference_check(agent, train_state, section: dict, seed: int) -> dict:
    """The agent's loss and priorities on a seeded batch against
    `reference.r2d2_loss` fed with the two unrolls' own Q-values, both
    within `childlib.LOSS_RTOL` (seen on the chip: 2.2e-5 and 2.6e-5,
    my chip run, PR 23)."""
    import jax

    import childlib
    import reference
    from distributed_reinforcement_learning_tpu.agents.r2d2 import R2D2Batch

    nb = seeded_batch(section, REFERENCE_BATCH, seed)
    if not (agent.cfg.fold_normalize and nb["state"].dtype == np.uint8):
        nb["state"] = nb["state"].astype(np.float32)
    is_weight = np.random.RandomState(seed % (2 ** 32) + 1).uniform(
        0.5, 1.0, size=(REFERENCE_BATCH,)).astype(np.float32)
    jb = R2D2Batch(**nb)

    def both(params, target_params, b, w):
        loss, prio = agent._loss(params, target_params, b, w)
        unroll = lambda p: agent.model.apply(
            p, agent._prep_obs(b.state), b.previous_action, b.done,
            b.initial_h, b.initial_c, method=agent.model.unroll)
        return loss, prio, unroll(params), unroll(target_params)

    # A target net that differs from the online net, as between syncs.
    target = jax.tree.map(lambda x: x * 0.97, train_state.params)
    loss, prio, mq, tq = jax.device_get(
        jax.jit(both)(train_state.params, target, jb, is_weight))
    want = reference.r2d2_loss(
        np.asarray(mq), np.asarray(tq), nb, is_weight,
        burn_in=agent.cfg.burn_in, discount=agent.cfg.discount_factor,
        eta=agent.cfg.priority_eta, rescale_eps=agent.cfg.rescale_eps)
    prio = np.asarray(prio)
    prio_err = float(np.max(np.abs(prio - want["priorities"])
                            / np.maximum(1.0, np.abs(want["priorities"]))))
    ok = (childlib.close(float(loss), want["loss"])
          and prio_err <= childlib.LOSS_RTOL)
    return {"ok": ok, "program": {"loss": float(loss)},
            "reference": {"loss": want["loss"]},
            "priority_max_rel_err": prio_err, "rtol": childlib.LOSS_RTOL}


def learn_step_kernels(agent, train_state, section: dict) -> int:
    return 0  # the configuration names no Mosaic kernel

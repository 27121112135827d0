"""The plain numpy losses against the program's agents, on a tiny
seeded batch on the CPU — and against a changed hyper-parameter, to
show that the comparison can fail."""

import json
import os

import numpy as np
import pytest


def _family(name):
    import discover
    from conftest import BENCH_DIR

    return discover.module(BENCH_DIR, "families", name)


def _impala(section):
    import jax

    from distributed_reinforcement_learning_tpu.agents.impala import (
        ImpalaAgent, ImpalaConfig)

    agent = ImpalaAgent(ImpalaConfig(
        obs_shape=tuple(section["model_input"]),
        num_actions=section["model_output"], trajectory=section["trajectory"],
        lstm_size=section["lstm_size"],
        discount_factor=section["discount_factor"],
        baseline_loss_coef=section["baseline_loss_coef"],
        entropy_coef=section["entropy_coef"]))
    return agent, agent.init_state(jax.random.PRNGKey(3))


@pytest.mark.parametrize("seed", [0, 3_000_000_019])
def test_impala_losses_agree_with_the_agent(tiny_sections, seed):
    section = tiny_sections["impala_tiny"]
    agent, state = _impala(section)
    out = _family("impala").reference_check(agent, state, section, seed)
    assert out["ok"], out
    assert abs(out["program"]["total_loss"]) > 1.0  # a loss worth comparing


def test_impala_reference_catches_a_changed_loss(tiny_sections):
    """The agent's loss against the reference at another discount, and
    with the reward clip left out: both must disagree."""
    import jax

    import childlib
    import reference
    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaBatch
    from distributed_reinforcement_learning_tpu.models.impala_net import (
        apply_stored_state)

    section = tiny_sections["impala_tiny"]
    agent, state = _impala(section)
    nb = _family("impala").seeded_batch(section, 4, 7)
    jb = ImpalaBatch(**nb)
    _, metrics = jax.jit(agent._loss)(state.params, jb)
    policy, value = apply_stored_state(
        agent.model, state.params, agent._prep_obs(jb.state),
        jb.previous_action, jb.initial_h, jb.initial_c)
    kw = dict(discount=agent.cfg.discount_factor,
              baseline_coef=agent.cfg.baseline_loss_coef,
              entropy_coef=agent.cfg.entropy_coef)
    same = reference.impala_losses(np.asarray(policy), np.asarray(value), nb, **kw)
    assert childlib.close(float(metrics["total_loss"]), same["total_loss"])
    for changed in (dict(kw, discount=0.9), dict(kw, reward_clipping="none")):
        other = reference.impala_losses(np.asarray(policy), np.asarray(value),
                                        nb, **changed)
        assert not childlib.close(float(metrics["total_loss"]),
                                   other["total_loss"])


@pytest.mark.parametrize("eta", [0.9, None])
def test_r2d2_loss_and_priorities_agree_with_the_agent(tiny_sections, eta):
    import jax

    from distributed_reinforcement_learning_tpu.agents.r2d2 import (
        R2D2Agent, R2D2Config)

    section = dict(tiny_sections["r2d2_tiny"], priority_eta=eta)
    agent = R2D2Agent(R2D2Config(
        obs_shape=tuple(section["model_input"]),
        num_actions=section["model_output"], seq_len=section["seq_len"],
        burn_in=section["burn_in"], lstm_size=section["lstm_size"],
        priority_eta=eta))
    state = agent.init_state(jax.random.PRNGKey(5))
    out = _family("r2d2").reference_check(agent, state, section, 11)
    assert out["ok"], out
    assert out["program"]["loss"] > 0


def test_flops_from_shapes():
    """Hand count for the published sizes: Nature-CNN 84x84x4 is
    3,276,800 + 2,654,208 + 1,806,336 multiply-adds; the IMPALA net
    23.6 MFLOP a frame forward; one R2D2-Atari update (64 x 120 frames
    of LSTM-512, online forward+backward and target forward) 0.97 TFLOP."""
    import flops
    import peaks
    from conftest import BENCH_DIR

    with open(os.path.join(BENCH_DIR, "configs", "impala_nature.json")) as f:
        imp = json.load(f)["impala_nature"]
    torso = flops.torso_macs(BENCH_DIR, imp)
    assert torso == (7_737_344, 3136)
    impala, r2d2 = _family("impala"), _family("r2d2")
    assert impala.forward_flops_per_frame(imp, torso) == 23_620_096
    assert impala.learn_flops_per_update(imp, torso) == 3 * 23_620_096 * 640
    # the fused loop learns from every env's unroll
    assert impala.learn_flops_per_update(imp, torso, 256) == \
        3 * 23_620_096 * 256 * 20
    r2 = {"model_input": [84, 84, 4], "model_output": 4, "lstm_size": 512,
          "batch_size": 64, "seq_len": 120, "torso": "nature"}
    assert r2d2.learn_flops_per_update(r2, torso) == \
        4 * r2d2.forward_flops_per_frame(r2, torso) * 64 * 120
    assert 0.9e12 < r2d2.learn_flops_per_update(r2, torso) < 1.0e12
    assert flops.torso_macs(BENCH_DIR, {"model_input": [4]}) == \
        (4 * 256 + 256 * 256, 256)
    with pytest.raises(ValueError):
        flops.torso_macs(BENCH_DIR, dict(imp, torso_width=2))
    cost = flops.vtrace_kernel_cost(18, 32)
    assert cost == {"flops": 11 * 576, "bytes": 4 * (6 * 576 + 32)}
    least, bound = flops.roofline_seconds(cost, peaks.device_peaks("TPU v5 lite"))
    assert bound == "memory" and 1e-8 < least < 2e-8
    with pytest.raises(KeyError):
        peaks.device_peaks("cpu")

"""R2D2-Atari as published (section `r2d2_atari`, ISSUE 26): the dueling
head, n-step double-Q targets, the on-device ring and its donated chunks,
against the plain reference `reference/r2d2_atari.py`, at small sizes on
the CPU. Default keys (`n_step` 1, no `dueling_hidden`) stay the parent's.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.agents.r2d2 import (
    R2D2Agent, R2D2Batch, R2D2Config)
from distributed_reinforcement_learning_tpu.data import device_replay
from distributed_reinforcement_learning_tpu.envs.cartpole import pomdp_project
from distributed_reinforcement_learning_tpu.ops import dqn, value_rescale
from distributed_reinforcement_learning_tpu.reference import r2d2_atari as ref
from distributed_reinforcement_learning_tpu.runtime.anakin_r2d2 import AnakinR2D2

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, T, A, BURN = 3, 12, 6, 4
# A 44x44 frame is the smallest neighbourhood of sizes the three VALID
# convolutions leave a 2x2 map of: the widths are the test's, the code
# paths (uint8 frames, folded 1/255, conv torso) the published ones.
SMALL = dict(obs_shape=(44, 44, 4), num_actions=A, seq_len=T, burn_in=BURN,
             lstm_size=32, dueling_hidden=16, torso="nature",
             priority_eta=0.9)


def _batch(seed: int = 0) -> dict:
    r = np.random.RandomState(seed)
    done = r.uniform(size=(B, T)) < 0.1
    done[0, 1] = True  # inside the burn-in
    done[1, BURN + 3] = True  # inside the supervised steps
    done[2, T - 2] = True  # inside the cut horizon
    return {
        "state": r.randint(0, 256, size=(B, T, 44, 44, 4)).astype(np.uint8),
        "previous_action": r.randint(0, A, size=(B, T)).astype(np.int32),
        "action": r.randint(0, A, size=(B, T)).astype(np.int32),
        "reward": r.choice([-1.0, 0.0, 0.0, 0.5, 2.0],
                           size=(B, T)).astype(np.float32),
        "done": done,
        "initial_h": (0.3 * r.normal(size=(B, 32))).astype(np.float32),
        "initial_c": (0.3 * r.normal(size=(B, 32))).astype(np.float32),
    }


@pytest.fixture(scope="module", params=[1, 3, 5], ids=lambda n: f"n{n}")
def both(request):
    """The program and the reference on one seeded batch, with a target
    net that differs from the online net."""
    n_step = request.param
    agent = R2D2Agent(R2D2Config(n_step=n_step, **SMALL))
    params = agent.init_state(jax.random.PRNGKey(1)).params
    target = jax.tree.map(lambda x: x * 0.9, params)
    nb = _batch()
    is_weight = np.asarray([1.0, 0.5, 0.25], np.float32)

    def program(p, t, b, w):
        (loss, prio), grads = jax.value_and_grad(agent._loss, has_aux=True)(
            p, t, b, w)
        q = agent.model.apply(p, b.state, b.previous_action, b.done,
                              b.initial_h, b.initial_c,
                              method=agent.model.unroll)
        return {"q": q, "loss": loss, "priorities": prio, "grads": grads}

    got = jax.device_get(jax.jit(program)(params, target, R2D2Batch(**nb),
                                          is_weight))
    hyper = dict(burn_in=BURN, n_step=n_step, discount=0.997,
                 rescale_eps=1e-3, eta=0.9)
    want = ref.evaluate(ref.rekey(params), ref.rekey(target), nb, is_weight,
                        hyper)
    return got, want


# Both sides are float32 on the CPU, where a matmul keeps float32: what
# is left is the order of the sums (one [z; h] product against two, a
# fold back to front against a sum front to back). 1e-5 of the largest
# magnitude holds that with room; a dropped n-step term, a wrong horizon
# or a state zeroed a step early is 1e-2 and more.
RTOL = 1e-5


def _close(got, want, scale=None):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(1.0, float(np.abs(want).max())) if scale is None else scale
    np.testing.assert_allclose(got, want, rtol=0, atol=RTOL * scale)


def test_q_values_over_a_sequence_with_dones(both):
    got, want = both
    assert got["q"].shape == (B, T, A)
    _close(got["q"], want["q"])


def test_loss(both):
    got, want = both
    _close(got["loss"], want["loss"])


def test_priorities(both):
    got, want = both
    _close(got["priorities"], want["priorities"])


def test_gradients_leaf_by_leaf(both):
    got, want = both
    flat_got = ref.rekey(got["grads"])
    leaves_got, tree_got = jax.tree.flatten(flat_got)
    leaves_want, tree_want = jax.tree.flatten(want["grads"])
    assert tree_got == tree_want
    scale = max(float(np.abs(g).max()) for g in leaves_want)
    assert scale > 0
    for g, w in zip(leaves_got, leaves_want):
        _close(g, w, scale=scale)


def test_dueling_head_is_value_plus_centred_advantage():
    agent = R2D2Agent(R2D2Config(**SMALL))
    params = agent.init_state(jax.random.PRNGKey(2)).params
    assert {"value_fc", "value_out", "advantage_fc", "advantage_out"} \
        <= set(params["params"])
    assert not {"head_fc", "value", "mean"} & set(params["params"])
    assert params["params"]["value_fc"]["kernel"].shape == (32, 16)
    # a constant added to every advantage leaves Q where it was
    shifted = jax.tree.map(lambda x: x, params)
    shifted["params"]["advantage_out"]["bias"] = \
        params["params"]["advantage_out"]["bias"] + 3.0
    obs = jnp.zeros((2, 44, 44, 4), jnp.uint8)
    args = (obs, jnp.zeros(2, jnp.int32), jnp.zeros((2, 32)), jnp.zeros((2, 32)))
    q0, h0, _ = agent.model.apply(params, *args)
    q1, h1, _ = agent.model.apply(shifted, *args)
    np.testing.assert_allclose(q0, q1, atol=1e-5)
    np.testing.assert_array_equal(h0, h1)


# -- the defaults are the parent's ------------------------------------------


def _parent_sequence_double_q_td(main_q, target_q, action, reward, discounts,
                                 *, burn_in, rescale_eps):
    """`agents/common.sequence_double_q_td` as it stood before `n_step`
    (commit 0452399), copied."""
    b = burn_in
    main_b, target_b = main_q[:, b:], target_q[:, b:]
    reward_b, disc_b, action_b = reward[:, b:], discounts[:, b:], action[:, b:]
    sav = dqn.take_state_action_value(main_b[:, :-1], action_b[:, :-1])
    next_action = jnp.argmax(main_b[:, 1:], axis=-1)
    next_sav = dqn.take_state_action_value(target_b[:, 1:], next_action)
    descaled = value_rescale.inverse_value_rescale(next_sav, rescale_eps)
    raw_target = jax.lax.stop_gradient(
        descaled * disc_b[:, :-1] + reward_b[:, :-1])
    return value_rescale.value_rescale(raw_target, rescale_eps), sav


def _r2d2_default():
    cfg = R2D2Config(obs_shape=(2,), num_actions=3, seq_len=8, burn_in=2,
                     lstm_size=16, priority_eta=0.9)
    agent = R2D2Agent(cfg)
    params = agent.init_state(jax.random.PRNGKey(7)).params
    r = np.random.RandomState(3)
    batch = R2D2Batch(
        state=jnp.asarray(r.normal(size=(4, 8, 2)).astype(np.float32)),
        previous_action=jnp.asarray(r.randint(0, 3, (4, 8))),
        action=jnp.asarray(r.randint(0, 3, (4, 8))),
        reward=jnp.asarray(r.normal(size=(4, 8)).astype(np.float32)),
        done=jnp.asarray(r.uniform(size=(4, 8)) < 0.2),
        initial_h=jnp.zeros((4, 16)), initial_c=jnp.zeros((4, 16)))
    forward = lambda p: agent.model.apply(
        p, batch.state, batch.previous_action, batch.done, batch.initial_h,
        batch.initial_c, method=agent.model.unroll)
    return agent, params, batch, forward


def _xformer_default():
    from distributed_reinforcement_learning_tpu.agents.xformer import (
        XformerAgent, XformerBatch, XformerConfig)

    cfg = XformerConfig(obs_shape=(2,), num_actions=3, seq_len=8, burn_in=2,
                        d_model=16, num_heads=2, num_layers=1)
    agent = XformerAgent(cfg)
    params = agent.init_state(jax.random.PRNGKey(7)).params
    r = np.random.RandomState(3)
    batch = XformerBatch(
        state=jnp.asarray(r.normal(size=(4, 8, 2)).astype(np.float32)),
        previous_action=jnp.asarray(r.randint(0, 3, (4, 8))),
        action=jnp.asarray(r.randint(0, 3, (4, 8))),
        reward=jnp.asarray(r.normal(size=(4, 8)).astype(np.float32)),
        done=jnp.asarray(r.uniform(size=(4, 8)) < 0.2))
    forward = lambda p: agent.model.apply(
        p, common.normalize_obs(batch.state, cfg.dtype), batch.previous_action,
        batch.done)
    return agent, params, batch, forward


@pytest.mark.parametrize("family", [_r2d2_default, _xformer_default],
                         ids=["r2d2", "xformer"])
def test_default_targets_are_bit_identical_to_the_parents(family):
    agent, params, batch, forward = family()
    target = jax.tree.map(lambda x: x * 0.9, params)
    tv, sav = agent._sequence_td(params, target, batch)[:2]
    discounts = (~batch.done).astype(jnp.float32) * agent.cfg.discount_factor
    want_tv, want_sav = _parent_sequence_double_q_td(
        forward(params), forward(target), batch.action, batch.reward,
        discounts, burn_in=agent.cfg.burn_in,
        rescale_eps=agent.cfg.rescale_eps)
    np.testing.assert_array_equal(np.asarray(tv), np.asarray(want_tv))
    np.testing.assert_array_equal(np.asarray(sav), np.asarray(want_sav))


def test_default_r2d2_parameters_and_loss_are_the_parents():
    """Recorded from the parent's tree (commit 0452399) on this CPU: the
    head's parameters keep their names and their draws, and a loss on a
    seeded batch its value."""
    agent, params, batch, _ = _r2d2_default()
    assert sorted(params["params"]) == [
        "action_embed", "cell", "head_fc", "mean", "state_fc1", "state_fc2",
        "value"]
    fingerprint = float(sum(np.abs(np.asarray(x, np.float64)).sum()
                            for x in jax.tree.leaves(params)))
    assert fingerprint == 9118.393453306959
    target = jax.tree.map(lambda x: x * 0.9, params)
    loss, prio = agent._loss(params, target, batch, jnp.ones(4))
    np.testing.assert_allclose(float(loss), 0.18670853972434998, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(prio), [0.3921048045158386, 0.6540170907974243,
                           0.8080228567123413, 0.4665968120098114], rtol=1e-6)


def test_n_step_horizon_is_cut_at_the_sequences_end():
    """Six supervised positions, n = 3, by hand: position t sums the
    rewards of t .. min(t + 3, 6) - 1 and bootstraps from min(t + 3, 6);
    with rescale eps 0 and h the identity near 0 that is plain algebra."""
    r = np.random.RandomState(0)
    q = r.normal(size=(1, 7, 2)).astype(np.float32)
    tq = r.normal(size=(1, 7, 2)).astype(np.float32)
    action = r.randint(0, 2, (1, 7))
    reward = r.normal(size=(1, 7)).astype(np.float32)
    disc = np.full((1, 7), 0.9, np.float32)
    disc[0, 4] = 0.0  # an episode ends at step 4
    tv, sav = common.sequence_double_q_td(
        jnp.asarray(q), jnp.asarray(tq), jnp.asarray(action),
        jnp.asarray(reward), jnp.asarray(disc), burn_in=0, rescale_eps=1e-3,
        n_step=3)
    h = lambda x: np.sign(x) * (np.sqrt(np.abs(x) + 1) - 1) + 1e-3 * x
    h_inv = lambda x: np.sign(x) * (np.square(
        (np.sqrt(1 + 4e-3 * (np.abs(x) + 1 + 1e-3)) - 1) / 2e-3) - 1)
    value = h_inv(tq[0, np.arange(7), q[0].argmax(-1)].astype(np.float64))
    want = []
    for t in range(6):
        end = min(t + 3, 6)
        ret = sum(np.prod(disc[0, t:k]) * reward[0, k] for k in range(t, end))
        want.append(h(ret + np.prod(disc[0, t:end]) * value[end]))
    np.testing.assert_allclose(np.asarray(tv)[0], want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(sav)[0],
                                  q[0, np.arange(6), action[0, :6]])
    # the last position has one step left whatever n is
    one, _ = common.sequence_double_q_td(
        jnp.asarray(q), jnp.asarray(tq), jnp.asarray(action),
        jnp.asarray(reward), jnp.asarray(disc), burn_in=0, rescale_eps=1e-3)
    np.testing.assert_array_equal(np.asarray(tv)[0, -1], np.asarray(one)[0, -1])


# -- the sampler and the ring against numpy -----------------------------------


def _ring(capacity=16, fill=12):
    r = np.random.RandomState(5)
    replay = device_replay.make(
        {"x": jax.ShapeDtypeStruct((3,), jnp.float32)}, capacity)
    new = {"x": jnp.asarray(r.normal(size=(fill, 3)).astype(np.float32))}
    errs = jnp.asarray(np.abs(r.normal(size=fill)).astype(np.float32))
    return device_replay.ingest(replay, new, errs), r


def test_stratified_sample_matches_the_numpy_sampler():
    replay, _ = _ring()
    replay = replay._replace(beta=jnp.float32(0.7))
    key = jax.random.PRNGKey(11)
    _, batch, idx, weights = device_replay.sample(replay, key, 8)
    uniforms = np.asarray(jax.random.uniform(key, (8,)))
    pri = np.asarray(replay.priorities)
    want_idx, want_w, edge = ref.stratified_sample(pri, 12, 0.7, uniforms)
    assert edge.min() > 1e-5  # no draw sits on a slot's edge
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(weights), want_w, rtol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(batch["x"]), np.asarray(replay.storage["x"])[want_idx])
    assert (want_idx < 12).all()  # an empty slot is never drawn


def test_ring_write_is_fifo_like_the_numpy_ring():
    capacity, width = 8, 4
    storage = {"x": np.zeros((capacity, 2), np.float32)}
    pri, ptr, size = np.zeros(capacity), 0, 0
    replay = device_replay.make(
        {"x": jax.ShapeDtypeStruct((2,), jnp.float32)}, capacity)
    r = np.random.RandomState(9)
    for _ in range(3):  # the third write overwrites the first
        new = {"x": r.normal(size=(width, 2)).astype(np.float32)}
        errs = np.abs(r.normal(size=width)).astype(np.float32)
        storage, pri, ptr, size = ref.ring_write(storage, pri, ptr, size,
                                                 new, errs)
        replay = device_replay.ingest(
            replay, {"x": jnp.asarray(new["x"])}, jnp.asarray(errs))
        assert (int(replay.ptr), int(replay.size)) == (ptr, size)
        np.testing.assert_array_equal(np.asarray(replay.storage["x"]),
                                      storage["x"])
        np.testing.assert_allclose(np.asarray(replay.priorities), pri,
                                   rtol=1e-6)
    assert (ptr, size) == (4, 8)


def test_priority_write_back_reaches_every_sampled_index():
    replay, r = _ring()
    idx = jnp.asarray([0, 3, 3, 11])
    errs = jnp.asarray(np.abs(r.normal(size=4)).astype(np.float32))
    after = np.asarray(device_replay.update_priorities(replay, idx, errs).priorities)
    before = np.asarray(replay.priorities)
    want = ref.priority(np.asarray(errs))
    np.testing.assert_allclose(after[[0, 11]], want[[0, 3]], rtol=1e-6)
    assert after[3] in (np.float32(want[1]), np.float32(want[2]))
    untouched = np.setdiff1d(np.arange(16), [0, 3, 11])
    np.testing.assert_array_equal(after[untouched], before[untouched])


# -- donated chunks -------------------------------------------------------------


def _tiny_anakin(obs_shape=(2,), obs_transform=pomdp_project, **kw):
    cfg = R2D2Config(obs_shape=obs_shape, num_actions=2, seq_len=6, burn_in=2,
                     lstm_size=16, learning_rate=1e-3, n_step=3,
                     dueling_hidden=8, priority_eta=0.9)
    return AnakinR2D2(R2D2Agent(cfg), num_envs=4, capacity=16, batch_size=4,
                      obs_transform=obs_transform, updates_per_collect=2, **kw)


def _as_bytes(obs):
    return jnp.clip(obs * 32.0 + 128.0, 0, 255).astype(jnp.uint8)


@pytest.mark.parametrize("leaf", ["plain_ring", "word_ring"])
@pytest.mark.parametrize("chunk", ["collect_chunk", "train_chunk"])
def test_donated_chunk_reuses_the_rings_buffer(chunk, leaf):
    """Both stored forms: the POMDP view (int32) as it is, and
    byte observations as the `WordRing`'s one `uint32` array."""
    an = (_tiny_anakin((4,), _as_bytes) if leaf == "word_ring"
          else _tiny_anakin())
    state = an.init(jax.random.PRNGKey(0))
    if chunk == "train_chunk":
        state, _ = an.collect_chunk(state, 4)
    array = lambda s: getattr(s.replay.storage.state, "words",
                              s.replay.storage.state)
    ring = array(state)
    assert ring.dtype == (jnp.uint32 if leaf == "word_ring" else jnp.int32)
    where = ring.unsafe_buffer_pointer()
    new_state, _ = getattr(an, chunk)(state, 2)
    assert ring.is_deleted()  # donated: the caller's reference is gone
    assert array(new_state).unsafe_buffer_pointer() == where


def test_chunk_counters_ride_in_the_metrics():
    an = _tiny_anakin(target_sync_interval=4)
    state = an.init(jax.random.PRNGKey(0))
    state, _ = an.collect_chunk(state, 4)
    state, m = an.train_chunk(state, 4)  # 8 optimizer steps: two target copies
    assert float(m["replay_size"][-1]) == 16
    assert np.asarray(m["target_syncs"]).sum() == 2
    assert (np.asarray(m["priority_max"]) >= np.asarray(m["priority_mean"])).all()
    assert (np.asarray(m["priority_mean"]) > 0).all()
    w = np.asarray(m["is_weight_min"])
    assert ((w > 0) & (w <= 1)).all()


def test_launcher_loop_survives_three_donated_chunks(tmp_path, capsys):
    from distributed_reinforcement_learning_tpu.runtime.launch import (
        train_anakin_r2d2)

    with open(os.path.join(ROOT, "config.json")) as f:
        section = dict(json.load(f)["r2d2"], n_step=3, dueling_hidden=8,
                       lstm_size=16, batch_size=4, updates_per_call=2,
                       priority_eta=0.9)
    path = tmp_path / "config.json"
    path.write_text(json.dumps({"r2d2_small": section}))
    out = train_anakin_r2d2(str(path), "r2d2_small", num_updates=12, chunk=2,
                            num_envs=4, capacity=16)
    assert len(out["chunk_mean_returns"]) == 3  # 3 chunks x 2 updates x K=2
    # the static fact of the compiled chunks, once at start-up (ISSUE 52)
    assert capsys.readouterr().out.count(
        "[anakin-r2d2] score order: time_major\n") == 1


# -- the configuration by name ----------------------------------------------------


def test_section_r2d2_atari_is_the_published_configuration():
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    cfg, rt = load_config(os.path.join(ROOT, "config.json"), "r2d2_atari")
    assert dataclasses.asdict(cfg) | {"dtype": None} == dict(
        obs_shape=(84, 84, 4), num_actions=18, seq_len=120, burn_in=40,
        lstm_size=512, discount_factor=0.997, learning_rate=1e-4,
        rescale_eps=1e-3, dtype=None, priority_eta=0.9,
        gradient_clip_norm=None, torso="nature", torso_width=1,
        n_step=5, dueling_hidden=512)
    assert (rt.batch_size, rt.target_sync_interval, rt.updates_per_call,
            rt.train_start_factor, rt.replay_capacity) == (64, 2500, 4, 32, 2048)
    assert rt.num_actors * rt.envs_per_actor == 256


def test_benchmark_configuration_holds_the_same_section():
    with open(os.path.join(ROOT, "config.json")) as f:
        program = json.load(f)["r2d2_atari"]
    with open(os.path.join(ROOT, "perfbench", "configs", "r2d2_atari.json")) as f:
        bench = json.load(f)
    assert bench[bench["section"]] == program


def test_benchmarks_copy_of_the_reference_is_the_same_text():
    with open(ref.__file__) as f:
        ours = f.read()
    with open(os.path.join(ROOT, "perfbench", "references", "r2d2_atari.py")) as f:
        assert f.read() == ours

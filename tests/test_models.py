"""Model shape, gradient, and unroll-semantics tests.

Conv-torso tests are gated behind DRL_TPU_SLOW_TESTS=1: XLA:CPU convolution
is pathologically slow on the single-core CI host (minutes per compile).
The conv path is exercised on real TPU by chip_smoke.py and __graft_entry__.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.models import (
    DuelingQNetwork,
    ImpalaActorCritic,
    R2D2Net,
    SimpleQNetwork,
    apply_stored_state,
)

slow = pytest.mark.skipif(
    os.environ.get("DRL_TPU_SLOW_TESTS") != "1",
    reason="conv compiles take minutes on single-core CPU; set DRL_TPU_SLOW_TESTS=1",
)


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)


@slow
def test_impala_shapes_atari(rng):
    model = ImpalaActorCritic(num_actions=18, lstm_size=64)
    obs = jnp.zeros((3, 84, 84, 4))
    pa = jnp.zeros((3,), jnp.int32)
    h = c = jnp.zeros((3, 64))
    params = model.init(rng, obs, pa, h, c)
    out = model.apply(params, obs, pa, h, c)
    assert out.policy.shape == (3, 18)
    assert out.value.shape == (3,)
    assert out.h.shape == (3, 64)
    np.testing.assert_allclose(out.policy.sum(-1), np.ones(3), rtol=1e-5)


def test_impala_vector_obs(rng):
    model = ImpalaActorCritic(num_actions=2, lstm_size=32)
    obs = jnp.zeros((5, 4))
    pa = jnp.zeros((5,), jnp.int32)
    h = c = jnp.zeros((5, 32))
    params = model.init(rng, obs, pa, h, c)
    out = model.apply(params, obs, pa, h, c)
    assert out.policy.shape == (5, 2)
    assert out.value.shape == (5,)
    np.testing.assert_allclose(out.policy.sum(-1), np.ones(5), rtol=1e-5)


def test_impala_stored_state_matches_per_step(rng):
    """Flattened [B*T] forward == applying the net step-by-step with stored states."""
    B, T, A, H = 2, 5, 4, 16
    model = ImpalaActorCritic(num_actions=A, lstm_size=H)
    key = jax.random.PRNGKey(1)
    obs = jax.random.normal(key, (B, T, 6))
    pa = jax.random.randint(key, (B, T), 0, A)
    hs = jax.random.normal(key, (B, T, H))
    cs = jax.random.normal(key, (B, T, H))
    params = model.init(rng, obs[:, 0], pa[:, 0], hs[:, 0], cs[:, 0])

    policy, value = apply_stored_state(model, params, obs, pa, hs, cs)
    assert policy.shape == (B, T, A)
    assert value.shape == (B, T)

    for t in range(T):
        out = model.apply(params, obs[:, t], pa[:, t], hs[:, t], cs[:, t])
        np.testing.assert_allclose(policy[:, t], out.policy, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(value[:, t], out.value, rtol=2e-4, atol=2e-4)


@slow
def test_dueling_q_shapes(rng):
    model = DuelingQNetwork(num_actions=4)
    obs = jnp.zeros((2, 84, 84, 4))
    pa = jnp.zeros((2,), jnp.int32)
    params = model.init(rng, obs, pa)
    q = model.apply(params, obs, pa)
    assert q.shape == (2, 4)


def test_simple_q_shapes(rng):
    model = SimpleQNetwork(num_actions=2)
    params = model.init(rng, jnp.zeros((2, 4)), jnp.zeros((2,), jnp.int32))
    q = model.apply(params, jnp.zeros((2, 4)), jnp.zeros((2,), jnp.int32))
    assert q.shape == (2, 2)


def test_r2d2_step_and_unroll_consistency(rng):
    """Scan unroll matches a manual Python loop with done-masked resets."""
    B, T, A, H = 2, 6, 2, 8
    model = R2D2Net(num_actions=A, lstm_size=H)
    key = jax.random.PRNGKey(2)
    obs = jax.random.normal(key, (B, T, 2))
    pa = jax.random.randint(key, (B, T), 0, A)
    done = jnp.asarray([[False, False, True, False, False, False],
                        [False, False, False, False, True, False]])
    h0 = jax.random.normal(key, (B, H))
    c0 = jax.random.normal(key, (B, H))

    params = model.init(rng, obs[:, 0], pa[:, 0], h0, c0)
    q_seq = model.apply(params, obs, pa, done, h0, c0, method=model.unroll)
    assert q_seq.shape == (B, T, A)

    h, c = h0, c0
    for t in range(T):
        q, h, c = model.apply(params, obs[:, t], pa[:, t], h, c)
        np.testing.assert_allclose(q_seq[:, t], q, rtol=2e-5, atol=2e-5)
        keep = (~done[:, t]).astype(h.dtype)[:, None]
        h, c = h * keep, c * keep


def test_models_have_gradients(rng):
    model = ImpalaActorCritic(num_actions=4, lstm_size=16)
    obs = jnp.ones((2, 6)) * 0.5
    pa = jnp.zeros((2,), jnp.int32)
    h = c = jnp.zeros((2, 16))
    params = model.init(rng, obs, pa, h, c)

    def loss(p):
        out = model.apply(p, obs, pa, h, c)
        return jnp.sum(out.value) + jnp.sum(out.policy * out.policy)

    grads = jax.grad(loss)(params)
    total = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(total) and total > 0


class TestResNetTorso:
    """IMPALA-paper deep torso (models/torso.py ResNetTorso): the
    MXU-dense variant (VERDICT r3 item 8). CPU tests run width 1 on
    small frames; the width-4 84x84 geometry is left to the chip."""

    def _agent(self, **kw):
        from distributed_reinforcement_learning_tpu.agents.impala import (
            ImpalaAgent, ImpalaConfig)

        base = dict(obs_shape=(16, 16, 4), num_actions=4, trajectory=4,
                    lstm_size=32, torso="resnet", torso_width=1,
                    start_learning_rate=1e-3, learning_frame=10**6)
        base.update(kw)
        return ImpalaAgent(ImpalaConfig(**base))

    def test_forward_and_learn(self):
        from distributed_reinforcement_learning_tpu.utils.synthetic import (
            synthetic_impala_batch)

        agent = self._agent()
        state = agent.init_state(jax.random.PRNGKey(0))
        batch = synthetic_impala_batch(2, 4, (16, 16, 4), 4, 32)
        state2, m = agent.learn(state, jax.tree.map(jnp.asarray, batch))
        assert np.isfinite(float(m["total_loss"]))
        assert float(m["grad_norm"]) > 0

    def test_param_structure_has_residual_sections(self):
        agent = self._agent()
        state = agent.init_state(jax.random.PRNGKey(0))
        torso = state.params["params"]["torso"]
        # conv0 is explicit (foldable); sections carry residual convs.
        assert "conv0_kernel" in torso
        assert "section1_res0_conv0" in torso and "section2_res1_conv1" in torso
        assert "trunk_out" in torso

    def test_fold_normalize_equivalent_on_resnet(self):
        """conv(x/255) == conv_{k/255}(x) holds for the deep torso's
        explicit conv0 exactly as for NatureConv."""
        from distributed_reinforcement_learning_tpu.utils.synthetic import (
            synthetic_impala_batch)

        agent = self._agent()
        batch = jax.tree.map(jnp.asarray, synthetic_impala_batch(2, 4, (16, 16, 4), 4, 32))
        assert batch.state.dtype == jnp.uint8
        floats = batch._replace(state=batch.state.astype(jnp.float32) / 255.0)
        _, m_plain = agent.learn(agent.init_state(jax.random.PRNGKey(0)), floats)
        _, m_fold = agent.learn(agent.init_state(jax.random.PRNGKey(0)), batch)
        np.testing.assert_allclose(float(m_plain["total_loss"]),
                                   float(m_fold["total_loss"]), rtol=2e-4)

    def test_config_plumbs_torso(self, tmp_path):
        import json as _json

        from distributed_reinforcement_learning_tpu.utils.config import load_config

        p = tmp_path / "c.json"
        p.write_text(_json.dumps({"impala": {
            "model_input": [84, 84, 4], "model_output": 18,
            "env": ["BreakoutDeterministic-v4"], "available_action": [4],
            "num_actors": 1, "torso": "resnet", "torso_width": 4,
        }}))
        cfg, _ = load_config(str(p), "impala")
        assert cfg.torso == "resnet" and cfg.torso_width == 4

    def test_repo_section_loads(self):
        from distributed_reinforcement_learning_tpu.utils.config import load_config

        cfg, rt = load_config("config.json", "impala_resnet")
        assert cfg.torso == "resnet" and cfg.torso_width == 4
        # the section still says `"fold_normalize": true`: accepted, and
        # no field is left for it to set
        assert not hasattr(cfg, "fold_normalize")

    @pytest.mark.parametrize("algorithm", ["impala", "apex", "r2d2"])
    def test_fold_normalize_key_true_accepted_false_refused(self, tmp_path, algorithm):
        """`true` is what the program always does; `false` asks for the
        agent-side /255 pass, which is gone."""
        import json as _json

        from distributed_reinforcement_learning_tpu.utils.config import load_config

        section = {"model_input": [84, 84, 4], "model_output": 4,
                   "env": ["BreakoutDeterministic-v4"], "available_action": [4],
                   "num_actors": 1, "torso": "nature"}
        p = tmp_path / "c.json"
        p.write_text(_json.dumps({
            algorithm: section,
            f"{algorithm}_on": dict(section, fold_normalize=True),
            f"{algorithm}_off": dict(section, fold_normalize=False)}))
        assert load_config(str(p), f"{algorithm}_on")[0] == load_config(str(p), algorithm)[0]
        with pytest.raises(ValueError, match="fold_normalize.*agent-side /255 pass is gone"):
            load_config(str(p), f"{algorithm}_off")


def test_r2d2_conv_torso_step_and_unroll_consistency(rng):
    """The pixel R2D2Net (nature torso, folded /255) keeps the same
    step/unroll contract as the MLP variant: the time-parallel conv pass
    + fused LSTM unroll matches a per-step Python loop with done-masked
    resets, on raw uint8 frames."""
    B, T, A, H = 2, 4, 4, 8
    model = R2D2Net(num_actions=A, lstm_size=H, torso="nature")
    key = jax.random.PRNGKey(5)
    obs = jax.random.randint(key, (B, T, 84, 84, 4), 0, 256, dtype=jnp.uint8)
    pa = jax.random.randint(key, (B, T), 0, A)
    done = jnp.asarray([[False, True, False, False],
                        [False, False, False, True]])
    h0 = jax.random.normal(key, (B, H))
    c0 = jax.random.normal(key, (B, H))

    params = model.init(rng, obs[:, 0], pa[:, 0], h0, c0)
    q_seq = model.apply(params, obs, pa, done, h0, c0, method=model.unroll)
    assert q_seq.shape == (B, T, A)

    h, c = h0, c0
    for t in range(T):
        q, h, c = model.apply(params, obs[:, t], pa[:, t], h, c)
        np.testing.assert_allclose(q_seq[:, t], q, rtol=2e-5, atol=2e-5)
        keep = (~done[:, t]).astype(h.dtype)[:, None]
        h, c = h * keep, c * keep

"""Perf-path equivalence tests: folded normalization and scanned learn.

Both paths exist purely for TPU throughput; their contract is exact (up
to float rounding) equivalence with the plain paths, checked here on CPU
in fp32 with small image shapes. Integer frames stay bytes until conv0,
whose kernel carries the /255 (no switch: the frames' dtype decides), so
the plain path of the fold is the same agent fed the same frames
pre-divided as float32.
"""

import jax
import jax.numpy as jnp
import numpy as np

from distributed_reinforcement_learning_tpu.agents import (
    ApexAgent,
    ApexBatch,
    ApexConfig,
    ImpalaAgent,
    ImpalaBatch,
    ImpalaConfig,
    R2D2Agent,
    R2D2Config,
)
from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.models.torso import NatureConv

OBS = (84, 84, 4)  # NatureConv's fixed geometry


def small_impala_cfg(**kw):
    base = dict(obs_shape=OBS, num_actions=4, trajectory=6, lstm_size=16,
                learning_frame=1000)
    base.update(kw)
    return ImpalaConfig(**base)


def impala_image_batch(cfg, key, B=2):
    T, A, H = cfg.trajectory, cfg.num_actions, cfg.lstm_size
    ks = jax.random.split(key, 8)
    policy = jax.nn.softmax(jax.random.normal(ks[0], (B, T, A)), axis=-1)
    return ImpalaBatch(
        state=jax.random.randint(ks[1], (B, T, *OBS), 0, 256, dtype=jnp.int32).astype(jnp.uint8),
        reward=jax.random.normal(ks[2], (B, T)),
        action=jax.random.randint(ks[3], (B, T), 0, A),
        done=jax.random.bernoulli(ks[4], 0.1, (B, T)),
        behavior_policy=policy,
        previous_action=jax.random.randint(ks[5], (B, T), 0, A),
        initial_h=jax.random.normal(ks[6], (B, T, H)) * 0.1,
        initial_c=jax.random.normal(ks[7], (B, T, H)) * 0.1,
    )


def as_float_frames(batch):
    """The batch with its uint8 frames divided by 255 as float32: what
    the agent-side pass used to hand the model."""
    return batch._replace(state=batch.state.astype(jnp.float32) / 255.0)


class TestFoldNormalize:
    def test_nature_conv_input_scale_exact(self):
        """conv_{k/255}(x) == conv_k(x/255) on the same params."""
        conv = NatureConv()
        conv_folded = NatureConv(input_scale=1.0 / 255.0)
        x8 = np.random.default_rng(0).integers(0, 256, (3, *OBS)).astype(np.uint8)
        params = conv.init(jax.random.PRNGKey(0), jnp.zeros((1, *OBS), jnp.float32))
        plain = conv.apply(params, jnp.asarray(x8, jnp.float32) / 255.0)
        folded = conv_folded.apply(params, jnp.asarray(x8))
        np.testing.assert_allclose(np.asarray(plain), np.asarray(folded),
                                   rtol=2e-5, atol=2e-5)

    def test_impala_fold_normalize_same_params_and_loss(self):
        """uint8 frames through the model == the same frames divided by
        255 and passed as float32: same parameters, same loss."""
        agent = ImpalaAgent(small_impala_cfg())
        s0 = agent.init_state(jax.random.PRNGKey(1))
        # the parameters do not depend on how the frames will arrive
        u8 = agent.model.init(
            jax.random.PRNGKey(1), jnp.zeros((1, *OBS), jnp.uint8),
            jnp.zeros((1,), jnp.int32), *agent.initial_lstm_state(1))
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
                     s0.params, u8)
        batch = impala_image_batch(agent.cfg, jax.random.PRNGKey(2))
        l0, _ = agent._loss(s0.params, as_float_frames(batch))
        l1, _ = agent._loss(s0.params, batch)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)

    def test_impala_fold_normalize_act_parity(self):
        agent = ImpalaAgent(small_impala_cfg())
        state = agent.init_state(jax.random.PRNGKey(1))
        obs = np.random.default_rng(1).integers(0, 256, (2, *OBS)).astype(np.uint8)
        pa = np.zeros(2, np.int32)
        h, c = agent.initial_lstm_state(2)
        rng = jax.random.PRNGKey(3)
        a0 = agent.act(state.params, obs.astype(np.float32) / 255.0, pa, h, c, rng)
        a1 = agent.act(state.params, obs, pa, h, c, rng)
        np.testing.assert_allclose(np.asarray(a0.policy), np.asarray(a1.policy),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(a0.action), np.asarray(a1.action))

    def test_impala_learn_step_from_bytes_and_from_floats_same_params(self):
        """One learn step from uint8 frames and one from the same frames
        pre-divided as float32 end in the same parameters: the gradient
        passes through the kernel's constant 1/255 (float32, CPU)."""
        agent = ImpalaAgent(small_impala_cfg())
        batch = impala_image_batch(agent.cfg, jax.random.PRNGKey(2))
        init = agent.init_state(jax.random.PRNGKey(1))  # `_learn` donates nothing
        s0, m0 = agent._learn(init, as_float_frames(batch))
        s1, m1 = agent._learn(init, batch)
        np.testing.assert_allclose(float(m0["grad_norm"]), float(m1["grad_norm"]),
                                   rtol=1e-5)
        moved = 0.0
        for a, b, start in zip(*(jax.tree.leaves(s.params) for s in (s0, s1, init))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-5)
            moved = max(moved, float(jnp.max(jnp.abs(a - start))))
        assert moved > 1e-4  # the step is not a no-op that any path would match

    def test_apex_fold_normalize_td_parity(self):
        agent = ApexAgent(ApexConfig(obs_shape=OBS, num_actions=4))
        state = agent.init_state(jax.random.PRNGKey(0))
        rng = np.random.default_rng(2)
        B = 3
        batch = ApexBatch(
            state=rng.integers(0, 256, (B, *OBS)).astype(np.uint8),
            next_state=rng.integers(0, 256, (B, *OBS)).astype(np.uint8),
            previous_action=rng.integers(0, 4, B).astype(np.int32),
            action=rng.integers(0, 4, B).astype(np.int32),
            reward=rng.random(B).astype(np.float32),
            done=rng.random(B) < 0.2,
        )
        floats = batch._replace(
            state=batch.state.astype(np.float32) / 255.0,
            next_state=batch.next_state.astype(np.float32) / 255.0)
        td0 = agent.td_error(state, floats)
        td1 = agent.td_error(state, batch)
        np.testing.assert_allclose(np.asarray(td0), np.asarray(td1), rtol=1e-4, atol=1e-5)

    def test_fold_normalize_ignores_vector_obs(self):
        """Vector observations keep the normalize/cast path untouched:
        float ones are cast, integer ones (the reference's x255
        quantization) are still divided by the agent."""
        cfg = ImpalaConfig(obs_shape=(4,), num_actions=2, trajectory=4,
                           lstm_size=8)
        agent = ImpalaAgent(cfg)
        state = agent.init_state(jax.random.PRNGKey(0))
        obs = np.random.default_rng(0).random((2, 4)).astype(np.float32)
        h, c = agent.initial_lstm_state(2)
        out = agent.act(state.params, obs, np.zeros(2, np.int32), h, c,
                        jax.random.PRNGKey(1))
        assert out.policy.shape == (2, 2)
        q = np.round(obs * 255).astype(np.int32)
        np.testing.assert_allclose(np.asarray(agent._prep_obs(jnp.asarray(q))),
                                   q / 255.0, rtol=1e-6)


def test_upgrade_nature_conv_params_maps_old_layout():
    """Pre-r3 nn.Conv nesting (`Conv_i/{kernel,bias}`) restores via the
    upgrade helper into the explicit conv{i}_* layout."""
    from distributed_reinforcement_learning_tpu.models.torso import upgrade_nature_conv_params

    conv = NatureConv()
    params = conv.init(jax.random.PRNGKey(0), jnp.zeros((1, *OBS), jnp.float32))
    new_tree = params["params"]
    old_tree = {
        f"Conv_{i}": {"kernel": new_tree[f"conv{i}_kernel"],
                      "bias": new_tree[f"conv{i}_bias"]}
        for i in range(3)
    }
    upgraded = upgrade_nature_conv_params({"params": {"torso": old_tree}})
    jax.tree.map(np.testing.assert_array_equal,
                 upgraded, {"params": {"torso": new_tree}})


def stack_trees(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


class TestLearnMany:
    def test_impala_learn_many_matches_sequential(self):
        cfg = ImpalaConfig(obs_shape=(4,), num_actions=2, trajectory=8,
                           lstm_size=16, learning_frame=1000)
        agent = ImpalaAgent(cfg)
        K = 3
        batches = [
            __import__("tests.test_agents", fromlist=["make_impala_batch"]).make_impala_batch(
                cfg, jax.random.PRNGKey(10 + i))
            for i in range(K)
        ]
        s_seq = agent.init_state(jax.random.PRNGKey(0))
        seq_metrics = []
        for b in batches:
            s_seq, m = agent.learn(s_seq, b)
            seq_metrics.append(m)
        s_many = agent.init_state(jax.random.PRNGKey(0))
        s_many, stacked = agent.learn_many(s_many, stack_trees(batches))
        assert int(s_many.step) == K
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6),
            s_seq.params, s_many.params)
        for i, m in enumerate(seq_metrics):
            np.testing.assert_allclose(float(stacked["total_loss"][i]),
                                       float(m["total_loss"]), rtol=2e-5)

    def test_apex_learn_many_matches_sequential(self):
        cfg = ApexConfig(obs_shape=(4,), num_actions=3)
        agent = ApexAgent(cfg)
        K, B = 3, 4
        rng = np.random.default_rng(0)

        def batch(i):
            r = np.random.default_rng(100 + i)
            return ApexBatch(
                state=r.random((B, 4), dtype=np.float32),
                next_state=r.random((B, 4), dtype=np.float32),
                previous_action=r.integers(0, 3, B).astype(np.int32),
                action=r.integers(0, 3, B).astype(np.int32),
                reward=r.random(B).astype(np.float32),
                done=r.random(B) < 0.2,
            )

        batches = [batch(i) for i in range(K)]
        weights = [rng.random(B).astype(np.float32) + 0.5 for _ in range(K)]
        s_seq = agent.init_state(jax.random.PRNGKey(0))
        tds = []
        for b, w in zip(batches, weights):
            s_seq, td, _ = agent.learn(s_seq, b, w)
            tds.append(np.asarray(td))
        s_many = agent.init_state(jax.random.PRNGKey(0))
        s_many, td_stack, _ = agent.learn_many(
            s_many, stack_trees(batches), jnp.stack([jnp.asarray(w) for w in weights]))
        assert int(s_many.step) == K
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6),
            s_seq.params, s_many.params)
        np.testing.assert_allclose(np.asarray(td_stack), np.stack(tds),
                                   rtol=2e-5, atol=1e-6)

    def test_learner_updates_per_call_matches_sequential(self):
        from tests.test_agents import make_impala_batch

        from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue
        from distributed_reinforcement_learning_tpu.runtime.impala_runner import ImpalaLearner
        from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

        cfg = ImpalaConfig(obs_shape=(4,), num_actions=2, trajectory=8,
                           lstm_size=16, learning_frame=1000)
        agent = ImpalaAgent(cfg)

        def fill(queue, n_items):
            for i in range(n_items):
                b = make_impala_batch(cfg, jax.random.PRNGKey(1000 + i), B=1)
                queue.put(jax.tree.map(lambda x: np.asarray(x)[0], b))

        qa, qb = TrajectoryQueue(capacity=64), TrajectoryQueue(capacity=64)
        fill(qa, 8)
        fill(qb, 8)
        la = ImpalaLearner(agent, qa, WeightStore(), batch_size=2,
                           rng=jax.random.PRNGKey(0))
        lb = ImpalaLearner(agent, qb, WeightStore(), batch_size=2,
                           rng=jax.random.PRNGKey(0), updates_per_call=2)
        for _ in range(4):
            la.step(timeout=1.0)
        for _ in range(2):
            lb.step(timeout=1.0)
        assert la.train_steps == lb.train_steps == 4
        assert la.frames_learned == lb.frames_learned
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6),
            la.state.params, lb.state.params)
        # Partial drain (only one batch available) trains sequentially
        # rather than dropping data or stalling.
        fill(qb, 2)
        assert lb.step(timeout=0.2) is not None
        assert lb.train_steps == 5
        la.close()
        lb.close()

        # Prefetched stacking: the prefetcher assembles [K, B, ...] stacks
        # on its background thread; results match the unprefetched path.
        qc = TrajectoryQueue(capacity=64)
        fill(qc, 8)
        lc = ImpalaLearner(agent, qc, WeightStore(), batch_size=2,
                           rng=jax.random.PRNGKey(0), updates_per_call=2,
                           prefetch=True)
        try:
            for _ in range(2):
                assert lc.step(timeout=5.0) is not None
            assert lc.train_steps == 4
            jax.tree.map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6),
                la.state.params, lc.state.params)
        finally:
            lc.close()

    def test_apex_learner_updates_per_call_trains(self):
        """Replay-family updates_per_call: K scanned prioritized updates
        per train() call, priorities updated for every sampled batch."""
        from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue
        from distributed_reinforcement_learning_tpu.runtime.apex_runner import ApexLearner
        from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore
        from distributed_reinforcement_learning_tpu.utils.synthetic import synthetic_apex_batch

        cfg = ApexConfig(obs_shape=(4,), num_actions=3)
        agent = ApexAgent(cfg)
        queue = TrajectoryQueue(capacity=64)
        learner = ApexLearner(agent, queue, WeightStore(), batch_size=8,
                              replay_capacity=1000, rng=jax.random.PRNGKey(0),
                              train_start_unrolls=1, updates_per_call=3)
        one, _ = synthetic_apex_batch(32, cfg.obs_shape, cfg.num_actions)
        for _ in range(4):
            queue.put(one)
        while learner.ingest_many(timeout=0.0):
            pass
        m = learner.train()
        assert m is not None and np.isfinite(float(m["loss"]))
        assert learner.train_steps == 3
        m = learner.train()
        assert m is not None
        assert learner.train_steps == 6
        learner.close()
        queue.close()

    def test_r2d2_learner_updates_per_call_trains(self):
        """Sequence-shaped replay items through prioritized_train_call."""
        from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue
        from distributed_reinforcement_learning_tpu.runtime.r2d2_runner import R2D2Learner
        from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore
        from distributed_reinforcement_learning_tpu.agents.r2d2 import R2D2Batch

        cfg = R2D2Config(obs_shape=(2,), num_actions=2, seq_len=6, burn_in=2,
                         lstm_size=16)
        agent = R2D2Agent(cfg)
        queue = TrajectoryQueue(capacity=64)
        learner = R2D2Learner(agent, queue, WeightStore(), batch_size=4,
                              replay_capacity=1000, rng=jax.random.PRNGKey(0),
                              updates_per_call=2)
        rng = np.random.default_rng(0)
        T = cfg.seq_len
        for _ in range(2 * 4 + 2):  # past the 2*batch_size warm-up gate
            queue.put(R2D2Batch(
                state=rng.integers(0, 255, (T, 2)).astype(np.int32),
                previous_action=rng.integers(0, 2, T).astype(np.int32),
                action=rng.integers(0, 2, T).astype(np.int32),
                reward=rng.random(T).astype(np.float32),
                done=rng.random(T) < 0.1,
                initial_h=(rng.standard_normal(16) * 0.1).astype(np.float32),
                initial_c=(rng.standard_normal(16) * 0.1).astype(np.float32),
            ))
        while learner.ingest_batch(timeout=0.0):
            pass
        m = learner.train()
        assert m is not None and np.isfinite(float(m["loss"]))
        assert learner.train_steps == 2
        learner.close()
        queue.close()

    def test_updates_per_call_must_not_exceed_target_sync(self):
        from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue
        from distributed_reinforcement_learning_tpu.runtime.apex_runner import ApexLearner
        from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

        with np.testing.assert_raises(ValueError):
            ApexLearner(ApexAgent(ApexConfig(obs_shape=(4,), num_actions=2)),
                        TrajectoryQueue(capacity=8), WeightStore(), batch_size=4,
                        target_sync_interval=4, updates_per_call=8)

    def test_r2d2_learn_many_matches_sequential(self):
        from tests.test_agents import make_r2d2_batch, r2d2_cfg

        cfg = r2d2_cfg()
        agent = R2D2Agent(cfg)
        K, B = 2, 3
        batches = [make_r2d2_batch(cfg, jax.random.PRNGKey(20 + i), B=B) for i in range(K)]
        weights = [np.full(B, 1.0, np.float32) for _ in range(K)]
        s_seq = agent.init_state(jax.random.PRNGKey(0))
        prios = []
        for b, w in zip(batches, weights):
            s_seq, p, _ = agent.learn(s_seq, b, w)
            prios.append(np.asarray(p))
        s_many = agent.init_state(jax.random.PRNGKey(0))
        s_many, p_stack, _ = agent.learn_many(
            s_many, stack_trees(batches), jnp.stack([jnp.asarray(w) for w in weights]))
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6),
            s_seq.params, s_many.params)
        np.testing.assert_allclose(np.asarray(p_stack), np.stack(prios),
                                   rtol=2e-5, atol=1e-6)

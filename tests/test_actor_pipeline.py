"""Pipelined actor data plane (runtime/actor_pipeline.py).

The load-bearing pins:

- BIT-IDENTITY: with frozen weights and the documented per-slice seeds,
  a pipelined actor's per-slice trajectories (including LSTM carry,
  life-loss shaping and episode-return accounting) are byte-identical
  to plain sequential actors constructed over each slice — for every
  family, and over the real TCP transport in a two-process e2e.
- FAILURE DRILLS: killing the publisher thread or erroring a slice
  mid-round demotes to the sequential per-slice loop with zero lost or
  corrupted unrolls, and the bounded RetryLadder re-promotes.
- GATE: `maybe_wrap` follows DRL_ACTOR_PIPE (off by default;
  tests/test_gate_defaults.py pins the resolution).
"""

import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import jax

from distributed_reinforcement_learning_tpu.agents.apex import ApexAgent, ApexConfig
from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent, ImpalaConfig
from distributed_reinforcement_learning_tpu.agents.r2d2 import R2D2Agent, R2D2Config
from distributed_reinforcement_learning_tpu.agents.xformer import XformerAgent, XformerConfig
from distributed_reinforcement_learning_tpu.agents.ximpala import XImpalaAgent, XImpalaConfig
from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue
from distributed_reinforcement_learning_tpu.envs.batched import BatchedEnv
from distributed_reinforcement_learning_tpu.envs.registry import make_env
from distributed_reinforcement_learning_tpu.runtime import (
    actor_pipeline,
    apex_runner,
    impala_runner,
    r2d2_runner,
    xformer_runner,
    ximpala_runner,
)
from distributed_reinforcement_learning_tpu.runtime.actor_pipeline import (
    ActorPipeline,
    UnrollPublisher,
    slice_bounds,
    slice_seed,
)
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

WORKER = Path(__file__).resolve().parent / "actor_pipeline_worker.py"


class _LifeEnv:
    """Deterministic single env with ALE-style lives: seeds the life-loss
    shaping path (lives drop mid-episode at t=2 and t=5; episode ends at
    t=8 with return 8.0). Obs encodes (seed, t, lives, last_action) so
    any trajectory divergence shows up in the bytes."""

    num_actions = 3

    def __init__(self, seed: int):
        self._seed = seed
        self._t = 0
        self._lives = 3

    def reset(self):
        self._t, self._lives = 0, 3
        return self._obs(0)

    def _obs(self, action):
        return np.array([self._seed, self._t, self._lives, action], np.float32)

    def step(self, action: int):
        self._t += 1
        if self._t in (2 + self._seed % 2, 5):
            self._lives -= 1
        done = self._t >= 8
        reward = 1.0
        info = {"lives": self._lives}
        if done:
            self._t, self._lives = 0, 3
        return self._obs(action), reward, done, info


def _life_env(seeds):
    return BatchedEnv([(lambda s=s: _LifeEnv(s)) for s in seeds])


def _cartpole_env(seeds):
    return BatchedEnv([
        (lambda s=s: make_env("CartPole-v1", seed=s, num_actions=2))
        for s in seeds
    ])


def _drain(queue):
    items = []
    while queue.size():
        items.append(queue.get(timeout=0))
    return items


def _tree_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return False
    return all(
        np.asarray(x).dtype == np.asarray(y).dtype
        and np.asarray(x).shape == np.asarray(y).shape
        and np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def _assert_slice_identity(got_by_slice, expected_by_slice):
    for i, (got, want) in enumerate(zip(got_by_slice, expected_by_slice)):
        assert len(got) == len(want), \
            f"slice {i}: {len(got)} trajectories vs {len(want)}"
        for j, (a, b) in enumerate(zip(got, want)):
            assert _tree_equal(a, b), f"slice {i} trajectory {j} diverged"


def _split_rounds(items, sizes, rounds):
    """Pipeline publication order is (round, slice, env); regroup the
    flat queue contents into per-slice trajectory streams."""
    per_round = sum(sizes)
    assert len(items) == rounds * per_round, (len(items), rounds, per_round)
    by_slice = [[] for _ in sizes]
    idx = 0
    for _ in range(rounds):
        for i, n in enumerate(sizes):
            for _ in range(n):
                by_slice[i].append(items[idx])
                idx += 1
    return by_slice


def test_slice_bounds_and_seed():
    assert slice_bounds(4, 2) == [(0, 2), (2, 4)]
    assert slice_bounds(5, 2) == [(0, 3), (3, 5)]
    assert slice_bounds(2, 2) == [(0, 1), (1, 2)]
    with pytest.raises(ValueError):
        slice_bounds(1, 2)
    assert slice_seed(9, 0) == 9  # slice 0 keeps the actor's own seed
    assert slice_seed(9, 1) != slice_seed(9, 0)


def _frozen_weights(agent, seed=0):
    weights = WeightStore()
    weights.publish(agent.init_state(jax.random.PRNGKey(seed)).params, 0)
    return weights


def test_impala_bit_identity_life_loss_and_lstm_carry():
    """The acceptance pin: pipelined IMPALA trajectories — LSTM carry,
    life-loss shaping, episode returns — are byte-identical to plain
    sequential actors over each slice."""
    cfg = ImpalaConfig(obs_shape=(4,), num_actions=3, trajectory=6,
                       lstm_size=16)
    agent = ImpalaAgent(cfg)
    weights = _frozen_weights(agent)
    N, K, SEED, ROUNDS = 4, 2, 11, 3

    q = TrajectoryQueue(512)
    actor = impala_runner.ImpalaActor(
        agent, _life_env(range(N)), q, weights, seed=SEED,
        life_loss_shaping=True)
    pipe = ActorPipeline(actor, num_slices=K)
    for _ in range(ROUNDS):
        pipe.run_unroll()
    pipe.close()
    sizes = [hi - lo for lo, hi in slice_bounds(N, K)]
    got = _split_rounds(_drain(q), sizes, ROUNDS)

    expected, exp_returns = [], []
    for i, (lo, hi) in enumerate(slice_bounds(N, K)):
        q2 = TrajectoryQueue(512)
        plain = impala_runner.ImpalaActor(
            agent, _life_env(range(lo, hi)), q2, weights,
            seed=slice_seed(SEED, i), life_loss_shaping=True)
        for _ in range(ROUNDS):
            plain.run_unroll()
        expected.append(_drain(q2))
        exp_returns.append(plain.episode_returns)

    _assert_slice_identity(got, expected)
    # Per-slice episode-return accounting matches too (order included).
    for sl, want in zip(pipe._slices, exp_returns):
        assert sl.episode_returns == want
    assert pipe.episode_returns == [r for rs in exp_returns for r in rs]


def test_apex_bit_identity_including_local_buffer_resamples():
    """The Ape-X acceptance pin: per-step warm buffer re-samples (the
    family's publication unit, drawn from per-slice seeded buffers) are
    byte-identical to plain per-slice actors', in per-slice order."""
    cfg = ApexConfig(obs_shape=(4,), num_actions=2)
    agent = ApexAgent(cfg)
    weights = _frozen_weights(agent)
    N, K, SEED = 4, 2, 7
    kw = dict(unroll_size=8, local_capacity=256, warmup_factor=2,
              life_loss_shaping=True)

    q = TrajectoryQueue(4096)
    actor = apex_runner.ApexActor(agent, _life_env(range(N)), q, weights,
                                  seed=SEED, **kw)
    pipe = ActorPipeline(actor, num_slices=K)
    for _ in range(3):
        pipe.run_steps(16)
    pipe.close()
    got = _drain(q)

    expected = []
    for i, (lo, hi) in enumerate(slice_bounds(N, K)):
        q2 = TrajectoryQueue(4096)
        # A slice mirrors a plain actor over its envs with the
        # SLICE-SCALED warmup/capacity (ceil by env fraction — see
        # pipeline_make_slices): the aggregate pipelined actor then
        # warms up and retains like the sequential N-env actor.
        skw = dict(kw, local_capacity=-(-kw["local_capacity"]
                                        * (hi - lo) // N))
        plain = apex_runner.ApexActor(
            agent, _life_env(range(lo, hi)), q2, weights,
            seed=slice_seed(SEED, i), **skw)
        plain.warmup = -(-plain.warmup * (hi - lo) // N)
        for _ in range(3):
            plain.run_steps(16)
        expected.append(_drain(q2))

    assert len(got) == sum(len(e) for e in expected)
    # Publication interleaves slices per step; each slice's stream must
    # appear in order. Greedy per-slice subsequence matching.
    ptrs = [0] * K
    for item in got:
        for i in range(K):
            if ptrs[i] < len(expected[i]) and _tree_equal(
                    item, expected[i][ptrs[i]]):
                ptrs[i] += 1
                break
        else:
            pytest.fail("published unroll matched no slice's next expected")
    assert ptrs == [len(e) for e in expected]


@pytest.mark.parametrize("family", ["r2d2", "xformer", "ximpala"])
def test_recurrent_and_window_families_bit_identity(family):
    """Slice identity for the remaining three families (sequence-start
    LSTM state / persistent window / per-unroll-reset window)."""
    N, K, SEED, ROUNDS = 4, 2, 5, 2
    if family == "r2d2":
        agent = R2D2Agent(R2D2Config(obs_shape=(4,), num_actions=2,
                                     seq_len=6, lstm_size=16))
        make = lambda env, q, w, s: r2d2_runner.R2D2Actor(  # noqa: E731
            agent, env, q, w, seed=s)
    elif family == "xformer":
        agent = XformerAgent(XformerConfig(
            obs_shape=(4,), num_actions=2, seq_len=6, d_model=16,
            num_layers=1, num_heads=2))
        make = lambda env, q, w, s: xformer_runner.XformerActor(  # noqa: E731
            agent, env, q, w, seed=s)
    else:
        agent = XImpalaAgent(XImpalaConfig(
            obs_shape=(4,), num_actions=2, trajectory=6, d_model=16,
            num_layers=1, num_heads=2))
        make = lambda env, q, w, s: ximpala_runner.XImpalaActor(  # noqa: E731
            agent, env, q, w, seed=s)
    weights = _frozen_weights(agent)

    q = TrajectoryQueue(512)
    actor = make(_cartpole_env(range(N)), q, weights, SEED)
    pipe = ActorPipeline(actor, num_slices=K)
    for _ in range(ROUNDS):
        pipe.run_unroll()
    pipe.close()
    sizes = [hi - lo for lo, hi in slice_bounds(N, K)]
    got = _split_rounds(_drain(q), sizes, ROUNDS)

    expected = []
    for i, (lo, hi) in enumerate(slice_bounds(N, K)):
        q2 = TrajectoryQueue(512)
        plain = make(_cartpole_env(range(lo, hi)), q2, weights,
                     slice_seed(SEED, i))
        for _ in range(ROUNDS):
            plain.run_unroll()
        expected.append(_drain(q2))
    _assert_slice_identity(got, expected)


def test_xformer_discarded_act_restores_persistent_window(monkeypatch):
    """A mid-round abort settles the in-flight act and discards its
    output; the xformer family's window PERSISTS across rounds (no
    begin-round reset), so the discard must un-push it — otherwise
    every later act of that slice conditions on a duplicated timestep.
    Pins both the unpush bytes and that ActorPipeline invokes the hook
    for the right slice."""
    agent = XformerAgent(XformerConfig(
        obs_shape=(4,), num_actions=2, seq_len=6, d_model=16,
        num_layers=1, num_heads=2))
    weights = _frozen_weights(agent)
    actor = xformer_runner.XformerActor(
        agent, _cartpole_env(range(4)), TrajectoryQueue(64), weights, seed=7)

    # Unit: slice_act pushes, slice_discard_act restores the exact bytes.
    slices = actor.pipeline_make_slices(2)
    actor.pipeline_sync_weights(slices)
    sl = slices[1]
    actor.slice_begin_round(sl, actor.pipeline_round_steps())
    before = (sl.win_obs.copy(), sl.win_pa.copy(), sl.win_done.copy())
    out = actor.slice_act(sl)
    assert not np.array_equal(sl.win_done, before[2])  # push happened
    actor.slice_discard_act(sl, out)
    for got, want in zip((sl.win_obs, sl.win_pa, sl.win_done), before):
        np.testing.assert_array_equal(got, want)

    # Wiring: a slice_step error at j=0 leaves slice 1's act in flight;
    # the pipeline must settle it and route the discard to slice 1.
    actor2 = xformer_runner.XformerActor(
        agent, _cartpole_env(range(4)), TrajectoryQueue(64), weights, seed=7)
    pipe = ActorPipeline(actor2, num_slices=2)
    discarded = []
    real_hook = type(actor2).slice_discard_act
    monkeypatch.setattr(
        type(actor2), "slice_discard_act",
        lambda self, s, o: (discarded.append(s.index), real_hook(self, s, o)))
    monkeypatch.setattr(
        type(actor2), "slice_step",
        lambda self, s, o: (_ for _ in ()).throw(OSError("injected")))
    with pytest.raises(OSError, match="injected"):
        pipe.run_unroll()
    assert pipe._demoted and discarded == [1]
    pipe.close()


class _FailOnceQueue:
    """Queue wrapper whose put path raises once at a chosen call — the
    publisher-death injection (the failure fires on the PUBLISHER
    thread, before any item of that round lands)."""

    def __init__(self, inner, fail_on_call: int):
        self._inner = inner
        self._calls = 0
        self._fail_on = fail_on_call
        self.failures = 0

    def _maybe_fail(self):
        self._calls += 1
        if self._calls == self._fail_on:
            self.failures += 1
            raise RuntimeError("injected publisher death")

    def put(self, item, timeout=None):
        self._maybe_fail()
        return self._inner.put(item, timeout=timeout)

    def put_many(self, items, timeout=None):
        self._maybe_fail()
        return self._inner.put_many(items, timeout=timeout)

    def size(self):
        return self._inner.size()


def test_publisher_death_demotes_with_zero_lost_unrolls():
    """THE publisher drill: the publisher thread dies mid-stream; the
    pipeline demotes to the sequential loop, replays the carried-over
    rounds inline, loses nothing, and the RetryLadder re-promotes."""
    cfg = ImpalaConfig(obs_shape=(4,), num_actions=3, trajectory=6,
                       lstm_size=16)
    agent = ImpalaAgent(cfg)
    weights = _frozen_weights(agent)
    N, K, SEED, ROUNDS = 4, 2, 3, 4

    inner = TrajectoryQueue(512)
    q = _FailOnceQueue(inner, fail_on_call=2)  # dies on round 1, slice 1
    actor = impala_runner.ImpalaActor(agent, _life_env(range(N)), q, weights,
                                      seed=SEED, life_loss_shaping=True)
    pipe = ActorPipeline(actor, num_slices=K)
    for _ in range(ROUNDS):
        pipe.run_unroll()
    pipe.close()
    assert q.failures == 1
    assert pipe.demotions == 1

    # Zero lost, zero corrupted, exactly once: every plain per-slice
    # trajectory arrived, in per-slice order.
    sizes = [hi - lo for lo, hi in slice_bounds(N, K)]
    got = _split_rounds(_drain(inner), sizes, ROUNDS)
    expected = []
    for i, (lo, hi) in enumerate(slice_bounds(N, K)):
        q2 = TrajectoryQueue(512)
        plain = impala_runner.ImpalaActor(
            agent, _life_env(range(lo, hi)), q2, weights,
            seed=slice_seed(SEED, i), life_loss_shaping=True)
        for _ in range(ROUNDS):
            plain.run_unroll()
        expected.append(_drain(q2))
    _assert_slice_identity(got, expected)
    # The ladder re-promoted after the demotion (first probe is
    # immediately due), so later rounds ran pipelined again.
    assert not pipe._demoted


def test_slice_error_mid_round_demotes_and_keeps_unrolls_sane(monkeypatch):
    """THE slice drill: an act error mid-round propagates (run_role's
    grace loop owns retries), demotes the pipeline, and every published
    unroll before/after stays well-formed — none lost, none corrupted."""
    cfg = ImpalaConfig(obs_shape=(4,), num_actions=3, trajectory=6,
                       lstm_size=16)
    agent = ImpalaAgent(cfg)
    weights = _frozen_weights(agent)
    q = TrajectoryQueue(512)
    actor = impala_runner.ImpalaActor(agent, _life_env(range(4)), q, weights,
                                      seed=1, life_loss_shaping=True)
    pipe = ActorPipeline(actor, num_slices=2)
    pipe.run_unroll()  # one clean round

    real_act = type(actor).slice_act
    calls = {"n": 0}

    def flaky_act(self, sl):
        calls["n"] += 1
        if calls["n"] == 3:  # mid-round, second timestep
            raise OSError("injected act failure")
        return real_act(self, sl)

    monkeypatch.setattr(type(actor), "slice_act", flaky_act)
    with pytest.raises(OSError, match="injected act failure"):
        pipe.run_unroll()
    assert pipe._demoted and pipe.demotions == 1
    monkeypatch.setattr(type(actor), "slice_act", real_act)

    # Recovery: the next rounds (sequential, then re-promoted) still
    # publish complete well-formed rounds; the failed round's partial
    # accumulation was discarded, not published (no corruption).
    pipe.run_unroll()
    pipe.run_unroll()
    assert not pipe._demoted  # ladder re-promoted
    pipe.close()
    items = _drain(q)
    assert len(items) == 3 * 4  # 3 completed rounds x N envs, none extra
    T = cfg.trajectory
    for item in items:
        assert item.state.shape[0] == T
        assert np.isfinite(np.asarray(item.behavior_policy)).all()


class _FailOnCallsQueue(_FailOnceQueue):
    """Put path raises on every call number in a set — models a
    transport OUTAGE spanning the publisher death AND the first inline
    replay attempt."""

    def __init__(self, inner, fail_on_calls):
        super().__init__(inner, fail_on_call=-1)
        self._fail_calls = set(fail_on_calls)

    def _maybe_fail(self):
        self._calls += 1
        if self._calls in self._fail_calls:
            self.failures += 1
            raise RuntimeError("injected transport outage")


def test_transport_outage_spanning_inline_replay_loses_nothing():
    """The publisher dies AND the immediate inline replay fails too (a
    real outage is not one failed call): the payload must survive in
    the backlog and land on the next round — zero lost unrolls across
    the whole outage window."""
    cfg = ImpalaConfig(obs_shape=(4,), num_actions=3, trajectory=6,
                       lstm_size=16)
    agent = ImpalaAgent(cfg)
    weights = _frozen_weights(agent)
    N, K, SEED, ROUNDS = 4, 2, 11, 4

    inner = TrajectoryQueue(512)
    q = _FailOnCallsQueue(inner, fail_on_calls={2, 3})  # worker death +
    #   first inline replay both hit the downed transport
    actor = impala_runner.ImpalaActor(agent, _life_env(range(N)), q, weights,
                                      seed=SEED, life_loss_shaping=True)
    pipe = ActorPipeline(actor, num_slices=K)
    completed = 0
    while completed < ROUNDS:
        try:
            pipe.run_unroll()
            completed += 1
        except RuntimeError:
            pass  # run_role's grace loop owns retries
    pipe.close()
    assert q.failures == 2
    assert pipe.demotions == 1

    # Stepping timeline: r1 OK; r2 aborts at slice 0's end-round put
    # (worker death on call 2, inline replay fails on call 3) with
    # slice 0 already EXTRACTED (must survive via the backlog) and
    # slice 1 not yet extracted (its fully-stepped accumulation is
    # discarded by the retry's begin-round reset — the slice drill's
    # pinned semantics); r3-r5 = the three remaining successes. So
    # slice 0 publishes plain rounds 1-5, slice 1 all but round 2.
    stepped = ROUNDS + 1
    expected = []
    for i, (lo, hi) in enumerate(slice_bounds(N, K)):
        q2 = TrajectoryQueue(512)
        plain = impala_runner.ImpalaActor(
            agent, _life_env(range(lo, hi)), q2, weights,
            seed=slice_seed(SEED, i), life_loss_shaping=True)
        rounds_i = []
        for _ in range(stepped):
            plain.run_unroll()
            rounds_i.append(_drain(q2))
        if i == 1:
            del rounds_i[1]  # the aborted round's discarded accumulation
        expected.append([item for rnd in rounds_i for item in rnd])
    got_flat = _drain(inner)
    assert len(got_flat) == sum(len(e) for e in expected)
    # Per-slice order is preserved even across the outage; match each
    # published item against its slice's next expected (publication
    # interleaves slices, so use greedy per-slice subsequences).
    ptrs = [0] * K
    for item in got_flat:
        for i in range(K):
            if ptrs[i] < len(expected[i]) and _tree_equal(
                    item, expected[i][ptrs[i]]):
                ptrs[i] += 1
                break
        else:
            pytest.fail("published unroll matched no slice's next expected")
    assert ptrs == [len(e) for e in expected]


def test_wedged_pipeline_dies_visibly():
    """A settle timeout (the act worker still running, owning a slice)
    latches the pipeline: further rounds raise instead of racing the
    worker from the demoted sequential loop."""
    cfg = ImpalaConfig(obs_shape=(4,), num_actions=3, trajectory=4,
                       lstm_size=16)
    agent = ImpalaAgent(cfg)
    actor = impala_runner.ImpalaActor(
        agent, _life_env(range(4)), TrajectoryQueue(64),
        _frozen_weights(agent), seed=1)
    pipe = ActorPipeline(actor, num_slices=2)
    pipe.run_unroll()
    pipe._wedged = True  # what the 30s settle timeout latches
    with pytest.raises(RuntimeError, match="wedged"):
        pipe.run_unroll()
    pipe.close()


def test_stuck_publisher_latches_wedge_instead_of_double_producing():
    """drain() timing out against a worker still INSIDE a put must not
    hand the payload to an inline replay on the same queue — on the
    SPSC shm ring that would be two concurrent producers. The publisher
    reports `stuck`; the pipeline's demote path latches dead-visible
    and keeps the payload in the backlog."""
    release = threading.Event()

    class _BlockingQueue:
        def __init__(self):
            self.puts = 0

        def put(self, item, timeout=None):
            self.puts += 1
            release.wait(timeout=30.0)

        put_many = put

    q = _BlockingQueue()
    pub = UnrollPublisher(q, depth=2).start()
    pub._JOIN_S = 0.2  # don't wait the real 10s in a test
    assert pub.submit(("put", {"a": np.zeros(2)}))
    deadline = time.monotonic() + 5.0
    while q.puts == 0 and time.monotonic() < deadline:
        time.sleep(0.01)  # worker is now inside the blocked put
    leftover = pub.drain()
    assert pub.stuck, "drain must report the worker still inside the put"
    assert len(leftover) == 1  # the in-flight payload handed back, not lost
    release.set()
    slow = TrajectoryQueue(64)
    real_put = slow.put

    def slow_put(item, timeout=None):
        time.sleep(0.15)
        return real_put(item, timeout=timeout)

    slow.put = slow_put
    pub = UnrollPublisher(slow, depth=2).start()
    # depth bounds the UNPUBLISHED rounds, the in-flight one included
    # (peek-then-pop: a payload leaves the deque only when its put
    # succeeded): 2 submits absorb without blocking...
    t0 = time.perf_counter()
    for _ in range(2):
        assert pub.submit(("put", {"a": np.zeros(2)}))
    fast = time.perf_counter() - t0
    t0 = time.perf_counter()
    assert pub.submit(("put", {"a": np.zeros(2)}))  # ...the 3rd must wait
    waited = time.perf_counter() - t0
    assert fast < 0.1, f"bounded submits should not block ({fast:.3f}s)"
    assert waited > 0.02, f"submit past depth must backpressure ({waited:.3f}s)"
    leftover = pub.drain()
    for payload in leftover:
        pub.publish_one(payload)
    assert slow.size() == 3


def test_maybe_wrap_respects_gate_and_sliceability(monkeypatch):
    cfg = ImpalaConfig(obs_shape=(4,), num_actions=3, trajectory=4,
                       lstm_size=8)
    agent = ImpalaAgent(cfg)
    weights = _frozen_weights(agent)
    q = TrajectoryQueue(64)
    actor = impala_runner.ImpalaActor(agent, _life_env(range(2)), q, weights,
                                      seed=0)
    monkeypatch.setenv("DRL_ACTOR_PIPE", "0")
    assert actor_pipeline.maybe_wrap(actor) is actor
    monkeypatch.setenv("DRL_ACTOR_PIPE", "1")
    wrapped = actor_pipeline.maybe_wrap(actor)
    assert isinstance(wrapped, ActorPipeline)
    wrapped.close()
    # Unsliceable (single env): stays sequential with a logged reason.
    solo = impala_runner.ImpalaActor(agent, _life_env(range(1)),
                                     TrajectoryQueue(64), weights, seed=0)
    assert actor_pipeline.maybe_wrap(solo) is solo


def test_run_actor_thread_logs_deaths(capsys):
    class _Dying:
        def run_unroll(self):
            raise ValueError("boom: injected actor death")

    stop = threading.Event()
    actor_pipeline.run_actor_thread(_Dying(), stop)
    err = capsys.readouterr().err
    assert "thread died" in err and "injected actor death" in err
    # Shutdown race stays quiet: a closing queue is not a death.
    stop.set()
    actor_pipeline.run_actor_thread(_Dying(), stop)
    assert "boom" not in capsys.readouterr().err


def test_two_process_e2e_over_real_transport():
    """The transport pin: a pipelined actor CHILD PROCESS shipping over
    real TCP lands trajectories bit-identical to plain per-slice actors
    run in-process against the same published weights."""
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        TransportServer)

    cfg = ImpalaConfig(obs_shape=(4,), num_actions=2, trajectory=8,
                       lstm_size=32)
    agent = ImpalaAgent(cfg)
    weights = _frozen_weights(agent)
    queue = TrajectoryQueue(1024)
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    server = TransportServer(queue, weights, host="127.0.0.1",
                             port=port).start()
    N, SEED, ROUNDS = 4, 21, 3
    try:
        proc = subprocess.run(
            [sys.executable, str(WORKER), "127.0.0.1", str(port), str(SEED),
             str(N), str(ROUNDS)],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-800:]
        line = next(ln for ln in proc.stdout.splitlines()
                    if ln.startswith("ACTOR_PIPE_WORKER="))
        report = json.loads(line.split("=", 1)[1])
        assert report["demotions"] == 0, "e2e must stay pipelined throughout"
        assert report["frames"] == ROUNDS * N * cfg.trajectory
    finally:
        server.stop()

    sizes = [hi - lo for lo, hi in slice_bounds(N, 2)]
    got = _split_rounds(_drain(queue), sizes, ROUNDS)
    expected = []
    for i, (lo, hi) in enumerate(slice_bounds(N, 2)):
        q2 = TrajectoryQueue(512)
        plain = impala_runner.ImpalaActor(
            agent, _cartpole_env(range(lo, hi)), q2, weights,
            seed=slice_seed(SEED, i))
        for _ in range(ROUNDS):
            plain.run_unroll()
        expected.append(_drain(q2))
    _assert_slice_identity(got, expected)


def test_apex_and_r2d2_run_async_smoke():
    """The new async loops drive stub learners without hanging and
    close cleanly (the per-family learner loops are covered by e2e
    tests; this pins the thread/shutdown plumbing)."""

    class _StubLearner:
        def __init__(self):
            self.train_steps = 0
            self.closed = False

        def ingest_many(self, timeout=None):
            return 0

        def ingest_batch(self, timeout=None):
            return 0

        def train(self):
            self.train_steps += 1
            return {}

        def close(self):
            self.closed = True

    class _StubActor:
        episode_returns: list = []

        def run_steps(self, n):
            time.sleep(0.001)
            return n

        def run_unroll(self):
            time.sleep(0.001)
            return 1

    for runner in (apex_runner, r2d2_runner):
        learner, queue = _StubLearner(), TrajectoryQueue(8)
        out = runner.run_async(learner, [_StubActor()], num_updates=3,
                               queue=queue)
        assert learner.train_steps >= 3 and learner.closed
        assert out["episode_returns"] == []

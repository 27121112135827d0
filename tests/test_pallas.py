"""Pallas kernel numerics: interpret-mode vs the lax.scan references.

On this CPU CI host the kernels run through the Pallas interpreter
(`interpret=True`), which exercises the exact kernel code the TPU
compiles. Forward outputs must match the scan references to fp32
round-off; LSTM gradients (hand-derived BPTT kernel) must match autodiff
of the reference scan.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.ops import vtrace as vt
from distributed_reinforcement_learning_tpu.ops.lstm import lstm_scan
from distributed_reinforcement_learning_tpu.ops.pallas import resolve_backend
from distributed_reinforcement_learning_tpu.ops.pallas.vtrace import vtrace_pallas


def test_resolve_backend():
    assert resolve_backend("reference") == "reference"
    assert resolve_backend("pallas_interpret") == "pallas_interpret"
    # On the CPU test host, auto falls back to the scan reference.
    assert resolve_backend("auto") == "reference"
    with pytest.raises(ValueError):
        resolve_backend("cuda")


def test_resolve_backend_opt_in_env(monkeypatch):
    """Ops without an established margin (the fused LSTM) stay demoted on
    TPU under `auto` unless their opt-in env var is set; an explicit
    backend always wins. Simulated-TPU so the gate is observable."""
    import distributed_reinforcement_learning_tpu.ops.pallas as pallas_pkg

    monkeypatch.setattr(pallas_pkg.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("DRL_LSTM_PALLAS", raising=False)
    # Established ops (no opt_in_env) auto-enable on TPU...
    assert resolve_backend("auto") == "pallas"
    # ...opt-in ops do not, until their env var says so.
    assert resolve_backend("auto", opt_in_env="DRL_LSTM_PALLAS") == "reference"
    monkeypatch.setenv("DRL_LSTM_PALLAS", "1")
    assert resolve_backend("auto", opt_in_env="DRL_LSTM_PALLAS") == "pallas"
    # Explicit selection bypasses the gate entirely.
    monkeypatch.delenv("DRL_LSTM_PALLAS")
    assert resolve_backend("pallas", opt_in_env="DRL_LSTM_PALLAS") == "pallas"
    # The global kill switch still dominates.
    monkeypatch.setenv("DRL_TPU_PALLAS", "0")
    assert resolve_backend("auto") == "reference"


@pytest.mark.parametrize("T,B", [(18, 32), (10, 16), (5, 256), (20, 384)])
def test_vtrace_kernel_matches_scan(T, B):
    rng = np.random.RandomState(0)
    log_rhos = (rng.randn(T, B) * 0.3).astype(np.float32)
    discounts = ((rng.rand(T, B) > 0.1) * 0.99).astype(np.float32)
    rewards = rng.randn(T, B).astype(np.float32)
    values = rng.randn(T, B).astype(np.float32)
    boot = rng.randn(B).astype(np.float32)

    ref = vt.from_importance_weights(
        jnp.array(log_rhos), jnp.array(discounts), jnp.array(rewards),
        jnp.array(values), jnp.array(boot), backend="reference")
    vs, rhos = vtrace_pallas(log_rhos, discounts, rewards, values, boot, interpret=True)
    np.testing.assert_allclose(np.array(ref.vs), np.array(vs), atol=2e-6)
    np.testing.assert_allclose(np.array(ref.clipped_rhos), np.array(rhos), atol=1e-7)


def test_vtrace_kernel_no_rho_clip():
    rng = np.random.RandomState(3)
    T, B = 8, 16
    args = [(rng.randn(T, B) * 0.3).astype(np.float32) for _ in range(4)]
    boot = rng.randn(B).astype(np.float32)
    discounts = np.full((T, B), 0.99, np.float32)
    ref = vt.from_importance_weights(
        jnp.array(args[0]), jnp.array(discounts), jnp.array(args[2]),
        jnp.array(args[3]), jnp.array(boot),
        clip_rho_threshold=None, backend="reference")
    vs, rhos = vtrace_pallas(args[0], discounts, args[2], args[3], boot,
                             clip_rho_threshold=None, interpret=True)
    np.testing.assert_allclose(np.array(ref.vs), np.array(vs), atol=2e-6)
    np.testing.assert_allclose(np.array(ref.clipped_rhos), np.array(rhos), atol=1e-7)


def test_from_importance_weights_backend_dispatch():
    """backend='pallas_interpret' through the public op returns the same
    stop-gradiented VTraceReturns as the reference path."""
    rng = np.random.RandomState(1)
    T, B = 12, 8
    log_rhos = jnp.array((rng.randn(T, B) * 0.2).astype(np.float32))
    discounts = jnp.full((T, B), 0.99)
    rewards = jnp.array(rng.randn(T, B).astype(np.float32))
    values = jnp.array(rng.randn(T, B).astype(np.float32))
    boot = jnp.array(rng.randn(B).astype(np.float32))
    ref = vt.from_importance_weights(log_rhos, discounts, rewards, values, boot,
                                     backend="reference")
    pal = vt.from_importance_weights(log_rhos, discounts, rewards, values, boot,
                                     backend="pallas_interpret")
    np.testing.assert_allclose(np.array(ref.vs), np.array(pal.vs), atol=2e-6)


def _lstm_inputs(B=8, T=10, H=32, seed=1):
    rng = np.random.RandomState(seed)
    return (
        (rng.randn(B, T, 4 * H) * 0.5).astype(np.float32),
        (rng.randn(H, 4 * H) / np.sqrt(H)).astype(np.float32),
        (rng.rand(B, T) > 0.15).astype(np.float32),
        (rng.randn(B, H) * 0.1).astype(np.float32),
        (rng.randn(B, H) * 0.1).astype(np.float32),
    )


@pytest.mark.parametrize("B,T,H", [(8, 10, 32), (16, 5, 64), (128, 4, 32)])
def test_lstm_kernel_forward_matches_scan(B, T, H):
    xg, wh, keep, h0, c0 = _lstm_inputs(B, T, H)
    ref_h, (ref_hT, ref_cT) = lstm_scan(xg, wh, keep, h0, c0, backend="reference")
    pal_h, (pal_hT, pal_cT) = lstm_scan(xg, wh, keep, h0, c0, backend="pallas_interpret")
    np.testing.assert_allclose(np.array(ref_h), np.array(pal_h), atol=1e-6)
    np.testing.assert_allclose(np.array(ref_hT), np.array(pal_hT), atol=1e-6)
    np.testing.assert_allclose(np.array(ref_cT), np.array(pal_cT), atol=1e-6)


def test_lstm_kernel_gradients_match_autodiff():
    """The hand-derived BPTT kernel vs jax.grad of the scan reference,
    through a loss touching h_all, hT and cT."""
    xg, wh, keep, h0, c0 = _lstm_inputs()
    H = h0.shape[-1]

    def loss(backend):
        def f(args):
            xg, wh, h0, c0 = args
            h_all, (hT, cT) = lstm_scan(xg, wh, keep, h0, c0, backend=backend)
            return (jnp.sum(h_all * jnp.cos(jnp.arange(H)))
                    + jnp.sum(hT ** 2) + 0.3 * jnp.sum(cT))
        return f

    args = tuple(map(jnp.asarray, (xg, wh, h0, c0)))
    ref_v, ref_g = jax.value_and_grad(loss("reference"))(args)
    pal_v, pal_g = jax.value_and_grad(loss("pallas_interpret"))(args)
    assert abs(float(ref_v - pal_v)) < 1e-4
    for name, a, b in zip(("dxg", "dwh", "dh0", "dc0"), ref_g, pal_g):
        err = np.abs(np.array(a) - np.array(b)).max()
        assert err < 5e-6, f"{name}: {err}"


def test_lstm_done_mask_resets_state():
    """A done at step t zeroes the carried state entering t+1: the kernel's
    post-done output must equal a fresh-state run of the tail."""
    xg, wh, _, h0, c0 = _lstm_inputs(B=4, T=6, H=16)
    keep = np.ones((4, 6), np.float32)
    keep[:, 2] = 0.0  # episode boundary after step 2
    h_all, _ = lstm_scan(xg, wh, keep, h0, c0, backend="pallas_interpret")
    zero = np.zeros_like(h0)
    tail, _ = lstm_scan(xg[:, 3:], wh, keep[:, 3:], zero, zero,
                        backend="pallas_interpret")
    np.testing.assert_allclose(np.array(h_all[:, 3:]), np.array(tail), atol=1e-6)


def test_r2d2_unroll_pallas_matches_reference_model():
    """Whole-model check: R2D2Net.unroll with the pallas cell backend vs
    the reference backend on identical params/inputs."""
    from distributed_reinforcement_learning_tpu.models.r2d2_net import R2D2Net

    rng = np.random.RandomState(5)
    B, T, A = 4, 10, 2
    obs = rng.randn(B, T, 2).astype(np.float32)
    pa = rng.randint(0, A, (B, T)).astype(np.int32)
    done = rng.rand(B, T) > 0.8
    h0 = np.zeros((B, 64), np.float32)
    c0 = np.zeros((B, 64), np.float32)

    net_ref = R2D2Net(num_actions=A, lstm_size=64)
    params = net_ref.init(jax.random.PRNGKey(0), obs[:, 0], pa[:, 0], h0, c0)
    q_ref = net_ref.apply(params, obs, pa, done, h0, c0, method="unroll")

    net_pal = R2D2Net(num_actions=A, lstm_size=64, cell_backend="pallas_interpret")
    q_pal = net_pal.apply(params, obs, pa, done, h0, c0, method="unroll")
    np.testing.assert_allclose(np.array(q_ref), np.array(q_pal), atol=1e-5)


class TestKernelsUnderAMesh:
    """`batch_partitioned`: a kernel traced under a context mesh (what
    `ShardedLearner` sets) wraps itself in a shard_map and runs on each
    device's own batch rows — on a TPU a Mosaic kernel does not lower
    any other way. Interpret mode on the 8 virtual devices."""

    @staticmethod
    def _on_mesh(mesh, fn):
        def traced(*args):
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                return fn(*args)
        return traced

    def test_vtrace_runs_on_each_devices_own_columns(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from distributed_reinforcement_learning_tpu.parallel import make_mesh

        mesh = make_mesh(8)
        rng = np.random.RandomState(0)
        T, B = 18, 32
        args = [(rng.randn(T, B) * 0.3).astype(np.float32) for _ in range(4)]
        boot = rng.randn(B).astype(np.float32)
        # Without a mesh first: the mesh trace must not reuse this one.
        plain = vtrace_pallas(*args, boot, interpret=True)
        seq = NamedSharding(mesh, P(None, "data"))
        f = jax.jit(
            self._on_mesh(mesh, lambda *a: vtrace_pallas(*a, interpret=True)),
            in_shardings=(seq,) * 4 + (NamedSharding(mesh, P("data")),),
            out_shardings=(seq, seq))
        vs, rhos = f(*args, boot)
        np.testing.assert_allclose(np.asarray(vs), np.asarray(plain[0]), atol=1e-6)
        np.testing.assert_allclose(np.asarray(rhos), np.asarray(plain[1]), atol=1e-7)
        assert "manual_computation" in f.lower(*args, boot).as_text()  # the shard_map
        assert "all-gather" not in f.lower(*args, boot).compile().as_text()

    def test_flash_attention_grads_match_the_single_device_call(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from distributed_reinforcement_learning_tpu.ops.pallas.attention import (
            flash_attention_bhtd)
        from distributed_reinforcement_learning_tpu.parallel import make_mesh

        mesh = make_mesh(8)
        rng = np.random.RandomState(1)
        q, k, v = (rng.randn(16, 16, 8).astype(np.float32) for _ in range(3))
        seg = np.zeros((16, 16), np.int32)

        def loss(q, k, v, seg):
            out = flash_attention_bhtd(q, k, v, seg, seg, block_q=8,
                                       block_kv=8, interpret=True)
            return jnp.sum(out ** 2)

        grad = jax.value_and_grad(loss, argnums=(0, 1, 2))
        rows = NamedSharding(mesh, P("data"))
        sharded = jax.jit(self._on_mesh(mesh, grad), in_shardings=(rows,) * 4)
        (l1, g1), (l0, g0) = sharded(q, k, v, seg), jax.jit(grad)(q, k, v, seg)
        np.testing.assert_allclose(l1, l0, rtol=1e-5)
        for a, b in zip(g1, g0):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
        assert g1[0].sharding.spec == P("data")

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


# -- a static sliding window in the flash kernels (ISSUE 49) ----------------------

def _window_case(t=64, b=2, h=2, d=16, seed=0):
    """q, k, v `[B, T, H, D]` and episode ids with ends inside blocks."""
    r = np.random.RandomState(seed)
    q, k, v = (jnp.asarray(r.normal(size=(b, t, h, d)), jnp.float32) for _ in range(3))
    done = np.zeros((b, t), bool)
    done[0, 21], done[1, 40] = True, True
    seg = jnp.asarray(np.cumsum(done, axis=1) - done, jnp.int32)
    return q, k, v, seg


def _flash_bthd(q, k, v, seg, bq, bkv, window):
    from distributed_reinforcement_learning_tpu.ops.pallas.attention import (
        flash_attention_bhtd)

    b, t, h, _ = q.shape
    flat = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, x.shape[-1])
    out = flash_attention_bhtd(flat(q), flat(k), flat(v), jnp.repeat(seg, h, 0),
                               jnp.repeat(seg, h, 0), block_q=bq, block_kv=bkv,
                               interpret=True, window=window)
    return out.reshape(b, h, t, v.shape[-1]).transpose(0, 2, 1, 3)


# T 64. 16: a block's side, so a q block's window ends ON a kv block's edge for
# its last row and cuts the block before it for every other; 24 and 33 cut
# inside; 1: itself alone; 32: two blocks; 64 and 100: the whole row and more.
@pytest.mark.parametrize("blocks", [(16, 16), (8, 32), (32, 16)])
@pytest.mark.parametrize("window", [1, 7, 16, 24, 32, 33, 64, 100])
def test_windowed_flash_kernels_match_dense(window, blocks):
    """Interpret mode: the forward and all three gradients against
    `dense_attention(window=W)`, with an episode end inside a block."""
    from distributed_reinforcement_learning_tpu.ops.attention import dense_attention

    q, k, v, seg = _window_case()
    weigh = lambda out: jnp.sum(jnp.sin(out))
    want = jax.value_and_grad(lambda *a: weigh(dense_attention(
        *a, q_seg=seg, k_seg=seg, window=window)), (0, 1, 2))(q, k, v)
    got = jax.value_and_grad(lambda *a: weigh(_flash_bthd(
        *a, seg, *blocks, window)), (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("window", [None, 64, 1000])
def test_without_a_window_the_kernels_are_what_they_were(window):
    """`window=None` is the call without the argument, bit for bit, values
    and gradients (the lowered text of the five token chunks is held to the
    parent's by `scripts/chunk_text_digest.py`); a window that reaches the
    whole row lets the same pairs through."""
    from distributed_reinforcement_learning_tpu.ops.pallas.attention import (
        flash_attention_bhtd)

    q, k, v, seg = _window_case()
    b, t, h, d = q.shape
    flat = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    ids = jnp.repeat(seg, h, 0)

    def run(**kw):
        return jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(flash_attention_bhtd(
            *a, ids, ids, block_q=16, block_kv=16, interpret=True, **kw))),
            (0, 1, 2))(flat(q), flat(k), flat(v))

    for a, b in zip(jax.tree.leaves(run()), jax.tree.leaves(run(window=window))):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("bq,bkv", [(16, 16), (8, 32), (32, 16), (512, 512)])
@pytest.mark.parametrize("window", [1, 7, 16, 24, 33, 4096])
def test_the_walks_are_the_blocks_the_mask_lets_through(bq, bkv, window):
    """`_kv_walk` / `_q_walk` against the mask itself: the kv blocks a q
    block computes are exactly those with a visible pair (so a block wholly
    outside the window is neither computed nor, the index maps being
    clamped to the same bounds, fetched), and the same for the q blocks of a
    kv block."""
    from distributed_reinforcement_learning_tpu.ops.pallas import attention as fa

    t = 16 * max(bq, bkv)
    pos = np.arange(t)
    seen = (pos[:, None] >= pos[None, :]) & (pos[:, None] - pos[None, :] < window)
    by_block = seen.reshape(t // bq, bq, t // bkv, bkv).any(axis=(1, 3))
    for iq in range(t // bq):
        first, last = fa._kv_walk(jnp.int32(iq), bq, bkv, window)
        assert list(np.flatnonzero(by_block[iq])) == list(range(int(first), int(last) + 1))
    for jk in range(t // bkv):
        first, last = fa._q_walk(jnp.int32(jk), bq, bkv, t // bq, window)
        assert list(np.flatnonzero(by_block[:, jk])) == list(range(int(first), int(last) + 1))
    assert fa._kv_walk(3, bq, bkv, None)[0] is None
    assert fa._q_walk(3, bq, bkv, t // bq, None)[1] is None
    # at the cell's shape a window layer computes 108 of a full layer's 136 blocks
    if (bq, bkv, window) == (512, 512, 4096):
        assert by_block.sum() == 108 and np.tril(np.ones((16, 16))).sum() == 136


@pytest.mark.parametrize("window", [5, 16, 40])
def test_every_path_of_causal_attention_takes_the_window(window):
    """The dense path, the blockwise path and the kernels (interpret mode)
    behind `causal_attention`'s one argument; a window under 1 is refused."""
    from distributed_reinforcement_learning_tpu.ops.attention import (
        blockwise_attention, causal_attention, dense_attention)

    q, k, v, seg = _window_case(t=128, d=8)
    want = dense_attention(q, k, v, q_seg=seg, k_seg=seg, window=window)
    full = dense_attention(q, k, v, q_seg=seg, k_seg=seg)
    assert float(jnp.max(jnp.abs(want - full))) > 1e-2
    for backend in ("reference", "pallas_interpret"):
        got = causal_attention(q, k, v, q_seg=seg, k_seg=seg, backend=backend,
                               window=window)
        np.testing.assert_allclose(got, want, atol=2e-5)
    got = blockwise_attention(q, k, v, block_size=32, segment_ids=seg,
                              kv_segment_ids=seg, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5)
    with pytest.raises(ValueError, match="sees itself"):
        causal_attention(q, k, v, q_seg=seg, k_seg=seg, backend="pallas_interpret",
                         window=0)

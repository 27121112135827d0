"""Ahead-of-time compiles for a DESCRIBED TPU v5e — no chip attached.

The TPU compiler ships with libtpu and compiles for a topology that is
described, not opened (`/opt/skills/guides/on-chip-measurement`, section
2). That catches what interpret mode cannot — a slice not aligned to the
tiling, a kernel over its VMEM budget, a program Mosaic refuses — at the
shapes the main path really runs (`config.json` sections `impala`,
`apex`, `r2d2_pixel`, `r2d2_atari`, `ouro_looplm`, `granite_hybrid`, `qwen3_next`, `joyai_flash`, `lfm2_moe`, `smallthinker_moe`, `nemotron_h_moe`; the Anakin chunk
`chip_smoke.py` drives), and
costs no chip time. It also shows what the compiler DID with a program:
which layout copies and which collectives it put in (the fused IMPALA
chunk's handoff, PR 29).

A compile that passes is not a chip run: nothing executes here, so
these tests say nothing about results or speed.

`resolve_backend("auto")` reads `jax.default_backend()`, which is the
CPU in this process, so the whole-step tests answer that one question
as the chip would (`kernels_as_on_chip`) — the program grows no option
for it.

The kernels and the float32 IMPALA step run in tier-1; the other whole
steps (10-25 s each) are marked slow and run before a chip call.
"""

import collections
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import distributed_reinforcement_learning_tpu.ops.pallas as pallas_pkg
from distributed_reinforcement_learning_tpu.ops import lstm as lstm_ops
from distributed_reinforcement_learning_tpu.ops.pallas.attention import (
    flash_attention_bhtd, flash_blocks)
from distributed_reinforcement_learning_tpu.ops.pallas.lstm import lstm_pallas
from distributed_reinforcement_learning_tpu.ops.pallas.vtrace import vtrace_pallas
from distributed_reinforcement_learning_tpu.utils import synthetic
from distributed_reinforcement_learning_tpu.utils.config import load_config

CONFIG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "config.json")


@pytest.fixture(scope="module")
def four_chips():
    """The devices of a described four-chip v5e host."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / no such topology
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return list(topo.devices)


@pytest.fixture(scope="module")
def chip(four_chips):
    """One described v5e device as a sharding (the compile target)."""
    return SingleDeviceSharding(four_chips[0])


def _kernels_as_on_chip(monkeypatch):
    """`auto` kernel selection answers as on the chip: the real
    resolver (env gates included) with `jax.default_backend()` reading
    "tpu" for the duration of that one call."""
    real = pallas_pkg.resolve_backend

    def resolve(backend="auto", opt_in_env=None):
        with pytest.MonkeyPatch.context() as m:
            m.setattr(pallas_pkg.jax, "default_backend", lambda: "tpu")
            return real(backend, opt_in_env)

    monkeypatch.setattr(pallas_pkg, "resolve_backend", resolve)
    monkeypatch.setattr(lstm_ops, "resolve_backend", resolve)


@pytest.fixture
def kernels_as_on_chip(monkeypatch):
    _kernels_as_on_chip(monkeypatch)


def _on(chip, tree):
    """Shapes of `tree`'s leaves, placed on the described device."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)


def _kernel_calls(compiled, inside: str = "") -> int:
    """Mosaic kernels in the compiled program (`tpu_custom_call`), of
    those whose op name mentions `inside`. An interpret-mode kernel
    lowers to plain HLO and counts zero."""
    return sum("tpu_custom_call" in line and inside in line
               for line in compiled.as_text().splitlines())


# T-2 = 18 is the learn step's view of a 20-step unroll; B = 32 the
# `impala` batch, 8 its per-device share on a four-chip mesh, 20 the
# Anakin env count of that section, 256 the largest learn-sweep batch
# one grid step owns.
@pytest.mark.parametrize("T,B", [(18, 32), (18, 8), (18, 20), (20, 256), (20, 512)])
def test_vtrace_kernel_compiles(chip, T, B):
    s = jax.ShapeDtypeStruct((T, B), jnp.float32, sharding=chip)
    boot = jax.ShapeDtypeStruct((B,), jnp.float32, sharding=chip)
    compiled = vtrace_pallas.lower(s, s, s, s, boot).compile()
    assert _kernel_calls(compiled) == 1


# (seq_len, batch, lstm) of `r2d2_pixel`, `r2d2`, and the IMPALA
# stored-state width at a learn-sweep batch.
@pytest.mark.parametrize("T,B,H", [(20, 32, 256), (10, 32, 512), (20, 256, 256)])
def test_lstm_kernel_fwd_bwd_compiles(chip, T, B, H):
    def loss(xg, wh, keep, h0, c0):
        h_all, hT, cT = lstm_pallas(xg, wh, keep, h0, c0)
        return jnp.sum(h_all * h_all) + jnp.sum(hT) + jnp.sum(cT)

    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        f32(T, B, 4 * H), f32(H, 4 * H), f32(T, B, 1), f32(B, H), f32(B, H)
    ).compile()
    assert _kernel_calls(compiled) == 2  # forward + BPTT


# (batch*heads, T, head_dim, dtype, the rule's tile): a transformer 256 wide
# (4 heads, T=32), a long-context row, a bf16 row, and the learner's rows of
# the Qwen3-Next, granite and Ouro cells (q/k = v there), and a T that only
# 8 divides (its kv side is the whole row: 128s or nothing along the lanes).
@pytest.mark.parametrize("BH,T,D,dtype,tile", [
    (128, 32, 64, jnp.float32, (32, 32)),
    (16, 2048, 64, jnp.bfloat16, (512, 512)),
    (64, 512, 64, jnp.bfloat16, (512, 512)),
    (64, 1024, 256, jnp.bfloat16, (512, 512)),
    (128, 1024, 64, jnp.bfloat16, (512, 512)),
    (512, 128, 128, jnp.bfloat16, (128, 128)),
    (8, 1000, 64, jnp.float32, (8, 1000)),
])
def test_flash_attention_fwd_bwd_compiles(chip, BH, T, D, dtype, tile):
    assert flash_blocks(T, D, D, jnp.dtype(dtype).itemsize) == tile

    def loss(q, k, v, seg):
        out = flash_attention_bhtd(q, k, v, seg, seg)  # the rule's tile
        return jnp.sum(out.astype(jnp.float32) ** 2)

    qkv = jax.ShapeDtypeStruct((BH, T, D), dtype, sharding=chip)
    seg = jax.ShapeDtypeStruct((BH, T), jnp.int32, sharding=chip)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv, seg).compile()
    assert _kernel_calls(compiled) == 3  # forward, dq, dkv


def _impala_learn_compiled(chip, dtype):
    import dataclasses

    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent

    cfg, rt = load_config(CONFIG, "impala")
    agent = ImpalaAgent(dataclasses.replace(cfg, dtype=dtype))
    state = jax.eval_shape(agent.init_state, jax.random.PRNGKey(0))
    batch = synthetic.synthetic_impala_batch(
        rt.batch_size, cfg.trajectory, cfg.obs_shape, cfg.num_actions,
        cfg.lstm_size)
    return agent.learn.lower(_on(chip, state), _on(chip, batch)).compile()


def test_impala_learn_step_holds_the_vtrace_kernel(chip, kernels_as_on_chip):
    """`impala` section widths (84x84x4 frames, LSTM 256, T=20, B=32):
    the step compiles for the v5e with both V-trace passes as Mosaic
    kernels — what chip_smoke.py asserts again on the chip itself."""
    compiled = _impala_learn_compiled(chip, jnp.float32)
    assert _kernel_calls(compiled, "vtrace_pallas") == 2


def _sharded_learn_compiled(devices, agent, *data):
    """`ShardedLearner.learn` over the `(data,)` mesh `run_role` builds
    on a multi-chip host, compiled for `data`'s shapes (batch, and for
    the replay families the IS weights)."""
    from distributed_reinforcement_learning_tpu.parallel import (
        ShardedLearner, data_sharding, make_mesh)

    learner = ShardedLearner(agent, make_mesh(devices=devices),
                             num_data_args=len(data), num_aux_outputs=len(data))
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(agent.init_state, jax.random.PRNGKey(0)),
        learner.state_sharding)
    rows = data_sharding(learner.mesh)
    data = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rows), data)
    return learner.learn.lower(state, *data).compile()


@pytest.mark.slow
def test_sharded_impala_learn_step_compiles_for_four_chips(
        four_chips, kernels_as_on_chip):
    """What a learner on a four-chip host runs: `ShardedLearner` over
    the `(data,)` mesh `run_role` builds. GSPMD cannot partition a
    Mosaic kernel, so each V-trace pass has to arrive as a shard_map
    over the mesh (`ops/pallas.batch_partitioned`): per-device [18, 8]
    kernels, one gradient all-reduce, and no all-gather of the batch.
    (This compile refused the step before PR 21, and it refused a
    `custom_partitioning` wrapper exactly as four real chips then did.)"""
    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent

    cfg, rt = load_config(CONFIG, "impala")
    compiled = _sharded_learn_compiled(
        four_chips, ImpalaAgent(cfg),
        synthetic.synthetic_impala_batch(
            rt.batch_size, cfg.trajectory, cfg.obs_shape, cfg.num_actions,
            cfg.lstm_size))
    text = compiled.as_text()
    assert _kernel_calls(compiled, "vtrace_pallas") == 2
    assert "f32[18,8]" in text and "all-reduce" in text
    assert "all-gather" not in text


@pytest.mark.slow
def test_sharded_transformer_learn_step_compiles_for_four_chips(
        four_chips, kernels_as_on_chip):
    """The same for the flash-attention kernels (`auto` on TPU whenever
    T divides by a block): the data-parallel Transformer-R2D2 step at
    width 256 (4 heads, T=32) over the four-chip mesh."""
    from distributed_reinforcement_learning_tpu.agents.xformer import (
        XformerAgent, XformerConfig)

    cfg = XformerConfig(obs_shape=(8,), num_actions=4, seq_len=32, burn_in=0,
                        d_model=256, num_heads=4, num_layers=2)
    compiled = _sharded_learn_compiled(
        four_chips, XformerAgent(cfg),
        *synthetic.synthetic_xformer_batch(32, cfg.seq_len, cfg.obs_shape,
                                           cfg.num_actions))
    assert _kernel_calls(compiled) > 0
    assert "all-gather" not in compiled.as_text()


@pytest.mark.slow
def test_impala_learn_step_bf16_compiles(chip, kernels_as_on_chip):
    compiled = _impala_learn_compiled(chip, jnp.bfloat16)
    assert _kernel_calls(compiled, "vtrace_pallas") == 2


@pytest.mark.slow
def test_apex_learn_step_compiles(chip, kernels_as_on_chip):
    from distributed_reinforcement_learning_tpu.agents.apex import ApexAgent

    cfg, rt = load_config(CONFIG, "apex")
    agent = ApexAgent(cfg)
    state = jax.eval_shape(agent.init_state, jax.random.PRNGKey(0))
    batch, is_weight = synthetic.synthetic_apex_batch(
        rt.batch_size, cfg.obs_shape, cfg.num_actions, obs_dtype="uint8")
    agent.learn.lower(_on(chip, state), _on(chip, batch),
                      _on(chip, is_weight)).compile()


@pytest.mark.slow
@pytest.mark.parametrize("lstm_kernel", ["0", "1"])
def test_r2d2_pixel_learn_step_compiles(chip, kernels_as_on_chip, monkeypatch,
                                        lstm_kernel):
    """Default (XLA scan) and with the opt-in fused LSTM kernel."""
    from distributed_reinforcement_learning_tpu.agents.r2d2 import R2D2Agent

    monkeypatch.setenv("DRL_LSTM_PALLAS", lstm_kernel)
    cfg, rt = load_config(CONFIG, "r2d2_pixel")
    agent = R2D2Agent(cfg)
    state = jax.eval_shape(agent.init_state, jax.random.PRNGKey(0))
    batch, is_weight = synthetic.synthetic_r2d2_batch(
        rt.batch_size, cfg.seq_len, cfg.obs_shape, cfg.num_actions,
        cfg.lstm_size)
    batch = batch._replace(state=batch.state.astype("uint8"))
    compiled = agent.learn.lower(_on(chip, state), _on(chip, batch),
                                 _on(chip, is_weight)).compile()
    assert (_kernel_calls(compiled) > 0) == (lstm_kernel == "1")


@pytest.mark.slow
def test_anakin_chunk_compiles(chip, kernels_as_on_chip):
    """The fused collect+learn chunk at the `impala` section's widths
    (20 on-device Breakout envs, 2 updates a chunk)."""
    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent
    from distributed_reinforcement_learning_tpu.envs import breakout_jax
    from distributed_reinforcement_learning_tpu.runtime.anakin import AnakinImpala

    cfg, rt = load_config(CONFIG, "impala")
    anakin = AnakinImpala(ImpalaAgent(cfg), rt.num_actors * rt.envs_per_actor,
                          env=breakout_jax)
    state = jax.eval_shape(anakin.init, jax.random.PRNGKey(0))
    compiled = anakin.train_chunk.lower(_on(chip, state), 2).compile()
    assert _kernel_calls(compiled, "vtrace_pallas") == 2


@pytest.mark.slow
@pytest.mark.parametrize("chunk, n", [("collect_chunk", 8), ("train_chunk", 4)])
def test_r2d2_atari_chunk_fits_with_its_ring_donated(chip, kernels_as_on_chip,
                                                     chunk, n):
    """The fused replay chunks at the `r2d2_atari` section's sizes (256
    envs, a ring of 2,048 x 120 x 84 x 84 x 4 bytes = 6.94 GB, as words): the
    donated state is aliased whole, so the program holds the ring once
    (undonated: twice, 14.2 GB before any activation), and arguments +
    scratch stay under the chip's 16.9 GB. No Mosaic kernel: the LSTM
    stays on the XLA scan."""
    from distributed_reinforcement_learning_tpu.agents.r2d2 import R2D2Agent
    from distributed_reinforcement_learning_tpu.envs import breakout_jax
    from distributed_reinforcement_learning_tpu.runtime.anakin_r2d2 import (
        AnakinR2D2)

    cfg, rt = load_config(CONFIG, "r2d2_atari")
    anakin = AnakinR2D2(
        R2D2Agent(cfg), num_envs=rt.num_actors * rt.envs_per_actor,
        batch_size=rt.batch_size, capacity=rt.replay_capacity,
        target_sync_interval=rt.target_sync_interval,
        updates_per_collect=rt.updates_per_call, env=breakout_jax)
    state = jax.eval_shape(anakin.init, jax.random.PRNGKey(0))
    compiled = getattr(anakin, chunk).lower(_on(chip, state), n).compile()
    mem = compiled.memory_analysis()
    ring = 2048 * 120 * 84 * 84 * 4
    assert mem.alias_size_in_bytes == mem.argument_size_in_bytes > ring
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert held < 12e9, held  # 10.7 GB when this was written
    # No second ring in scratch (3.6 GB): a gather or a write that re-lays
    # the word ring shows here first (PERF.md, PRs 26-27).
    assert mem.temp_size_in_bytes < 4e9, mem.temp_size_in_bytes
    assert _kernel_calls(compiled) == 0


def test_ouro_looplm_chunk_fits_and_holds_its_six_kernels(chip,
                                                          kernels_as_on_chip):
    """The fused token chunk at the `ouro_looplm` section's sizes (32 envs
    x 128 tokens, 8 layers x 4 passes at 2048 wide, chunk of 2): it
    compiles for a described v5e, the donated state (parameters +
    RMSProp's second moments, 8 B a parameter) is aliased whole, and
    arguments + scratch stay under the chip's `bytes_limit`. Six Mosaic
    kernels whatever L and R: flash attention in the scanned layer body
    (forward, rematerialised forward, dq, dkv) and V-trace's two views
    with the four passes in the kernel's batch. The 128 decode steps are
    8 scans (PR 31): each reads its keys and its values as a static
    prefix `bf16[1,1,32,P,16,128]` of a cache row, P = 16, 32, ..., 128,
    and the cache passes from scan to scan in place (a `copy` of it is
    1.07 GB, 2.7 ms, eight times an update)."""
    from distributed_reinforcement_learning_tpu.agents.looplm import LoopLMAgent
    from distributed_reinforcement_learning_tpu.envs.registry import (
        make_jittable_env)
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
        AnakinTokens)

    cfg, rt = load_config(CONFIG, "ouro_looplm")
    env = make_jittable_env(rt.envs[0], vocab=cfg.vocab_size,
                            episode_len=cfg.trajectory,
                            distance=cfg.recall_distance)
    anakin = AnakinTokens(LoopLMAgent(cfg), rt.num_actors * rt.envs_per_actor,
                          env)
    state = jax.eval_shape(anakin.init, jax.random.PRNGKey(0))
    lowered = anakin.train_chunk.lower(_on(chip, state), 2)
    assert len(re.findall("tpu_custom_call", lowered.as_text())) == 6
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    params = 8 * (4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048) + 2 * 49152 * 2048
    assert mem.alias_size_in_bytes == mem.argument_size_in_bytes > 8 * params
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert held < 15.0e9 < 16_909_336_064, held  # 14.16 GB when written
    spans = tuple(range(16, 129, 16))
    assert anakin.static_facts == {"loop_passes": 4, "compute_dtype": "bfloat16",
                                   "kv_cache_bytes": 2 ** 30,
                                   "decode_spans": spans,
                                   "cache_read_share": 0.5625}
    text = compiled.as_text()
    reads = collections.Counter(int(p) for p in re.findall(
        r"= bf16\[1,1,32,(\d+),16,128\]\S* dynamic-slice\(", text))
    assert reads == {p: 2 for p in spans}, reads  # keys and values, per segment
    assert not re.findall(r"= bf16\[4,8,32,128,16,128\]\S* copy\(", text)


def test_granite_hybrid_chunk_fits_and_updates_its_state_in_place(
        chip, kernels_as_on_chip):
    """The fused token chunk at the `granite_hybrid` section's sizes (32
    envs x 1,024 tokens, 9 Mamba-2 layers and 1 attention layer at 2048
    wide, chunk of 1): it compiles for a described v5e, the donated state
    (772.2 M parameters + their second moments, 8 B each) is aliased
    whole, and arguments + scratch stay under the chip's `bytes_limit` by
    `memory_analysis`, whose scratch reads 1.5 times the chip's own
    (PERF.md, PRs 30 and 32). Six Mosaic kernels: flash attention in the
    one attention layer (forward, rematerialised forward, dq, dkv) and
    V-trace's two views; the state-space scan is plain XLA. No copy of a
    layer's recurrent state `f32[32,64,64,128]` in any decode body: the
    step updates it in place (as slices of one stacked array under a scan
    over layers it was copied twice a layer a step)."""
    from distributed_reinforcement_learning_tpu.agents.hybridlm import (
        HybridLMAgent)
    from distributed_reinforcement_learning_tpu.envs.registry import (
        make_jittable_env)
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
        AnakinTokens)

    cfg, rt = load_config(CONFIG, "granite_hybrid")
    env = make_jittable_env(rt.envs[0], vocab=cfg.vocab_size,
                            episode_len=cfg.trajectory,
                            distance=cfg.recall_distance)
    anakin = AnakinTokens(HybridLMAgent(cfg), rt.num_actors * rt.envs_per_actor,
                          env)
    state = jax.eval_shape(anakin.init, jax.random.PRNGKey(0))
    lowered = anakin.train_chunk.lower(_on(chip, state), 1)
    assert len(re.findall("tpu_custom_call", lowered.as_text())) == 6
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    params = 772_162_497
    assert mem.alias_size_in_bytes == mem.argument_size_in_bytes > 8 * params
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert held < 16.5e9 < 16_909_336_064, held  # 16.18 GB when written
    facts = anakin.static_facts
    assert facts["layer_order"] == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (facts["ssm_state_bytes"], facts["conv_state_bytes"],
            facts["kv_cache_bytes"]) == (9 * 32 * 64 * 64 * 128 * 4,
                                         9 * 32 * 3 * 4352 * 4,
                                         2 * 32 * 1024 * 8 * 64 * 2)
    assert facts["decode_spans"] == tuple(range(128, 1025, 128))
    text = compiled.as_text()
    assert not re.findall(r"= f32\[32,64,64,128\]\S* copy\(", text)
    assert not re.findall(r"= f32\[\d+,32,64,64,128\]\S* copy\(", text)


def test_qwen3_next_chunk_fits_and_holds_its_six_kernels(chip, kernels_as_on_chip):
    """The fused token chunk at the `qwen3_next` section's sizes (32 envs x
    1,024 tokens; three gated-delta-rule layers and one gated attention
    layer at 2048 wide, a 512-way router over 32 held experts in every
    layer; chunk of 1): it compiles for a described v5e, the donated state
    (625.7 M parameters + their second moments, 8 B each) is aliased
    whole, and arguments + scratch stay under the chip's `bytes_limit` by
    `memory_analysis` (14.87 GB at PR 36, 15.75 since PR 37 hoisted the delta
    rule's chunk-independent work out of its scan). Six Mosaic kernels in the
    LOWERED chunk, the configuration file's count: flash attention in the
    one attention layer (forward, rematerialised forward, dq, dkv) and
    V-trace's two views; the chunked delta rule and the expert layer are
    plain XLA there (the compiler makes the grouped products its own
    kernels afterwards). No copy of a layer's delta-rule state
    `f32[32,32,128,128]` in any decode body: the step updates it in
    place."""
    import json

    from distributed_reinforcement_learning_tpu.agents.moelm import MoELMAgent
    from distributed_reinforcement_learning_tpu.envs.registry import (
        make_jittable_env)
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
        AnakinTokens)

    cfg, rt = load_config(CONFIG, "qwen3_next")
    env = make_jittable_env(rt.envs[0], vocab=cfg.vocab_size,
                            episode_len=cfg.trajectory,
                            distance=cfg.recall_distance)
    anakin = AnakinTokens(MoELMAgent(cfg), rt.num_actors * rt.envs_per_actor, env)
    state = jax.eval_shape(anakin.init, jax.random.PRNGKey(0))
    lowered = anakin.train_chunk.lower(_on(chip, state), 1)
    with open(os.path.join(os.path.dirname(CONFIG), "perfbench", "configs",
                           "qwen3_next.json")) as f:
        named = json.load(f)["kernels"]["tpu_custom_call"]
    assert len(re.findall("tpu_custom_call", lowered.as_text())) == named == 6
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    params = 625_669_185
    assert mem.alias_size_in_bytes == mem.argument_size_in_bytes > 8 * params
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert held < 16.5e9 < 16_909_336_064, held
    facts = anakin.static_facts
    assert facts["layer_order"] == ("linear_attention",) * 3 + ("full_attention",)
    assert (facts["gdn_state_bytes"], facts["conv_state_bytes"],
            facts["kv_cache_bytes"]) == (3 * 32 * 32 * 128 * 128 * 4,
                                         3 * 32 * 3 * 8192 * 4,
                                         2 * 32 * 1024 * 2 * 256 * 2)
    assert (facts["experts_held"], facts["router_width"]) == (32, 512)
    assert facts["decode_spans"] == tuple(range(128, 1025, 128))
    text = compiled.as_text()
    assert not re.findall(r"= f32\[32,32,128,128\]\S* copy\(", text)
    # no array with the router's width AND a capacity beside the tokens
    assert not re.findall(r"\[4096,512,\d+\]|\[32768,512,\d+\]", text)


# (batch*heads, T, q/k width, value width, the rule's tile): the
# `joyai_flash` learner's row block (2 rows x 32 heads, 192 | 128, no
# padding), and a row of equal widths.
@pytest.mark.parametrize("BH,T,D,DV,tile", [(64, 2048, 192, 128, (512, 512)),
                                            (64, 512, 64, 64, (512, 512))])
def test_flash_attention_with_its_own_value_width_compiles(chip, BH, T, D, DV, tile):
    assert flash_blocks(T, D, DV, 2) == tile

    def loss(q, k, v, seg):
        out = flash_attention_bhtd(q, k, v, seg, seg)  # the rule's tile
        return jnp.sum(out.astype(jnp.float32) ** 2)

    qk = jax.ShapeDtypeStruct((BH, T, D), jnp.bfloat16, sharding=chip)
    v = jax.ShapeDtypeStruct((BH, T, DV), jnp.bfloat16, sharding=chip)
    seg = jax.ShapeDtypeStruct((BH, T), jnp.int32, sharding=chip)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        qk, qk, v, seg).compile()
    assert _kernel_calls(compiled) == 3  # forward, dq, dkv
    text = compiled.as_text()
    assert f"bf16[{BH},{T},{DV}]" in text  # the output and dv at the value's width


def test_joyai_flash_chunk_fits_and_holds_its_fourteen_kernels(chip,
                                                              kernels_as_on_chip):
    """The fused token chunk at the `joyai_flash` section's sizes (16 envs x
    2,048 tokens; latent attention in a dense layer, four expert layers
    and the prediction module at 2048 wide, a 256-way router over 16 held
    experts; chunk of 1): it compiles for a described v5e, the donated
    state (680.4 M parameters + their second moments, 8 B each) is
    aliased whole, and arguments + scratch stay under the chip's
    `bytes_limit` by `memory_analysis` (16.25 GB at a row block of 2,
    which reads about 1.2 times the chip's own). Fourteen Mosaic kernels
    in the LOWERED chunk, the configuration file's count: flash attention
    with q/k of 192 and v of 128 in the learner's three runs of layers
    (forward, rematerialised forward, dq, dkv each) and V-trace's two
    views. The cache is the latent's: no array of expanded keys or values
    a layer (`[16, 2048, 32, 192]`) in any decode body."""
    import json

    from distributed_reinforcement_learning_tpu.agents.mlalm import MLALMAgent
    from distributed_reinforcement_learning_tpu.envs.registry import (
        make_jittable_env)
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
        AnakinTokens)

    cfg, rt = load_config(CONFIG, "joyai_flash")
    env = make_jittable_env(rt.envs[0], vocab=cfg.vocab_size,
                            episode_len=cfg.trajectory,
                            distance=cfg.recall_distance)
    anakin = AnakinTokens(MLALMAgent(cfg), rt.num_actors * rt.envs_per_actor, env)
    state = jax.eval_shape(anakin.init, jax.random.PRNGKey(0))
    lowered = anakin.train_chunk.lower(_on(chip, state), 1)
    with open(os.path.join(os.path.dirname(CONFIG), "perfbench", "configs",
                           "joyai_flash.json")) as f:
        named = json.load(f)["kernels"]["tpu_custom_call"]
    assert len(re.findall("tpu_custom_call", lowered.as_text())) == named == 14
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    params = 680_443_137
    assert mem.alias_size_in_bytes == mem.argument_size_in_bytes > 8 * params
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert held < 16.5e9 < 16_909_336_064, held
    facts = anakin.static_facts
    assert facts["layer_order"] == ("dense",) + ("moe",) * 4
    assert facts["latent_cache_bytes"] == 16 * 2048 * 5 * 576 * 2
    assert (facts["cache_bytes_per_token"],
            facts["expanded_cache_bytes_per_token"]) == (5_760, 102_400)
    assert (facts["experts_held"], facts["router_width"]) == (16, 256)
    assert facts["decode_spans"] == tuple(range(256, 2049, 256))
    text = compiled.as_text()
    assert not re.findall(r"bf16\[16,\d+,32,(192|128)\]", text)
    # no array with the router's width AND a capacity beside the tokens
    assert not re.findall(r"\[4096,256,\d+\]|\[32768,256,\d+\]", text)


@pytest.mark.slow  # 80-100 s, in the file that ends tier-1's run: before a chip call
def test_lfm2_moe_chunk_fits_and_holds_its_six_kernels(chip, kernels_as_on_chip):
    """The fused token chunk at the `lfm2_moe` section's sizes (64 envs x
    1,024 tokens; a dense layer and one period of LFM2-24B-A2B's order at
    2048 wide: four double-gated short convolutions and one grouped-query
    attention, a 64-way router over 16 held experts, no shared expert;
    chunk of 1): it compiles for a described v5e and the donated state
    (788.1 M parameters + their second moments, 8 B each) is aliased
    whole. Arguments + scratch by `memory_analysis` read 18.98 GB at a row
    block of 4 (18.47 at 2: the temporaries are the optimizer's whole
    trees, 15.4 B a parameter as in `joyai_flash`, not the row block's),
    which reads about 1.2 times the chip's own peak (PERF.md section 4):
    held here under 19.2 GB, the chip's own under 95 % of its
    `bytes_limit` by the benchmark's run. Six Mosaic kernels in the
    LOWERED chunk, the configuration file's count: flash attention in the
    ONE run of layers that holds attention (forward, rematerialised
    forward, dq, dkv) and V-trace's two views. The act-time state is four
    windows of two columns in the compute dtype and one cache of the
    key/value heads: no cache of the 32 query heads."""
    import json

    from distributed_reinforcement_learning_tpu.agents.convlm import ConvLMAgent
    from distributed_reinforcement_learning_tpu.envs.registry import (
        make_jittable_env)
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
        AnakinTokens)

    cfg, rt = load_config(CONFIG, "lfm2_moe")
    env = make_jittable_env(rt.envs[0], vocab=cfg.vocab_size,
                            episode_len=cfg.trajectory,
                            distance=cfg.recall_distance)
    anakin = AnakinTokens(ConvLMAgent(cfg), rt.num_actors * rt.envs_per_actor, env)
    state = jax.eval_shape(anakin.init, jax.random.PRNGKey(0))
    lowered = anakin.train_chunk.lower(_on(chip, state), 1)
    with open(os.path.join(os.path.dirname(CONFIG), "perfbench", "configs",
                           "lfm2_moe.json")) as f:
        named = json.load(f)["kernels"]["tpu_custom_call"]
    assert len(re.findall("tpu_custom_call", lowered.as_text())) == named == 6
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    params = 788_054_145
    assert mem.alias_size_in_bytes == mem.argument_size_in_bytes > 8 * params
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert held < 19.2e9, held
    facts = anakin.static_facts
    assert facts["layer_order"] == ("conv+dense", "full_attention+moe",
                                    "conv+moe", "conv+moe", "conv+moe")
    assert facts["conv_state_bytes"] == 4 * 64 * 2 * 2048 * 2
    assert facts["kv_cache_bytes"] == 2 * 64 * 1024 * 8 * 64 * 2
    assert (facts["experts_held"], facts["router_width"]) == (16, 64)
    assert facts["act_weight_bytes"] == 1_577_058_304
    assert facts["decode_spans"] == tuple(range(128, 1025, 128))
    text = compiled.as_text()
    assert re.findall(r"bf16\[64,2,2048\]", text)  # the windows, in the compute dtype
    assert not re.findall(r"bf16\[64,\d{3,4},32,64\]", text)  # no cache of the query heads
    # no array with the router's width AND a capacity beside the tokens
    assert not re.findall(r"\[4096,64,\d+\]|\[65536,64,\d+\]", text)


@pytest.mark.slow  # minutes, in the file that ends tier-1's run: before a chip call
def test_smallthinker_moe_chunk_fits_and_holds_its_eight_kernels(chip, kernels_as_on_chip):
    """The fused token chunk at the `smallthinker_moe` section's sizes (8
    envs x 8,192 tokens; one period of SmallThinker-21BA3B's order at 2560
    wide: one global NoPE attention layer and three sliding-window rotary
    layers of 4,096, a 64-way router over 16 held ReGLU experts in every
    layer, an untied head; chunk of 1): it compiles for a described v5e and
    the donated state (656.5 M parameters + their second moments, 8 B
    each) is aliased whole. Eight Mosaic kernels in the LOWERED chunk, the
    configuration file's count: flash attention in the TWO runs of layers
    (the global run and the window run, each forward, rematerialised
    forward, dq, dkv); V-trace's two views of 8,190 steps take the scan
    (`ops/vtrace._kernel_fits`: the kernel unrolls T and asked for 35.8 MB
    of 16 MB of scoped VMEM). The act-time state is one
    full cache and three rings of 4,096 positions of the key/value heads:
    no window layer holds 8,192 positions, none the 28 query heads."""
    import json

    from distributed_reinforcement_learning_tpu.agents.swalm import SwaLMAgent
    from distributed_reinforcement_learning_tpu.envs.registry import (
        make_jittable_env)
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
        AnakinTokens)

    cfg, rt = load_config(CONFIG, "smallthinker_moe")
    env = make_jittable_env(rt.envs[0], vocab=cfg.vocab_size,
                            episode_len=cfg.trajectory,
                            distance=cfg.recall_distance)
    anakin = AnakinTokens(SwaLMAgent(cfg), rt.num_actors * rt.envs_per_actor, env)
    state = jax.eval_shape(anakin.init, jax.random.PRNGKey(0))
    lowered = anakin.train_chunk.lower(_on(chip, state), 1)
    with open(os.path.join(os.path.dirname(CONFIG), "perfbench", "configs",
                           "smallthinker_moe.json")) as f:
        named = json.load(f)["kernels"]["tpu_custom_call"]
    assert len(re.findall("tpu_custom_call", lowered.as_text())) == named == 8
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    params = 656_532_481
    assert mem.alias_size_in_bytes == mem.argument_size_in_bytes > 8 * params
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"smallthinker_moe chunk: {held / 1e9:.2f} GB held, arguments "
          f"{mem.argument_size_in_bytes / 1e9:.2f}, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f}")
    assert held < 19.2e9, held
    facts = anakin.static_facts
    assert facts["layer_order"] == ("global", "window", "window", "window")
    assert facts["kv_cache_bytes"] == 2 * 8 * 8192 * 4 * 128 * 2 == 134_217_728
    assert facts["ring_bytes"] == 3 * 2 * 8 * 4096 * 4 * 128 * 2 == 3 * 67_108_864
    assert (facts["experts_held"], facts["router_width"]) == (16, 64)
    assert facts["decode_spans"] == tuple(range(1024, 8193, 1024))
    text = compiled.as_text()
    # the benchmark's reader of the kernels' share of their roofline tells the
    # two kinds of layer apart by the Mosaic calls' op names
    with open(os.path.join(os.path.dirname(CONFIG), "perfbench", "layer_metrics",
                           "swa_flash_roofline.json")) as f:
        pattern = re.compile(json.load(f)["source_detail"]["pattern"])
    calls = [pattern.search(name) for name in re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]+)"', text)
        if "pallas_call" in name]  # the compiler's own grouped products beside them
    assert sorted(m.group(1) for m in calls) == 4 * ["global"] + 4 * ["window"]
    assert re.findall(r"bf16\[8,4096,4,128\]", text)  # the rings, in the compute dtype
    assert not re.findall(r"bf16\[8,\d{4},28,128\]", text)  # no cache of the query heads
    # no array with the router's width AND a capacity beside the tokens
    assert not re.findall(r"\[8192,64,\d+\]|\[65536,64,\d+\]", text)
    # the decode step's touched form (PR 54) reads an expert's two matrices
    # inside its products' fusions: no copy of one
    assert not re.findall(r"= bf16\[(?:1,)?(?:2560,1536|768,2560)\]\S* copy\(", text)


@pytest.mark.slow  # minutes, in the file that ends tier-1's run: before a chip call
def test_nemotron_h_moe_chunk_fits_and_holds_its_kernels(chip, kernels_as_on_chip):
    """The fused token chunk at the `nemotron_h_moe` section's sizes (16
    envs x 2,048 tokens; layers 0-8 of Nemotron-3-Nano-30B-A3B's order at
    2688 wide, `MEMEM*EME`: four Mamba-2 mixers with eight B/C groups, four
    128-way routers over 8 held relu^2 experts beside a shared expert, one
    NoPE attention layer of 32 / 2 heads of 128, an untied head; chunk of
    1): it compiles for a described v5e and the donated state (667.0 M
    parameters + their second moments, 8 B each) is aliased whole. The
    Mosaic kernels in the LOWERED chunk are the configuration file's count:
    flash attention in the ONE attention layer (forward, rematerialised
    forward, dq, dkv) and V-trace's two views. The act-time state is four
    float32 recurrent states, four windows, ONE cache of the two key/value
    heads and the route record."""
    import json

    from distributed_reinforcement_learning_tpu.agents.ssmoelm import SSMoELMAgent
    from distributed_reinforcement_learning_tpu.envs.registry import (
        make_jittable_env)
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
        AnakinTokens)

    cfg, rt = load_config(CONFIG, "nemotron_h_moe")
    env = make_jittable_env(rt.envs[0], vocab=cfg.vocab_size,
                            episode_len=cfg.trajectory,
                            distance=cfg.recall_distance)
    anakin = AnakinTokens(SSMoELMAgent(cfg), rt.num_actors * rt.envs_per_actor, env)
    state = jax.eval_shape(anakin.init, jax.random.PRNGKey(0))
    lowered = anakin.train_chunk.lower(_on(chip, state), 1)
    with open(os.path.join(os.path.dirname(CONFIG), "perfbench", "configs",
                           "nemotron_h_moe.json")) as f:
        named = json.load(f)["kernels"]["tpu_custom_call"]
    assert len(re.findall("tpu_custom_call", lowered.as_text())) == named == 6
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    params = 666_965_633
    assert mem.alias_size_in_bytes == mem.argument_size_in_bytes > 8 * params
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"nemotron_h_moe chunk: {held / 1e9:.2f} GB held, arguments "
          f"{mem.argument_size_in_bytes / 1e9:.2f}, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f}")
    assert held < 19.2e9, held
    facts = anakin.static_facts
    assert facts["layer_order"] == "MEMEM*EME"
    assert facts["ssm_state_bytes"] == 4 * 16 * 64 * 64 * 128 * 4 == 134_217_728
    assert facts["conv_state_bytes"] == 4 * 16 * 3 * 6144 * 4
    assert facts["kv_cache_bytes"] == 2 * 16 * 2048 * 2 * 128 * 2 == 33_554_432
    assert (facts["experts_held"], facts["router_width"]) == (8, 128)
    text = compiled.as_text()
    assert re.findall(r"f32\[16,64,64,128\]", text)  # the recurrent state, float32
    assert not re.findall(r"bf16\[16,64,64,128\]", text)
    assert re.findall(r"bf16\[16,2048,2,128\]", text)  # the cache: two heads
    assert not re.findall(r"bf16\[16,\d{4},32,128\]", text)  # none of the query heads
    # no array with the router's width AND a capacity beside the tokens
    assert not re.findall(r"\[8192,128,\d+\]|\[32768,128,\d+\]", text)
    # the decode step's touched form (PR 54) reads an expert's two matrices
    # inside its products' fusions: no copy of one
    assert not re.findall(r"= bf16\[(?:1,)?(?:2688,1856|1856,2688)\]\S* copy\(", text)


def _breakout_step_text(chip, n: int) -> str:
    """`breakout_jax.step` alone at `n` envs, compiled for the described chip."""
    from distributed_reinforcement_learning_tpu.envs import breakout_jax

    state = jax.eval_shape(
        lambda: breakout_jax.reset(jax.random.PRNGKey(0), n)[0])
    return breakout_jax.step.lower(
        _on(chip, state),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=chip),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip),
    ).compile().as_text()


def test_breakout_step_keeps_no_raster_and_one_luma(chip):
    """`breakout_jax.step` at 64 envs makes no RGB raster: `u8[N,210,160,3]`
    is nowhere in the compiled step, fusions included; no class mask
    `pred[N,210,160]` (or of the crop's rows) is a buffer of the entry
    computation; ONE fusion writes the luma plane, over the scanlines the
    crop reads, and the nine tables it selects from are made on the device
    (a constant folded by the compiler would hold the host's arithmetic,
    not the chip's). The step before PR 35 shows four `pred[N,210,160]`
    buffers, the raster inside a fusion and a `[N,210,160]` reduction."""
    from distributed_reinforcement_learning_tpu.envs import pixel_jax

    n = 64
    lo, hi = pixel_jax.CROP_ROWS
    text = _breakout_step_text(chip, n)
    buffers = text[text.index("\nENTRY"):]  # fused computations come before
    assert f"u8[{n},210,160,3]" not in text
    assert not re.findall(rf"= \(?pred\[{n},(?:210|{hi - lo}),160\]", buffers)
    planes = re.findall(rf"= \w+\[{n},(?:210|{hi - lo}),160\]\S* (\w+)\(", buffers)
    assert planes == ["fusion"], planes
    tables = re.findall(rf"= \w+\[3,3,{hi - lo},160\]\S* (\w+)\(", buffers)
    assert tables == ["fusion"], tables


def _breakout_chunk_compiled(anakin, state_sharding):
    """The fused IMPALA chunk (1 update) compiled for described devices;
    `state_sharding(abstract state)` places its arguments."""
    state = jax.eval_shape(anakin.init, jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        state, state_sharding(state))
    return anakin.train_chunk.lower(state, 1).compile()


def _frame_batch_ops(text: str, frames: int, ops: str) -> list[str]:
    """Lines of `text` whose op is one of `ops` (a regex alternation) and
    whose RESULT holds `frames` 84x84x4 frames, whatever its shape."""
    import math

    hits = []
    for m in re.finditer(
            rf"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\S* (?:{ops})\(.*$", text, re.M):
        if math.prod(int(d) for d in m[1].split(",")) == frames * 84 * 84 * 4:
            hits.append(m[0].strip()[:300])
    return hits


@pytest.fixture(scope="module")
def breakout_chunk(chip):
    """The fused IMPALA chunk at 256 Breakout envs x T=20 from the
    UNCHANGED `impala` section, compiled once for the tests below:
    (compiled, its text, frames an update)."""
    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent
    from distributed_reinforcement_learning_tpu.envs import breakout_jax
    from distributed_reinforcement_learning_tpu.runtime.anakin import AnakinImpala

    cfg, _ = load_config(CONFIG, "impala")
    n = 256
    with pytest.MonkeyPatch.context() as m:
        _kernels_as_on_chip(m)
        anakin = AnakinImpala(ImpalaAgent(cfg), n, env=breakout_jax)
        assert anakin.handoff == "time_major, frames uint8"
        compiled = _breakout_chunk_compiled(
            anakin, lambda state: jax.tree.map(lambda _: chip, state))
    return compiled, compiled.as_text(), cfg.trajectory * n


def test_breakout_chunk_learns_from_the_rollout_where_the_scan_wrote_it(
        breakout_chunk):
    """The fused IMPALA chunk at 256 Breakout envs x T=20: the learner
    takes the rollout time-major (`AnakinImpala._update`), so nothing is
    named `to_batch_major`, and between the scan's `[20,256,84,84,4]`
    output and conv0 ONE copy touches a frame-batch-sized operand (T moved
    inside H and W in whole tiles, under `learn`). The batch-major handoff
    (before PR 29) shows two: a true transposition through a W-minor
    intermediate. Both V-trace passes stay Mosaic kernels."""
    from distributed_reinforcement_learning_tpu.observability import scopes

    compiled, text, frames = breakout_chunk
    assert scopes.TO_BATCH_MAJOR not in text
    copies = _frame_batch_ops(text, frames, "copy|transpose")
    assert len(copies) <= 1, copies
    assert all(f"/{scopes.LEARN}/" in c for c in copies), copies
    assert _kernel_calls(compiled, "vtrace_pallas") == 2


def test_breakout_chunk_frames_stay_bytes_until_conv0(breakout_chunk):
    """The same chunk: the frames are uint8 from the scan's output to
    conv0. No op between them has a float result of T x N x 84 x 84 x 4
    elements (the agent-side /255 pass, `multiply_bitcast_fusion` before
    PR 33, wrote the batch as bfloat16: twice the bytes, a pass of its
    own), the one frame-batch copy moves bytes, and conv0's forward and
    weight-gradient fusions read the `u8[T*N,84,84,4]` batch themselves:
    the convert happens inside the convolution, whose kernel carries the
    1/255 (`models/torso.NatureConv`)."""
    _, text, frames = breakout_chunk
    dtype = lambda line: re.search(r"= (\w+)\[", line)[1]
    ops = _frame_batch_ops(text, frames, "copy|transpose|convert|multiply|fusion")
    assert ops and {dtype(o) for o in ops} == {"u8"}, ops
    copies = _frame_batch_ops(text, frames, "copy|transpose")
    assert [dtype(c) for c in copies] == ["u8"], copies
    batch = re.findall(rf"^\s*%(\S+) = u8\[{frames},84,84,4\]\S* bitcast\(%copy",
                       text, re.M)
    assert len(batch) == 1, batch
    readers = [line for line in text.splitlines()
               if re.search(rf" fusion\([^)]*%{re.escape(batch[0])}[,)]", line)
               and "torso/conv_general_dilated" in line]
    assert any("/jvp(learn/loss)/" in r for r in readers), readers  # conv0 forward
    assert any("/transpose(jvp(learn/loss))/" in r for r in readers), readers  # dL/dk


def _history_ops(text: str, n: int) -> list[tuple[str, str, str]]:
    """(result, opcode, scope path) of every op, in the computation of
    `text` whose fusions are named `collect/env/render` (the collect
    scan's body; the entry of a lone `step`), whose result is an array of
    `n` x 84 x 84 x {1, 3, 4} `u8` or `n` x 84 x 84 `u32` elements: a
    frame, the old stack's three planes, a stack, the history's words."""
    import math

    from distributed_reinforcement_learning_tpu.observability import scopes

    bodies = [c for c in re.split(r"\n(?=(?:ENTRY )?%\S+ \([^\n]*\{\n)", text)
              if not c.startswith("%fused_computation")
              and re.search(rf" fusion\(.*{scopes.RENDER}/", c)]
    if len(bodies) > 1:  # the entry holds what was hoisted out of the scan
        bodies = [c for c in bodies if f"body={c.split(' ', 1)[0]}," in text]
    assert len(bodies) == 1, len(bodies)
    sizes = {("u8", n * 84 * 84 * k) for k in (1, 3, 4)} | {("u32", n * 84 * 84)}
    hits = []
    for m in re.finditer(r"^\s*(?:ROOT )?%\S+ = ((\w+)\[([\d,]+)\]\S*) ([\w-]+)\((.*)$",
                         bodies[0], re.M):
        result, dtype, dims, opcode, rest = m.groups()
        if opcode in ("parameter", "get-tuple-element", "bitcast"):
            continue  # no bytes move
        if (dtype, math.prod(int(d) for d in dims.split(","))) in sizes:
            name = re.search(r'op_name="([^"]*)"', rest)
            hits.append((result, opcode, name[1] if name else ""))
    return hits


@pytest.mark.parametrize("program, n", [("chunk", 256), ("step", 2048)])
def test_breakout_history_is_two_fusions_a_step(request, chip, program, n):
    """The four-frame history is `u32[N,84,84]` words (PR 48). In the fused
    chunk's collect body at 256 envs and in `breakout_jax.step` alone at
    2,048, what touches a frame-, stack- or words-sized array is TWO
    fusions under `collect/env/render`: one writes the words batch-minor
    (the resize's last product, the shift, the or and the game-over select:
    the frame is never an array of its own) and one unpacks them to
    `u8[N,84,84,4]` batch-minor with the four bytes of a pixel in one word,
    the layout conv0 and the scan's stacked write read. Beside them at most
    one async copy of the words between memory spaces. The byte stack
    before showed six a step: the frame's layout `copy`, a select of zeros
    (`broadcast_select_fusion`), the concat (`pad_add_fusion`), and, in
    the chunk, two nameless copies of the whole stack, which `step`
    returned twice. Without `pixel_jax.observe`'s layout pin the chunk
    holds two transposing copies of the words."""
    from distributed_reinforcement_learning_tpu.observability import scopes

    if program == "chunk":
        _, text, _ = request.getfixturevalue("breakout_chunk")
    else:
        text = _breakout_step_text(chip, n)
    ops = _history_ops(text, n)
    fusions = [(result, path) for result, opcode, path in ops if opcode == "fusion"]
    assert len(fusions) == 2, ops
    (words, words_path), (obs, obs_path) = sorted(fusions)  # "u32" < "u8"
    assert words.startswith(f"u32[{n},84,84]{{0,2,1:"), words
    assert obs.startswith(f"u8[{n},84,84,4]{{0,3,2,1:T(4,128)(4,1)"), obs
    assert f"/{scopes.RENDER}/" in words_path and f"/{scopes.RENDER}/" in obs_path
    others = [(result, opcode) for result, opcode, _ in ops if opcode != "fusion"]
    assert all(opcode in ("copy-start", "copy-done") and result.startswith("u32[")
               for result, opcode in others) and len(others) <= 1, others


def test_mesh_breakout_chunk_moves_no_frames_between_chips(
        four_chips, kernels_as_on_chip):
    """`AnakinImpala(mesh=...)` over a described v5e:2x2, envs sharded
    over `data`: the chunk lowers (each V-trace kernel per device, under
    the context mesh) and no collective carries a frame batch: the
    gradient all-reduce is all that crosses. That is why a mesh keeps the
    batch-major handoff: `[B/4, T]` flattens in place, where the
    time-major `[T, B/4]` made the partitioner all-gather the whole
    `bf16[20,B,84,84,4]` batch five times (compiled so in PR 29)."""
    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent
    from distributed_reinforcement_learning_tpu.envs import breakout_jax
    from distributed_reinforcement_learning_tpu.parallel import make_mesh
    from distributed_reinforcement_learning_tpu.runtime.anakin import AnakinImpala

    cfg, _ = load_config(CONFIG, "impala")
    n = 256
    anakin = AnakinImpala(ImpalaAgent(cfg), n, mesh=make_mesh(devices=four_chips),
                          env=breakout_jax)
    assert anakin.handoff == "batch_major, frames uint8"
    compiled = _breakout_chunk_compiled(anakin, lambda _: anakin._state_sharding)
    text = compiled.as_text()
    collectives = "all-gather|all-to-all|collective-permute|all-reduce|reduce-scatter"
    for frames in (cfg.trajectory * n, cfg.trajectory * n // 4, n, n // 4):
        assert _frame_batch_ops(text, frames, collectives + "|\\S*-start") == []
    assert "all-reduce" in text and "all-gather" not in text
    assert _kernel_calls(compiled, "vtrace_pallas") == 2

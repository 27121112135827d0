"""Headline benchmark: IMPALA END-TO-END pipeline throughput in
env-frames/sec (the reference's operating mode is the full actors ->
queue -> learner -> weights loop, `train_impala.py:89-194`).

Measures (a) the e2e data-plane pipeline (saturating feeders -> bounded
queue -> device prefetch -> learn -> publish) in two modes — real-TCP
batched-PUT clients, and in-process shared-memory feeders that remove
this host's TCP+GIL tax — with per-stage timings, (b) the jitted learn
step (stored-state [B,T] forward + double V-trace + RMSProp) on the
reference's own Atari workload shape — 84x84x4 uint8 frames, T=20
unrolls (`/root/reference/config.json:25-67`) — over a batch-size sweep
with FLOPs + MFU roofline accounting, (c) a per-stage BUDGET table
(encode / shm_put / tcp_put / gather / h2d / learn / publish measured
independently vs the 50k frames/s/chip target — the evidence for where
a 1-core host binds the pipeline), and (d) the Pallas-vs-XLA kernel
comparison for the V-trace recursion and the fused LSTM, with
two-window stability checks on every estimate.

Prints the headline JSON line on stdout (consumers take the LAST line):
the headline section runs FIRST and emits a parsed line immediately, and
a second, enriched line is emitted after the remaining sections — so a
driver timeout mid-run still leaves a parsed headline. Sections are
gated on a wall-clock budget (BENCH_TIME_BUDGET, default 2700 s);
sections that would overrun are skipped and listed in
extra["skipped_sections"]. Diagnostics go to stderr; the full detail is
also written to bench_artifacts/bench_detail.json.

Runs on the accelerator JAX finds; with none, and no explicit
BENCH_PLATFORM=cpu (a smoke of the bench's own mechanics), it exits
non-zero. It never re-runs itself on another backend: a number taken on
the CPU is not printed under a device metric's name. A failed section,
a run with no measurement, and the budget watchdog all end in a
non-zero exit code.
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time

# Small, bounded extra fields the compact stdout line keeps; everything
# else (section results, rooflines, sweeps) lives only in the detail file.
_COMPACT_KEYS = ("platform", "device", "headline", "partial", "error", "phase",
                 "watchdog", "failed_sections", "transport_verdict",
                 "codec_verdict", "weights_verdict", "weights_shard_verdict",
                 "replay_verdict", "inference_verdict", "chaos_verdict",
                 "actor_pipeline_verdict", "learner_verdict",
                 "device_path_verdict", "admission_verdict",
                 "collective_verdict", "replay_spill_verdict")


def _emit(value: float, extra: dict,
          metric: str = "impala_e2e_env_frames_per_s") -> None:
    """Full detail -> bench_artifacts/bench_detail.json; stdout gets a
    COMPACT line. The driver parses only the last ~2000 bytes of stdout,
    and an enriched final line of ~3.6 KB once both failed to parse AND
    pushed the early headline emit out of the tail window.
    test_bench_contract.py pins len(last_line) <= 2000."""
    detail = {
        "metric": metric,
        "value": round(value, 1),
        "unit": "frames/s",
        "vs_baseline": round(value / 50_000.0, 4),
        "extra": extra,
    }
    detail_path = "bench_artifacts/bench_detail.json"
    try:
        os.makedirs("bench_artifacts", exist_ok=True)
        with open(detail_path, "w") as f:
            json.dump(detail, f, indent=2)
    except OSError:
        detail_path = None  # full/unwritable disk: don't point the driver
        #                     at a stale artifact — and still print the line
    compact = {k: extra[k] for k in _COMPACT_KEYS if k in extra}
    skipped = extra.get("skipped_sections")
    if skipped is not None:
        compact["skipped_sections"] = len(skipped)
    compact["detail"] = detail_path
    print(json.dumps({**detail, "extra": compact}))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _pctl(sorted_vals, q):
    """Percentile of an already-sorted list (nearest-rank, the repo's
    bench convention — shared by the weight-plane sections)."""
    return round(sorted_vals[min(int(q * (len(sorted_vals) - 1) + 0.5),
                                 len(sorted_vals) - 1)], 3)


def _stage_p(samples: dict, name: str) -> dict:
    """p50/p99/n summary of one `_RecTimer` stage."""
    vals = sorted(samples.get(name, []))
    if not vals:
        return {"p50_ms": 0.0, "p99_ms": 0.0, "n": 0}
    return {"p50_ms": _pctl(vals, 0.50), "p99_ms": _pctl(vals, 0.99),
            "n": len(vals)}


class _RecTimer:
    """StageTimer.stage duck-type keeping per-invocation samples —
    maybe_publish's publish/publish_handoff/publish_stall split
    (shared by the weight-plane A/B sections)."""

    def __init__(self):
        self.samples: dict[str, list[float]] = {}

    @contextlib.contextmanager
    def stage(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.samples.setdefault(name, []).append(
                (time.perf_counter() - t0) * 1e3)


def _marginal_step_s(window, iters: int, samples: int | None = None) -> tuple[float, dict]:
    """Per-step seconds from pipelined dispatch windows, reproducibly.

    `window(n)` dispatches n steps and returns elapsed seconds, forcing
    completion only by materializing one final host float (see
    bench_learn_step's methodology note). One marginal estimate is
    (window(2n) - window(n)) / n — constant overhead (dispatch ramp, the
    single materialization RTT) cancels between the windows.

    A single pair is too noisy (not measured on the attached chip;
    the builders' earlier account had a 5.8x run-to-run spread on one
    section and one 0.0 reading). So:
    take `samples` independent pairs, REJECT non-positive marginals
    (they are artifacts of RTT jitter exceeding the window, not times),
    report the median + the IQR/median spread, and if the spread is
    above 15% auto-lengthen the window (noise is constant, signal grows
    with n) and re-measure, up to 2 doublings.

    Returns (median_step_s, stats) where stats carries iqr_rel /
    samples / window / stable for the artifact.
    """
    if samples is None:
        import jax

        samples = 5 if jax.default_backend() not in ("cpu",) else 2
    window(max(iters // 4, 5))  # warm the dispatch path
    n = iters
    best: tuple[float, dict] | None = None
    for _ in range(3):  # initial + up to 2 doublings
        marginals = []
        for _ in range(samples):
            t1 = window(n)
            t2 = window(2 * n)
            m = (t2 - t1) / n
            if m > 0:  # non-positive = jitter artifact, never a time
                marginals.append(m)
        if len(marginals) >= max(2, samples - 2):
            marginals.sort()
            k = len(marginals)
            med = marginals[k // 2] if k % 2 else 0.5 * (
                marginals[k // 2 - 1] + marginals[k // 2])
            iqr = marginals[(3 * (k - 1)) // 4] - marginals[(k - 1) // 4]
            stats = {"iqr_rel": round(iqr / med, 4), "samples": k, "window": n}
            if best is None or stats["iqr_rel"] < best[1]["iqr_rel"]:
                best = (med, stats)
            if iqr / med <= 0.15:
                stats["stable"] = True
                return med, stats
        n *= 2
    if best is None:  # every sample rejected: there is NO measurement
        raise RuntimeError(
            "no positive marginal estimate — window jitter exceeded the "
            "signal at every length")
    best[1]["stable"] = False
    return best


def _analytic_flops(fn, *args) -> float | None:
    """FLOPs of one call from XLA's compiled cost analysis (host-side
    metadata — no device execution), None when unavailable."""
    import jax

    try:
        c = jax.jit(fn).lower(*args).compile().cost_analysis()
        f = float(c.get("flops", 0.0))
        return f if f > 0 else None
    except Exception as e:  # noqa: BLE001 — cost analysis is best-effort
        print(f"[bench] cost_analysis unavailable: {e}", file=sys.stderr)
        return None


# Published per-chip peaks, keyed by a substring of jax's `device_kind`
# (lower-cased). A device that is not here is an error, not a default:
# add its row with its source.
_DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16 (393
    # is the chip's int8 TOP/s — using it would halve every MFU), 16 GB
    # of HBM at 819 GB/s. device_kind on the attached chip: see
    # chip_smoke.py's last line.
    "v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "v5e": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def _device_peaks() -> tuple[dict, str]:
    """(peaks row for this process's device, source note)."""
    import jax

    kind = jax.devices()[0].device_kind.lower()
    for key, peaks in _DEVICE_PEAKS.items():
        if key in kind:
            return peaks, f"device_kind={kind}"
    raise RuntimeError(
        f"no published peaks for device_kind {kind!r}: add a row to "
        f"_DEVICE_PEAKS (with its source)")


def _peak_flops() -> tuple[float, str]:
    """(peak FLOP/s for the dense-matmul dtype in use, source note).

    BENCH_PEAK_TFLOPS overrides; otherwise the `_DEVICE_PEAKS` row
    (bf16 peak — the bench runs bf16 compute on the chip).
    """
    env = os.environ.get("BENCH_PEAK_TFLOPS")
    if env:
        return float(env) * 1e12, "BENCH_PEAK_TFLOPS"
    peaks, src = _device_peaks()
    return peaks["bf16_flops_per_s"], src


def _mfu_fields(flops_per_step: float | None, step_s: float) -> dict:
    """Roofline accounting for a learn section: achieved TFLOP/s and
    MFU. Device metrics: a CPU run (BENCH_PLATFORM=cpu) reports none."""
    import jax

    if not flops_per_step or jax.default_backend() == "cpu":
        return {}
    peak, src = _peak_flops()
    return {"flops_per_step": round(flops_per_step, 0),
            "tflops_per_s": round(flops_per_step / step_s / 1e12, 2),
            "mfu": round(flops_per_step / step_s / peak, 4),
            "mfu_peak_source": src}


def _make_batch(cfg, B: int):
    from distributed_reinforcement_learning_tpu.utils.synthetic import synthetic_impala_batch

    return synthetic_impala_batch(
        B, cfg.trajectory, cfg.obs_shape, cfg.num_actions, cfg.lstm_size,
        uniform_behavior=False,
    )


def bench_learn_step(cfg, B: int, iters: int) -> dict:
    """Jitted learn-step throughput at batch size B.

    Timing methodology: pipeline two equal windows of `iters`
    dispatches, forcing completion only by materializing the final
    window's loss as a host float. The marginal rate between the windows
    strips constant overhead (dispatch ramp, the one materialization);
    per-step time = (t2 - t1) / iters.
    """
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent

    agent = ImpalaAgent(cfg)
    state = agent.init_state(jax.random.PRNGKey(0))
    batch = jax.device_put(jax.tree.map(jnp.asarray, _make_batch(cfg, B)))

    t0 = time.perf_counter()
    state, metrics = agent.learn(state, batch)  # compile + 1 step
    loss0 = float(metrics["total_loss"])
    compile_s = time.perf_counter() - t0

    box = {"state": state, "loss": loss0}

    def window(n):
        t0 = time.perf_counter()
        state = box["state"]
        for _ in range(n):
            state, metrics = agent.learn(state, batch)
        box["loss"] = float(metrics["total_loss"])  # the only completion barrier
        box["state"] = state
        return time.perf_counter() - t0

    step_s, stats = _marginal_step_s(window, iters)
    fps = B * cfg.trajectory / step_s
    out = {"B": B, "frames_per_s": round(fps, 1), "step_ms": round(1e3 * step_s, 3),
           "compile_s": round(compile_s, 1), "timing": stats}
    out.update(_mfu_fields(_analytic_flops(agent.learn, state, batch), step_s))
    print(f"[bench] learn B={B}: {1e3*step_s:.3f}ms/step = {fps:,.0f} frames/s "
          f"(iqr {stats['iqr_rel']:.0%}, mfu {out.get('mfu', 'n/a')}, "
          f"compile {compile_s:.1f}s, loss {loss0:.1f}->{box['loss']:.1f})",
          file=sys.stderr)
    return out


def bench_learn_scan(cfg, B: int, K: int, iters: int) -> dict:
    """`learn_many` throughput: K optimizer steps per dispatch (lax.scan).

    The spread between this and `bench_learn_step` at the same B is pure
    per-step host-dispatch overhead (not measured on the attached
    chip) — overhead a free-running learner pays identically
    unless it scans. Math is step-for-step identical to K sequential
    learns (tests/test_fastpath.py)."""
    import jax
    import numpy as np

    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent

    agent = ImpalaAgent(cfg)
    state = agent.init_state(jax.random.PRNGKey(0))
    # K DISTINCT batches (different seeds): the scanned steps see fresh
    # data like a real learner would, so the loss window is representative
    # — not K updates on one batch (advisor r3 finding).
    from distributed_reinforcement_learning_tpu.utils.synthetic import synthetic_impala_batch

    distinct = [synthetic_impala_batch(B, cfg.trajectory, cfg.obs_shape,
                                       cfg.num_actions, cfg.lstm_size,
                                       seed=k, uniform_behavior=False)
                for k in range(K)]
    one = distinct[0]  # _analytic_flops sees the same shapes the scan times
    stacked = jax.device_put(
        jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]), *distinct))

    t0 = time.perf_counter()
    state, m = agent.learn_many(state, stacked)
    float(m["total_loss"][-1])
    compile_s = time.perf_counter() - t0
    box = {"state": state}

    def window(n):
        t0 = time.perf_counter()
        state = box["state"]
        for _ in range(n):
            state, m = agent.learn_many(state, stacked)
        box["loss"] = float(m["total_loss"][-1])
        box["state"] = state
        return time.perf_counter() - t0

    call_s, stats = _marginal_step_s(window, iters)
    step_s = call_s / K
    fps = B * cfg.trajectory / step_s
    out = {"B": B, "K": K, "frames_per_s": round(fps, 1),
           "step_ms": round(1e3 * step_s, 3), "compile_s": round(compile_s, 1),
           "timing": stats}
    flops = _analytic_flops(agent.learn, box["state"], one)
    out.update(_mfu_fields(flops, step_s))
    print(f"[bench] learn_scan B={B} K={K}: {1e3*step_s:.3f}ms/step = "
          f"{fps:,.0f} frames/s (iqr {stats['iqr_rel']:.0%}, "
          f"mfu {out.get('mfu', 'n/a')})", file=sys.stderr)
    return out


def bench_anakin(num_envs: int, chunk: int, iters: int) -> dict:
    """Fully on-device IMPALA (the Podracer 'Anakin' pattern,
    runtime/anakin.py): env step + act + trajectory buffer + optimizer
    update all inside ONE compiled scan over the pure-JAX CartPole.
    Zero host round-trips and zero H2D per update — the configuration
    that answers 'can the pipeline feed the chip' by deleting the
    pipeline. frames/s here are env frames collected AND learned on."""
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent, ImpalaConfig
    from distributed_reinforcement_learning_tpu.runtime.anakin import AnakinImpala

    on_accel = jax.default_backend() not in ("cpu",)
    cfg = ImpalaConfig(obs_shape=(4,), num_actions=2, trajectory=16,
                       lstm_size=256, start_learning_rate=5e-3,
                       end_learning_rate=5e-3, entropy_coef=0.01,
                       baseline_loss_coef=0.5, learning_frame=10**9,
                       dtype=jnp.bfloat16 if on_accel else jnp.float32)
    anakin = AnakinImpala(ImpalaAgent(cfg), num_envs=num_envs)
    state = anakin.init(jax.random.PRNGKey(0))

    t0 = time.perf_counter()
    state, m = anakin.train_chunk(state, chunk)
    float(m["total_loss"][-1])
    compile_s = time.perf_counter() - t0
    box = {"state": state}

    def window(n):
        t0 = time.perf_counter()
        state = box["state"]
        for _ in range(n):
            state, m = anakin.train_chunk(state, chunk)
        box["ret_sum"] = float(m["episode_return_sum"].sum())
        box["eps"] = float(m["episodes_done"].sum())
        box["state"] = state
        return time.perf_counter() - t0

    call_s, stats = _marginal_step_s(window, iters)
    update_s = call_s / chunk
    frames = num_envs * cfg.trajectory
    out = {
        "num_envs": num_envs, "trajectory": cfg.trajectory, "chunk": chunk,
        "updates_per_s": round(1.0 / update_s, 1),
        "frames_per_s": round(frames / update_s, 1),
        "device_chunk_s": round(call_s, 4),
        "compile_s": round(compile_s, 1), "timing": stats,
        "last_chunk_mean_return": round(
            box.get("ret_sum", 0.0) / max(box.get("eps", 0.0), 1.0), 1),
    }
    print(f"[bench] anakin B={num_envs}: {1e3*update_s:.3f}ms/update = "
          f"{frames / update_s:,.0f} on-device frames/s "
          f"(iqr {stats['iqr_rel']:.0%}, mean return "
          f"{out['last_chunk_mean_return']})", file=sys.stderr)
    return out


def bench_anakin_breakout(num_envs: int, chunk: int, iters: int) -> dict:
    """Anakin over the PIXEL env (envs/breakout_jax.py): game dynamics,
    sprite rendering, the full Atari preprocessing pipeline (2-frame
    max, luma, INTER_AREA-resize matmuls, crop, 4-stack), act, and the
    V-trace learn step — all inside one compiled scan. This answers the
    e2e feed question for Atari-CLASS observations, not just vector
    CartPole: frames/s here are 84x84x4 uint8 frames rendered,
    preprocessed, collected, and learned on without touching the host.
    """
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent, ImpalaConfig
    from distributed_reinforcement_learning_tpu.envs import breakout_jax
    from distributed_reinforcement_learning_tpu.runtime.anakin import AnakinImpala

    on_accel = jax.default_backend() not in ("cpu",)
    cfg = ImpalaConfig(obs_shape=breakout_jax.OBS_SHAPE, num_actions=4,
                       trajectory=20, lstm_size=256,
                       entropy_coef=0.01, baseline_loss_coef=0.5,
                       start_learning_rate=6e-4, end_learning_rate=6e-4,
                       learning_frame=10**9, fold_normalize=True,
                       dtype=jnp.bfloat16 if on_accel else jnp.float32)
    anakin = AnakinImpala(ImpalaAgent(cfg), num_envs=num_envs,
                          env=breakout_jax)
    state = anakin.init(jax.random.PRNGKey(0))

    t0 = time.perf_counter()
    state, m = anakin.train_chunk(state, chunk)
    float(m["total_loss"][-1])
    compile_s = time.perf_counter() - t0
    box = {"state": state}

    def window(n):
        t0 = time.perf_counter()
        state = box["state"]
        for _ in range(n):
            state, m = anakin.train_chunk(state, chunk)
        box["loss"] = float(m["total_loss"][-1])
        box["state"] = state
        return time.perf_counter() - t0

    call_s, stats = _marginal_step_s(window, iters)
    update_s = call_s / chunk
    frames = num_envs * cfg.trajectory
    out = {
        "num_envs": num_envs, "trajectory": cfg.trajectory, "chunk": chunk,
        "updates_per_s": round(1.0 / update_s, 1),
        "frames_per_s": round(frames / update_s, 1),
        "device_chunk_s": round(call_s, 4),
        "compile_s": round(compile_s, 1), "timing": stats,
        "last_loss": round(box.get("loss", float("nan")), 3),
    }
    print(f"[bench] anakin_breakout B={num_envs}: {1e3*update_s:.3f}ms/update "
          f"= {frames / update_s:,.0f} on-device pixel frames/s "
          f"(iqr {stats['iqr_rel']:.0%})", file=sys.stderr)
    return out


def bench_anakin_r2d2(num_envs: int, chunk: int, iters: int) -> dict:
    """Fully on-device REPLAY-family training (runtime/anakin_r2d2.py):
    collect, the prioritized sequence ring, sampling, recurrent learn,
    and target syncs all inside one compiled scan. frames/s are env
    frames collected while training at updates_per_collect=1 — the
    on-device expression of the reference's train_r2d2.py stack."""
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.r2d2 import R2D2Agent, R2D2Config
    from distributed_reinforcement_learning_tpu.envs.cartpole import pomdp_project
    from distributed_reinforcement_learning_tpu.runtime.anakin_r2d2 import AnakinR2D2

    on_accel = jax.default_backend() not in ("cpu",)
    cfg = R2D2Config(obs_shape=(2,), num_actions=2, seq_len=10, burn_in=5,
                     lstm_size=256,
                     dtype=jnp.bfloat16 if on_accel else jnp.float32)
    anakin = AnakinR2D2(R2D2Agent(cfg), num_envs=num_envs, batch_size=64,
                        capacity=max(4096 - 4096 % num_envs, num_envs),
                        epsilon_floor=0.02, obs_transform=pomdp_project)
    state = anakin.init(jax.random.PRNGKey(0))
    state, _ = anakin.collect_chunk(state, -(-3 * 64 // num_envs))

    t0 = time.perf_counter()
    state, m = anakin.train_chunk(state, chunk)
    float(m["loss"][-1])
    compile_s = time.perf_counter() - t0
    box = {"state": state}

    def window(n):
        t0 = time.perf_counter()
        state = box["state"]
        for _ in range(n):
            state, m = anakin.train_chunk(state, chunk)
        box["loss"] = float(m["loss"][-1])
        box["state"] = state
        return time.perf_counter() - t0

    call_s, stats = _marginal_step_s(window, iters)
    update_s = call_s / chunk
    frames = num_envs * cfg.seq_len
    out = {
        "num_envs": num_envs, "seq_len": cfg.seq_len, "chunk": chunk,
        "updates_per_s": round(1.0 / update_s, 1),
        "frames_per_s": round(frames / update_s, 1),
        "device_chunk_s": round(call_s, 4),
        "compile_s": round(compile_s, 1), "timing": stats,
        "last_loss": round(box.get("loss", float("nan")), 5),
    }
    print(f"[bench] anakin_r2d2 B={num_envs}: {1e3*update_s:.3f}ms/update = "
          f"{frames / update_s:,.0f} on-device frames/s "
          f"(iqr {stats['iqr_rel']:.0%})", file=sys.stderr)
    return out


def bench_anakin_apex(num_envs: int, chunk: int, iters: int) -> dict:
    """Fully on-device Ape-X over the PIXEL env: dueling-conv double-DQN
    with the uint8 transition ring, prioritized sampling, IS weights,
    and target syncs all inside one compiled scan
    (runtime/anakin_apex.py + envs/breakout_jax.py). frames/s are env
    frames collected while training; the emitted `sampled_ratio` is the
    sampled-to-collected ratio the run actually trained at.
    """
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.apex import ApexAgent, ApexConfig
    from distributed_reinforcement_learning_tpu.envs import breakout_jax
    from distributed_reinforcement_learning_tpu.runtime.anakin_apex import AnakinApex

    on_accel = jax.default_backend() not in ("cpu",)
    cfg = ApexConfig(obs_shape=breakout_jax.OBS_SHAPE, num_actions=4,
                     fold_normalize=True,
                     dtype=jnp.bfloat16 if on_accel else jnp.float32)
    steps = 16 if on_accel else 4
    width = num_envs * steps
    cap = max(width, 32768 - 32768 % width) if on_accel else width * 2
    anakin = AnakinApex(ApexAgent(cfg), num_envs=num_envs,
                        batch_size=128 if on_accel else 8,
                        capacity=cap, steps_per_collect=steps,
                        updates_per_collect=2, epsilon_floor=0.02,
                        env=breakout_jax)
    state = anakin.init(jax.random.PRNGKey(0))
    state, _ = anakin.collect_chunk(state, 1)

    t0 = time.perf_counter()
    state, m = anakin.train_chunk(state, chunk)
    float(m["loss"][-1])
    compile_s = time.perf_counter() - t0
    box = {"state": state}

    def window(n):
        t0 = time.perf_counter()
        state = box["state"]
        for _ in range(n):
            state, m = anakin.train_chunk(state, chunk)
        box["loss"] = float(m["loss"][-1])
        box["state"] = state
        return time.perf_counter() - t0

    call_s, stats = _marginal_step_s(window, iters)
    update_s = call_s / chunk
    frames = width
    out = {
        "num_envs": num_envs, "steps_per_collect": steps, "chunk": chunk,
        "capacity": cap,
        "sampled_ratio": round(
            anakin.updates_per_collect * anakin.batch_size / width, 3),
        "updates_per_s": round(1.0 / update_s, 1),
        "frames_per_s": round(frames / update_s, 1),
        "device_chunk_s": round(call_s, 4),
        "compile_s": round(compile_s, 1), "timing": stats,
        "last_loss": round(box.get("loss", float("nan")), 5),
    }
    print(f"[bench] anakin_apex B={num_envs}: {1e3*update_s:.3f}ms/update = "
          f"{frames / update_s:,.0f} on-device pixel frames/s "
          f"(iqr {stats['iqr_rel']:.0%})", file=sys.stderr)
    return out


def _pad_util(n: int, q: int = 128) -> float:
    """Fraction of a q-wide MXU dimension a size-n operand actually fills."""
    import math

    return n / (math.ceil(n / q) * q)


def impala_roofline(cfg, B: int, measured_step_s: float | None) -> dict:
    """Analytic per-layer roofline for the IMPALA learn step.

    Either the MFU gap is closed or it is justified; this is the
    justification machinery. Nature-CNN's channel widths (32/64)
    fill a quarter/half of the 128-wide MXU output dimension, so the
    ATTAINABLE peak for this model is far below the chip's nominal bf16
    peak no matter how the program is scheduled. Per layer: analytic
    fwd FLOPs, a backward multiplier (2x for conv0 — its input gradient
    is dead since observations need no grad — 3x elsewhere), and an MXU
    utilization model util = fill(N) * fill(K) on 128-wide tiles (M is
    B*T*spatial, effectively full). attainable_ms = sum over layers of
    flops / (peak * util); `mfu_attainable` = attainable_ms / measured.
    """
    peak, src = _peak_flops()
    A, H = cfg.num_actions, cfg.lstm_size
    frames = B * cfg.trajectory
    layers: list[tuple[str, float, float, float]] = []  # name, fwd flops/frame, util, bwd_mult
    if len(cfg.obs_shape) == 3 and getattr(cfg, "torso", "nature") == "resnet":
        # ResNetTorso geometry (models/torso.py): per section a SAME conv
        # (spatial preserved), maxpool /2 (ceil), then 2 residual blocks
        # of two SAME convs each. First conv's input gradient is dead.
        wmul = getattr(cfg, "torso_width", 1)
        h, w, c = cfg.obs_shape
        for s, base in enumerate((16, 32, 32)):
            f = base * wmul
            contraction = 9 * c
            layers.append((f"sec{s}_conv", 2 * h * w * f * contraction,
                           _pad_util(f) * _pad_util(contraction),
                           2.0 if s == 0 else 3.0))
            h, w = (h + 1) // 2, (w + 1) // 2  # maxpool 3x3 stride 2 SAME
            for r in range(2):
                layers.append((f"sec{s}_res{r}", 2 * (2 * h * w * f * 9 * f),
                               _pad_util(f) * _pad_util(9 * f), 3.0))
            c = f
        flat = h * w * c
        layers.append(("trunk_out", 2 * flat * 256,
                       _pad_util(256) * _pad_util(flat), 3.0))
        feat = 256
    elif len(cfg.obs_shape) == 3:
        # NatureConv geometry (models/torso.py), VALID padding, from the
        # actual obs_shape. conv0's backward multiplier is 2 (its input
        # gradient is dead — observations need no grad), 3 elsewhere.
        h, w, c = cfg.obs_shape
        for i, (f, k, s) in enumerate(((32, 8, 4), (64, 4, 2), (64, 3, 1))):
            h, w = (h - k) // s + 1, (w - k) // s + 1
            contraction = k * k * c
            layers.append((
                f"conv{i}_{k}x{k}s{s}",
                2 * h * w * f * contraction,
                _pad_util(f) * _pad_util(contraction),
                2.0 if i == 0 else 3.0,
            ))
            c = f
        feat = h * w * c
    else:
        layers += [("torso_mlp", 2 * (cfg.obs_shape[0] * 256 + 256 * 256),
                    _pad_util(256), 3.0)]
        feat = 256
    layers += [
        ("action_embed", 2 * (A * 256 + 256 * 256), _pad_util(256), 3.0),
        ("lstm_cell", 2 * (feat + 256 + H) * 4 * H, _pad_util(4 * H), 3.0),
        ("policy_head", 2 * (H * 256 + 256 * 256 + 256 * A), _pad_util(256), 3.0),
        ("value_head", 2 * (H * 256 + 256 * 256 + 256), _pad_util(256), 3.0),
    ]
    rows = []
    total_flops = 0.0
    attainable_s = 0.0
    for name, fwd, util, mult in layers:
        flops = fwd * frames * mult
        total_flops += flops
        t = flops / (peak * util)
        attainable_s += t
        rows.append({"layer": name, "gflops": round(flops / 1e9, 2),
                     "mxu_util": round(util, 3), "ideal_ms": round(1e3 * t, 3)})
    out = {
        "B": B,
        "peak_source": src,
        "model_note": ("attainable = per-layer FLOPs at peak*util, "
                       "util = MXU 128-lane fill of the output-channel and "
                       "contraction dims; conv0 backward omits the dead "
                       "input-gradient"),
        "layers": rows,
        "total_gflops": round(total_flops / 1e9, 2),
        "attainable_step_ms": round(1e3 * attainable_s, 3),
        "attainable_tflops_per_s": round(total_flops / attainable_s / 1e12, 1),
    }
    if measured_step_s:
        out["measured_step_ms"] = round(1e3 * measured_step_s, 3)
        out["mfu_attainable"] = round(attainable_s / measured_step_s, 3)
    return out


def bench_e2e(cfg, B: int, updates: int, feeders: int = 3,
              mode: str = "tcp") -> dict:
    """Data-plane pipeline throughput: pre-encoded synthetic trajectories
    pushed by feeder clients into the learner's bounded queue, prefetched
    onto the device, trained.

    Feeders replay encoded unrolls as fast as the plane accepts them
    (i.e. saturating actors), so this measures the SUSTAINABLE pipeline
    rate — SURVEY §7 hard part (a), "keep the chip fed" — with the
    per-stage split showing whether the chip or the host path bounds it.

    mode="tcp": feeders are real TransportClients shipping K-unroll
    batches per round trip (OP_PUT_TRAJ_N) over loopback — the deployed
    topology, including this host's TCP + GIL tax.
    mode="shm": feeders put the same encoded blobs straight into the
    (C++, GIL-releasing) queue from in-process threads — the framework's
    own ceiling with the socket hop removed. On a 1-core host the spread
    between the two IS the host tax, not framework cost.
    """
    import jax

    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent
    from distributed_reinforcement_learning_tpu.data import codec
    from distributed_reinforcement_learning_tpu.runtime.impala_runner import ImpalaLearner
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        OP_PUT_TRAJ_N, ST_OK, TransportClient, TransportServer, _make_queue,
        pack_batch)
    from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

    # Every-10 matches a realistic actor-pull cadence (the cost of a
    # per-step publish is not measured on the attached chip) —
    # override via env.
    on_accel = jax.default_backend() not in ("cpu",)
    publish_interval = int(
        os.environ.get("BENCH_PUBLISH_INTERVAL", "10" if on_accel else "1"))
    unrolls_per_put = int(os.environ.get("BENCH_PUT_BATCH", "16"))
    agent = ImpalaAgent(cfg)
    queue = _make_queue(max(4 * B, 128))
    weights = WeightStore()
    # BENCH_E2E_K>1: the learner drains K batches per learn_many dispatch
    # (prefetcher stacks them in the background).
    learner = ImpalaLearner(
        agent, queue, weights, batch_size=B, prefetch=True,
        publish_interval=publish_interval,
        updates_per_call=int(os.environ.get("BENCH_E2E_K", "1")))
    learner.timer.log_every = updates  # one flush covering the measured window
    server = None
    port = 0
    if mode == "tcp":  # shm mode must not pay even the accept thread
        port = _free_port()
        server = TransportServer(queue, weights, host="127.0.0.1", port=port).start()

    # One encoded single-env unroll, replayed by every feeder (codec encode
    # cost is the actors'; the learner-side decode+stack cost is measured).
    one = jax.tree.map(lambda x: x[0], _make_batch(cfg, 1))
    blob = codec.encode(one)

    stop = threading.Event()

    def feed_tcp():
        client = TransportClient("127.0.0.1", port, busy_timeout=600.0)
        parts = pack_batch([blob] * unrolls_per_put)
        try:
            while not stop.is_set():
                status, _ = client._exchange(OP_PUT_TRAJ_N, parts,
                                             retry=False, resend=False)
                if status != ST_OK:  # closed/unavailable queue: stop
                    raise ConnectionError(f"PUT answered status {status}")
        except (ConnectionError, OSError):
            pass
        finally:
            client.close()

    def feed_shm():
        blobs = [blob] * unrolls_per_put
        try:
            while not stop.is_set():
                if hasattr(queue, "put_bytes_many"):
                    accepted = queue.put_bytes_many(blobs, timeout=0.5)
                else:
                    accepted = queue.put_many(
                        [codec.decode(b, copy=True) for b in blobs],
                        timeout=0.5)
                if not accepted:
                    # Queue stayed full through the whole timeout: back
                    # off instead of re-arming the condvar herd at full
                    # rate — N shm feeders have no RTT throttling them
                    # (tcp feeders idle in recv between round trips), and
                    # their wakeup stampede on every learner pop is host
                    # time stolen from the learn loop (r3 run1's shm<tcp).
                    time.sleep(0.02)
        except RuntimeError:  # queue closed at teardown
            pass

    feed = feed_shm if mode == "shm" else feed_tcp
    threads = [threading.Thread(target=feed, daemon=True) for _ in range(feeders)]
    for t in threads:
        t.start()
    try:
        learner.step(timeout=120.0)  # compile + warm the pipeline
        learner.timer.reset()  # stage means must exclude the compile step
        t0 = time.perf_counter()
        start_steps = learner.train_steps  # step() may do K>1 updates/call
        last_m = None
        while learner.train_steps - start_steps < updates:
            m = learner.step(timeout=120.0)
            if m is not None:
                last_m = m
        # Completion barrier: with async publication+metrics nothing else
        # syncs the host loop to the device, so the window would count
        # DISPATCHED updates. Materializing the last step's metric forces
        # it (and, by program order, every prior step) to finish.
        if last_m:
            float(next(iter(last_m.values())))
        dt = time.perf_counter() - t0
    finally:
        stop.set()
        learner.close()
        queue.close()
        if server is not None:
            server.stop()
        for t in threads:
            t.join(timeout=5.0)
    fps = B * cfg.trajectory * (learner.train_steps - start_steps) / dt
    stage_ms = dict(learner.timer.last_means_ms) or {
        n: round(1e3 * s / learner.timer._counts[n], 3)
        for n, s in learner.timer._sums.items()
    }
    stage_ms = {k: round(v, 3) for k, v in stage_ms.items()}
    done = learner.train_steps - start_steps
    print(f"[bench] e2e[{mode}] B={B}: {done} updates in {dt:.2f}s = "
          f"{fps:,.0f} frames/s, stages {stage_ms}", file=sys.stderr)
    out = {"B": B, "mode": mode, "feeders": feeders,
           "unrolls_per_put": unrolls_per_put,
           "publish_interval": publish_interval,
           "updates_per_call": learner.updates_per_call,
           "frames_per_s": round(fps, 1), "stage_ms": stage_ms}
    if publish_interval > 1:
        # With interval K the learn stage times dispatch only; the publish
        # step's stage absorbs ~K steps of queued device compute + D2H.
        out["stage_ms_note"] = (
            f"interval={publish_interval}: 'learn' is dispatch-only, 'publish' "
            "absorbs the queued device compute; total fps is the honest number")
    return out


def bench_stage_budget(cfg, B: int, learn_fps: float | None) -> dict:
    """Independent sustained rate of every framework-owned pipeline stage,
    in env-frames/s at the Atari unroll shape, vs the 50k/chip target.

    The end-to-end number on a 1-core host is bounded by whichever stage
    the single core is currently starving; this table is the evidence
    for WHERE the ceiling is: if every framework stage independently
    clears the target but e2e doesn't, the binding constraint is the
    host's core count (stages can't run concurrently on one core), not
    any framework stage.
    """
    import jax
    import numpy as np

    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent
    from distributed_reinforcement_learning_tpu.data import codec, native
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        OP_PUT_TRAJ_N, ST_OK, TransportClient, TransportServer, pack_batch)
    from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

    T = cfg.trajectory
    target = 50_000.0
    one = jax.tree.map(lambda x: x[0], _make_batch(cfg, 1))
    blob = bytes(codec.encode(one))
    out: dict = {
        "B": B,
        "target_frames_per_s": target,
        "note": ("encode/shm_put/tcp_put/gather are host-only; h2d and "
                 "publish traverse the host<->device link"),
    }

    def med(fn, n, reps=5):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(n)
            ts.append((time.perf_counter() - t0) / n)
        return sorted(ts)[len(ts) // 2]

    # encode: actor-side serialization of one unroll.
    enc_s = med(lambda n: [codec.encode(one) for _ in range(n)], 20)
    out["encode"] = {"per_unroll_ms": round(1e3 * enc_s, 3),
                     "frames_per_s": round(T / enc_s, 1)}

    if native.native_available():
        # shm_put: C++ queue ingest, one producer, no consumer — a fresh
        # queue per rep so the bounded capacity is never hit (a blocked
        # put would measure backpressure, not ingest).
        blobs16 = [blob] * 16
        ts = []
        for _ in range(3):
            q = native.NativeTrajectoryQueue(300)
            t0 = time.perf_counter()
            for _ in range(16):
                q.put_bytes_many(blobs16)
            ts.append((time.perf_counter() - t0) / 256)
            q.close()
            del q
        put_s = sorted(ts)[1]
        out["shm_put"] = {"per_unroll_ms": round(1e3 * put_s, 4),
                          "frames_per_s": round(T / put_s, 1)}

        # gather: pooled strided batch pop + C++ field gathers at B.
        q = native.NativeTrajectoryQueue(4 * B)

        def fill():
            q.put_bytes_many([blob] * B)

        fill(); q.get_batch(B, pooled=True)  # warm pool + stride
        ts = []
        for _ in range(5):
            fill()
            t0 = time.perf_counter()
            q.get_batch(B, pooled=True)
            ts.append(time.perf_counter() - t0)
        gather_s = sorted(ts)[len(ts) // 2]
        out["gather"] = {"per_batch_ms": round(1e3 * gather_s, 2),
                         "frames_per_s": round(B * T / gather_s, 1)}

        # tcp_put: loopback transport with the batched PUT, one feeder +
        # one drainer (the deployed wire path, incl. loopback TCP cost).
        q2 = native.NativeTrajectoryQueue(4 * B)
        server = TransportServer(q2, WeightStore(), host="127.0.0.1",
                                 port=_free_port()).start()
        stop = threading.Event()

        def drain():
            while not stop.is_set():
                q2._q.get_batch_raw(16, len(blob) + 256, timeout=0.2)

        dt_thread = threading.Thread(target=drain, daemon=True)
        dt_thread.start()
        client = TransportClient("127.0.0.1", server.port, busy_timeout=60.0)
        parts = pack_batch([blob] * 16)

        def tcp_n(n):
            for _ in range(n // 16):
                status, _ = client._exchange(OP_PUT_TRAJ_N, parts,
                                             retry=False, resend=False)
                if status != ST_OK:
                    raise ConnectionError(f"PUT answered status {status}")

        tcp_n(32)  # warm
        tcp_s = med(tcp_n, 128, reps=3)
        out["tcp_put"] = {"per_unroll_ms": round(1e3 * tcp_s, 3),
                          "frames_per_s": round(T / tcp_s, 1)}
        stop.set(); client.close(); server.stop(); q2.close()
        dt_thread.join(timeout=2.0)

    # h2d: host batch -> device, marginal over pipelined windows (each
    # iteration's input is perturbed host-side so nothing is memoized).
    import jax.numpy as jnp

    batch_np = _make_batch(cfg, B)
    total_bytes = sum(np.asarray(x).nbytes for x in jax.tree.leaves(batch_np))
    reduce_fn = jax.jit(lambda b: sum(jnp.sum(x.astype(jnp.float32))
                                      for x in jax.tree.leaves(b)))

    h2d_ctr = [0]  # persists across windows: every iteration of every
    # window ships different bytes

    def h2d_window(n):
        t0 = time.perf_counter()
        acc = 0.0
        state = batch_np.state.reshape(-1)
        for _ in range(n):
            h2d_ctr[0] += 1
            state[h2d_ctr[0] % 4096] = h2d_ctr[0] % 251
            acc = acc + reduce_fn(jax.device_put(batch_np))
        float(acc)
        return time.perf_counter() - t0

    h2d_s, h2d_stats = _marginal_step_s(h2d_window, 6, samples=3)
    out["h2d"] = {"per_batch_ms": round(1e3 * h2d_s, 2),
                  "bytes_per_batch": total_bytes,
                  "gb_per_s": round(total_bytes / h2d_s / 1e9, 2),
                  "frames_per_s": round(B * T / h2d_s, 1),
                  "timing": h2d_stats}

    # h2d_overlap: effective H2D with double buffering — the device
    # sample path's copy discipline (data/device_path.py): the
    # device_put for batch k+1 is issued while batch k's compute is in
    # flight, so the marginal per-batch time prices only the NON-hidden
    # part of the copy. overlap_vs_serial > 1 means the link really
    # does overlap with compute on this host (vs the serial h2d row's
    # committed 0.87 GB/s); ~1 means copies serialize anyway (one
    # memory system — the 2-core CPU answer).
    def h2d_overlap_window(n):
        t0 = time.perf_counter()
        acc = 0.0
        state = batch_np.state.reshape(-1)
        h2d_ctr[0] += 1
        state[h2d_ctr[0] % 4096] = h2d_ctr[0] % 251
        dev = jax.device_put(batch_np)
        for _ in range(n):
            h2d_ctr[0] += 1
            state[h2d_ctr[0] % 4096] = h2d_ctr[0] % 251
            nxt = jax.device_put(batch_np)  # k+1's copy, k's compute below
            acc = acc + reduce_fn(dev)
            dev = nxt
        float(acc)
        return time.perf_counter() - t0

    ov_s, ov_stats = _marginal_step_s(h2d_overlap_window, 6, samples=3)
    out["h2d_overlap"] = {
        "per_batch_ms": round(1e3 * ov_s, 2),
        "gb_per_s_effective": round(total_bytes / ov_s / 1e9, 2),
        "frames_per_s": round(B * T / ov_s, 1),
        "overlap_vs_serial": round(h2d_s / ov_s, 2),
        "timing": ov_stats,
        "note": ("double-buffered: device_put(k+1) issued while "
                 "compute(k) is in flight — the effective feed rate the "
                 "fused device sample path sustains"),
    }

    if learn_fps is not None:
        out["learn"] = {"frames_per_s": learn_fps}

    # publish: weight snapshot off the learn thread. Sync = full D2H on
    # the caller; async = on-device copy enqueue (the learn-thread cost)
    # + background drain (the sustainable publish rate).
    agent = ImpalaAgent(cfg)
    params = agent.init_state(jax.random.PRNGKey(0)).params
    ws = WeightStore()
    t0 = time.perf_counter(); ws.publish(params, 1)
    sync_ms = 1e3 * (time.perf_counter() - t0)
    # Per-publish drain cost: enqueue-then-flush one at a time (a burst
    # would be latest-wins coalesced and understate the true D2H cost).
    enq, drains = [], []
    for v in range(2, 8):
        t0 = time.perf_counter()
        ws.publish_async(params, v)
        enq.append(time.perf_counter() - t0)
        ws.flush_async(timeout=120.0)
        drains.append(time.perf_counter() - t0)
    drain_s = sorted(drains)[len(drains) // 2]
    ws.close()
    out["publish"] = {
        "sync_ms": round(sync_ms, 2),
        "async_enqueue_ms": round(1e3 * sorted(enq)[len(enq) // 2], 3),
        "async_drain_ms": round(1e3 * drain_s, 2),
        "note": ("async enqueue is the per-publish learn-thread cost; "
                 "drain bounds publishes/s, amortized by publish_interval"),
    }

    for k in ("encode", "shm_put", "gather", "tcp_put", "h2d",
              "h2d_overlap", "learn"):
        if k in out and "frames_per_s" in out[k]:
            out[k]["meets_target"] = out[k]["frames_per_s"] >= target

    # e2e_attainable: every stage overlaps in deployment (actor
    # processes / prefetch thread / device queue), so the pipelined e2e
    # these stages would sustain = min over the MEASURED stage rates —
    # h2d included, at the rate this run measured (no assumed link).
    rates = {k: out[k]["frames_per_s"]
             for k in ("encode", "shm_put", "gather", "tcp_put", "h2d", "learn")
             if k in out and "frames_per_s" in out[k]}
    binding = min(rates, key=rates.get)
    out["e2e_attainable"] = {
        "attainable_frames_per_s": round(rates[binding], 1),
        "binding_stage": binding,
        "meets_target": rates[binding] >= target,
        "note": ("DERIVED: min over this run's measured stage rates "
                 "(overlapped pipeline model)"),
    }

    print(f"[bench] stage budget: " + ", ".join(
        f"{k}={out[k]['frames_per_s']:,.0f}f/s"
        for k in ("encode", "shm_put", "gather", "tcp_put", "h2d",
                  "h2d_overlap", "learn")
        if k in out and "frames_per_s" in out[k])
        + f"; attainable={rates[binding]:,.0f}f/s (binding: {binding})",
        file=sys.stderr)
    return out


def bench_transport_compare(cfg, n_unrolls: int = 256,
                            unrolls_per_put: int = 16, reps: int = 3) -> dict:
    """Honest A/B of the actor->learner PUT path for CO-HOSTED processes:
    real loopback TCP (batched OP_PUT_TRAJ_N, the deployed wire path)
    vs the shared-memory SPSC ring (runtime/shm_ring.py), at the bench
    unroll shape, with identical pre-encoded blobs, the same queue
    backend behind both, and a drain thread keeping backpressure honest
    on each side. Host-only (no device), so the numbers are
    link-independent and reproducible on any box.

    The verdict follows the repo's adjudication bar (Pallas-LSTM rule):
    the ring ships enabled-by-default ONLY if it sustains >= 1.2x the
    TCP PUT throughput; the committed `benchmarks/transport_verdict.json`
    carries the decision `runtime/shm_ring.ring_enabled()` consults.
    Caveat recorded in the section: both ends share this process (GIL),
    exactly like the tcp_put stage-budget row — the two-process
    correctness e2e lives in tests/test_shm_ring.py.
    """
    import jax

    from distributed_reinforcement_learning_tpu.data import codec
    from distributed_reinforcement_learning_tpu.runtime import shm_ring
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        OP_PUT_TRAJ_N, ST_OK, TransportClient, TransportServer, _make_queue,
        pack_batch)
    from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

    T = cfg.trajectory
    one = jax.tree.map(lambda x: x[0], _make_batch(cfg, 1))
    blob = bytes(codec.encode(one))

    def pctl(sorted_ms, q):
        return round(sorted_ms[min(int(q * (len(sorted_ms) - 1) + 0.5),
                                   len(sorted_ms) - 1)], 3)

    def drain_loop(queue, stop):
        raw = hasattr(queue, "put_bytes")
        while not stop.is_set():
            try:
                if raw:
                    queue._q.get_batch_raw(16, len(blob) + 256, timeout=0.2)
                else:
                    queue.get(timeout=0.2)
            except RuntimeError:
                return

    def run_phase(put_call, calls: int) -> tuple[float, list[float]]:
        """-> (elapsed_s, per-call ms) for `calls` invocations."""
        lat = []
        t0 = time.perf_counter()
        for _ in range(calls):
            c0 = time.perf_counter()
            put_call()
            lat.append((time.perf_counter() - c0) * 1e3)
        return time.perf_counter() - t0, lat

    out: dict = {"unroll_bytes": len(blob), "n_unrolls": n_unrolls,
                 "note": ("same pre-encoded blob, same queue backend, one "
                          "drain thread per side; both ends in-process "
                          "(GIL shared) like the tcp_put budget row — "
                          "two-process correctness is pinned by "
                          "tests/test_shm_ring.py")}

    # --- TCP: loopback transport, batched PUT (the deployed path).
    queue = _make_queue(128)
    server = TransportServer(queue, WeightStore(), host="127.0.0.1",
                             port=_free_port()).start()
    stop = threading.Event()
    dt_thread = threading.Thread(target=drain_loop, args=(queue, stop),
                                 daemon=True)
    dt_thread.start()
    client = TransportClient("127.0.0.1", server.port, busy_timeout=120.0)
    parts = pack_batch([blob] * unrolls_per_put)
    def tcp_call():
        status, _ = client._exchange(OP_PUT_TRAJ_N, parts, retry=False,
                                     resend=False)
        if status != ST_OK:
            raise ConnectionError(f"PUT answered status {status}")
    try:
        run_phase(tcp_call, 2)  # warm the connection + server buffers
        best = None
        for _ in range(reps):
            elapsed, lat = run_phase(tcp_call, max(n_unrolls // unrolls_per_put, 1))
            fps = (len(lat) * unrolls_per_put * T) / elapsed
            if best is None or fps > best[0]:
                best = (fps, lat)
        lat = sorted(best[1])
        out["tcp"] = {"frames_per_s": round(best[0], 1),
                      "unrolls_per_s": round(best[0] / T, 1),
                      "unrolls_per_call": unrolls_per_put,
                      "enqueue_wait_ms_p50": pctl(lat, 0.50),
                      "enqueue_wait_ms_p99": pctl(lat, 0.99)}
    finally:
        stop.set()
        client.close()
        server.stop()
        queue.close()
        dt_thread.join(timeout=2.0)

    # --- Ring: one memcpy per unroll into shared memory, learner-side
    # drainer feeding the identical queue type.
    queue2 = _make_queue(128)
    ring = shm_ring.ShmRing.create(f"bench-ring-{os.getpid()}",
                                   shm_ring.ring_capacity_bytes())
    drainer = shm_ring.RingDrainer([ring], queue2).start()
    stop2 = threading.Event()
    dt2 = threading.Thread(target=drain_loop, args=(queue2, stop2), daemon=True)
    dt2.start()
    ring_call = lambda: ring.put_blob(blob, timeout=120.0)  # noqa: E731
    try:
        run_phase(ring_call, 2 * unrolls_per_put)  # warm the segment
        best = None
        for _ in range(reps):
            elapsed, lat = run_phase(ring_call, n_unrolls)
            fps = (len(lat) * T) / elapsed
            if best is None or fps > best[0]:
                best = (fps, lat)
        lat = sorted(best[1])
        out["ring"] = {"frames_per_s": round(best[0], 1),
                       "unrolls_per_s": round(best[0] / T, 1),
                       "unrolls_per_call": 1,
                       "enqueue_wait_ms_p50": pctl(lat, 0.50),
                       "enqueue_wait_ms_p99": pctl(lat, 0.99)}
    finally:
        stop2.set()
        drainer.stop()  # closes + unlinks the segment
        queue2.close()
        dt2.join(timeout=2.0)

    ratio = out["ring"]["frames_per_s"] / max(out["tcp"]["frames_per_s"], 1e-9)
    out["ring_vs_tcp"] = round(ratio, 2)
    out["auto_enable"] = ratio >= 1.2  # the repo's adjudication bar
    out["verdict"] = (f"ring {ratio:.2f}x tcp put: "
                      + ("auto-on" if out["auto_enable"] else "opt-in"))
    print(f"[bench] transport_compare: tcp {out['tcp']['frames_per_s']:,.0f} "
          f"f/s vs ring {out['ring']['frames_per_s']:,.0f} f/s "
          f"-> {out['verdict']}", file=sys.stderr)
    return out


# Child-process actor for bench_codec_compare: encodes the deterministic
# synthetic trees (rebuilt from argv, no pickling) and PUTs them over the
# parent's real TCP server — the DEPLOYED co-hosted topology, so the
# learner-side serve/ingest work genuinely overlaps the actor's encode
# instead of time-slicing one GIL with it (the in-process transport_compare
# caveat this section must not inherit: encode is exactly what is being
# adjudicated here).
_CODEC_CHILD = r"""
import json, os, sys, time
import numpy as np

from distributed_reinforcement_learning_tpu.data import codec
from distributed_reinforcement_learning_tpu.runtime.transport import TransportClient
from distributed_reinforcement_learning_tpu.utils.synthetic import (
    synthetic_impala_batch)

(host, port, T, n_unrolls, upp, reps,
 obs_shape, num_actions, lstm) = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]), int(sys.argv[6]), json.loads(sys.argv[7]),
    int(sys.argv[8]), int(sys.argv[9]))
batch = synthetic_impala_batch(1, T, tuple(obs_shape), num_actions, lstm,
                               uniform_behavior=False)
one = type(batch)(*[np.asarray(v)[0] for v in batch])
trees = [one] * upp
if len(obs_shape) == 3 and 2 <= obs_shape[-1] <= 8:
    h, w, s = obs_shape
    planes = np.random.RandomState(0).randint(
        0, 255, (T + s - 1, h, w)).astype(np.uint8)
    stacked = np.lib.stride_tricks.sliding_window_view(
        planes, s, axis=0).copy()
    # Distinct trees, and every third one carries a mid-unroll reset at
    # a VARYING step: real actor traffic has per-trajectory reset
    # positions, so the dedup plan cache (keyed on them) must not be
    # allowed a 100%-hit fantasy the deployment can't reach.
    trees = []
    for i in range(upp):
        st = stacked.copy()
        if i % 3 == 2:
            t_reset = 1 + (i % (T - 1))
            st[t_reset] = 0
            st[t_reset, :, :, -1] = planes[t_reset + s - 1]
        trees.append(one._replace(state=st))
client = TransportClient(host, port, busy_timeout=120.0)


def pctl(sorted_ms, q):
    return round(sorted_ms[min(int(q * (len(sorted_ms) - 1) + 0.5),
                               len(sorted_ms) - 1)], 3)


def run_variant(cache_env, dedup_env):
    # The DEPLOYED client path end-to-end: put_trajectories encodes per
    # tree (honoring DRL_OBS_DEDUP exactly as a real actor does), loops
    # on the server's accepted count, and retries refused tails — so a
    # variant that outruns the drain pays the backpressure instead of
    # counting dropped unrolls as throughput.
    os.environ["DRL_CODEC_CACHE"] = cache_env
    os.environ["DRL_OBS_DEDUP"] = dedup_env
    codec.refresh_flags()
    codec.clear_caches()

    def call():
        sent = client.put_trajectories(trees)
        assert sent == len(trees), f"dropped {len(trees) - sent} unrolls"

    call()  # warm the connection, caches, and server buffers
    best = None
    for _ in range(reps):
        lat = []
        t0 = time.perf_counter()
        for _ in range(max(n_unrolls // upp, 1)):
            c0 = time.perf_counter()
            call()
            lat.append((time.perf_counter() - c0) * 1e3)
        elapsed = time.perf_counter() - t0
        fps = (len(lat) * upp * T) / elapsed
        if best is None or fps > best[0]:
            best = (fps, lat)
    lat = sorted(best[1])
    return {"frames_per_s": round(best[0], 1),
            "unrolls_per_s": round(best[0] / T, 1),
            "put_ms_p50": pctl(lat, 0.50), "put_ms_p99": pctl(lat, 0.99)}


out = {"unroll_bytes": len(codec.encode(trees[0])),
       "packed_bytes": len(codec.encode(trees[0], dedup=True)),
       "cold": run_variant("0", "0"),
       "cached": run_variant("1", "0"),
       "dedup": run_variant("1", "1")}
client.close()
print("CODEC_CHILD=" + json.dumps(out))
"""


def bench_codec_compare(cfg, n_unrolls: int = 192,
                        unrolls_per_put: int = 16, reps: int = 3) -> dict:
    """Old-vs-new ENCODE+PUT A/B for the actor->learner hot path: the
    same trajectory trees are codec-encoded per call (this is the stage
    the schema cache and frame-stack dedup attack — transport_compare
    deliberately pre-encodes and so never sees encode cost) and shipped
    over real loopback TCP (batched OP_PUT_TRAJ_N) into the default
    queue backend, a drain thread keeping backpressure honest.

    TWO PROCESSES, the deployed co-hosted topology: the actor side runs
    in a child process (`_CODEC_CHILD`) so the learner-side serve +
    ingest (incl. the dedup reconstruction in `fifo.blob_ingest`)
    overlaps the actor's encode on its own core instead of sharing one
    GIL with the stage under adjudication.

    Three child variants: `cold` (DRL_CODEC_CACHE=0 — the pre-cache
    codec, the adjudication baseline), `cached` (schema + layout caches
    on), `dedup` (caches + frame-stack packing; the observation leaf is
    synthesized with real newest-last stacking so the packer sees the
    deployed redundancy). Verdicts per the repo's 1.2x adjudication
    bar: `cache_auto_enable` from cached/cold, `dedup_auto_enable` from
    dedup/cached; the committed decision lives in
    `benchmarks/codec_verdict.json`, which `codec.cache_enabled()` /
    `codec.obs_dedup_enabled()` consult when their env knobs are unset.
    Host-only, link-independent.
    """
    import subprocess

    from distributed_reinforcement_learning_tpu.data import codec
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        TransportServer, _make_queue)
    from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

    T = cfg.trajectory
    out: dict = {
        "n_unrolls": n_unrolls, "unrolls_per_call": unrolls_per_put,
        "note": ("encode included per call (the stage under test), real "
                 "loopback TCP + default queue + drain thread; actor side "
                 "is a separate PROCESS (deployed co-hosted topology), so "
                 "serve/ingest overlap the encode under adjudication")}

    queue = _make_queue(128)
    server = TransportServer(queue, WeightStore(), host="127.0.0.1",
                             port=_free_port()).start()
    stop = threading.Event()

    def drain_loop():
        raw = hasattr(queue, "put_bytes")
        cap = 1 << 16
        while not stop.is_set():
            try:
                if raw:
                    got = queue._q.get_batch_raw(16, cap, timeout=0.2)
                    if got is not None:
                        cap = got[1]  # keep the learned stride: the pop
                        # regrows it internally with a fresh buffer per
                        # doubling, and repaying that every iteration
                        # would depress all three variants' ratios
                else:
                    queue.get(timeout=0.2)
            except RuntimeError:
                return

    dt = threading.Thread(target=drain_loop, daemon=True)
    dt.start()
    repo_root = os.path.dirname(os.path.abspath(__file__))
    env = {k: v for k, v in os.environ.items()
           if k not in ("DRL_CODEC_CACHE", "DRL_OBS_DEDUP")}
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # the child never touches a device
    # LEARNER side of the A/B: the only server-side codec work is the
    # dedup variant's reconstruction (plain blobs pass blob_ingest on a
    # substring scan), and an opted-in deployment opts in both roles —
    # so this process runs it CACHED, not at the committed default.
    saved_parent = {"DRL_CODEC_CACHE": os.environ.get("DRL_CODEC_CACHE")}
    os.environ["DRL_CODEC_CACHE"] = "1"
    codec.refresh_flags()
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _CODEC_CHILD, "127.0.0.1", str(server.port),
             str(T), str(n_unrolls), str(unrolls_per_put), str(reps),
             json.dumps(list(cfg.obs_shape)), str(cfg.num_actions),
             str(cfg.lstm_size)],
            env=env, capture_output=True, text=True, timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(
                f"codec_compare child rc={proc.returncode}: "
                f"{proc.stderr.strip()[-500:]}")
        line = next(ln for ln in proc.stdout.splitlines()
                    if ln.startswith("CODEC_CHILD="))
        out.update(json.loads(line.split("=", 1)[1]))
    finally:
        if saved_parent["DRL_CODEC_CACHE"] is None:
            os.environ.pop("DRL_CODEC_CACHE", None)
        else:
            os.environ["DRL_CODEC_CACHE"] = saved_parent["DRL_CODEC_CACHE"]
        codec.refresh_flags()
        stop.set()
        server.stop()
        queue.close()
        dt.join(timeout=2.0)

    r_cache = out["cached"]["frames_per_s"] / max(out["cold"]["frames_per_s"], 1e-9)
    r_dedup = out["dedup"]["frames_per_s"] / max(out["cached"]["frames_per_s"], 1e-9)
    out["cached_vs_cold"] = round(r_cache, 2)
    out["dedup_vs_cached"] = round(r_dedup, 2)
    out["cache_auto_enable"] = r_cache >= 1.2  # the repo's adjudication bar
    out["dedup_auto_enable"] = r_dedup >= 1.2
    out["verdict"] = (
        f"codec cache {r_cache:.2f}x cold "
        f"({'auto-on' if out['cache_auto_enable'] else 'opt-in'}), "
        f"dedup {r_dedup:.2f}x cached "
        f"({'auto-on' if out['dedup_auto_enable'] else 'opt-in'})")
    print(f"[bench] codec_compare: cold {out['cold']['frames_per_s']:,.0f} "
          f"f/s vs cached {out['cached']['frames_per_s']:,.0f} f/s vs "
          f"dedup {out['dedup']['frames_per_s']:,.0f} f/s -> {out['verdict']}",
          file=sys.stderr)
    return out


# Child-process actor for bench_weights_compare: the deployed co-hosted
# actor loop at one remove — each round PUTs a batch of pre-encoded
# trajectory blobs over the real TCP transport AND polls the weight
# plane (TCP GET_WEIGHTS vs the shm board, selected by argv), so the
# learner-side publish/serve work genuinely overlaps the pulls under
# adjudication instead of time-slicing one GIL with them.
_WEIGHTS_CHILD = r"""
import json, os, sys, time
import numpy as np

from distributed_reinforcement_learning_tpu.data import codec
from distributed_reinforcement_learning_tpu.runtime.transport import (
    OP_PUT_TRAJ_N, RemoteWeights, TransportClient, pack_batch)
from distributed_reinforcement_learning_tpu.utils.synthetic import (
    synthetic_impala_batch)

(host, port, board_name, T, rounds, upp, obs_shape, num_actions, lstm) = (
    sys.argv[1], int(sys.argv[2]), sys.argv[3], int(sys.argv[4]),
    int(sys.argv[5]), int(sys.argv[6]), json.loads(sys.argv[7]),
    int(sys.argv[8]), int(sys.argv[9]))
batch = synthetic_impala_batch(1, T, tuple(obs_shape), num_actions, lstm,
                               uniform_behavior=False)
one = type(batch)(*[np.asarray(v)[0] for v in batch])
blob = bytes(codec.encode(one))
parts = pack_batch([blob] * upp)
client = TransportClient(host, port, busy_timeout=120.0)
if board_name:
    from distributed_reinforcement_learning_tpu.runtime import weight_board

    src = weight_board.attach_board_weights(board_name, client,
                                            deadline_s=10.0)
    assert src is not None and src._board is not None, "board attach failed"
else:
    src = RemoteWeights(client)


def put_call():
    status, resp = client._exchange(OP_PUT_TRAJ_N, parts, retry=False,
                                    resend=False)
    assert status == 0, f"put failed: status {status}"


put_call()  # warm the connection + server buffers
have = -1
got = src.get_if_newer(have)  # warm the pull path (and any codec caches)
if got is not None:
    have = got[1]
pull_ms = []
pulled = 0
t0 = time.perf_counter()
for _ in range(rounds):
    c0 = time.perf_counter()
    got = src.get_if_newer(have)
    pull_ms.append((time.perf_counter() - c0) * 1e3)
    if got is not None:
        have = got[1]
        pulled += 1
    put_call()
elapsed = time.perf_counter() - t0
out = {"frames_per_s": round(rounds * upp * T / elapsed, 1),
       "pull_ms": [round(ms, 4) for ms in pull_ms],
       "weight_pulls": pulled, "last_version": have}
if board_name and hasattr(src, "snapshot_stats"):
    out["board_stats"] = src.snapshot_stats()
print("WEIGHTS_CHILD=" + json.dumps(out))
"""


def bench_weights_compare(cfg, n_actors: int = 2, rounds: int = 96,
                          unrolls_per_put: int = 8,
                          publish_period_s: float = 0.04) -> dict:
    """Two-process A/B of the learner->actor WEIGHT plane for co-hosted
    topologies: TCP GET_WEIGHTS pulls (the deployed wire path, already
    encode-once via `WeightStore.get_blob`) vs the shared-memory weight
    board (runtime/weight_board.py — a pull is a shm version peek plus
    one memcpy only when the version changed). Both variants run the
    SAME params pytree, the same publish cadence through the real
    `PublishCadenceMixin.maybe_publish` (async publication, handoff +
    bounded-staleness stall stages recorded per invocation), and the
    same actor-side trajectory PUT load from `n_actors` REAL child
    processes — so the learner-side serve work overlaps the pulls on
    its own core and e2e frames/s reflects what the weight plane costs
    the data plane.

    The verdict follows the repo's adjudication bar (Pallas-LSTM rule):
    the board ships enabled-by-default ONLY if the A/B shows >= 1.2x
    e2e frames/s; the committed `benchmarks/weights_verdict.json`
    carries the decision `runtime/weight_board.board_enabled()` consults.
    Host-only, link-independent.
    """
    import numpy as np

    from distributed_reinforcement_learning_tpu.runtime import weight_board
    from distributed_reinforcement_learning_tpu.runtime.publishing import (
        PublishCadenceMixin)
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        TransportServer, _make_queue)
    from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

    T = cfg.trajectory
    # A mid-sized conv-net-shaped params pytree (~4 MB), identical for
    # both variants — the blob the weight plane actually moves.
    rng = np.random.RandomState(0)
    params = {
        f"layer{i}": {"w": rng.standard_normal((256, 512)).astype(np.float32),
                      "b": rng.standard_normal(512).astype(np.float32)}
        for i in range(8)
    }
    params["step"] = np.zeros((), np.int64)

    class _Publisher(PublishCadenceMixin):
        publish_interval = 1

        def __init__(self, weights):
            self.weights = weights
            self.train_steps = 0
            self.timer = _RecTimer()

            class _State:
                pass

            self.state = _State()
            self.state.params = params

    pctl, stage_p = _pctl, _stage_p  # shared weight-plane helpers

    repo_root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # the children never touch a device

    def run_variant(board_name: str) -> dict:
        queue = _make_queue(128)
        weights = WeightStore()
        board = None
        if board_name:
            board = weight_board.WeightBoard.create(
                board_name, weight_board.board_capacity_bytes())
            weights.attach_board(board)
        server = TransportServer(queue, weights, host="127.0.0.1",
                                 port=_free_port()).start()
        stop = threading.Event()

        def drain_loop():
            raw = hasattr(queue, "put_bytes")
            cap = 1 << 16
            while not stop.is_set():
                try:
                    if raw:
                        got = queue._q.get_batch_raw(16, cap, timeout=0.2)
                        if got is not None:
                            cap = got[1]
                    else:
                        queue.get(timeout=0.2)
                except RuntimeError:
                    return

        pub = _Publisher(weights)
        pub.train_steps = 1
        pub.maybe_publish()  # version 1 lands before any child attaches
        assert weights.flush_async(timeout=30.0)

        def pub_loop():
            while not stop.wait(publish_period_s):
                params["step"] = np.asarray(pub.train_steps + 1, np.int64)
                pub.train_steps += 1
                pub.maybe_publish()

        threads = [threading.Thread(target=drain_loop, daemon=True),
                   threading.Thread(target=pub_loop, daemon=True)]
        for t in threads:
            t.start()
        try:
            procs = [subprocess.Popen(
                [sys.executable, "-c", _WEIGHTS_CHILD, "127.0.0.1",
                 str(server.port), board_name, str(T), str(rounds),
                 str(unrolls_per_put), json.dumps(list(cfg.obs_shape)),
                 str(cfg.num_actions), str(cfg.lstm_size)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True) for _ in range(n_actors)]
            results = []
            for proc in procs:
                out_s, err_s = proc.communicate(timeout=600)
                if proc.returncode != 0:
                    raise RuntimeError(
                        f"weights_compare child rc={proc.returncode}: "
                        f"{err_s.strip()[-500:]}")
                line = next(ln for ln in out_s.splitlines()
                            if ln.startswith("WEIGHTS_CHILD="))
                results.append(json.loads(line.split("=", 1)[1]))
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5.0)
            weights.close()
            server.stop()
            queue.close()
            if board is not None:
                board.close_writer()
                board.close()
                board.unlink()
        pull_ms = sorted(ms for r in results for ms in r["pull_ms"])
        samples = pub.timer.samples
        out = {
            "frames_per_s": round(sum(r["frames_per_s"] for r in results), 1),
            "weight_pulls": sum(r["weight_pulls"] for r in results),
            "weight_pull_ms_p50": pctl(pull_ms, 0.50),
            "weight_pull_ms_p99": pctl(pull_ms, 0.99),
            "publish": stage_p(samples, "publish"),
            "publish_handoff": stage_p(samples, "publish_handoff"),
            "publish_stall": stage_p(samples, "publish_stall"),
            "versions_published": pub.train_steps,
        }
        if board_name:
            # Aggregate EVERY child's board counters — and refuse to
            # record a "board" number that silently measured TCP: a
            # child that demoted mid-run (tcp_fallbacks > 0) would
            # poison the adjudication artifact with a mislabeled ratio.
            agg: dict = {}
            for r in results:
                for k, v in r.get("board_stats", {}).items():
                    agg[k] = agg.get(k, 0) + v
            out["board_stats"] = agg
            if agg.get("tcp_fallbacks", 0):
                raise RuntimeError(
                    f"board variant demoted to TCP mid-run "
                    f"(tcp_fallbacks={agg['tcp_fallbacks']}): the measurement "
                    f"is not a board number; rerun on a quiet host")
        return out

    from distributed_reinforcement_learning_tpu.data import codec as _codec

    blob_bytes = len(_codec.encode(params, cache=True))
    out: dict = {
        "params_bytes": blob_bytes, "n_actors": n_actors,
        "rounds_per_actor": rounds, "unrolls_per_put": unrolls_per_put,
        "publish_period_s": publish_period_s,
        "note": ("same params pytree + publish cadence + PUT load both "
                 "sides; actors are separate PROCESSES (deployed "
                 "co-hosted topology), learner publishes via the real "
                 "async PublishCadenceMixin path")}
    out["tcp"] = run_variant("")
    out["board"] = run_variant(f"drlwb-bench-{os.getpid()}")
    # Broadcast bytes per landed version (ISSUE 8 satellite): the
    # whole-blob plane moves the full params blob per version on both
    # variants — per-pull on TCP, one memcpy on the board. The sharded
    # section (weights_shard_compare) is where this number moves.
    for side in ("tcp", "board"):
        out[side]["broadcast_bytes_per_version"] = blob_bytes
    ratio = out["board"]["frames_per_s"] / max(out["tcp"]["frames_per_s"], 1e-9)
    pull_ratio = out["tcp"]["weight_pull_ms_p50"] / max(
        out["board"]["weight_pull_ms_p50"], 1e-9)
    out["board_vs_tcp"] = round(ratio, 2)
    out["pull_p50_speedup"] = round(pull_ratio, 2)
    out["auto_enable"] = ratio >= 1.2  # the repo's adjudication bar
    out["verdict"] = (f"board {ratio:.2f}x tcp e2e "
                      f"(pull p50 {pull_ratio:.1f}x): "
                      + ("auto-on" if out["auto_enable"] else "opt-in"))
    print(f"[bench] weights_compare: tcp {out['tcp']['frames_per_s']:,.0f} "
          f"f/s vs board {out['board']['frames_per_s']:,.0f} f/s "
          f"-> {out['verdict']}", file=sys.stderr)
    return out


def _shard_bench_params(shape: str, seed: int = 0) -> dict:
    """Synthetic params pytrees for the sharded-weight-plane A/B.

    "cnn": the weights_compare ~4.2 MB conv-policy-sized pytree (every
    leaf-name below the model-sharding rules — one replicated shard plus
    the big-kernel shard, the degenerate case sharding must not regress).
    "xformer": an xformer-sized (~19 MB) stacked-transformer pytree whose
    names hit the pipe/model partition rules — the policy scale the
    sharded plane exists for (ROADMAP item 1)."""
    import numpy as np

    rng = np.random.RandomState(seed)
    if shape == "cnn":
        params = {
            f"layer{i}": {"w": rng.standard_normal((256, 512)).astype(np.float32),
                          "b": rng.standard_normal(512).astype(np.float32)}
            for i in range(8)
        }
        params["step"] = np.zeros((), np.int64)
        return params
    layers, d = 6, 256
    blocks = {
        "qkv_kernel": rng.standard_normal((layers, d, 3 * d)).astype(np.float32),
        "proj_kernel": rng.standard_normal((layers, d, d)).astype(np.float32),
        "mlp_in_kernel": rng.standard_normal((layers, d, 4 * d)).astype(np.float32),
        "mlp_out_kernel": rng.standard_normal((layers, 4 * d, d)).astype(np.float32),
        "ln1_scale": np.ones((layers, d), np.float32),
        "ln1_bias": np.zeros((layers, d), np.float32),
        "ln2_scale": np.ones((layers, d), np.float32),
        "ln2_bias": np.zeros((layers, d), np.float32),
    }
    return {
        "blocks_stacked": blocks,
        "embed": rng.standard_normal((128, d)).astype(np.float32),
        "head": {"w": rng.standard_normal((d, 512)).astype(np.float32),
                 "b": np.zeros(512, np.float32)},
        "step": np.zeros((), np.int64),
    }


def _bf16_policy_equivalence(envs: int = 16, steps: int = 16) -> dict:
    """The quantized-broadcast acceptance pin: actions sampled from a
    REAL ImpalaAgent acting on bf16-cast-then-dequantized params vs the
    f32 originals, over a fixed rollout (same obs stream, same rng keys,
    each side advancing its own LSTM chain so any divergence compounds
    the way it would on a live actor)."""
    import jax
    import numpy as np

    from distributed_reinforcement_learning_tpu.agents.impala import (
        ImpalaAgent, ImpalaConfig)
    from distributed_reinforcement_learning_tpu.runtime import weight_shards

    cfg = ImpalaConfig(obs_shape=(64,), num_actions=8, trajectory=8,
                       lstm_size=64)
    agent = ImpalaAgent(cfg)
    params = jax.device_get(agent.init_state(jax.random.PRNGKey(0)).params)
    bundle = weight_shards.build_bundle(params, quant="bf16")
    qparams = weight_shards.materialize(dict(bundle.manifest, version=0),
                                        bundle.blobs)
    rng = np.random.RandomState(7)
    key0 = jax.random.PRNGKey(123)
    pa_f = pa_q = np.zeros(envs, np.int32)
    h_f, c_f = agent.initial_lstm_state(envs)
    h_q, c_q = h_f, c_f
    matches = total = 0
    max_policy_diff = 0.0
    for t in range(steps):
        obs = rng.standard_normal((envs, *cfg.obs_shape)).astype(np.float32)
        key = jax.random.fold_in(key0, t)
        out_f = agent.act(params, obs, pa_f, h_f, c_f, key)
        out_q = agent.act(qparams, obs, pa_q, h_q, c_q, key)
        a_f, a_q = np.asarray(out_f.action), np.asarray(out_q.action)
        matches += int((a_f == a_q).sum())
        total += envs
        max_policy_diff = max(max_policy_diff, float(np.max(np.abs(
            np.asarray(out_f.policy) - np.asarray(out_q.policy)))))
        pa_f, pa_q = a_f.astype(np.int32), a_q.astype(np.int32)
        h_f, c_f = out_f.h, out_f.c
        h_q, c_q = out_q.h, out_q.c
    return {"action_match": round(matches / total, 4),
            "max_policy_diff": round(max_policy_diff, 6),
            "rollout": [envs, steps]}


def bench_weights_shard_compare(cfg, n_actors: int = 2, rounds: int = 40,
                                unrolls_per_put: int = 8,
                                publish_period_s: float = 0.05,
                                shapes: tuple = ("cnn", "xformer")) -> dict:
    """Sharded-weight-plane A/B (ISSUE 8): whole-blob vs sharded vs
    sharded+bf16, at the CNN shape AND an xformer-sized pytree, each
    variant a full two-child-process topology over the deployed
    broadcast path (shm board + real TCP PUT load, exactly the
    weights_compare harness). The publisher MUTATES every float leaf
    in place each cadence tick (the learner's train step rewrites every
    parameter every update), so changed-shard elision cannot fake a win
    — sharding has to pay for its per-shard encodes with real pull/
    publish savings, and bf16 with its halved broadcast bytes.

    Verdict (the repo's 1.2x adjudication bar, per shape, min across
    shapes): `auto_enable` for DRL_WEIGHTS_SHARDED, `quant_auto_enable`
    for the bf16 broadcast (additionally requiring the policy-
    equivalence pin), committed to
    benchmarks/weights_shard_verdict.json. Delta publication is NOT
    adjudicated here — loopback bytes are free, so a local A/B cannot
    say anything honest about it; it stays opt-in with its own note.
    """
    import numpy as np

    from distributed_reinforcement_learning_tpu.data import codec as codec_mod
    from distributed_reinforcement_learning_tpu.runtime import weight_board
    from distributed_reinforcement_learning_tpu.runtime.publishing import (
        PublishCadenceMixin)
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        TransportServer, _make_queue)
    from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

    T = cfg.trajectory
    pctl, stage_p = _pctl, _stage_p  # shared weight-plane helpers
    repo_root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # the children never touch a device
    for key in ("DRL_WEIGHTS_SHARDED", "DRL_WEIGHTS_QUANT",
                "DRL_WEIGHTS_DELTA", "DRL_WEIGHTS_KEYS"):
        env.pop(key, None)  # children follow the board/server, not env

    def run_variant(shape: str, sharded: bool, quant: str) -> dict:
        params = _shard_bench_params(shape)
        float_leaves = []
        import jax

        jax.tree.map(lambda a: float_leaves.append(a)
                     if getattr(a, "dtype", None) == np.float32 else None,
                     params)
        blob_bytes = len(codec_mod.encode(params, cache=True))
        queue = _make_queue(128)
        weights = WeightStore(sharded=sharded, quant=quant)
        cap = max(int(blob_bytes * 1.5), 8 << 20)
        name = f"drlwsb-{os.getpid()}-{shape}"
        if sharded:
            board = weight_board.ShardedWeightBoard.create(name, 2 * cap)
        else:
            board = weight_board.WeightBoard.create(name, cap)
        weights.attach_board(board)
        server = TransportServer(queue, weights, host="127.0.0.1",
                                 port=_free_port()).start()
        stop = threading.Event()

        def drain_loop():
            raw = hasattr(queue, "put_bytes")
            dcap = 1 << 16
            while not stop.is_set():
                try:
                    if raw:
                        got = queue._q.get_batch_raw(16, dcap, timeout=0.2)
                        if got is not None:
                            dcap = got[1]
                    else:
                        queue.get(timeout=0.2)
                except RuntimeError:
                    return

        class _Publisher(PublishCadenceMixin):
            publish_interval = 1

            def __init__(self):
                self.weights = weights
                self.train_steps = 0
                self.timer = _RecTimer()

                class _State:
                    pass

                self.state = _State()
                self.state.params = params

        pub = _Publisher()
        pub.train_steps = 1
        pub.maybe_publish()  # version 1 lands before any child attaches
        assert weights.flush_async(timeout=60.0)

        def pub_loop():
            while not stop.wait(publish_period_s):
                # Every float leaf drifts IN PLACE — the honest model of
                # a train step (every parameter moves every update), so
                # every shard is genuinely changed every version.
                for leaf in float_leaves:
                    leaf += np.float32(1e-6)
                params["step"] = np.asarray(pub.train_steps + 1, np.int64)
                pub.train_steps += 1
                pub.maybe_publish()

        threads = [threading.Thread(target=drain_loop, daemon=True),
                   threading.Thread(target=pub_loop, daemon=True)]
        for t in threads:
            t.start()
        try:
            procs = [subprocess.Popen(
                [sys.executable, "-c", _WEIGHTS_CHILD, "127.0.0.1",
                 str(server.port), name, str(T), str(rounds),
                 str(unrolls_per_put), json.dumps(list(cfg.obs_shape)),
                 str(cfg.num_actions), str(cfg.lstm_size)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True) for _ in range(n_actors)]
            results = []
            for proc in procs:
                out_s, err_s = proc.communicate(timeout=600)
                if proc.returncode != 0:
                    raise RuntimeError(
                        f"weights_shard_compare child rc={proc.returncode}: "
                        f"{err_s.strip()[-500:]}")
                line = next(ln for ln in out_s.splitlines()
                            if ln.startswith("WEIGHTS_CHILD="))
                results.append(json.loads(line.split("=", 1)[1]))
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5.0)
            weights.close()
            server.stop()
            queue.close()
            board.close_writer()
            board.close()
            board.unlink()
        pull_ms = sorted(ms for r in results for ms in r["pull_ms"])
        agg: dict = {}
        for r in results:
            for k, v in r.get("board_stats", {}).items():
                agg[k] = agg.get(k, 0) + v
        if agg.get("tcp_fallbacks", 0) or agg.get("board_shard_fallbacks", 0):
            raise RuntimeError(
                f"board variant fell back mid-run ({agg}): the measurement "
                f"is not a board number; rerun on a quiet host")
        sst = weights.shard_stats()
        if sharded and sst["shard_publishes"]:
            bcast = round(sst["broadcast_bytes"] / sst["shard_publishes"])
        else:
            bcast = blob_bytes
        return {
            "frames_per_s": round(sum(r["frames_per_s"] for r in results), 1),
            "weight_pulls": sum(r["weight_pulls"] for r in results),
            "weight_pull_ms_p50": pctl(pull_ms, 0.50),
            "weight_pull_ms_p99": pctl(pull_ms, 0.99),
            "publish": stage_p(pub.timer.samples, "publish"),
            "publish_handoff": stage_p(pub.timer.samples, "publish_handoff"),
            "publish_stall": stage_p(pub.timer.samples, "publish_stall"),
            "versions_published": pub.train_steps,
            "params_bytes": blob_bytes,
            "broadcast_bytes_per_version": bcast,
            "board_stats": agg,
        }

    out: dict = {
        "n_actors": n_actors, "rounds_per_actor": rounds,
        "unrolls_per_put": unrolls_per_put,
        "publish_period_s": publish_period_s,
        "note": ("same pytree + publish cadence + PUT load across "
                 "variants; every float leaf mutates in place per "
                 "publish (train-step model) so changed-shard elision "
                 "cannot fake the ratio; children are real processes on "
                 "the deployed board/BoardWeights path")}
    ratios, qratios = [], []
    for shape in shapes:
        sec = {"whole": run_variant(shape, False, ""),
               "sharded": run_variant(shape, True, ""),
               "sharded_bf16": run_variant(shape, True, "bf16")}
        base = max(sec["whole"]["frames_per_s"], 1e-9)
        sec["sharded_vs_whole"] = round(sec["sharded"]["frames_per_s"] / base, 2)
        sec["bf16_vs_whole"] = round(
            sec["sharded_bf16"]["frames_per_s"] / base, 2)
        ratios.append(sec["sharded_vs_whole"])
        qratios.append(sec["bf16_vs_whole"])
        out[shape] = sec
        print(f"[bench] weights_shard[{shape}]: whole "
              f"{sec['whole']['frames_per_s']:,.0f} f/s, sharded "
              f"{sec['sharded']['frames_per_s']:,.0f} "
              f"({sec['sharded_vs_whole']}x), +bf16 "
              f"{sec['sharded_bf16']['frames_per_s']:,.0f} "
              f"({sec['bf16_vs_whole']}x); bcast B/ver "
              f"{sec['whole']['broadcast_bytes_per_version']} -> "
              f"{sec['sharded_bf16']['broadcast_bytes_per_version']}",
              file=sys.stderr)
    out["policy_equiv"] = _bf16_policy_equivalence()
    out["sharded_ratio"] = min(ratios)
    out["bf16_ratio"] = min(qratios)
    out["auto_enable"] = min(ratios) >= 1.2  # the repo's adjudication bar
    out["quant_auto_enable"] = (min(qratios) >= 1.2
                                and out["policy_equiv"]["action_match"] >= 0.99)
    out["delta_auto_enable"] = False  # loopback cannot adjudicate bytes
    out["verdict"] = (
        f"sharded {min(ratios):.2f}x whole, +bf16 {min(qratios):.2f}x "
        f"(equiv {out['policy_equiv']['action_match']:.2%}): "
        + ("auto-on" if out["auto_enable"] else "opt-in"))
    return out


# Child-process actor for bench_replay_compare: PUTs deterministic Ape-X
# unrolls over the real TCP client path (put_trajectories, accepted
# counts honored — a variant that outruns ingest pays the backpressure
# instead of counting dropped unrolls as throughput). No jax import: the
# unroll is a structural ApexBatch namedtuple, exactly what the server
# side decodes either way.
_REPLAY_CHILD = r"""
import sys
from collections import namedtuple

import numpy as np

from distributed_reinforcement_learning_tpu.data import codec  # noqa: F401
from distributed_reinforcement_learning_tpu.runtime.transport import TransportClient

host, port, n_unrolls, upp, steps, obs_dim = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]), int(sys.argv[6]))
ApexBatch = namedtuple("ApexBatch", ["state", "next_state", "previous_action",
                                     "action", "reward", "done"])
rng = np.random.RandomState(0)
trees = []
for _ in range(upp):
    trees.append(ApexBatch(
        state=rng.rand(steps, obs_dim).astype(np.float32),
        next_state=rng.rand(steps, obs_dim).astype(np.float32),
        previous_action=rng.randint(0, 2, steps).astype(np.int32),
        action=rng.randint(0, 2, steps).astype(np.int32),
        reward=rng.randn(steps).astype(np.float32),
        done=(rng.rand(steps) < 0.1)))
client = TransportClient(host, port, busy_timeout=120.0)
sent = 0
while sent < n_unrolls:
    chunk = trees[: min(upp, n_unrolls - sent)]
    got = client.put_trajectories(chunk)
    assert got == len(chunk), f"dropped {len(chunk) - got} unrolls"
    sent += got
client.close()
print("REPLAY_CHILD_DONE")
"""


def bench_replay_compare(n_unrolls: int = 192, unrolls_per_put: int = 8,
                         steps: int = 32, obs_dim: int = 64,
                         num_shards: int = 2, reps: int = 1) -> dict:
    """Two-process A/B of the Ape-X INGEST plane: monolithic replay (the
    learner thread decodes, TD-scores, and sum-tree-inserts every unroll
    it drains — `apex_runner.ingest_many`) vs the sharded service
    (data/replay_service.py: the SERVE thread decodes + scores + inserts
    at ingest; the learner only gathers samples). A real child process
    PUTs identical blobs over loopback TCP into each variant while the
    learner loop trains continuously — so the number measured is
    PUT-to-replay throughput UNDER training load, which is exactly the
    contention the service exists to remove.

    The verdict follows the repo's adjudication bar (Pallas-LSTM rule):
    shards ship enabled-by-default ONLY at >= 1.2x monolithic
    ingest+train frames/s; the committed `benchmarks/replay_verdict.json`
    carries the decision `runtime/replay_shard.shard_count()` consults.
    """
    from collections import namedtuple

    import jax
    import numpy as np

    from distributed_reinforcement_learning_tpu.agents.apex import (
        ApexAgent, ApexConfig)
    from distributed_reinforcement_learning_tpu.data import codec
    from distributed_reinforcement_learning_tpu.data.replay_service import (
        ShardedReplayService)
    from distributed_reinforcement_learning_tpu.runtime import apex_runner
    from distributed_reinforcement_learning_tpu.runtime.replay_shard import (
        ReplayIngestFifo)
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        TransportServer, _make_queue)
    from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

    acfg = ApexConfig(obs_shape=(obs_dim,), num_actions=2)
    agent = ApexAgent(acfg)  # ONE jit cache shared by both variants
    rng = np.random.RandomState(0)
    # The child's structural namedtuple (no jax import over there); the
    # warm path round-trips the codec so the learner compiles against
    # the same reconstructed class the wire path yields.
    cls = namedtuple("ApexBatch", ["state", "next_state", "previous_action",
                                   "action", "reward", "done"])

    def warm_unrolls(count):
        out = []
        for _ in range(count):
            out.append(bytes(codec.encode(cls(
                state=rng.rand(steps, obs_dim).astype(np.float32),
                next_state=rng.rand(steps, obs_dim).astype(np.float32),
                previous_action=rng.randint(0, 2, steps).astype(np.int32),
                action=rng.randint(0, 2, steps).astype(np.int32),
                reward=rng.randn(steps).astype(np.float32),
                done=rng.rand(steps) < 0.1))))
        return out

    def pctl(sorted_ms, q):
        return round(sorted_ms[min(int(q * (len(sorted_ms) - 1) + 0.5),
                                   len(sorted_ms) - 1)], 3)

    def run_variant(sharded: bool) -> dict:
        queue = _make_queue(64)
        svc = None
        ingest_q = queue
        if sharded:
            svc = ShardedReplayService(num_shards, 16384, mode="transition",
                                       scorer="max", seed=0)
            ingest_q = ReplayIngestFifo(svc, queue)
        weights = WeightStore()
        learner = apex_runner.ApexLearner(
            agent, queue, weights, batch_size=32, replay_capacity=16384,
            rng=jax.random.PRNGKey(0), replay_service=svc)
        # Warm + compile OUTSIDE the timed window: prefill past the
        # warm-up gate, run one train (td_error + learn compile).
        from distributed_reinforcement_learning_tpu.data.fifo import blob_ingest

        prepare, put = blob_ingest(ingest_q)
        for blob in warm_unrolls(12):
            put(prepare(blob))
        while learner.ingest_many(timeout=0.0):
            pass
        assert learner.train() is not None
        server = TransportServer(ingest_q, weights, host="127.0.0.1",
                                 port=_free_port()).start()

        def ingested() -> int:
            return (svc.ingested_blobs() if sharded
                    else learner.ingested_unrolls)

        base = ingested()
        target = base + n_unrolls
        proc = subprocess.Popen(
            [sys.executable, "-c", _REPLAY_CHILD, "127.0.0.1",
             str(server.port), str(n_unrolls), str(unrolls_per_put),
             str(steps), str(obs_dim)],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        train_ms: list[float] = []
        train_steps0 = learner.train_steps
        try:
            # Clock starts at the FIRST observed arrival (child startup
            # excluded; in the mono variant arrival is queue depth — the
            # learner loop below is what drains it) and stops when every
            # unroll landed in replay.
            while ingested() == base and queue.size() == 0:
                if proc.poll() is not None and proc.returncode != 0:
                    raise RuntimeError(
                        f"child died: {proc.stderr.read()[-500:]}")
                time.sleep(0.001)
            t0 = time.perf_counter()
            counted_from = ingested()
            while ingested() < target:
                # A child that died nonzero mid-run (busy_timeout, a
                # dropped-unroll assert) can never reach `target`: fail
                # THIS section instead of spinning until the bench
                # watchdog kills every later one.
                if proc.poll() is not None and proc.returncode != 0:
                    raise RuntimeError(
                        f"child died mid-run: {proc.stderr.read()[-500:]}")
                drained = False
                while learner.ingest_many(timeout=0.002):
                    drained = True
                c0 = time.perf_counter()
                m = learner.train()
                train_ms.append((time.perf_counter() - c0) * 1e3)
                if m is None and not drained:
                    time.sleep(0.001)
            elapsed = time.perf_counter() - t0
            assert proc.wait(timeout=60) == 0, proc.stderr.read()[-500:]
        finally:
            if proc.poll() is None:
                proc.kill()
            server.stop()
            queue.close()
        # Post-run sample latency on the variant's active replay.
        replay = learner._active_replay()
        sample_ms = []
        sample_rng = np.random.RandomState(1)
        for _ in range(50):
            s0 = time.perf_counter()
            replay.sample(32, sample_rng)
            sample_ms.append((time.perf_counter() - s0) * 1e3)
        sample_ms.sort()
        train_ms.sort()
        frames = (target - counted_from) * steps
        out = {"frames_per_s": round(frames / elapsed, 1),
               "unrolls_per_s": round(frames / steps / elapsed, 1),
               "train_steps_in_window": learner.train_steps - train_steps0,
               "train_ms_p50": pctl(train_ms, 0.50) if train_ms else 0.0,
               "sample_ms_p50": pctl(sample_ms, 0.50),
               "sample_ms_p99": pctl(sample_ms, 0.99)}
        if svc is not None:
            out["shards"] = num_shards
            stats = svc.shard_stats()
            out["shard_fill"] = [round(s["fill"], 4) for s in stats]
            svc.close()
        learner.close()
        return out

    one_blob = warm_unrolls(1)[0]
    out: dict = {
        "unroll_bytes": len(one_blob), "n_unrolls": n_unrolls,
        "note": ("real two-process A/B: child PUTs identical unrolls over "
                 "loopback TCP (put_trajectories, accepted counts "
                 "honored) while the learner trains; mono pays "
                 "decode+TD+insert on the learn thread, sharded pays it "
                 "on the serve thread")}
    best_m = best_s = None
    for _ in range(reps):
        m = run_variant(sharded=False)
        s = run_variant(sharded=True)
        if best_m is None or m["frames_per_s"] > best_m["frames_per_s"]:
            best_m = m
        if best_s is None or s["frames_per_s"] > best_s["frames_per_s"]:
            best_s = s
    out["mono"] = best_m
    out["sharded"] = best_s
    ratio = best_s["frames_per_s"] / max(best_m["frames_per_s"], 1e-9)
    out["sharded_vs_mono"] = round(ratio, 2)
    out["auto_enable"] = ratio >= 1.2  # the repo's adjudication bar
    out["verdict"] = (f"replay shards {ratio:.2f}x mono ingest+train: "
                      + ("auto-on" if out["auto_enable"] else "opt-in"))
    print(f"[bench] replay_compare: mono {best_m['frames_per_s']:,.0f} "
          f"f/s vs sharded {best_s['frames_per_s']:,.0f} f/s "
          f"-> {out['verdict']}", file=sys.stderr)
    return out


_ADMISSION_CHILD = r"""
import json
import sys
from collections import namedtuple

import numpy as np

from distributed_reinforcement_learning_tpu.data import admission
from distributed_reinforcement_learning_tpu.runtime.transport import TransportClient

host, port, n_unrolls, upp, steps, obs_dim = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]), int(sys.argv[6]))
ApexBatch = namedtuple("ApexBatch", ["state", "next_state", "previous_action",
                                     "action", "reward", "done"])
rng = np.random.RandomState(0)
trees = []
for i in range(upp):
    # Mixed-value traffic: reward scale cycles so unroll priorities
    # straddle the fleet mean and the admission ladder has both sides
    # to act on (uniform-priority traffic would make FULL/subsample
    # degenerate).
    scale = 1.0 if i % 4 == 0 else 0.05
    trees.append(ApexBatch(
        state=rng.rand(steps, obs_dim).astype(np.float32),
        next_state=rng.rand(steps, obs_dim).astype(np.float32),
        previous_action=rng.randint(0, 2, steps).astype(np.int32),
        action=rng.randint(0, 2, steps).astype(np.int32),
        reward=(scale * rng.randn(steps)).astype(np.float32),
        done=(rng.rand(steps) < 0.1)))
client = TransportClient(host, port, busy_timeout=120.0)
ctrl = admission.configure(client, "apex", seed=7)
sent = 0
while sent < n_unrolls:
    chunk = trees[: min(upp, n_unrolls - sent)]
    got = client.put_trajectories(chunk)
    assert got == len(chunk), f"dropped {len(chunk) - got} unrolls"
    sent += got
client.close()
snap = ctrl.snapshot() if ctrl is not None else {}
print(json.dumps({
    "stamped": ctrl is not None,
    "wire_unrolls": client.stats["unrolls_sent"],
    "admission_dropped": client.stats["unrolls_admission_dropped"],
    "sent_transitions": snap.get("sent_transitions",
                                 client.stats["unrolls_sent"] * steps),
    "subsample_dropped": snap.get("subsample_dropped_transitions", 0),
    "dropped_mass": snap.get("dropped_mass", 0.0),
    "pending_folded": (ctrl.pending_folded_mass() if ctrl is not None
                       else 0.0)}))
print("ADMISSION_CHILD_DONE")
"""


def bench_admission_compare(n_unrolls: int = 192, unrolls_per_put: int = 8,
                            steps: int = 32, obs_dim: int = 64,
                            num_shards: int = 2, reps: int = 1) -> dict:
    """Two-process A/B of SAMPLE-AT-SOURCE (data/admission.py): actors
    that stamp actor-computed initial priorities into the wire blob
    (`DRL_ACTOR_PRIORITY=1` in the child) vs the baseline fleet whose
    blobs the learner's ingest threads must score (`=0`). Identical
    unrolls PUT over loopback TCP into an identical sharded service
    while the learner trains; the adjudicated number is learner
    ingest-CPU-per-accepted-transition (DutyMeter cumulative busy
    seconds over shard-stored transitions) — exactly the work the stamp
    exists to move off the learner box.

    A third leg ("admitted") adds priority-mass admission under a
    pinned pressure override (`DRL_ADMISSION_PRESSURE=0.75` — the bench
    learner is never genuinely saturated, so the ladder is driven
    explicitly) and reports accepted-transitions-per-KB: the wire/ingest
    efficiency bought by thinning low-priority traffic at the source.
    Admission stays OPT-IN regardless (verdict note): a synthetic
    window cannot adjudicate "matched return", which is the honest bar
    for a knob that reshapes the training distribution.

    The committed `benchmarks/admission_verdict.json` carries the
    decision `data/admission.actor_priority_enabled()` consults, at the
    repo's >= 1.2x bar."""
    from collections import namedtuple

    import jax
    import numpy as np

    from distributed_reinforcement_learning_tpu.agents.apex import (
        ApexAgent, ApexConfig)
    from distributed_reinforcement_learning_tpu.data import codec
    from distributed_reinforcement_learning_tpu.data.replay_service import (
        ShardedReplayService)
    from distributed_reinforcement_learning_tpu.runtime import apex_runner
    from distributed_reinforcement_learning_tpu.runtime.replay_shard import (
        ReplayIngestFifo)
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        TransportServer, _make_queue)
    from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

    acfg = ApexConfig(obs_shape=(obs_dim,), num_actions=2)
    agent = ApexAgent(acfg)  # ONE jit cache shared by all variants
    rng = np.random.RandomState(0)
    cls = namedtuple("ApexBatch", ["state", "next_state", "previous_action",
                                   "action", "reward", "done"])

    def warm_unrolls(count):
        out = []
        for _ in range(count):
            out.append(bytes(codec.encode(cls(
                state=rng.rand(steps, obs_dim).astype(np.float32),
                next_state=rng.rand(steps, obs_dim).astype(np.float32),
                previous_action=rng.randint(0, 2, steps).astype(np.int32),
                action=rng.randint(0, 2, steps).astype(np.int32),
                reward=rng.randn(steps).astype(np.float32),
                done=rng.rand(steps) < 0.1))))
        return out

    def run_variant(child_env: dict) -> dict:
        queue = _make_queue(64)
        svc = ShardedReplayService(num_shards, 16384, mode="transition",
                                   scorer="td_proxy", seed=0)
        fifo = ReplayIngestFifo(svc, queue)
        weights = WeightStore()
        learner = apex_runner.ApexLearner(
            agent, queue, weights, batch_size=32, replay_capacity=16384,
            rng=jax.random.PRNGKey(0), replay_service=svc)
        # Warm + compile OUTSIDE the timed window (plain blobs: the
        # decode/layout caches are shared by both ingest paths).
        for blob in warm_unrolls(12):
            fifo.ingest_blob(blob)
        assert learner.train() is not None
        server = TransportServer(fifo, weights, host="127.0.0.1",
                                 port=_free_port()).start()

        def stored() -> int:
            return sum(s.mass_count()[1] for s in svc.shards)

        base_blobs = svc.ingested_blobs()
        base_stored = stored()
        base_cpu = fifo.duty.total()
        base_bytes = fifo.admission_stats()["ingest_bytes"]
        proc = subprocess.Popen(
            [sys.executable, "-c", _ADMISSION_CHILD, "127.0.0.1",
             str(server.port), str(n_unrolls), str(unrolls_per_put),
             str(steps), str(obs_dim)],
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "DRL_REPLAY_SCORER": "td_proxy", **child_env},
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        try:
            # Clock from the FIRST arrival (child startup excluded).
            # The serve thread ingests synchronously before each PUT
            # reply, so child exit == every accepted blob is in replay.
            while svc.ingested_blobs() == base_blobs:
                if proc.poll() is not None and proc.returncode != 0:
                    raise RuntimeError(
                        f"child died: {proc.stderr.read()[-500:]}")
                time.sleep(0.001)
            t0 = time.perf_counter()
            while proc.poll() is None:
                # Train continuously: the number measured is ingest cost
                # UNDER training load, like replay_compare.
                learner.ingest_many(timeout=0.0)
                learner.train()
            elapsed = time.perf_counter() - t0
            assert proc.returncode == 0, proc.stderr.read()[-500:]
            child_out = proc.stdout.read()
        finally:
            if proc.poll() is None:
                proc.kill()
            server.stop()
            queue.close()
        child = {}
        for ln in child_out.splitlines():
            try:
                child = json.loads(ln)
                break
            except json.JSONDecodeError:
                continue
        accepted = stored() - base_stored
        cpu_s = fifo.duty.total() - base_cpu
        wire_bytes = fifo.admission_stats()["ingest_bytes"] - base_bytes
        stats = fifo.admission_stats()
        out = {
            "accepted_transitions": accepted,
            "ingest_cpu_s": round(cpu_s, 4),
            "ingest_cpu_us_per_transition": round(
                cpu_s * 1e6 / max(accepted, 1), 3),
            "wire_bytes": wire_bytes,
            "transitions_per_kb": round(accepted / max(wire_bytes / 1024, 1e-9), 3),
            "elapsed_s": round(elapsed, 3),
            "stamped_blobs": stats["stamped_blobs"],
            "scored_blobs": stats["scored_blobs"],
            "folded_mass": round(stats["folded_mass"], 6),
            "child": child,
        }
        svc.close()
        learner.close()
        return out

    out: dict = {
        "n_unrolls": n_unrolls, "steps": steps,
        "note": ("real two-process A/B: child PUTs identical unrolls over "
                 "loopback TCP while the learner trains; 'scored' pays "
                 "decode+TD-score on the learner's serve thread, 'stamped' "
                 "fast-accepts actor-computed priorities, 'admitted' adds "
                 "priority-mass thinning under a pinned 0.75 pressure")}
    best: dict[str, dict] = {}
    legs = [("scored", {"DRL_ACTOR_PRIORITY": "0", "DRL_ADMISSION": "0"}),
            ("stamped", {"DRL_ACTOR_PRIORITY": "1", "DRL_ADMISSION": "0"}),
            ("admitted", {"DRL_ACTOR_PRIORITY": "1", "DRL_ADMISSION": "1",
                          "DRL_ADMISSION_PRESSURE": "0.75"})]
    for _ in range(reps):
        for name, env in legs:
            r = run_variant(env)
            if (name not in best
                    or r["ingest_cpu_us_per_transition"]
                    < best[name]["ingest_cpu_us_per_transition"]):
                best[name] = r
    out.update(best)
    ratio = (best["scored"]["ingest_cpu_us_per_transition"]
             / max(best["stamped"]["ingest_cpu_us_per_transition"], 1e-9))
    out["scored_vs_stamped_cpu"] = round(ratio, 2)
    out["admitted_vs_scored_transitions_per_kb"] = round(
        best["admitted"]["transitions_per_kb"]
        / max(best["scored"]["transitions_per_kb"], 1e-9), 2)
    out["auto_enable"] = ratio >= 1.2  # the repo's adjudication bar
    out["admission_auto_enable"] = False  # opt-in by design (docstring)
    out["verdict"] = (
        f"actor stamps cut learner ingest CPU/transition {ratio:.2f}x: "
        + ("auto-on" if out["auto_enable"] else "opt-in")
        + f"; admission {out['admitted_vs_scored_transitions_per_kb']:.2f}x "
          "transitions/KB, opt-in (return-match not benchable)")
    print(f"[bench] admission_compare: scored "
          f"{best['scored']['ingest_cpu_us_per_transition']:.1f} us/tr vs "
          f"stamped {best['stamped']['ingest_cpu_us_per_transition']:.1f} "
          f"us/tr -> {out['verdict']}", file=sys.stderr)
    return out


def bench_admission_sequence_compare(n_unrolls: int = 256, steps: int = 32,
                                     obs_dim: int = 64,
                                     num_shards: int = 2) -> dict:
    """SEQUENCE-MODE (R2D2) leg of the sample-at-source adjudication —
    the re-run the admission verdict's honest-negative note called for.

    The apex/transition A/B (`bench_admission_compare`) measured the
    stamp's win as "skip a cheap numpy scorer" because transition-mode
    shards must decode at ingest regardless. Sequence-mode shards on the
    opaque-item backend are where the design's real deferral lives: a
    usable stamp stores the raw wire blob as a `LazyBlob` (decode
    deferred to first sample materialization), so the stamped ingest
    path touches ZERO payload bytes. This leg ingests identical R2D2
    unrolls into a sequence-mode sharded service — scored (unstamped:
    decode + td_proxy score on the ingest thread) vs stamped (fast-
    accept, LazyBlob defer) — and reports ingest-CPU-per-unroll. In-
    process single-threaded: no training load, no GIL contention — the
    pure ingest-path delta the two-process bench could not isolate.

    Adjudicates `rerun_sequence_mode` inside the committed
    `benchmarks/admission_verdict.json` (the apex gates are unchanged:
    stamping stays adjudicated per-mode)."""
    from collections import namedtuple

    import numpy as np

    from distributed_reinforcement_learning_tpu.data import codec
    from distributed_reinforcement_learning_tpu.data.replay_service import (
        ShardedReplayService)
    from distributed_reinforcement_learning_tpu.runtime.replay_shard import (
        ReplayIngestFifo)
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        _make_queue)

    cls = namedtuple("R2D2Batch", ["obs", "action", "reward", "done",
                                   "core_state"])
    rng = np.random.RandomState(0)
    blobs, errs = [], []
    for i in range(n_unrolls):
        scale = 1.0 if i % 4 == 0 else 0.05
        tree = cls(obs=rng.rand(steps, obs_dim).astype(np.float32),
                   action=rng.randint(0, 2, steps).astype(np.int32),
                   reward=(scale * rng.randn(steps)).astype(np.float32),
                   done=(rng.rand(steps) < 0.1),
                   core_state=rng.rand(2, 64).astype(np.float32))
        blobs.append(bytes(codec.encode(tree)))
        errs.append(float(np.abs(tree.reward).mean() + 0.01))

    def run_variant(stamped: bool) -> dict:
        queue = _make_queue(64)
        svc = ShardedReplayService(num_shards, 4096, mode="sequence",
                                   backend="python", scorer="td_proxy",
                                   seed=0)
        fifo = ReplayIngestFifo(svc, queue)

        def wire(blob, err):
            if not stamped:
                return blob
            return bytes(codec.stamp_blob(blob, {
                "scorer": "td_proxy", "mode": "sequence",
                "pri": [err], "t": steps}))

        # Warm the decode/layout caches outside the timed window so the
        # scored leg pays steady-state decode, not first-touch layout —
        # warmed IN-PROTOCOL: an unstamped first blob would latch this
        # thread to the plain path permanently (`_plain_threads`).
        fifo.ingest_blob(wire(blobs[0], errs[0]))
        base_cpu = fifo.duty.total()
        t0 = time.perf_counter()
        for blob, err in zip(blobs, errs):
            assert fifo.ingest_blob(wire(blob, err))
        elapsed = time.perf_counter() - t0
        cpu_s = fifo.duty.total() - base_cpu
        stats = fifo.admission_stats()
        accepted = sum(s.mass_count()[1] for s in svc.shards)
        out = {
            "accepted_sequences": accepted,
            "ingest_cpu_s": round(cpu_s, 4),
            "ingest_cpu_us_per_unroll": round(
                cpu_s * 1e6 / max(accepted, 1), 3),
            "elapsed_s": round(elapsed, 3),
            "stamped_blobs": stats["stamped_blobs"],
            "scored_blobs": stats["scored_blobs"],
        }
        svc.close()
        queue.close()
        return out

    out: dict = {
        "n_unrolls": n_unrolls, "steps": steps, "mode": "sequence",
        "note": ("in-process sequence-mode ingest A/B: identical R2D2 "
                 "unroll blobs into a 2-shard opaque-item service; "
                 "scored decodes + td_proxy-scores each blob on the "
                 "ingest thread, stamped fast-accepts the actor "
                 "priority and stores the LazyBlob undecoded"),
        "scored": run_variant(stamped=False),
        "stamped": run_variant(stamped=True),
    }
    assert out["stamped"]["stamped_blobs"] >= n_unrolls, \
        "stamped leg fell back to learner-side scoring"
    ratio = (out["scored"]["ingest_cpu_us_per_unroll"]
             / max(out["stamped"]["ingest_cpu_us_per_unroll"], 1e-9))
    out["scored_vs_stamped_cpu"] = round(ratio, 2)
    out["auto_enable"] = ratio >= 1.2  # the repo's adjudication bar
    out["verdict"] = (
        f"sequence-mode actor stamps cut ingest CPU/unroll {ratio:.2f}x "
        "(LazyBlob defer skips decode entirely): "
        + ("auto-on" if out["auto_enable"] else "opt-in"))
    print(f"[bench] admission_sequence_compare: scored "
          f"{out['scored']['ingest_cpu_us_per_unroll']:.1f} us/unroll vs "
          f"stamped {out['stamped']['ingest_cpu_us_per_unroll']:.1f} "
          f"us/unroll -> {out['verdict']}", file=sys.stderr)
    return out


def bench_replay_spill_compare(budget_mb: float = 2.0, capacity_mult: int = 8,
                               obs_dim: int = 128, seg_items: int = 256,
                               batch: int = 64, rounds: int = 200,
                               reps: int = 1) -> dict:
    """In-process A/B of the TIERED REPLAY SPILL (data/replay_spill.py):
    an all-RAM prioritized store vs the hot/cold tiered store at the
    SAME learner-RAM budget, with the tiered store's capacity
    `capacity_mult`x larger — the hot budget forces most segments to
    disk, which is the deployment the tier exists for.

    The adjudicated number is STORAGE DENSITY: stored transitions per
    GB of learner RAM (payload bytes resident + the 16 B/item the tier
    keeps RAM-side for every item — 8 B priority + index bookkeeping —
    so the tier is charged for its own overhead). The density win only
    counts if the learner's sample+writeback loop holds up, so the
    verdict gates on SAMPLE-THROUGHPUT PARITY: a timed
    sample->update_batch loop must stay within 10% of the all-RAM loop.

    Priorities are SEGMENT-CORRELATED heavy-tail — a small fraction of
    insert-time blocks carries nearly all the priority mass, the rest
    sits near the priority floor, and writebacks preserve each item's
    scale (jittered inverse-transform of the sampled priority). That is
    the regime prioritized replay lives in: TD errors correlate in time,
    so co-inserted items share a scale, and the min-mass victim policy
    keeps the high-mass segments resident while the floor-mass tail
    spills. Uncorrelated-priority traffic degenerates to mass-uniform
    draws over a mostly-cold store and the tier (correctly) loses the
    parity gate — the knob stays opt-in for such fleets.

    Tier IO runs on ONE background thread driving the same
    plan -> run_io -> commit protocol `ReplayShard.tier_step` rides on
    the ingest threads, with the store lock held exactly where the
    shard lock would be — the timed loop pays lock contention and any
    promote the draw-ahead window failed to hide, and nothing else,
    which is what the learn thread pays in deployment.

    The committed `benchmarks/replay_spill_verdict.json` carries the
    decision `runtime/replay_shard.spill_auto_enabled()` consults, at
    the issue's >= 4x density bar with the >= 0.9 parity gate."""
    import numpy as np

    from distributed_reinforcement_learning_tpu.data.replay import (
        PrioritizedReplay, make_replay)
    from distributed_reinforcement_learning_tpu.data.replay_spill import (
        SpillConfig, TieredStore)

    budget = int(budget_mb * 1024 * 1024)
    # Transition payload: obs + next_obs f32[obs_dim] + action/reward/tag.
    item_bytes = 2 * obs_dim * 4 + 4 + 4 + 8
    cap_a = budget // (item_bytes + 16)
    cap_b = cap_a * capacity_mult
    inv_alpha = 1.0 / PrioritizedReplay.ALPHA

    def make_items(n, rng):
        # One scale per insert-time block of seg_items: every ~10th
        # block is "interesting" (large TD errors), the rest sit at the
        # floor — so ~10% of segments carry ~99% of the transformed
        # mass and the resident set covers nearly the whole draw
        # distribution.
        nblk = (n + seg_items - 1) // seg_items
        scales = np.where(np.arange(nblk) % 10 == 0, 2000.0, 1e-4)
        errs = (np.repeat(scales, seg_items)[:n]
                * (rng.pareto(1.5, n) + 0.05))
        items = []
        for i in range(n):
            items.append({
                "obs": rng.rand(obs_dim).astype(np.float32),
                "next_obs": rng.rand(obs_dim).astype(np.float32),
                "action": np.int32(i % 4),
                "reward": np.float32(min(errs[i], 1e6)),
                "tag": np.int64(i)})
        return errs, items

    def writeback_errs(pris, rng):
        # Jittered inverse-transform: the new error keeps the item's
        # scale (TD errors decay/drift, they don't re-randomize), so
        # the hot/cold split the victim policy learned stays valid.
        base = np.maximum(pris, 1e-12) ** inv_alpha
        return np.maximum(base * np.exp(0.1 * rng.randn(len(pris))), 1e-6)

    def tier_pump(store, lock, stop):
        # The ingest-thread role: one job at a time, lock held only for
        # plan/commit, IO lock-free — ReplayShard.tier_step verbatim.
        while not stop.is_set():
            with lock:
                job = store.plan_tier_work()
            if job is None:
                time.sleep(0.001)
                continue
            job.run_io()
            with lock:
                snap = store.commit_tier_work(job)
            if snap is not None:
                store.write_manifest(snap)

    def timed_loop(store, rng, lock) -> float:
        drawn = 0
        t0 = time.perf_counter()
        for _ in range(rounds):
            if lock is None:
                _, idxs, pris = store.sample_with_priorities(batch, rng)
                store.update_batch(idxs, writeback_errs(pris, rng))
            else:
                while True:
                    with lock:
                        out = store.sample_step(batch, rng)
                    if out is not None:
                        break
                    time.sleep(0.0002)  # promote in flight on the pump
                _, idxs, pris = out
                with lock:
                    store.update_batch(idxs, writeback_errs(pris, rng))
            drawn += len(idxs)
        return drawn / (time.perf_counter() - t0)

    def run_once(rep: int) -> dict:
        rng = np.random.RandomState(100 + rep)
        # Leg A: all-RAM python backend at the RAM budget.
        store_a = make_replay(cap_a, backend="python", seed=rep)
        errs, items = make_items(cap_a, rng)
        for lo in range(0, cap_a, 512):
            store_a.add_batch(errs[lo:lo + 512], items[lo:lo + 512])
        ram_a = cap_a * item_bytes + 16 * cap_a
        rate_a = timed_loop(store_a, np.random.RandomState(1), lock=None)

        # Leg B: tiered store, same hot budget, capacity_mult x capacity.
        spill_dir = tempfile.mkdtemp(prefix="drl_bench_spill_")
        cfg = SpillConfig(directory=spill_dir, hot_bytes=budget,
                          seg_items=seg_items, fresh=True)
        store_b = TieredStore(cap_b, cfg, mode="transition", seed=rep)
        lock = threading.Lock()
        stop = threading.Event()
        pump = threading.Thread(target=tier_pump, args=(store_b, lock, stop),
                                daemon=True, name="bench-spill-pump")
        pump.start()
        try:
            errs, items = make_items(cap_b, rng)
            for lo in range(0, cap_b, 512):
                with lock:
                    store_b.add_batch(errs[lo:lo + 512], items[lo:lo + 512])
            # Let the pump drain the fill's spill backlog before timing.
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                with lock:
                    pending = store_b.tier_pending()
                if not pending:
                    break
                time.sleep(0.002)
            rate_b = timed_loop(store_b, np.random.RandomState(1), lock=lock)
            with lock:
                stats = store_b.tier_stats()
                stored_b = len(store_b)
            ram_b = stats["ram_bytes"]  # steady-state, post-loop
        finally:
            stop.set()
            pump.join(timeout=10.0)
            store_b.close()
            shutil.rmtree(spill_dir, ignore_errors=True)
        assert stats["spilled_segments"] > 0, \
            "hot budget did not force a spill: the A/B measured nothing"
        gb = 1024 ** 3
        density_a = cap_a / (ram_a / gb)
        density_b = stored_b / (max(ram_b, 1) / gb)
        return {
            "all_ram": {"stored": cap_a, "ram_mb": round(ram_a / 2**20, 2),
                        "transitions_per_gb": round(density_a),
                        "sample_tr_per_s": round(rate_a)},
            "tiered": {"stored": stored_b,
                       "ram_mb": round(ram_b / 2**20, 2),
                       "disk_mb": round(stats["disk_bytes"] / 2**20, 2),
                       "transitions_per_gb": round(density_b),
                       "sample_tr_per_s": round(rate_b),
                       "spilled_segments": stats["spilled_segments"],
                       "promoted_segments": stats["promoted_segments"],
                       "forced_pads": stats["forced_pads"],
                       "crc_dropped": stats["crc_dropped"]},
            "density_ratio": round(density_b / max(density_a, 1e-9), 2),
            "sample_parity": round(rate_b / max(rate_a, 1e-9), 3),
        }

    out: dict = {
        "budget_mb": budget_mb, "capacity_mult": capacity_mult,
        "seg_items": seg_items, "batch": batch, "rounds": rounds,
        "note": ("in-process A/B at one learner-RAM budget: all-RAM "
                 "python backend at the budget's capacity vs the tiered "
                 "store at {}x capacity with the same hot budget; "
                 "density = stored transitions per GB RAM (tier charged "
                 "16 B/item bookkeeping), gated on a timed sample+"
                 "writeback loop staying within 10%".format(capacity_mult))}
    best = None
    for rep in range(reps):
        r = run_once(rep)
        if best is None or r["density_ratio"] > best["density_ratio"]:
            best = r
    out.update(best)
    out["auto_enable"] = (out["density_ratio"] >= 4.0
                          and out["sample_parity"] >= 0.9)
    out["verdict"] = (
        f"tiered replay stores {out['density_ratio']:.2f}x transitions/GB-RAM "
        f"at {out['sample_parity']:.2f}x sample throughput: "
        + ("auto-on" if out["auto_enable"] else "opt-in"))
    print(f"[bench] replay_spill_compare: all-RAM "
          f"{out['all_ram']['transitions_per_gb']:,}/GB vs tiered "
          f"{out['tiered']['transitions_per_gb']:,}/GB "
          f"-> {out['verdict']}", file=sys.stderr)
    return out


def bench_device_path_compare(window_s: float = 6.0, unrolls_per_put: int = 8,
                              steps: int = 32, obs_dim: int = 64,
                              num_shards: int = 2, k: int | None = None,
                              batch_size: int = 32, reps: int = 1) -> dict:
    """Two-process A/B of the fused DEVICE SAMPLE PATH (data/
    device_path.py) against the host sample loop it replaces — both
    variants run the AUTO-ENABLED sharded replay service (PR 6), so the
    only delta is where the per-update gather -> stack -> H2D -> D2H
    round-trip runs: on the learn thread (host path,
    `prioritized_train_call`) or on the path's background thread with
    double-buffered H2D and ONE D2H per K (`device_train_call`). A
    duration-mode child PUTs identical unrolls over loopback TCP into
    the real transport server for the whole window (shard ingest
    contends with the gather exactly as deployed), and the measured
    number is LEARNER train throughput — train steps x batch transitions
    per second — because removed learn-thread host work is precisely
    what this path claims.

    The verdict follows the repo's adjudication bar (Pallas-LSTM rule):
    the path ships enabled-by-default ONLY at >= 1.2x the host loop's
    train throughput; the committed `benchmarks/device_path_verdict.json`
    carries the decision `data/device_path.device_path_enabled` consults.
    """
    import jax
    import numpy as np

    from distributed_reinforcement_learning_tpu.agents.apex import (
        ApexAgent, ApexConfig)
    from distributed_reinforcement_learning_tpu.data import codec
    from distributed_reinforcement_learning_tpu.data.fifo import blob_ingest
    from distributed_reinforcement_learning_tpu.data.replay_service import (
        ShardedReplayService)
    from distributed_reinforcement_learning_tpu.runtime import apex_runner
    from distributed_reinforcement_learning_tpu.runtime.replay_shard import (
        ReplayIngestFifo)
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        TransportServer, _make_queue)
    from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

    if k is None:
        k = int(os.environ.get("BENCH_DEVPATH_K", "4"))
    k = max(1, k)
    acfg = ApexConfig(obs_shape=(obs_dim,), num_actions=2)
    agent = ApexAgent(acfg)  # ONE jit cache shared by both variants
    from collections import namedtuple

    cls = namedtuple("ApexBatch", ["state", "next_state", "previous_action",
                                   "action", "reward", "done"])
    wrng = np.random.RandomState(0)

    def warm_blobs(count):
        return [bytes(codec.encode(cls(
            state=wrng.rand(steps, obs_dim).astype(np.float32),
            next_state=wrng.rand(steps, obs_dim).astype(np.float32),
            previous_action=wrng.randint(0, 2, steps).astype(np.int32),
            action=wrng.randint(0, 2, steps).astype(np.int32),
            reward=wrng.randn(steps).astype(np.float32),
            done=wrng.rand(steps) < 0.1))) for _ in range(count)]

    def run_variant(device_path: bool) -> dict:
        queue = _make_queue(64)
        svc = ShardedReplayService(num_shards, 16384, mode="transition",
                                   scorer="max", seed=0)
        ingest_q = ReplayIngestFifo(svc, queue)
        weights = WeightStore()
        learner = apex_runner.ApexLearner(
            agent, queue, weights, batch_size=batch_size,
            replay_capacity=16384, rng=jax.random.PRNGKey(0),
            replay_service=svc, updates_per_call=k)
        # Explicit per-variant gate (no env mutation): the mixin
        # resolves device_path_force before DRL_DEVICE_PATH/verdict.
        learner.device_path_force = device_path
        proc = server = None
        train_ms: list[float] = []
        try:
            prepare, put = blob_ingest(ingest_q)
            for blob in warm_blobs(14):
                put(prepare(blob))
            # Warm + compile OUTSIDE the timed window (learn/learn_many
            # + the path's first gather/H2D round on the device variant).
            warm_deadline = time.monotonic() + 120.0
            while learner.train() is None:
                if time.monotonic() > warm_deadline:
                    raise RuntimeError("warm train step never landed")
                time.sleep(0.002)
            server = TransportServer(ingest_q, weights, host="127.0.0.1",
                                     port=_free_port()).start()
            proc = subprocess.Popen(
                [sys.executable, "-c", _LEARNER_PUT_CHILD, "127.0.0.1",
                 str(server.port), str(window_s + 10.0),
                 str(unrolls_per_put), str(steps), str(obs_dim)],
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
                stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
                text=True)
            base = svc.ingested_blobs()
            while svc.ingested_blobs() == base:  # window starts under load
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"feeder died: {proc.stderr.read()[-500:]}")
                time.sleep(0.001)
            t0 = time.perf_counter()
            steps0 = learner.train_steps
            ing0 = svc.ingested_blobs()
            deadline = t0 + window_s
            while time.perf_counter() < deadline:
                c0 = time.perf_counter()
                m = learner.train()
                train_ms.append((time.perf_counter() - c0) * 1e3)
                if m is None:
                    time.sleep(0.001)
            elapsed = time.perf_counter() - t0
            steps_done = learner.train_steps - steps0
            ingested = svc.ingested_blobs() - ing0
            if ingested == 0:
                raise RuntimeError("feeder landed zero unrolls in the "
                                   "window — not an under-load "
                                   "measurement")
            out = {"train_steps_in_window": steps_done,
                   "train_frames_per_s": round(
                       steps_done * batch_size / elapsed, 1),
                   "train_call_ms_p50": _pctl(sorted(train_ms), 0.50),
                   "train_call_ms_p99": _pctl(sorted(train_ms), 0.99),
                   "ingested_unrolls_in_window": ingested}
            if device_path:
                dp = learner._device_path
                if dp is None or learner._device_path_demoted:
                    # A demoted variant measured the HOST path under a
                    # devpath label — fail it instead of recording a
                    # mislabeled ratio (the weights_compare rule).
                    raise RuntimeError("device path never activated or "
                                       "demoted mid-window")
                out["devpath"] = dp.stats()
            return out
        finally:
            # Error exits must not leak threads into the later bench
            # sections (the gather thread + 2 serve threads + router
            # would contend for the 2-core host and skew their ratios).
            if proc is not None:
                if proc.poll() is None:
                    proc.kill()
                proc.wait(timeout=10)
            if server is not None:
                server.stop()
            learner.close()
            svc.close()
            queue.close()

    out: dict = {
        "k": k, "batch_size": batch_size, "window_s": window_s,
        "shards": num_shards,
        "note": ("two-process A/B: a duration-mode child PUTs identical "
                 "unrolls over loopback TCP into the sharded ingest for "
                 "the whole window while the learner trains; host = "
                 "learn-thread gather+stack+H2D+D2H per call "
                 "(prioritized_train_call), device = background gather "
                 "thread + double-buffered H2D + one scanned learn_many "
                 "+ one D2H per K (data/device_path.py); metric is "
                 "train transitions/s")}
    best_h = best_d = None
    for _ in range(reps):
        h = run_variant(device_path=False)
        d = run_variant(device_path=True)
        if best_h is None or h["train_frames_per_s"] > best_h["train_frames_per_s"]:
            best_h = h
        if best_d is None or d["train_frames_per_s"] > best_d["train_frames_per_s"]:
            best_d = d
    out["host"] = best_h
    out["device"] = best_d
    ratio = (best_d["train_frames_per_s"]
             / max(best_h["train_frames_per_s"], 1e-9))
    out["device_vs_host"] = round(ratio, 2)
    out["auto_enable"] = ratio >= 1.2  # the repo's adjudication bar
    out["verdict"] = (f"device sample path {ratio:.2f}x host train "
                      f"throughput at K={k}: "
                      + ("auto-on" if out["auto_enable"] else "opt-in"))
    print(f"[bench] device_path_compare: host "
          f"{best_h['train_frames_per_s']:,.0f} tr/s vs device "
          f"{best_d['train_frames_per_s']:,.0f} tr/s -> {out['verdict']}",
          file=sys.stderr)
    return out


# Children for bench_learner_compare: one learner SEAT of the tier
# (runtime/learner_tier.py — real collective, real transport server,
# real sharded-replay ingest) and one duration-mode PUT feeder. The
# seat child is the production ApexLearner + LearnerTier wiring, so the
# A/B prices exactly what `launch_local_cluster --learners N` deploys.
# Every child here is started with JAX_PLATFORMS=cpu: the parent holds
# the chip, a chip belongs to one process, and what this A/B prices is
# the host collective — none of the seats needs a device.
_LEARNER_SEAT_CHILD = r"""
import json, sys, time

import numpy as np

# Collective endpoint up FIRST (cheap, before the seconds of jax/agent
# init): peers' startup barriers probe it.
(host, port, rank, seats, sync, peers, window_s, steps, obs_dim) = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
    sys.argv[5], sys.argv[6], float(sys.argv[7]), int(sys.argv[8]),
    int(sys.argv[9]))
tier = None
if seats > 1:
    from distributed_reinforcement_learning_tpu.runtime.learner_tier import (
        LearnerTier)

    tier = LearnerTier(rank, peers.split(","), sync=sync).start()

import jax
from collections import namedtuple

from distributed_reinforcement_learning_tpu.agents.apex import ApexAgent, ApexConfig
from distributed_reinforcement_learning_tpu.data import codec
from distributed_reinforcement_learning_tpu.data.fifo import blob_ingest
from distributed_reinforcement_learning_tpu.data.replay_service import (
    ShardedReplayService)
from distributed_reinforcement_learning_tpu.runtime import apex_runner
from distributed_reinforcement_learning_tpu.runtime.replay_shard import (
    ReplayIngestFifo)
from distributed_reinforcement_learning_tpu.runtime.transport import (
    TransportServer, _make_queue)
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

agent = ApexAgent(ApexConfig(obs_shape=(obs_dim,), num_actions=2))
queue = _make_queue(64)
svc = ShardedReplayService(2, 16384, mode="transition", scorer="max",
                           seed=rank)
ingest_q = ReplayIngestFifo(svc, queue)
weights = WeightStore()
learner = apex_runner.ApexLearner(
    agent, queue, weights, batch_size=32, replay_capacity=16384,
    rng=jax.random.PRNGKey(0), replay_service=svc)
if tier is not None:
    tier.attach(learner)

# Warm + compile OUTSIDE the timed window; the first tier-wrapped train
# is a collective round, so the startup barrier runs first.
cls = namedtuple("ApexBatch", ["state", "next_state", "previous_action",
                               "action", "reward", "done"])
rng = np.random.RandomState(rank)
prepare, put = blob_ingest(ingest_q)
for _ in range(12):
    put(prepare(bytes(codec.encode(cls(
        state=rng.rand(steps, obs_dim).astype(np.float32),
        next_state=rng.rand(steps, obs_dim).astype(np.float32),
        previous_action=rng.randint(0, 2, steps).astype(np.int32),
        action=rng.randint(0, 2, steps).astype(np.int32),
        reward=rng.randn(steps).astype(np.float32),
        done=rng.rand(steps) < 0.1)))))
while learner.ingest_many(timeout=0.0):
    pass
if tier is not None:
    assert tier.await_peers(120.0), "tier startup barrier failed"
assert learner.train() is not None
server = TransportServer(ingest_q, weights, host=host, port=port).start()
print("SEAT_READY", flush=True)

base = svc.ingested_blobs()
while svc.ingested_blobs() == base:
    time.sleep(0.001)
t0 = time.perf_counter()
f0 = svc.ingested_blobs()
steps0 = learner.train_steps
deadline = t0 + window_s
while time.perf_counter() < deadline:
    # Bounded drain (see the seat-drill child): the collective couples
    # train cadences, and an unbounded drain under a saturating feeder
    # starves this seat's rounds and stalls the peer.
    drained = False
    for _ in range(8):
        if not learner.ingest_many(timeout=0.002):
            break
        drained = True
    if learner.train() is None and not drained:
        time.sleep(0.001)
elapsed = time.perf_counter() - t0
frames = (svc.ingested_blobs() - f0) * steps
out = {"rank": rank, "frames": frames, "elapsed": round(elapsed, 3),
       "frames_per_s": round(frames / elapsed, 1),
       "train_steps_in_window": learner.train_steps - steps0,
       "tier_stats": tier.snapshot_stats() if tier is not None else None,
       "coll_stats": (tier.collective.snapshot_stats()
                      if tier is not None else None)}
print("SEAT_RESULT=" + json.dumps(out), flush=True)
learner.close()
server.stop()
queue.close()
svc.close()
if tier is not None:
    tier.close()
"""

# Duration-mode feeder: PUTs identical unrolls (put_trajectories,
# accepted counts honored) until the window closes.
_LEARNER_PUT_CHILD = r"""
import sys, time
from collections import namedtuple

import numpy as np

from distributed_reinforcement_learning_tpu.runtime.transport import TransportClient

host, port, secs, upp, steps, obs_dim = (
    sys.argv[1], int(sys.argv[2]), float(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]), int(sys.argv[6]))
ApexBatch = namedtuple("ApexBatch", ["state", "next_state", "previous_action",
                                     "action", "reward", "done"])
rng = np.random.RandomState(0)
trees = []
for _ in range(upp):
    trees.append(ApexBatch(
        state=rng.rand(steps, obs_dim).astype(np.float32),
        next_state=rng.rand(steps, obs_dim).astype(np.float32),
        previous_action=rng.randint(0, 2, steps).astype(np.int32),
        action=rng.randint(0, 2, steps).astype(np.int32),
        reward=rng.randn(steps).astype(np.float32),
        done=(rng.rand(steps) < 0.1)))
client = TransportClient(host, port, busy_timeout=120.0)
sent = 0
deadline = time.monotonic() + secs
while time.monotonic() < deadline:
    sent += client.put_trajectories(trees)
client.close()
print("PUT_CHILD_DONE", sent)
"""


def bench_learner_compare(seats: int = 2, sync: str = "allreduce",
                          window_s: float = 10.0, unrolls_per_put: int = 8,
                          steps: int = 32, obs_dim: int = 64,
                          reps: int = 1) -> dict:
    """Real multi-process A/B of the learner TIER (runtime/
    learner_tier.py): ONE learner seat vs N cooperating seats, each a
    REAL process running the deployed ApexLearner + sharded-replay
    ingest + LearnerTier wiring, fed by one duration-mode PUT child per
    seat over loopback TCP. The measured number is aggregate
    ingest+train frames/s over a fixed window — the N-seat variant pays
    the collective's host exchange inside its train steps, so the ratio
    prices exactly what `--learners N` would deploy.

    The verdict follows the repo's adjudication bar (Pallas-LSTM rule):
    the tier ships enabled-by-default ONLY at >= 1.2x one seat's
    throughput; the committed `benchmarks/learner_verdict.json` carries
    the decision `runtime/learner_tier.seat_count()` (and the launcher
    gate) consult. On a 2-core container N seats split the SAME cores —
    an honest negative ships the tier opt-in, and the equivalence/chaos
    pins in tests/test_learner_tier.py are the durable value."""
    repo_root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    # Transient stalls (a peer's jit compile) must not read as deaths
    # inside the measured window.
    env.setdefault("DRL_LEARNER_WAIT_S", "30")

    def run_variant(n: int) -> dict:
        ports = [_free_port() for _ in range(n)]
        peers = ",".join(f"127.0.0.1:{_free_port()}" for _ in range(n))
        seat_procs = []
        put_procs = []
        # Dedicated stdout/stderr readers per seat (the seat-drill
        # pattern): an undrained stderr pipe would block a chatty child
        # mid-window, and a plain readline() would make the result
        # deadline dead code against a wedged one.
        stderr_tails: dict = {}
        result_lines: dict = {}
        watchers: list = []

        def watch(idx, proc):
            tail = stderr_tails.setdefault(idx, [])

            def drain_err():
                for line in proc.stderr:
                    tail.append(line)
                    del tail[:-60]

            def drain_out():
                for line in proc.stdout:
                    if line.startswith("SEAT_RESULT="):
                        result_lines[idx] = line
            for fn in (drain_err, drain_out):
                t = threading.Thread(target=fn, daemon=True)
                t.start()
                watchers.append(t)

        try:
            for r in range(n):
                seat_procs.append(subprocess.Popen(
                    [sys.executable, "-c", _LEARNER_SEAT_CHILD, "127.0.0.1",
                     str(ports[r]), str(r), str(n), sync, peers,
                     str(window_s), str(steps), str(obs_dim)],
                    env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                    text=True))
            for r, proc in enumerate(seat_procs):
                line = proc.stdout.readline()  # blocks only until READY
                if "SEAT_READY" not in line:
                    raise RuntimeError(
                        f"seat failed to start: {proc.stderr.read()[-800:]}")
                watch(r, proc)
            for r in range(n):
                put_procs.append(subprocess.Popen(
                    [sys.executable, "-c", _LEARNER_PUT_CHILD, "127.0.0.1",
                     str(ports[r]), str(window_s + 10.0),
                     str(unrolls_per_put), str(steps), str(obs_dim)],
                    env=env, stdout=subprocess.DEVNULL,
                    stderr=subprocess.DEVNULL))
            deadline = time.monotonic() + window_s + 180.0
            while (len(result_lines) < n and time.monotonic() < deadline
                   and not any(p.poll() is not None and r not in result_lines
                               for r, p in enumerate(seat_procs))):
                time.sleep(0.1)
            time.sleep(0.5)  # let the drain threads consume any result
            results = []     # line still buffered at a child's exit
            for r in range(n):
                line = result_lines.get(r)
                if line is None:
                    raise RuntimeError(
                        f"seat {r} died or wedged mid-window: "
                        f"{''.join(stderr_tails.get(r, []))[-800:]}")
                results.append(json.loads(line.split("=", 1)[1]))
        finally:
            for proc in put_procs + seat_procs:
                if proc.poll() is None:
                    proc.kill()
            for proc in put_procs + seat_procs:
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    pass
            for t in watchers:
                t.join(timeout=3.0)
        total_fps = round(sum(r["frames_per_s"] for r in results), 1)
        out = {"seats": n,
               "frames_per_s": total_fps,
               "per_seat_frames_per_s": [r["frames_per_s"] for r in results],
               "train_steps_in_window": sum(r["train_steps_in_window"]
                                            for r in results)}
        if n > 1:
            out["rounds_ok"] = sum((r["coll_stats"] or {}).get("rounds_ok", 0)
                                   for r in results)
            out["rounds_aborted"] = sum(
                (r["tier_stats"] or {}).get("round_retries", 0)
                for r in results)
            if sync == "allreduce" and out["rounds_ok"] == 0:
                # A 2-seat run whose seats never actually exchanged a
                # round measured two INDEPENDENT learners — fail the
                # variant instead of recording a mislabeled ratio.
                raise RuntimeError("tier variant completed zero collective "
                                   "rounds — not a tier measurement")
        return out

    out: dict = {
        "seats": seats, "sync": sync, "window_s": window_s,
        "note": ("real multi-process A/B: each seat is a full learner "
                 "process (ApexLearner + 2 replay shards + LearnerTier "
                 "collective), fed by its own PUT child over loopback "
                 "TCP for a fixed window; aggregate ingest+train "
                 "frames/s, collective exchange priced inside the "
                 "window")}
    best_solo = best_tier = None
    for _ in range(reps):
        solo = run_variant(1)
        tier = run_variant(seats)
        if best_solo is None or solo["frames_per_s"] > best_solo["frames_per_s"]:
            best_solo = solo
        if best_tier is None or tier["frames_per_s"] > best_tier["frames_per_s"]:
            best_tier = tier
    out["solo"] = best_solo
    out["tier"] = best_tier
    ratio = best_tier["frames_per_s"] / max(best_solo["frames_per_s"], 1e-9)
    out["tier_vs_solo"] = round(ratio, 2)
    out["auto_enable"] = ratio >= 1.2  # the repo's adjudication bar
    out["verdict"] = (f"learner tier ({seats} seats, {sync}) "
                      f"{ratio:.2f}x solo ingest+train: "
                      + ("auto-on" if out["auto_enable"] else "opt-in"))
    print(f"[bench] learner_compare: solo "
          f"{best_solo['frames_per_s']:,.0f} f/s vs {seats} seats "
          f"{best_tier['frames_per_s']:,.0f} f/s -> {out['verdict']}",
          file=sys.stderr)
    return out


def bench_collective_compare(shape: str = "xformer", rounds: int = 10,
                             warmup: int = 2) -> dict:
    """In-process two-seat A/B of the partition-aware learner collective
    (parallel/collective.py): the xformer-shaped gradient pytree
    (`_shard_bench_params` — the ~19 MB policy scale the partitioned
    exchange exists for) flattened to the tier's flat vector and
    exchanged over loopback TCP between two HostCollective seats, three
    ways — the legacy whole-vector f32 ring, the partition-aware f32
    exchange (replicated segments ring, pipe/model classes
    owner-scoped), and the same plan bf16-encoded (data/bf16.py RNE
    codec, f32 master accumulation). Reports median wall-clock per round
    and wire bytes per round by spec class. `quant_auto_enable` follows
    the repo's 1.2x wall-clock rule (bf16 vs f32 under the SAME plan);
    the byte cut is recorded either way — on a loopback container the
    wire is memcpy-cheap, so an honest negative ships bf16 opt-in with
    the byte economics on record for real-NIC hosts. A fourth
    measurement prices DRL_COLL_OVERLAP the same way: the bf16 exchange
    pipelined against a calibrated simulated backward (one round in
    flight, delayed apply — runtime/learner_tier.py's worker) vs the
    same work run serially."""
    import threading as _threading

    import numpy as np

    from distributed_reinforcement_learning_tpu.parallel.collective import (
        HostCollective)
    from distributed_reinforcement_learning_tpu.parallel.partition import (
        build_exchange_plan)
    from distributed_reinforcement_learning_tpu.runtime.learner_tier import (
        flatten_tree)

    params = _shard_bench_params(shape)
    vec0, _ = flatten_tree(params)
    plan_f32 = build_exchange_plan(params, quant="f32")
    plan_bf16 = build_exchange_plan(params, quant="bf16")
    addrs = [f"127.0.0.1:{_free_port()}" for _ in range(2)]
    colls = [HostCollective(r, addrs) for r in range(2)]
    for c in colls:
        c.wait_s = 30.0
        c.start()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if all(colls[r].probe_peer(1 - r, timeout=1.0) for r in range(2)):
            break
        time.sleep(0.1)

    def run_rounds(plan, n: int) -> list:
        times: list = []

        def seat(rank):
            v = vec0 + np.float32(rank)
            for _ in range(n):
                t0 = time.perf_counter()
                colls[rank].allreduce_mean(v, plan=plan)
                if rank == 0:
                    times.append((time.perf_counter() - t0) * 1e3)

        ths = [_threading.Thread(target=seat, args=(r,)) for r in range(2)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120.0)
        if any(t.is_alive() for t in ths):
            raise RuntimeError("collective variant wedged mid-round")
        return times

    def class_bytes() -> dict:
        s = colls[0].snapshot_stats()
        return {k: s[k] for k in s if k.startswith("coll_bytes")} | {
            "bytes_sent": s["bytes_sent"]}

    out: dict = {"shape": shape, "rounds": rounds,
                 "vector_mb": round(vec0.nbytes / 2**20, 2),
                 "plan_classes": plan_f32.classes}
    variants = (("ring_f32", None), ("part_f32", plan_f32),
                ("part_bf16", plan_bf16))
    try:
        for name, plan in variants:
            run_rounds(plan, warmup)
            before = class_bytes()
            times = run_rounds(plan, rounds)
            after = class_bytes()
            per_class = {k: (after[k] - before[k]) // rounds
                         for k in after if after[k] != before[k]}
            out[name] = {
                "round_ms_p50": round(float(np.median(times)), 2),
                "round_ms_max": round(float(np.max(times)), 2),
                "bytes_per_round": int((after["bytes_sent"]
                                        - before["bytes_sent"]) // rounds),
                "bytes_by_class": {k: int(v) for k, v in per_class.items()
                                   if k != "bytes_sent"},
            }

        byte_cut = 1.0 - (out["part_bf16"]["bytes_per_round"]
                          / max(out["part_f32"]["bytes_per_round"], 1))
        ratio_quant = (out["part_f32"]["round_ms_p50"]
                       / max(out["part_bf16"]["round_ms_p50"], 1e-9))

        # Overlap A/B: exchange pipelined against a calibrated simulated
        # backward (busy f32 matmuls ~ one round's wall clock) vs serial.
        bw_ms = out["part_bf16"]["round_ms_p50"]
        a = np.random.RandomState(0).standard_normal((256, 256)).astype(
            np.float32)
        t0 = time.perf_counter()
        a @ a
        unit_ms = max((time.perf_counter() - t0) * 1e3, 1e-3)
        reps_per_bw = max(1, int(bw_ms / unit_ms))

        def backward():
            for _ in range(reps_per_bw):
                a @ a  # noqa: B018 — busy work standing in for backward

        def overlap_variant(pipelined: bool) -> float:
            def seat0():
                if not pipelined:
                    for _ in range(rounds):
                        backward()
                        colls[0].allreduce_mean(vec0, plan=plan_bf16)
                    return
                worker_in: list = []
                sem = _threading.Semaphore(0)
                done = _threading.Semaphore(0)

                def worker():
                    for _ in range(rounds):
                        sem.acquire()
                        colls[0].allreduce_mean(worker_in.pop(), plan=plan_bf16)
                        done.release()

                w = _threading.Thread(target=worker)
                w.start()
                for i in range(rounds):
                    worker_in.append(vec0)
                    sem.release()  # round i exchanges while we backward
                    backward()
                    done.acquire()  # delayed apply: join round i
                w.join(timeout=60.0)

            def seat1():
                for _ in range(rounds):
                    colls[1].allreduce_mean(vec0, plan=plan_bf16)

            t0 = time.perf_counter()
            ths = [_threading.Thread(target=f) for f in (seat0, seat1)]
            for t in ths:
                t.start()
            for t in ths:
                t.join(timeout=120.0)
            if any(t.is_alive() for t in ths):
                raise RuntimeError("overlap variant wedged mid-round")
            return (time.perf_counter() - t0) * 1e3 / rounds

        serial_ms = overlap_variant(False)
        overlapped_ms = overlap_variant(True)
        ratio_overlap = serial_ms / max(overlapped_ms, 1e-9)
    finally:
        for c in colls:
            c.close()

    out["byte_cut"] = round(byte_cut, 4)
    out["quant_ratio"] = round(ratio_quant, 2)
    out["quant_auto_enable"] = ratio_quant >= 1.2
    out["overlap"] = {"simulated_backward_ms": round(bw_ms, 2),
                      "serial_step_ms": round(serial_ms, 2),
                      "overlapped_step_ms": round(overlapped_ms, 2)}
    out["overlap_ratio"] = round(ratio_overlap, 2)
    out["overlap_auto_enable"] = ratio_overlap >= 1.2
    out["verdict"] = (
        f"partitioned collective @ {shape}: bf16 cuts "
        f"{byte_cut:.0%} wire bytes/round, {ratio_quant:.2f}x round "
        f"wall-clock ({'auto-on' if out['quant_auto_enable'] else 'opt-in'}); "
        f"overlap {ratio_overlap:.2f}x step wall-clock "
        f"({'auto-on' if out['overlap_auto_enable'] else 'opt-in'})")
    print(f"[bench] collective_compare: {out['verdict']}", file=sys.stderr)
    return out


# Child processes for bench_inference_compare. The REPLICA child is one
# act-serving process of the inference tier (runtime/serving.py): it
# pulls weights from the parent's transport server, warms the bucketed
# act shapes, and serves OP_ACT with continuous batching + admission
# control. The CLIENT child is one member of the synthetic swarm: it
# hammers acts through the SAME RemoteActService selection path the
# deployed remote-act actor uses (jax-free import footprint), so both
# variants measure the production client code. The replica acts on the
# CPU (JAX_PLATFORMS=cpu, like `--mode inference`'s default): the
# parent holds the chip, and a replica that wanted one would need its
# own.
_INFER_REPLICA_CHILD = r"""
import sys, time

import numpy as np

from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent, ImpalaConfig
from distributed_reinforcement_learning_tpu.runtime.serving import ContinuousInferenceServer
from distributed_reinforcement_learning_tpu.runtime.transport import (
    RemoteWeights, TransportClient, TransportServer)
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

(host, lport, port, obs_dim, num_actions, lstm, rows, max_batch, seed) = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]), int(sys.argv[6]), int(sys.argv[7]), int(sys.argv[8]),
    int(sys.argv[9]))
agent = ImpalaAgent(ImpalaConfig(obs_shape=(obs_dim,), num_actions=num_actions,
                                 trajectory=8, lstm_size=lstm))
client = TransportClient(host, lport)
src = RemoteWeights(client)
local = WeightStore()
version = -1
while True:
    got = src.get_if_newer(version)
    if got is not None:
        local.publish(got[0], got[1])
        version = got[1]
        break
    time.sleep(0.05)
infer = ContinuousInferenceServer.for_agent(
    "impala", agent, local, max_batch=max_batch,
    admission_rows=4 * max_batch, seed=seed)

def req(n):
    return {"obs": np.zeros((n, obs_dim), np.float32),
            "prev_action": np.zeros(n, np.int32),
            "h": np.zeros((n, lstm), np.float32),
            "c": np.zeros((n, lstm), np.float32)}

n = rows
while n <= max_batch:  # warm every bucket the swarm can coalesce into
    infer.submit(req(n))
    n *= 2
server = TransportServer(None, local, host="127.0.0.1", port=port,
                         inference=infer).start()
print("REPLICA_READY", flush=True)
sys.stdin.readline()  # parent closes stdin to stop
server.stop()
infer.stop()
client.close()
"""

_INFER_CLIENT_CHILD = r"""
import json, sys, time

import numpy as np

from distributed_reinforcement_learning_tpu.runtime.transport import (
    RemoteActService, TransportClient)

(endpoints, fb_addr, rows, n_req, obs_dim, lstm, warmup) = (
    json.loads(sys.argv[1]), sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]), int(sys.argv[6]), int(sys.argv[7]))
fb_host, _, fb_port = fb_addr.rpartition(":")
fallback = TransportClient(fb_host, int(fb_port))
svc = RemoteActService.from_addrs(endpoints, fallback=fallback)
rng = np.random.RandomState(0)
req = {"obs": rng.rand(rows, obs_dim).astype(np.float32),
       "prev_action": np.zeros(rows, np.int32),
       "h": np.zeros((rows, lstm), np.float32),
       "c": np.zeros((rows, lstm), np.float32)}
for _ in range(warmup):  # connection setup + any residual compile, untimed
    svc(req)
lat = []
t0 = time.perf_counter()
for _ in range(n_req):
    t = time.perf_counter()
    out = svc(req)
    lat.append((time.perf_counter() - t) * 1e3)
wall = time.perf_counter() - t0
assert out["action"].shape == (rows,)
stats = svc.snapshot_stats()
svc.close()
fallback.close()
print("INFER_CLIENT=" + json.dumps(
    {"act_ms": lat, "actions_per_s": rows * n_req / wall, "stats": stats}))
"""

# The PIPELINED actor client (ISSUE 10 satellite): instead of a
# closed-loop request hammer, each client child is a REAL pipelined
# ImpalaActor (runtime/actor_pipeline.py, 2 slices) whose acts go
# through the same RemoteActService selection path — while one slice's
# act RPC is in flight the main thread steps the other slice's envs, so
# the service's act LATENCY (the replica tier's weak spot on loopback)
# is partially hidden and the A/B measures what a deployed remote-act
# actor would actually see: frames/s. The env is a cheap synthetic
# vector-obs generator and unroll PUTs go to a local sink — the act
# path is the measurement, identical on both sides of the A/B.
_INFER_ACTOR_CLIENT_CHILD = r"""
import json, sys, time

import numpy as np

from distributed_reinforcement_learning_tpu.agents.impala import (
    ImpalaAgent, ImpalaConfig)
from distributed_reinforcement_learning_tpu.envs.batched import BatchedEnv
from distributed_reinforcement_learning_tpu.runtime import (
    actor_pipeline, impala_runner)
from distributed_reinforcement_learning_tpu.runtime.transport import (
    RemoteActService, TransportClient)
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

(endpoints, fb_addr, num_envs, rounds, obs_dim, num_actions, lstm, T,
 warmup, seed) = (
    json.loads(sys.argv[1]), sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]), int(sys.argv[6]), int(sys.argv[7]), int(sys.argv[8]),
    int(sys.argv[9]), int(sys.argv[10]))


class VecObsEnv:
    # Endless synthetic vector-obs episode: the act path is the
    # measurement; the env only has to be cheap and deterministic.
    def __init__(self, s):
        self.num_actions = num_actions
        self._rng = np.random.RandomState(s)

    def reset(self):
        return self._rng.rand(obs_dim).astype(np.float32)

    def step(self, action):
        return (self._rng.rand(obs_dim).astype(np.float32), 0.0, False,
                {"lives": -1})


class SinkQueue:
    # Unroll publication is not what this A/B measures; both variants
    # pay the same (zero) cost.
    def put(self, item, timeout=None):
        return None

    def put_many(self, items, timeout=None):
        return None


fb_host, _, fb_port = fb_addr.rpartition(":")
fallback = TransportClient(fb_host, int(fb_port))
svc = RemoteActService.from_addrs(endpoints, fallback=fallback)
agent = ImpalaAgent(ImpalaConfig(obs_shape=(obs_dim,), num_actions=num_actions,
                                 trajectory=T, lstm_size=lstm))
env = BatchedEnv([(lambda s=s: VecObsEnv(s)) for s in range(num_envs)])
actor = impala_runner.ImpalaActor(agent, env, SinkQueue(), WeightStore(),
                                  seed=seed, remote_act=svc)
pipe = actor_pipeline.ActorPipeline(actor, num_slices=2)
for _ in range(warmup):
    pipe.run_unroll()
frames = 0
t0 = time.perf_counter()
for _ in range(rounds):
    frames += pipe.run_unroll()
pipe.close()  # inside the clock, like actor_compare
wall = time.perf_counter() - t0
assert pipe.demotions == 0, "pipeline demoted mid-run: not a pipelined number"
stats = svc.snapshot_stats()
overlap = pipe.stage_stats()
svc.close()
fallback.close()
# act_ms here is what the step loop actually WAITED on acts (the RPC
# latency minus what env stepping hid) — the deployed client-side cost.
print("INFER_ACTOR_CLIENT=" + json.dumps(
    {"frames_per_s": round(frames / wall, 1), "frames": frames,
     "act_wait_ms": overlap.get("act_wait_ms"), "stats": stats}))
"""


def bench_inference_compare(cfg, n_clients: int = 4, requests: int = 64,
                            rows: int = 16, replicas: int = 2,
                            max_batch: int = 64,
                            client: str = "hammer") -> dict:
    """Client-swarm A/B of the ACT path under synthetic heavy traffic:
    the learner-hosted inference service (one InferenceServer thread
    inside the learner process — the pre-tier deployed path) vs N
    dedicated act-serving REPLICA processes (runtime/serving.py:
    continuous batching, admission control, own ports). `n_clients`
    REAL child processes hammer `requests` act round trips of `rows`
    rows each through the production RemoteActService selection path;
    reported are act-latency p50/p99 and summed actions/s.

    The verdict follows the repo's adjudication bar (Pallas-LSTM rule):
    replicas ship as the --remote_act default ONLY if the A/B shows
    >= 1.2x actions/s; the committed `benchmarks/inference_verdict.json`
    carries the decision `runtime/serving.replica_count()` (and the
    local-cluster launcher's inlined gate) consults. Host-only,
    link-independent.
    """
    import numpy as np

    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent
    from distributed_reinforcement_learning_tpu.runtime.inference import InferenceServer
    from distributed_reinforcement_learning_tpu.runtime.transport import TransportServer
    from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

    import jax

    if len(cfg.obs_shape) != 1:
        # Serving-path A/B, not a model benchmark: a vector policy keeps
        # the act itself cheap so the measurement weighs batching, wire,
        # and scheduling — the things the tier changes (main passes a
        # dedicated vector config, not the Atari conv section).
        raise ValueError(f"inference_compare wants a vector obs_shape, "
                         f"got {cfg.obs_shape}")
    obs_dim = int(cfg.obs_shape[0])
    agent = ImpalaAgent(cfg)
    weights = WeightStore()
    weights.publish(agent.init_state(jax.random.PRNGKey(0)).params, 0)
    # The learner-hosted service: classic batcher, deployed semantics
    # (no admission budget — submits queue unboundedly, which is exactly
    # the behavior the tier's admission control exists to replace).
    inference = InferenceServer.for_agent("impala", agent, weights,
                                          max_batch=max_batch, seed=7)

    def req(n):
        return {"obs": np.zeros((n, obs_dim), np.float32),
                "prev_action": np.zeros(n, np.int32),
                "h": np.zeros((n, cfg.lstm_size), np.float32),
                "c": np.zeros((n, cfg.lstm_size), np.float32)}

    n = rows
    while n <= max_batch:  # warm the buckets the swarm can coalesce into
        inference.submit(req(n))
        n *= 2
    lport = _free_port()
    server = TransportServer(None, weights, host="127.0.0.1", port=lport,
                             inference=inference).start()

    repo_root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"

    def pctl(sorted_ms, q):
        return round(sorted_ms[min(int(q * (len(sorted_ms) - 1) + 0.5),
                                   len(sorted_ms) - 1)], 3)

    if client not in ("hammer", "pipe_actor"):
        raise ValueError(f"unknown inference_compare client {client!r}")

    def run_swarm(endpoints: list[str]) -> dict:
        if client == "hammer":
            argv = [sys.executable, "-c", _INFER_CLIENT_CHILD,
                    json.dumps(endpoints), f"127.0.0.1:{lport}", str(rows),
                    str(requests), str(obs_dim), str(cfg.lstm_size), "4"]
            marker = "INFER_CLIENT="
        else:  # pipe_actor: real 2-slice pipelined actors as the clients
            argv = [sys.executable, "-c", _INFER_ACTOR_CLIENT_CHILD,
                    json.dumps(endpoints), f"127.0.0.1:{lport}", str(rows),
                    str(requests), str(obs_dim), str(cfg.num_actions),
                    str(cfg.lstm_size), str(cfg.trajectory), "2", "0"]
            marker = "INFER_ACTOR_CLIENT="
        procs = [subprocess.Popen(
            argv, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True) for _ in range(n_clients)]
        results = []
        for proc in procs:
            out_s, err_s = proc.communicate(timeout=600)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"inference_compare client rc={proc.returncode}: "
                    f"{err_s.strip()[-500:]}")
            line = next(ln for ln in out_s.splitlines()
                        if ln.startswith(marker))
            results.append(json.loads(line.split("=", 1)[1]))
        agg: dict = {}
        for r in results:
            for k, v in r["stats"].items():
                agg[k] = agg.get(k, 0) + v
        if client == "pipe_actor":
            # frames/s is the deployed actor-side metric; act_ms is what
            # the step loop WAITED on acts (RPC minus what stepping hid).
            waits = [r["act_wait_ms"] for r in results if r["act_wait_ms"]]
            return {
                "actions_per_s": round(
                    sum(r["frames_per_s"] for r in results), 1),
                "act_ms_p50": round(
                    sum(w["p50"] for w in waits) / max(len(waits), 1), 3),
                "act_ms_p99": round(max(w["p99"] for w in waits), 3)
                if waits else 0.0,
                "client_stats": agg,
            }
        act_ms = sorted(ms for r in results for ms in r["act_ms"])
        return {
            "actions_per_s": round(sum(r["actions_per_s"] for r in results), 1),
            "act_ms_p50": pctl(act_ms, 0.50),
            "act_ms_p99": pctl(act_ms, 0.99),
            "client_stats": agg,
        }

    out: dict = {
        "n_clients": n_clients, "requests_per_client": requests,
        "rows_per_request": rows, "replicas": replicas,
        "max_batch": max_batch, "client": client,
        "note": ("real multi-process client swarm through the deployed "
                 "RemoteActService path both sides; learner-hosted = the "
                 "in-process InferenceServer behind the learner's "
                 "transport port, replicas = N serving.py processes "
                 "(continuous batching + admission) pulling weights from "
                 "the same store"
                 + ("; clients are 2-slice PIPELINED actors (runtime/"
                    "actor_pipeline.py) stepping synthetic vector envs — "
                    "rows = envs per actor, requests = unroll rounds"
                    if client == "pipe_actor" else ""))}
    rep_procs: list = []
    try:
        out["learner_hosted"] = run_swarm([])

        ports = [_free_port() for _ in range(replicas)]
        rep_procs = [subprocess.Popen(
            [sys.executable, "-c", _INFER_REPLICA_CHILD, "127.0.0.1",
             str(lport), str(port), str(obs_dim), str(cfg.num_actions),
             str(cfg.lstm_size), str(rows), str(max_batch), str(1000 + i)],
            env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
            for i, port in enumerate(ports)]
        for proc in rep_procs:
            line = proc.stdout.readline()
            if "REPLICA_READY" not in line:
                err = proc.stderr.read() if proc.poll() is not None else ""
                raise RuntimeError(
                    f"inference replica failed to start: {err.strip()[-500:]}")
        out["replica_tier"] = run_swarm([f"127.0.0.1:{p}" for p in ports])
        stats = out["replica_tier"]["client_stats"]
        # Refuse to record a "replica" number that silently measured the
        # learner: a demoted replica or fallback acts would poison the
        # adjudication artifact with a mislabeled ratio.
        if stats.get("replica_demotes", 0) or stats.get("fallback_acts", 0):
            raise RuntimeError(
                f"replica variant leaked acts off the tier "
                f"(demotes={stats.get('replica_demotes', 0)}, "
                f"fallback_acts={stats.get('fallback_acts', 0)}): the "
                f"measurement is not a replica number; rerun on a quiet host")
    finally:
        for proc in rep_procs:
            try:
                proc.stdin.close()  # READY loop exits
            except OSError:
                pass
        for proc in rep_procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
        server.stop()
        inference.stop()
        weights.close()
    ratio = (out["replica_tier"]["actions_per_s"]
             / max(out["learner_hosted"]["actions_per_s"], 1e-9))
    p50_ratio = (out["learner_hosted"]["act_ms_p50"]
                 / max(out["replica_tier"]["act_ms_p50"], 1e-9))
    out["replicas_vs_learner"] = round(ratio, 2)
    out["act_p50_speedup"] = round(p50_ratio, 2)
    out["auto_enable"] = ratio >= 1.2  # the repo's adjudication bar
    out["verdict"] = (f"inference replicas {ratio:.2f}x learner-hosted "
                      f"actions/s (act p50 {p50_ratio:.2f}x): "
                      + ("auto-on" if out["auto_enable"] else "opt-in"))
    print(f"[bench] inference_compare: learner "
          f"{out['learner_hosted']['actions_per_s']:,.0f} act/s vs "
          f"{replicas} replicas "
          f"{out['replica_tier']['actions_per_s']:,.0f} act/s "
          f"-> {out['verdict']}", file=sys.stderr)
    return out


# Child-process actor for bench_actor_compare: one REAL ImpalaActor over
# host envs (the in-tree Breakout simulator at the deployed pixel shape
# by default) shipping unrolls over real loopback TCP through the
# deployed client surfaces. `variant` selects the sequential reference
# loop or the pipelined data plane (runtime/actor_pipeline.py); the
# pipelined child FAILS (rather than recording a mislabeled ratio) if
# the pipeline demoted mid-run.
_ACTOR_COMPARE_CHILD = r"""
import json, sys, time
import numpy as np

from distributed_reinforcement_learning_tpu.agents.impala import (
    ImpalaAgent, ImpalaConfig)
from distributed_reinforcement_learning_tpu.envs.batched import BatchedEnv
from distributed_reinforcement_learning_tpu.envs.registry import make_env
from distributed_reinforcement_learning_tpu.runtime import (
    actor_pipeline, impala_runner)
from distributed_reinforcement_learning_tpu.runtime.transport import (
    RemoteQueue, RemoteWeights, TransportClient)

(host, port, variant, rounds, warmup, num_envs, env_name, obs_shape,
 num_actions, T, lstm, avail, seed) = (
    sys.argv[1], int(sys.argv[2]), sys.argv[3], int(sys.argv[4]),
    int(sys.argv[5]), int(sys.argv[6]), sys.argv[7], json.loads(sys.argv[8]),
    int(sys.argv[9]), int(sys.argv[10]), int(sys.argv[11]),
    int(sys.argv[12]), int(sys.argv[13]))
cfg = ImpalaConfig(obs_shape=tuple(obs_shape), num_actions=num_actions,
                   trajectory=T, lstm_size=lstm)
agent = ImpalaAgent(cfg)
env = BatchedEnv([
    (lambda s=s: make_env(env_name, seed=s, num_actions=num_actions))
    for s in range(num_envs)])
client = TransportClient(host, port)
queue = RemoteQueue(client)
actor = impala_runner.ImpalaActor(
    agent, env, queue, RemoteWeights(client), seed=seed,
    available_action=avail or None)
put_ms = []
pub_client = None
if variant == "pipe":
    # Deployed shape (run_role): the publisher PUTs on its own client so
    # they never serialize against the step loop's weight pulls on the
    # shared client's request/reply lock.
    pub_client = TransportClient(host, port)
    runner = actor_pipeline.ActorPipeline(
        actor, num_slices=2, publisher_queue=RemoteQueue(pub_client))
else:
    runner = actor
    real_put_many = queue.put_many

    def timed_put_many(items, timeout=None):
        t0 = time.perf_counter()
        r = real_put_many(items, timeout=timeout)
        put_ms.append((time.perf_counter() - t0) * 1e3)
        return r

    queue.put_many = timed_put_many
for _ in range(warmup):
    runner.run_unroll()
frames = 0
round_ms = []
t0 = time.perf_counter()
for _ in range(rounds):
    r0 = time.perf_counter()
    frames += runner.run_unroll()
    round_ms.append((time.perf_counter() - r0) * 1e3)
if variant == "pipe":
    runner.close()  # inside the clock: shipped frames, not stepped frames
elapsed = time.perf_counter() - t0


def pctl(vals, q):
    vals = sorted(vals)
    return round(vals[min(int(q * (len(vals) - 1) + 0.5), len(vals) - 1)], 3)


out = {"frames": frames, "elapsed_s": round(elapsed, 3),
       "frames_per_s": round(frames / elapsed, 1),
       "round_ms_p50": pctl(round_ms, 0.5), "round_ms_p99": pctl(round_ms, 0.99)}
if variant == "pipe":
    assert runner.demotions == 0, "pipeline demoted mid-run: not a pipelined number"
    out["overlap"] = runner.stage_stats()
else:
    out["put_ms_p50"] = pctl(put_ms, 0.5)
    out["put_ms_p99"] = pctl(put_ms, 0.99)
if pub_client is not None:
    pub_client.close()
client.close()
print("ACTOR_CHILD=" + json.dumps(out))
"""


def bench_actor_compare(cfg=None, num_envs: int = 8, rounds: int = 24,
                        warmup: int = 3,
                        env_name: str = "BreakoutDeterministic-v4",
                        available_action: int = 4) -> dict:
    """Sequential-vs-pipelined actor A/B (the auto-enable adjudication
    for runtime/actor_pipeline.py): one REAL actor child process per
    variant steps `num_envs` host envs and ships unrolls over real
    loopback TCP to this process's TransportServer, whose drain thread
    keeps backpressure honest (the learner side of the deployed
    topology) and whose accepted counts are verified against what the
    child produced — a dropped unroll fails the measurement instead of
    flattering it. Default shape is the deployed pixel workload (84x84x4
    Breakout sim + Nature-CNN-LSTM act: act(8) ~15ms vs env.step(8)
    ~14ms on this container — the balanced act/step mix the double
    buffer exists to overlap). Reported per variant: actor-side frames/s
    and round p50/p99, plus the pipelined act-wait/env-step/put-wait
    overlap percentiles and the sequential PUT p50/p99 it hides.

    Verdict per the repo's 1.2x adjudication bar; the committed decision
    lives in `benchmarks/actor_pipeline_verdict.json`, which
    `actor_pipeline.pipeline_enabled()` consults when DRL_ACTOR_PIPE is
    unset. Host-only, link-independent.
    """
    import subprocess

    import jax

    from distributed_reinforcement_learning_tpu.agents.impala import (
        ImpalaAgent, ImpalaConfig)
    from distributed_reinforcement_learning_tpu.runtime.transport import (
        TransportServer, _make_queue)
    from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

    if cfg is None:
        cfg = ImpalaConfig(trajectory=16)
    agent = ImpalaAgent(cfg)
    weights = WeightStore()
    weights.publish(agent.init_state(jax.random.PRNGKey(0)).params, 0)
    queue = _make_queue(64)
    server = TransportServer(queue, weights, host="127.0.0.1",
                             port=_free_port()).start()
    stop = threading.Event()
    drained = {"n": 0}

    def drain_loop():
        while not stop.is_set():
            try:
                if queue.get(timeout=0.2) is not None:
                    drained["n"] += 1
            except RuntimeError:
                return

    dt = threading.Thread(target=drain_loop, daemon=True)
    dt.start()

    repo_root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"

    out: dict = {
        "num_envs": num_envs, "rounds": rounds, "trajectory": cfg.trajectory,
        "env": env_name,
        "note": ("one real actor child process per variant over loopback "
                 "TCP (RemoteQueue PUTs + RemoteWeights pulls), learner "
                 "side draining with accepted counts verified; pipe = 2 "
                 "env slices double-buffered through one act worker + "
                 "bounded async publisher, seq = the reference serial "
                 "loop")}
    per_variant = (warmup + rounds) * num_envs
    try:
        for variant in ("seq", "pipe"):
            proc = subprocess.run(
                [sys.executable, "-c", _ACTOR_COMPARE_CHILD, "127.0.0.1",
                 str(server.port), variant, str(rounds), str(warmup),
                 str(num_envs), env_name, json.dumps(list(cfg.obs_shape)),
                 str(cfg.num_actions), str(cfg.trajectory),
                 str(cfg.lstm_size), str(available_action), "0"],
                env=env, capture_output=True, text=True, timeout=600)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"actor_compare {variant} child rc={proc.returncode}: "
                    f"{proc.stderr.strip()[-500:]}")
            line = next(ln for ln in proc.stdout.splitlines()
                        if ln.startswith("ACTOR_CHILD="))
            out[variant] = json.loads(line.split("=", 1)[1])
            # Accepted counts honored: every unroll the child produced
            # must have landed in the learner-side queue.
            expect = per_variant * (1 if variant == "seq" else 2)
            deadline = time.monotonic() + 30.0
            while drained["n"] < expect and time.monotonic() < deadline:
                time.sleep(0.05)
            if drained["n"] != expect:
                raise RuntimeError(
                    f"actor_compare {variant}: learner accepted "
                    f"{drained['n'] - (expect - per_variant)} of "
                    f"{per_variant} unrolls — lost PUTs poison the ratio")
    finally:
        stop.set()
        server.stop()
        queue.close()
        dt.join(timeout=2.0)

    ratio = out["pipe"]["frames_per_s"] / max(out["seq"]["frames_per_s"], 1e-9)
    out["pipe_vs_seq"] = round(ratio, 2)
    out["auto_enable"] = ratio >= 1.2  # the repo's adjudication bar
    out["verdict"] = (f"actor pipeline {ratio:.2f}x sequential actor "
                      f"frames/s: "
                      + ("auto-on" if out["auto_enable"] else "opt-in"))
    print(f"[bench] actor_compare: seq {out['seq']['frames_per_s']:,.0f} f/s "
          f"vs pipelined {out['pipe']['frames_per_s']:,.0f} f/s -> "
          f"{out['verdict']}", file=sys.stderr)
    return out


# Children for bench_chaos_compare. The LEARNER child is one incarnation
# of a fleet-supervised learner endpoint: bounded queue + WeightStore +
# shm weight board + one shm ring per actor + FleetSupervisor, all under
# the SAME segment names across respawns (create_or_reclaim reclaims the
# SIGKILLed incarnation's leftovers by creator-pid), "checkpoint" = a
# version file republished at startup. It VERIFIES every trajectory that
# lands in the queue (crc32 over the payload leaf — the bit-identity
# assertion) and appends verified/corrupt tallies to a stats file so the
# counts survive its own SIGKILL. The ACTOR child is one surviving
# member: ring PUTs + board pulls + the fleet heartbeat loop driving the
# reattach ladders — the deployed re-promotion path, not a simulation.
# Both children run with JAX_PLATFORMS=cpu (the parent holds the chip):
# the drill is about shm segments, sockets and process death, and the
# learner child does no device work.
_CHAOS_LEARNER_CHILD = r"""
import json, os, signal, sys, threading, time, zlib

import numpy as np

from distributed_reinforcement_learning_tpu.data import fifo
from distributed_reinforcement_learning_tpu.runtime import fleet, shm_ring, weight_board
from distributed_reinforcement_learning_tpu.runtime.transport import TransportServer
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

(host, port, ring_names, board_name, state_path, stats_path, period) = (
    sys.argv[1], int(sys.argv[2]), json.loads(sys.argv[3]), sys.argv[4],
    sys.argv[5], sys.argv[6], float(sys.argv[7]))

queue = fifo.TrajectoryQueue(256)
store = WeightStore(sharded=False)
board = weight_board.WeightBoard.create(board_name, 1 << 20)
store.attach_board(board)
version = 0
if os.path.exists(state_path):  # checkpoint restore: republish, same name
    with open(state_path) as f:
        version = int(json.load(f)["version"])
store.publish({"w": np.full(4096, version % 251, np.uint8),
               "v": np.int64(version)}, version)
drainer = shm_ring.RingDrainer(
    [shm_ring.ShmRing.create(n, 1 << 22) for n in ring_names], queue).start()
sup = fleet.FleetSupervisor().start()
server = TransportServer(queue, store, host=host, port=port,
                         fleet=sup).start()

stop = threading.Event()
signal.signal(signal.SIGTERM, lambda *a: stop.set())
verified = corrupt = 0
vlock = threading.Lock()

def verify_loop():
    global verified, corrupt
    while not stop.is_set():
        item = queue.get(timeout=0.2)
        if item is None:
            continue
        try:
            ok = int(item["crc"]) == (zlib.crc32(
                np.ascontiguousarray(item["payload"]).tobytes()) & 0xFFFFFFFF)
        except Exception:
            ok = False
        with vlock:
            if ok:
                verified += 1
            else:
                corrupt += 1

vt = threading.Thread(target=verify_loop, daemon=True)
vt.start()
print("LEARNER_READY", os.getpid(), flush=True)
next_pub = time.monotonic() + period
while not stop.wait(0.05):
    if time.monotonic() >= next_pub:
        next_pub = time.monotonic() + period
        version += 1
        store.publish({"w": np.full(4096, version % 251, np.uint8),
                       "v": np.int64(version)}, version)
        tmp = state_path + ".tmp"  # torn-write-safe "checkpoint"
        with open(tmp, "w") as f:
            json.dump({"version": version}, f)
        os.replace(tmp, state_path)
    with vlock:
        line = {"pid": os.getpid(), "verified": verified,
                "corrupt": corrupt, "version": version}
    with open(stats_path, "a") as f:
        f.write(json.dumps(line) + "\n")
vt.join(timeout=2.0)
server.stop()
sup.stop()
drainer.stop()
store.close()
board.close_writer()
board.close()
board.unlink()
"""

_CHAOS_ACTOR_CHILD = r"""
import json, os, sys, time, zlib

import numpy as np

from distributed_reinforcement_learning_tpu.runtime import fleet, shm_ring, weight_board
from distributed_reinforcement_learning_tpu.runtime.transport import (
    RemoteQueue, RemoteWeights, TransportClient)

(host, port, rank, ring_name, board_name, steps, obs_dim, secs) = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4], sys.argv[5],
    int(sys.argv[6]), int(sys.argv[7]), float(sys.argv[8]))
client = TransportClient(host, port)
rq = shm_ring.attach_ring_queue(ring_name, client)
queue = rq if rq is not None else RemoteQueue(client)
bw = weight_board.attach_board_weights(board_name, client)
weights = bw if bw is not None else RemoteWeights(client)
client.connect_retries = 3  # the loop below owns outage grace from here
hb = fleet.HeartbeatLoop(host, port, "actor", rank)
hb.watch(rq)
hb.watch(bw)
hb.start()
base = np.random.RandomState(rank).randint(
    0, 256, (steps, obs_dim)).astype(np.uint8)
sent = i = 0
version = -1
deadline = time.monotonic() + secs
t0 = time.perf_counter()
while time.monotonic() < deadline:
    payload = np.roll(base, i).astype(np.uint8)
    tree = {"payload": payload,
            "crc": np.uint32(zlib.crc32(payload.tobytes()) & 0xFFFFFFFF)}
    try:
        sent += bool(queue.put(tree))
    except (ConnectionError, OSError):
        time.sleep(0.2)  # learner outage: ride it out (elastic grace)
    i += 1
    if i % 16 == 0:
        try:
            got = weights.get_if_newer(version)
            if got is not None:
                version = got[1]
        except (ConnectionError, OSError):
            pass
    time.sleep(0.001)
elapsed = time.perf_counter() - t0
hb.stop()
out = {"sent": sent, "elapsed": elapsed, "weight_version": version,
       "ring_stats": queue.snapshot_stats() if rq is not None else None,
       "board_stats": weights.snapshot_stats() if bw is not None else None,
       "hb_stats": hb.snapshot_stats()}
if rq is not None:
    queue.close()
if bw is not None:
    weights.close()
client.close()
print("CHAOS_ACTOR=" + json.dumps(out), flush=True)
"""


def _chaos_read_stats(stats_path: str) -> dict:
    """Per-pid last stats line of each learner incarnation (the file is
    append-only so a SIGKILL can lose at most a torn final line)."""
    per_pid: dict = {}
    try:
        with open(stats_path) as f:
            for raw in f:
                try:
                    rec = json.loads(raw)
                except ValueError:
                    continue  # torn final line of a SIGKILLed incarnation
                per_pid[rec["pid"]] = rec
    except FileNotFoundError:
        pass
    return per_pid


def bench_chaos_compare(n_actors: int = 2, secs: float = 18.0,
                        kill_at: float = 6.0, steps: int = 16,
                        obs_dim: int = 64, publish_period_s: float = 0.1,
                        repromote_deadline_s: float = 15.0,
                        dip_bound: float = 0.5, reps: int = 1) -> dict:
    """Chaos adjudication of the elastic fleet (runtime/fleet.py): the
    SAME real topology (learner child with shm rings + weight board +
    fleet supervisor; actor children with ring PUTs, board pulls and the
    heartbeat-driven reattach ladders) run twice — a quiet baseline vs a
    chaos run that SIGKILLs the learner mid-window and immediately
    respawns it (same segment names, creator-pid reclaim, checkpoint
    file republished). Three assertions, all measured not assumed:

    - ZERO corrupted trajectories: the learner crc32-verifies every
      unroll that lands in its queue, across BOTH incarnations (tallies
      persist in a stats file the SIGKILL cannot lose) — bit-identity
      through ring and TCP paths under kill/respawn.
    - BOUNDED throughput dip: delivered-and-verified frames/s of the
      chaos window vs the baseline window, `dip_bound` the floor.
    - FULL re-promotion within `repromote_deadline_s` of the respawned
      learner serving: every actor's ring AND board reattach (counted
      in its exit stats; latency from the parent timestamping the
      actors' re-attach stderr lines).

    The committed `benchmarks/chaos_verdict.json` records the verdict —
    honest-negative allowed but measured, like every adjudication in
    this repo. Probe pacing is scaled to the bench window
    (DRL_FLEET_HB_S / DRL_REATTACH_* exported to the children);
    production defaults are seconds-scale, same ladder."""
    import shutil
    import tempfile

    from distributed_reinforcement_learning_tpu.runtime.shm_ring import (
        _attach_shm)

    repo_root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    # Probe pacing scaled to the bench window; the ladder shape (bounded
    # attempts, exponential backoff) is the production one.
    env.setdefault("DRL_FLEET_HB_S", "0.25")
    env.setdefault("DRL_REATTACH_BASE_S", "0.25")
    env.setdefault("DRL_REATTACH_MAX_S", "1.0")

    def reap(names) -> None:
        for name in names:
            try:
                seg = _attach_shm(name)
                seg.unlink()
                seg.close()
            except (FileNotFoundError, OSError):
                pass

    def run_variant(chaos: bool) -> dict:
        tag = f"drlchaos-{os.getpid()}-{os.urandom(3).hex()}"
        ring_names = [f"{tag}-r{i}" for i in range(n_actors)]
        board_name = f"{tag}-b"
        tmp = tempfile.mkdtemp(prefix="bench_chaos_")
        state_path = os.path.join(tmp, "state.json")
        stats_path = os.path.join(tmp, "learner_stats.jsonl")
        port = _free_port()
        learner_argv = [sys.executable, "-c", _CHAOS_LEARNER_CHILD,
                        "127.0.0.1", str(port), json.dumps(ring_names),
                        board_name, state_path, stats_path,
                        str(publish_period_s)]
        reattach_times: list = []  # (monotonic, line) from actor stderr
        stderr_tails: dict = {}

        def watch_stderr(name, proc):
            tail = stderr_tails.setdefault(name, [])
            for line in proc.stderr:
                if "re-attached" in line or "re-promoted" in line:
                    reattach_times.append((time.monotonic(), line.strip()))
                tail.append(line)
                del tail[:-40]

        watchers: list = []

        def spawn_learner():
            proc = subprocess.Popen(learner_argv, env=env,
                                    stdout=subprocess.PIPE,
                                    stderr=subprocess.PIPE, text=True)
            t = threading.Thread(target=watch_stderr, args=("learner", proc),
                                 daemon=True)
            t.start()
            watchers.append(t)
            line = proc.stdout.readline()
            if "LEARNER_READY" not in line:
                raise RuntimeError(
                    f"chaos learner failed to start: "
                    f"{''.join(stderr_tails.get('learner', []))[-500:]}")
            return proc

        learner = actors = None
        try:
            learner = spawn_learner()
            actors = [subprocess.Popen(
                [sys.executable, "-c", _CHAOS_ACTOR_CHILD, "127.0.0.1",
                 str(port), str(i), ring_names[i], board_name, str(steps),
                 str(obs_dim), str(secs)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True) for i in range(n_actors)]
            for i, proc in enumerate(actors):
                t = threading.Thread(target=watch_stderr,
                                     args=(f"actor{i}", proc),
                                     daemon=True)
                t.start()
                watchers.append(t)
            t_ready = None
            if chaos:
                # Gate the kill on OBSERVED traffic, not wall clock: on a
                # loaded 2-core host the actor child's imports+attach can
                # exceed kill_at, and a kill landing before the actor is
                # flowing produces a vacuous drill (the actor attaches
                # straight to incarnation 2 and never exercises the
                # demote/re-promote ladder it is supposed to pin).
                t_gate = time.monotonic() + 60.0
                while time.monotonic() < t_gate:
                    per = _chaos_read_stats(stats_path)
                    if sum(r["verified"] for r in per.values()) >= 50:
                        break
                    time.sleep(0.1)
                else:
                    raise RuntimeError(
                        "chaos drill: no verified traffic within 60s — "
                        "cannot place a meaningful kill")
                time.sleep(kill_at)
                learner.kill()  # SIGKILL: no atexit, segments leak until
                learner.wait()  # the respawn's creator-pid reclaim
                learner = spawn_learner()  # same names, same state file
                t_ready = time.monotonic()
            results = []
            for proc in actors:
                # The watcher thread is the SOLE stderr reader —
                # communicate() here would race it for the pipe and
                # sometimes swallow the re-attach lines the re-promote
                # latency is computed from. The result line on stdout is
                # tiny (one json object), so wait-then-read cannot
                # deadlock on a full pipe.
                proc.wait(timeout=secs + 120)
                out_s = proc.stdout.read()
                if proc.returncode != 0:
                    name = f"actor{actors.index(proc)}"
                    raise RuntimeError(
                        f"chaos actor rc={proc.returncode}: "
                        f"{''.join(stderr_tails.get(name, []))[-500:]}")
                line = next(ln for ln in out_s.splitlines()
                            if ln.startswith("CHAOS_ACTOR="))
                results.append(json.loads(line.split("=", 1)[1]))
            # weights_compare precedent: an actor that never attached its
            # fast plane would ride TCP the whole window — fail the
            # variant instead of recording a mislabeled drill. Fleet-on
            # attach failure returns a DEMOTED-AT-BIRTH surface (stats
            # present, zero shm traffic), so presence of the stats dict
            # alone proves nothing: require actual shm traffic.
            bad = [i for i, r in enumerate(results)
                   if r["ring_stats"] is None or r["board_stats"] is None
                   or r["ring_stats"]["unrolls_sent"] == 0
                   or r["board_stats"]["board_pulls"] == 0]
            if bad:
                raise RuntimeError(
                    f"chaos actors {bad} never exercised ring/board: "
                    f"{''.join(stderr_tails.get(f'actor{bad[0]}', []))[-400:]}")
        finally:
            for proc in (actors or []):
                if proc.poll() is None:
                    proc.kill()
            if learner is not None:
                learner.terminate()
                try:
                    learner.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    learner.kill()
            reap([*ring_names, board_name])
        for t in watchers:  # drain trailing stderr before reading
            t.join(timeout=5.0)  # reattach_times / stderr_tails
        # Verified/corrupt tallies, summed over incarnations (per pid).
        per_pid = _chaos_read_stats(stats_path)
        shutil.rmtree(tmp, ignore_errors=True)
        verified = sum(r["verified"] for r in per_pid.values())
        corrupt = sum(r["corrupt"] for r in per_pid.values())
        ring_reattaches = sum((r["ring_stats"] or {}).get("reattaches", 0)
                              for r in results)
        board_reattaches = sum((r["board_stats"] or {}).get("reattaches", 0)
                               for r in results)
        repromote_s = None
        if t_ready is not None and reattach_times:
            late = [t for t, _ in reattach_times if t >= t_ready]
            if late:
                repromote_s = round(max(late) - t_ready, 2)
        return {
            "frames_per_s": round(verified * steps / secs, 1),
            "unrolls_verified": verified, "unrolls_corrupt": corrupt,
            "unrolls_sent": sum(r["sent"] for r in results),
            "incarnations": len(per_pid),
            # Attach honesty: an actor that never attached its ring or
            # board at startup would ride TCP the whole window and show
            # a vacuous zero-reattach "success" — surface the count so
            # the drill (and the committed verdict) can prove the fast
            # plane was actually exercised (demoted-at-birth surfaces
            # carry a stats dict with zero shm traffic, hence the
            # traffic check, not a None check).
            "actors_on_ring": sum(r["ring_stats"] is not None
                                  and r["ring_stats"]["unrolls_sent"] > 0
                                  for r in results),
            "actors_on_board": sum(r["board_stats"] is not None
                                   and r["board_stats"]["board_pulls"] > 0
                                   for r in results),
            "ring_reattaches": ring_reattaches,
            "board_reattaches": board_reattaches,
            "repromote_s": repromote_s,
            "hb_stats": [r["hb_stats"] for r in results],
            "ring_stats": [r["ring_stats"] for r in results],
            "board_stats": [r["board_stats"] for r in results],
        }

    out: dict = {
        "n_actors": n_actors, "window_s": secs, "kill_at_s": kill_at,
        "dip_bound": dip_bound,
        "repromote_deadline_s": repromote_deadline_s,
        "note": ("real kill/respawn drill: learner child SIGKILLed "
                 "mid-window and respawned under the SAME shm names "
                 "(creator-pid reclaim) + checkpoint republish; actors "
                 "ride through on the fleet heartbeat reattach ladders; "
                 "every landed unroll crc32-verified across both "
                 "incarnations")}
    best_b = best_c = None
    for _ in range(reps):
        b = run_variant(chaos=False)
        c = run_variant(chaos=True)
        if best_b is None or b["frames_per_s"] > best_b["frames_per_s"]:
            best_b = b
        if best_c is None or c["frames_per_s"] > best_c["frames_per_s"]:
            best_c = c
    out["baseline"] = best_b
    out["chaos"] = best_c
    corrupt = best_b["unrolls_corrupt"] + best_c["unrolls_corrupt"]
    ratio = best_c["frames_per_s"] / max(best_b["frames_per_s"], 1e-9)
    repromoted = (best_c["ring_reattaches"] >= n_actors
                  and best_c["board_reattaches"] >= n_actors
                  and best_c["repromote_s"] is not None
                  and best_c["repromote_s"] <= repromote_deadline_s)
    out["dip_ratio"] = round(ratio, 2)
    out["zero_corruption"] = corrupt == 0
    out["repromoted_in_deadline"] = repromoted
    # Kill-ONE-OF-N-learners drill (runtime/learner_tier.py): SIGKILL
    # one of two cooperating learner seats mid-run; the survivor must
    # re-form the collective SOLO, take over publication (board
    # re-created under the same name, version identity), and every
    # landed trajectory must still crc-verify. BENCH_SEAT_DRILL=0
    # skips (it spawns 4 jax children).
    if os.environ.get("BENCH_SEAT_DRILL", "1") == "1":
        try:
            out["seat_drill"] = _chaos_seat_drill(
                repromote_deadline_s=repromote_deadline_s)
            out["seat_drill_pass"] = bool(out["seat_drill"]["pass"])
        except Exception as e:  # noqa: BLE001
            out["seat_drill"] = {"error": f"{type(e).__name__}: {e}"}
            out["seat_drill_pass"] = False
    out["chaos_pass"] = bool(corrupt == 0 and ratio >= dip_bound
                             and repromoted
                             and out.get("seat_drill_pass", True))
    rs = best_c["repromote_s"]
    seat_note = ""
    if "seat_drill_pass" in out:
        seat_note = (", seat-kill "
                     + ("ok" if out["seat_drill_pass"] else "FAIL"))
    out["verdict"] = (
        f"chaos {ratio:.2f}x baseline (bound {dip_bound}), "
        f"{corrupt} corrupt, re-promote "
        f"{'%.1fs' % rs if rs is not None else 'MISSING'}"
        f"/{repromote_deadline_s:.0f}s{seat_note}: "
        + ("PASS" if out["chaos_pass"] else "FAIL"))
    print(f"[bench] chaos_compare: baseline "
          f"{best_b['frames_per_s']:,.0f} f/s vs chaos "
          f"{best_c['frames_per_s']:,.0f} f/s -> {out['verdict']}",
          file=sys.stderr)
    return out


# Children for the kill-one-of-N-learners drill: one learner SEAT of a
# 2-seat tier (real LearnerTier collective + FleetSupervisor + crc
# verification of every landed trajectory) and one actor per seat
# (crc-stamped PUTs + weight-board pulls with the heartbeat-driven
# reattach ladder — the surviving seat's takeover must reach it).
_SEAT_DRILL_LEARNER_CHILD = r"""
import json, os, signal, sys, threading, time, zlib

import numpy as np

(host, port, rank, seats, peers, board_name, stats_path, window_s,
 steps, obs_dim) = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
    sys.argv[5], sys.argv[6], sys.argv[7], float(sys.argv[8]),
    int(sys.argv[9]), int(sys.argv[10]))
from distributed_reinforcement_learning_tpu.runtime.learner_tier import (
    LearnerTier)

tier = LearnerTier(rank, peers.split(","), sync="allreduce").start()

import jax

from distributed_reinforcement_learning_tpu.agents.apex import (
    ApexAgent, ApexBatch, ApexConfig)
from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue
from distributed_reinforcement_learning_tpu.runtime import (
    apex_runner, fleet, weight_board)
from distributed_reinforcement_learning_tpu.runtime.transport import (
    TransportServer)
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore

agent = ApexAgent(ApexConfig(obs_shape=(obs_dim,), num_actions=2))
wire_q = TrajectoryQueue(256)     # crc-verified, then forwarded
learner_q = TrajectoryQueue(256)  # what the learner ingests
weights = WeightStore()
learner = apex_runner.ApexLearner(
    agent, learner_q, weights, batch_size=16, replay_capacity=4096,
    train_start_unrolls=2, rng=jax.random.PRNGKey(rank))
tier.attach(learner)

board = None

def make_board():
    # Publisher-only: create (or RECLAIM, creator-pid) the tier's
    # shared board and replay the current snapshot into it.
    global board
    b = weight_board.WeightBoard.create(board_name, 4 << 20)
    weights.attach_board(b)
    board = b

if tier.is_publisher():
    make_board()
tier.set_promote_cb(make_board)
sup = fleet.FleetSupervisor(board_pid_fn=tier.publisher_pid).start()
server = TransportServer(wire_q, weights, host=host, port=port,
                         fleet=sup).start()

stop = threading.Event()
signal.signal(signal.SIGTERM, lambda *a: stop.set())
verified = corrupt = 0
vlock = threading.Lock()

def verify_loop():
    global verified, corrupt
    while not stop.is_set():
        item = wire_q.get(timeout=0.2)
        if item is None:
            continue
        try:
            state = np.ascontiguousarray(item["batch"].state)
            ok = int(item["crc"]) == (zlib.crc32(state.tobytes())
                                      & 0xFFFFFFFF)
        except Exception:
            ok = False
        with vlock:
            if ok:
                verified += 1
            else:
                corrupt += 1
        if ok:
            learner_q.put(item["batch"], timeout=0.5)

vt = threading.Thread(target=verify_loop, daemon=True)
vt.start()

# Warm/compile outside the drill: local prefill + one collective round
# (both seats reach this barrier together).
# Warm unrolls use the SAME unroll length as the drill actor's PUTs
# (a mixed-length queue would fail the stacked dequeue) and round-trip
# the CODEC so the replay store is seeded with the reconstructed
# namedtuple class the wire path yields (replay_compare's precedent —
# the SoA store's tree map is namedtuple-TYPE-strict).
from distributed_reinforcement_learning_tpu.data import codec

rng = np.random.RandomState(rank)
for _ in range(4):
    learner_q.put(codec.decode(codec.encode(ApexBatch(
        state=rng.rand(steps, obs_dim).astype(np.float32),
        next_state=rng.rand(steps, obs_dim).astype(np.float32),
        previous_action=rng.randint(0, 2, steps).astype(np.int32),
        action=rng.randint(0, 2, steps).astype(np.int32),
        reward=rng.randn(steps).astype(np.float32),
        done=(rng.rand(steps) < 0.1))), copy=True))
while learner.ingest_many(timeout=0.0):
    pass
assert tier.await_peers(120.0), "tier startup barrier failed"
assert learner.train() is not None
print("SEAT_READY", os.getpid(), flush=True)

deadline = time.monotonic() + window_s
next_stats = 0.0
while not stop.is_set() and time.monotonic() < deadline:
    # BOUNDED drain: allreduce couples the seats' TRAIN cadences, so an
    # unbounded ingest drain under a fast producer would starve this
    # seat's rounds and stall the peer mid-round (the BSP livelock the
    # tier docs call out) — cap unrolls per train call instead.
    drained = False
    for _ in range(8):
        if not learner.ingest_many(timeout=0.005):
            break
        drained = True
    if learner.train() is None and not drained:
        time.sleep(0.01)
    if time.monotonic() >= next_stats:
        next_stats = time.monotonic() + 0.2
        with vlock:
            line = {"pid": os.getpid(), "rank": rank, "verified": verified,
                    "corrupt": corrupt, "train_steps": learner.train_steps,
                    "version": weights.version,
                    "publisher": tier.is_publisher(),
                    "solo": tier.collective.membership.solo,
                    "wire_q": wire_q.size(), "learner_q": learner_q.size(),
                    "rounds_ok": tier.collective.stat("rounds_ok")}
        with open(stats_path, "a") as f:
            f.write(json.dumps(line) + "\n")
stop.set()
vt.join(timeout=2.0)
learner.close()
server.stop()
sup.stop()
tier.close()
if board is not None:
    board.close_writer()
    board.close()
    board.unlink()
"""

_SEAT_DRILL_ACTOR_CHILD = r"""
import json, sys, time, zlib

import numpy as np

from distributed_reinforcement_learning_tpu.runtime import fleet, weight_board
from distributed_reinforcement_learning_tpu.runtime.transport import (
    RemoteQueue, TransportClient)

(host, port, rank, board_name, steps, obs_dim, secs) = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4],
    int(sys.argv[5]), int(sys.argv[6]), float(sys.argv[7]))
ApexBatch = __import__("collections").namedtuple(
    "ApexBatch", ["state", "next_state", "previous_action", "action",
                  "reward", "done"])
client = TransportClient(host, port)
queue = RemoteQueue(client)
bw = weight_board.attach_board_weights(board_name, client)
hb = fleet.HeartbeatLoop(host, port, "actor", rank)
hb.watch(bw)
hb.start()
client.connect_retries = 3
rng = np.random.RandomState(rank)
sent = i = 0
version = -1
version_changes = []  # (monotonic t, version) on every observed change
deadline = time.monotonic() + secs
while time.monotonic() < deadline:
    state = rng.rand(steps, obs_dim).astype(np.float32)
    tree = {"batch": ApexBatch(
        state=state,
        next_state=rng.rand(steps, obs_dim).astype(np.float32),
        previous_action=rng.randint(0, 2, steps).astype(np.int32),
        action=rng.randint(0, 2, steps).astype(np.int32),
        reward=rng.randn(steps).astype(np.float32),
        done=(rng.rand(steps) < 0.1)),
        "crc": np.uint32(zlib.crc32(np.ascontiguousarray(state).tobytes())
                         & 0xFFFFFFFF)}
    try:
        sent += bool(queue.put(tree))
    except (ConnectionError, OSError):
        time.sleep(0.2)  # seat outage: ride it out
    i += 1
    if i % 8 == 0 and bw is not None:
        try:
            got = bw.get_if_newer(version)
            if got is not None:
                version = got[1]
                version_changes.append([round(time.monotonic(), 3), version])
        except (ConnectionError, OSError):
            pass
    time.sleep(0.002)
hb.stop()
out = {"sent": sent, "version_changes": version_changes,
       "board_stats": bw.snapshot_stats() if bw is not None else None,
       "hb_stats": hb.snapshot_stats()}
if bw is not None:
    bw.close()
client.close()
print("DRILL_ACTOR=" + json.dumps(out), flush=True)
"""


def _chaos_seat_drill(secs: float = 22.0, steps: int = 8, obs_dim: int = 16,
                      repromote_deadline_s: float = 15.0) -> dict:
    """Kill ONE of N=2 learner seats mid-run (the PUBLISHER, seat 0 —
    the hardest case) and measure, not assume:

    - the SURVIVOR re-forms the collective solo and keeps training
      (stats lines show solo=true + train_steps advancing);
    - the survivor takes over PUBLICATION: promoted to publisher,
      re-creates the shared board under the same name (creator-pid
      reclaim), and the surviving seat's actor observes post-kill
      version changes THROUGH its reattached board (version-identity
      semantics — the ladder validates the new creator via the
      heartbeat reply's board_pid);
    - ZERO corrupted trajectories: every unroll that landed on either
      seat crc32-verifies, across the kill.
    """
    import shutil
    import tempfile

    from distributed_reinforcement_learning_tpu.runtime.shm_ring import (
        _attach_shm)

    repo_root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    # Probe pacing scaled to the drill window; ladder/collective shapes
    # are the production ones.
    env.setdefault("DRL_FLEET_HB_S", "0.25")
    env.setdefault("DRL_REATTACH_BASE_S", "0.25")
    env.setdefault("DRL_REATTACH_MAX_S", "1.0")
    env.setdefault("DRL_LEARNER_WAIT_S", "2.0")
    env.setdefault("DRL_FLEET_DEAD_S", "1.5")

    tag = f"drlseat-{os.getpid()}-{os.urandom(3).hex()}"
    board_name = f"{tag}-b"
    tmp = tempfile.mkdtemp(prefix="bench_seatdrill_")
    stats_paths = [os.path.join(tmp, f"seat{r}.jsonl") for r in range(2)]
    ports = [_free_port() for _ in range(2)]
    peers = ",".join(f"127.0.0.1:{_free_port()}" for _ in range(2))
    seats: list = []
    actors: list = []
    stderr_tails: dict = {}
    watchers: list = []

    def watch_stderr(name, proc):
        tail = stderr_tails.setdefault(name, [])
        for line in proc.stderr:
            tail.append(line)
            del tail[:-60]

    def last_stats(r: int) -> dict:
        per = _chaos_read_stats(stats_paths[r])
        # newest line per pid; one pid per seat here (no respawn)
        return per.popitem()[1] if per else {}

    try:
        for r in range(2):
            proc = subprocess.Popen(
                [sys.executable, "-c", _SEAT_DRILL_LEARNER_CHILD,
                 "127.0.0.1", str(ports[r]), str(r), "2", peers, board_name,
                 stats_paths[r], str(secs), str(steps), str(obs_dim)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True)
            seats.append(proc)
            t = threading.Thread(target=watch_stderr, args=(f"seat{r}", proc),
                                 daemon=True)
            t.start()
            watchers.append(t)
        for r, proc in enumerate(seats):
            line = proc.stdout.readline()
            if "SEAT_READY" not in line:
                raise RuntimeError(
                    f"drill seat {r} failed to start: "
                    f"{''.join(stderr_tails.get(f'seat{r}', []))[-800:]}")
        for r in range(2):
            proc = subprocess.Popen(
                [sys.executable, "-c", _SEAT_DRILL_ACTOR_CHILD, "127.0.0.1",
                 str(ports[r]), str(r), board_name, str(steps), str(obs_dim),
                 str(secs)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True)
            actors.append(proc)
            t = threading.Thread(target=watch_stderr,
                                 args=(f"actor{r}", proc), daemon=True)
            t.start()
            watchers.append(t)
        # Kill only after OBSERVED verified traffic on BOTH seats (a
        # vacuous early kill would prove nothing).
        t_gate = time.monotonic() + 90.0
        while time.monotonic() < t_gate:
            if all(last_stats(r).get("verified", 0) >= 10 for r in range(2)):
                break
            if any(p.poll() is not None for p in seats):
                raise RuntimeError(
                    "a drill seat died before the kill: "
                    + "".join(stderr_tails.get("seat0", [])
                              + stderr_tails.get("seat1", []))[-800:])
            time.sleep(0.1)
        else:
            raise RuntimeError("seat drill: no verified traffic within 90s")
        pre_kill = last_stats(1)
        t_kill = time.monotonic()
        seats[0].kill()  # SIGKILL the PUBLISHER seat
        seats[0].wait()
        # Survivor must go solo + publisher + keep training, inside the
        # re-promotion deadline.
        reelected_s = None
        while time.monotonic() - t_kill < repromote_deadline_s:
            s = last_stats(1)
            if (s.get("solo") and s.get("publisher")
                    and s.get("train_steps", 0)
                    > pre_kill.get("train_steps", 0)):
                reelected_s = round(time.monotonic() - t_kill, 2)
                break
            time.sleep(0.1)
        results = []
        for r, proc in enumerate(actors):
            proc.wait(timeout=secs + 120)
            out_s = proc.stdout.read()
            line = next((ln for ln in out_s.splitlines()
                         if ln.startswith("DRILL_ACTOR=")), None)
            results.append(json.loads(line.split("=", 1)[1])
                           if line else None)
        seats[1].wait(timeout=secs + 120)
        final = last_stats(1)
        dead_final = last_stats(0)
        corrupt = (final.get("corrupt", 0) or 0) + \
            (dead_final.get("corrupt", 0) or 0)
        verified = (final.get("verified", 0) or 0) + \
            (dead_final.get("verified", 0) or 0)
        surv_actor = results[1] or {}
        post_kill_versions = [
            v for t, v in surv_actor.get("version_changes", ())
            if t >= t_kill]
        board_reattaches = (surv_actor.get("board_stats") or {}).get(
            "reattaches", 0)
        ok = bool(corrupt == 0 and verified > 0
                  and reelected_s is not None
                  and post_kill_versions
                  and board_reattaches >= 1)
        return {
            "verified": verified, "corrupt": corrupt,
            "reelected_s": reelected_s,
            "repromote_deadline_s": repromote_deadline_s,
            "survivor_solo": bool(final.get("solo")),
            "survivor_publisher": bool(final.get("publisher")),
            "survivor_train_steps": final.get("train_steps", 0),
            "post_kill_versions_observed": len(post_kill_versions),
            "survivor_board_reattaches": board_reattaches,
            "actor_stats": results,
            "pass": ok,
        }
    finally:
        for proc in seats + actors:
            if proc.poll() is None:
                proc.kill()
        for proc in seats + actors:
            try:
                proc.wait(timeout=10)
            except (subprocess.TimeoutExpired, OSError):
                pass
        for t in watchers:
            t.join(timeout=3.0)
        try:
            seg = _attach_shm(board_name)
            seg.unlink()
            seg.close()
        except (FileNotFoundError, OSError):
            pass
        shutil.rmtree(tmp, ignore_errors=True)


def bench_r2d2_learn(B: int, iters: int) -> dict:
    """R2D2 learn-step throughput (env-frames/s) at the reference replay
    shape — the training hot path that runs the fused Pallas LSTM
    (fwd + BPTT) twice per step (main + target unrolls)."""
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.r2d2 import R2D2Agent, R2D2Config
    from distributed_reinforcement_learning_tpu.utils.synthetic import synthetic_r2d2_batch

    cfg = R2D2Config()  # seq_len 10, lstm 512 (`config.json:2-24`)
    agent = R2D2Agent(cfg)
    state = agent.init_state(jax.random.PRNGKey(0))
    batch, w = synthetic_r2d2_batch(B, cfg.seq_len, cfg.obs_shape, cfg.num_actions,
                                    cfg.lstm_size)
    batch = jax.device_put(jax.tree.map(jnp.asarray, batch))
    w = jax.device_put(jnp.asarray(w))

    box = {"state": state, "loss": float("nan")}

    def window(n):
        t0 = time.perf_counter()
        state = box["state"]
        for _ in range(n):
            state, pri, metrics = agent.learn(state, batch, w)
        box["loss"] = float(metrics["loss"])
        box["state"] = state
        return time.perf_counter() - t0

    window(1)  # compile
    # The r2d2 step is the bench's shortest, so its two-window marginal
    # sits closest to the jitter floor (not measured on the attached
    # chip). Start with 4x the window; the estimator still
    # auto-lengthens.
    step_s, stats = _marginal_step_s(window, 4 * iters)
    fps = B * cfg.seq_len / step_s
    out = {"B": B, "frames_per_s": round(fps, 1), "step_ms": round(1e3 * step_s, 3),
           "timing": stats}
    out.update(_mfu_fields(
        _analytic_flops(agent.learn, box["state"], batch, w), step_s))
    out["mfu_note"] = (
        "structurally latency-bound, not a scheduling gap: the hot loop is "
        "2 (main+target) x seq_len=10 SEQUENTIAL recurrent matmuls of "
        "[B,512]x[512,2048] — ~0.1 GFLOP each, microseconds of MXU work "
        "per kernel — so per-kernel launch/latency dominates and nominal "
        "MFU cannot approach the conv families'")
    print(f"[bench] r2d2 learn B={B}: {1e3*step_s:.3f}ms/step = {fps:,.0f} frames/s "
          f"(iqr {stats['iqr_rel']:.0%}, loss {box['loss']:.4f})", file=sys.stderr)
    return out


def bench_apex_learn(B: int, iters: int) -> dict:
    """Ape-X learn-step throughput (transitions/s) at the reference's
    Breakout conv workload (`config.json:68-106`): double-DQN fwd x3
    (main s, main s', target s') + backward on the dueling conv net."""
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.apex import ApexAgent, ApexConfig
    from distributed_reinforcement_learning_tpu.utils.synthetic import synthetic_apex_batch

    cfg = ApexConfig()
    agent = ApexAgent(cfg)
    state = agent.init_state(jax.random.PRNGKey(0))
    batch, w = synthetic_apex_batch(B, cfg.obs_shape, cfg.num_actions)
    batch = jax.device_put(jax.tree.map(jnp.asarray, batch))
    w = jax.device_put(jnp.asarray(w))

    box = {"state": state, "loss": float("nan")}

    def window(n):
        t0 = time.perf_counter()
        state = box["state"]
        for _ in range(n):
            state, td, metrics = agent.learn(state, batch, w)
        box["loss"] = float(metrics["loss"])
        box["state"] = state
        return time.perf_counter() - t0

    window(1)  # compile
    step_s, stats = _marginal_step_s(window, iters)
    tps = B / step_s
    out = {"B": B, "transitions_per_s": round(tps, 1),
           "step_ms": round(1e3 * step_s, 3), "timing": stats}
    out.update(_mfu_fields(
        _analytic_flops(agent.learn, box["state"], batch, w), step_s))
    print(f"[bench] apex learn B={B}: {1e3*step_s:.3f}ms/step = {tps:,.0f} transitions/s "
          f"(iqr {stats['iqr_rel']:.0%}, loss {box['loss']:.4f})", file=sys.stderr)
    return out


def bench_ximpala_learn(B: int, iters: int) -> dict:
    """Transformer-IMPALA learn-step throughput (env-frames/s): V-trace
    over a [B, T] causal-transformer forward+backward — the fifth
    family's hot path (one forward, no stored state)."""
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.ximpala import XImpalaAgent, XImpalaConfig
    from distributed_reinforcement_learning_tpu.utils.synthetic import synthetic_ximpala_batch

    on_accel = jax.default_backend() not in ("cpu",)
    cfg = XImpalaConfig(obs_shape=(64,), num_actions=18, trajectory=32,
                        d_model=256, num_heads=4, num_layers=4,
                        dtype=jnp.bfloat16 if on_accel else jnp.float32)
    agent = XImpalaAgent(cfg)
    state = agent.init_state(jax.random.PRNGKey(0))
    batch = jax.device_put(jax.tree.map(
        jnp.asarray,
        synthetic_ximpala_batch(B, cfg.trajectory, cfg.obs_shape, cfg.num_actions)))

    box = {"state": state, "loss": float("nan")}

    def window(n):
        t0 = time.perf_counter()
        state = box["state"]
        for _ in range(n):
            state, metrics = agent.learn(state, batch)
        box["loss"] = float(metrics["total_loss"])
        box["state"] = state
        return time.perf_counter() - t0

    window(1)  # compile
    step_s, stats = _marginal_step_s(window, iters)
    fps = B * cfg.trajectory / step_s
    out = {"B": B, "frames_per_s": round(fps, 1), "step_ms": round(1e3 * step_s, 3),
           "timing": stats}
    out.update(_mfu_fields(_analytic_flops(agent.learn, box["state"], batch), step_s))
    print(f"[bench] ximpala learn B={B}: {1e3*step_s:.3f}ms/step = {fps:,.0f} frames/s "
          f"(iqr {stats['iqr_rel']:.0%}, loss {box['loss']:.2f})", file=sys.stderr)
    return out


def bench_ingest(B: int, iters: int) -> dict:
    """Host-side batch ingest assembly: native strided pop + C++
    batch-gather vs per-blob decode + np.stack, on the IMPALA Atari
    unroll (SURVEY §7 hard part (a) — the host path that feeds the
    chip). Platform-independent (pure host work)."""
    import jax

    from distributed_reinforcement_learning_tpu.data import codec, native
    from distributed_reinforcement_learning_tpu.data.fifo import stack_pytrees

    if not native.native_available():
        return {"error": "native library unavailable"}
    import numpy as np

    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaConfig

    cfg = ImpalaConfig()
    one = jax.tree.map(lambda x: np.asarray(x[0]), _make_batch(cfg, 1))
    q = native.NativeTrajectoryQueue(4 * B)

    def fill():
        for _ in range(B):
            q.put(one)

    def timed(f):
        ts = []
        for _ in range(iters):
            fill()
            t0 = time.perf_counter()
            f()
            ts.append(time.perf_counter() - t0)
        return 1e3 * sorted(ts)[len(ts) // 2]

    for _ in range(2):
        fill()
        q.get_batch(B)
    gather_ms = timed(lambda: q.get_batch(B))

    def per_blob():
        blobs = q._q.get_batch_blobs(B, q._item_cap)
        stack_pytrees([codec.decode(b) for b in blobs])

    decode_stack_ms = timed(per_blob)
    frames = B * cfg.trajectory
    out = {
        "B": B,
        "gather_ms": round(gather_ms, 2),
        "decode_stack_ms": round(decode_stack_ms, 2),
        "speedup": round(decode_stack_ms / gather_ms, 2),
        "gather_frames_per_s": round(frames / (gather_ms / 1e3), 1),
    }
    print(f"[bench] ingest: {out}", file=sys.stderr)
    return out


def bench_apex_ingest(iters: int = 5) -> dict:
    """Ape-X learner-side ingest rate: K buffered
    unrolls scored in one [K*32] TD forward + C++ sum-tree batch add,
    vs the reference's one-unroll-per-sess.run loop
    (`/root/reference/train_apex.py:98-122`). Target: ingest must keep
    up with the learn step's transitions/s at B=256."""
    import jax
    import numpy as np

    from distributed_reinforcement_learning_tpu.agents.apex import ApexAgent, ApexConfig
    from distributed_reinforcement_learning_tpu.runtime.apex_runner import ApexLearner
    from distributed_reinforcement_learning_tpu.runtime.transport import _make_queue
    from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore
    from distributed_reinforcement_learning_tpu.utils.synthetic import synthetic_apex_batch

    cfg = ApexConfig()
    agent = ApexAgent(cfg)
    U, K = 32, 8  # unroll transitions; max unrolls per device call
    queue = _make_queue(256)
    learner = ApexLearner(agent, queue, WeightStore(), batch_size=32,
                          replay_capacity=100_000, rng=jax.random.PRNGKey(0))
    one, _ = synthetic_apex_batch(U, cfg.obs_shape, cfg.num_actions)

    def fill(n):
        for _ in range(n):
            queue.put(one)

    out: dict = {}
    for mode, kw in (("per_unroll", {"max_unrolls": 1}), ("batched", {"max_unrolls": K})):
        fill(2 * K)
        while learner.ingest_many(timeout=0.0, **kw):  # warm/compile
            pass
        ts = []
        for _ in range(iters):
            fill(2 * K)
            t0 = time.perf_counter()
            got = 0
            while got < 2 * K:
                got += learner.ingest_many(timeout=1.0, **kw)
            ts.append((time.perf_counter() - t0) / (2 * K))
        per_unroll_s = sorted(ts)[len(ts) // 2]
        out[mode] = {
            "unrolls_per_s": round(1.0 / per_unroll_s, 1),
            "transitions_per_s": round(U / per_unroll_s, 1),
        }
    queue.close()
    out["speedup"] = round(out["batched"]["transitions_per_s"]
                           / out["per_unroll"]["transitions_per_s"], 2)
    # Ingest is H2D-coupled: every scored unroll ships its frames to the
    # device. Report the bytes so a slow reading is attributable to the
    # host<->device link or to the batching.
    unroll_bytes = sum(np.asarray(x).nbytes for x in jax.tree.leaves(one))
    out["h2d_mb_per_unroll"] = round(unroll_bytes / 1e6, 2)
    for mode in ("per_unroll", "batched"):
        rate = out[mode]["unrolls_per_s"]
        out[mode]["implied_h2d_gb_per_s"] = round(rate * unroll_bytes / 1e9, 4)
    print(f"[bench] apex ingest: {out}", file=sys.stderr)
    return out


def bench_long_context(iters: int) -> dict:
    """Single-chip long-context attention fwd+bwd at T=8192: dense vs
    blockwise online-softmax vs the fused Pallas flash kernels — plus
    flash alone at T=32768, a length whose XLA backward (O(T^2) saved
    probabilities) does not fit HBM at all."""
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.ops.attention import (
        blockwise_attention, causal_attention, dense_attention)

    B, T, H, D = 1, 8192, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (0.2 * jax.random.normal(kk, (B, T, H, D), jnp.bfloat16) for kk in ks)
    out = {}
    for name, fn in (("dense", dense_attention),
                     ("blockwise", lambda q, k, v: blockwise_attention(q, k, v, block_size=512)),
                     ("flash", lambda q, k, v: causal_attention(q, k, v, backend="pallas"))):
        def loss(q, k, v, _f=fn):
            return jnp.sum(_f(q, k, v).astype(jnp.float32) ** 2)

        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        seedbox = [0]

        def window(n):
            # A fresh seed per window perturbs the inputs so no window
            # replays a byte-identical computation; acc chains the calls
            # within a window.
            seedbox[0] += 1
            acc = jnp.float32(seedbox[0])
            t0 = time.perf_counter()
            for i in range(n):
                gs = g(q * (1.0 + 1e-6 * acc), k, v)
                acc = acc + jnp.sum(gs[0][0, 0, 0]).astype(jnp.float32)
            float(acc)
            return time.perf_counter() - t0

        window(2)  # compile + warm
        step_s, stats = _marginal_step_s(window, iters, samples=3)
        out[f"attn_grad_T{T}_{name}_us"] = round(1e6 * step_s, 1)
        out[f"attn_grad_T{T}_{name}_stable"] = stats.get("stable", False)

    # T=32k: flash-only (the XLA paths' backward OOMs HBM here).
    T2 = 32768
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (0.2 * jax.random.normal(kk, (B, T2, H, D), jnp.bfloat16) for kk in ks)
    g = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(causal_attention(q, k, v, backend="pallas").astype(jnp.float32) ** 2),
        argnums=(0, 1, 2)))

    seedbox32 = [100]

    def window32(n):
        seedbox32[0] += 1
        acc = jnp.float32(seedbox32[0])
        t0 = time.perf_counter()
        for _ in range(n):
            gs = g(q * (1.0 + 1e-6 * acc), k, v)
            acc = acc + jnp.sum(gs[0][0, 0, 0]).astype(jnp.float32)
        float(acc)
        return time.perf_counter() - t0

    window32(2)
    step32_s, stats32 = _marginal_step_s(window32, max(iters // 2, 3), samples=3)
    out[f"attn_grad_T{T2}_flash_us"] = round(1e6 * step32_s, 1)
    out[f"attn_grad_T{T2}_flash_stable"] = stats32.get("stable", False)
    print(f"[bench] long-context: {out}", file=sys.stderr)
    return out


def bench_kernels(cfg, B: int, iters: int) -> dict:
    """Pallas vs XLA-scan timings for the V-trace recursion and the fused
    LSTM at IMPALA shapes — the committed evidence behind the backend
    `auto` resolution choices in ops/vtrace.py and ops/lstm.py."""
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.ops import lstm as lstm_ops
    from distributed_reinforcement_learning_tpu.ops import vtrace as vt

    on_tpu = jax.default_backend() == "tpu"
    T, H = cfg.trajectory, cfg.lstm_size
    rng = jax.random.PRNGKey(0)
    out: dict = {}

    def timeit(fn, *args):
        """us/call with the timing loop ON DEVICE.

        Host-side per-call timing of a microsecond kernel measures the
        dispatch, not the kernel. Instead: one jitted `lax.scan` chains
        `iters` calls
        through a scalar carry that perturbs the inputs (a data
        dependency neither XLA nor the runtime can CSE away), and the
        whole loop is one dispatch whose final scalar is materialized as
        a host float. A length-1 run of the same loop is subtracted to
        strip the round-trip + dispatch constant. The per-iteration
        input-perturbation multiply is bandwidth-trivial next to the
        kernels and identical across compared backends.
        """

        def body(carry, _):
            scaled = jax.tree.map(lambda a: a * (1.0 + 1e-20 * carry), args)
            r = fn(*scaled)
            s = sum(jnp.sum(x.astype(jnp.float32)) for x in jax.tree.leaves(r))
            return carry + 1e-20 * s, None

        seed = iter(range(1, 1000))

        def loop(n, samples=3):
            # Each timed run gets a fresh seed input, and the min over
            # samples rejects dispatch latency spikes.
            run = jax.jit(lambda s: jax.lax.scan(body, s, None, length=n)[0])
            float(run(jnp.float32(next(seed))))  # compile + warm
            best = float("inf")
            for _ in range(samples):
                t0 = time.perf_counter()
                float(run(jnp.float32(next(seed))))
                best = min(best, time.perf_counter() - t0)
            return best

        # The long loop must dwarf the dispatch constant and its variance.
        # Reproducibility: estimate
        # at two loop lengths; accept only when both marginals are
        # POSITIVE and agree within 15%, else lengthen the loop (signal
        # grows with n, the RTT noise floor doesn't) and retry.
        n = max(iters, 200)
        for _ in range(3):
            base = loop(1)
            e1 = (loop(n) - base) / (n - 1)
            e2 = (loop(2 * n) - base) / (2 * n - 1)
            if e1 > 0 and e2 > 0:
                spread = abs(e1 - e2) / max(e1, e2)
                if spread <= 0.15:
                    return 1e6 * 0.5 * (e1 + e2), round(spread, 3), True
            if n >= 16000:
                break
            n *= 4
        good = [e for e in (e1, e2) if e > 0]
        est = sum(good) / len(good) if good else 0.0
        return 1e6 * est, None, False

    # V-trace core, time-major [T, B].
    ks = jax.random.split(rng, 4)
    log_rhos = 0.1 * jax.random.normal(ks[0], (T, B))
    discounts = jnp.full((T, B), 0.99)
    rewards = jax.random.normal(ks[1], (T, B))
    values = jax.random.normal(ks[2], (T, B))
    bootstrap = jax.random.normal(ks[3], (B,))
    def record(key, fn, *args):
        us, spread, stable = timeit(fn, *args)
        out[f"{key}_us"] = round(us, 1)
        out[f"{key}_stable"] = stable
        if spread is not None:
            out[f"{key}_spread"] = spread

    for backend in ("reference",) + (("pallas",) if on_tpu else ()):
        f = jax.jit(lambda lr, d, r, v, bv, _b=backend: vt.from_importance_weights(
            lr, d, r, v, bv, backend=_b))
        record(f"vtrace_{backend}", f, log_rhos, discounts, rewards,
               values, bootstrap)

    # LSTM sequence recursion, batch-major [B, T, 4H] + grad (the training
    # direction exercises the hand-derived Pallas BPTT too).
    ks = jax.random.split(rng, 3)
    xg = 0.1 * jax.random.normal(ks[0], (B, T, 4 * H))
    wh = 0.1 * jax.random.normal(ks[1], (H, 4 * H))
    keep = jnp.ones((B, T))
    h0 = c0 = jnp.zeros((B, H))
    for backend in ("reference",) + (("pallas",) if on_tpu else ()):
        def loss(xg, wh, _b=backend):
            h_all, _ = lstm_ops.lstm_scan(xg, wh, keep, h0, c0, backend=_b)
            return jnp.sum(h_all * h_all)

        f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
        record(f"lstm_grad_{backend}", f, xg, wh)
    print(f"[bench] kernels: {out}", file=sys.stderr)
    return out


def main() -> int:
    # BENCH_PLATFORM=cpu forces the CPU backend: a smoke of the bench's
    # own mechanics, whose numbers are not device metrics.
    forced = os.environ.get("BENCH_PLATFORM")
    import jax

    if forced:
        jax.config.update("jax_platforms", forced)
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent, ImpalaConfig
    from distributed_reinforcement_learning_tpu.utils.device import (
        enable_compile_cache, open_devices)

    enable_compile_cache()
    device = open_devices("bench")
    platform = device["platform"]
    if platform == "cpu" and forced != "cpu":
        # No fall-back: a CPU number under a device metric's name is
        # worse than no number.
        print("[bench] JAX found no accelerator; set BENCH_PLATFORM=cpu to "
              "smoke the bench's mechanics on the CPU", file=sys.stderr)
        return 1
    on_accel = platform not in ("cpu",)
    # bfloat16 compute on TPU keeps the matmuls on the MXU's fast path.
    dtype = jnp.bfloat16 if on_accel else jnp.float32
    iters = int(os.environ.get("BENCH_ITERS", "150" if on_accel else "3"))
    sweep_default = "32,64,128,256" if on_accel else "8"
    sweep = [int(b) for b in os.environ.get("BENCH_SWEEP", sweep_default).split(",")]

    remat = os.environ.get("BENCH_REMAT", "0") == "1"
    cfg = ImpalaConfig(dtype=dtype, remat=remat)
    extra: dict = {"platform": platform, "device": device,
                   "dtype": str(dtype.__name__), "remat": remat}

    # Wall-clock budget: a driver's time limit once killed the bench
    # before its single end-of-run emit. Two defenses, both here: (a) the
    # headline section runs FIRST and emits its parsed line IMMEDIATELY
    # (the driver takes the last JSON line, so the enriched end-of-run
    # emit supersedes it when it lands); (b) every later section is
    # gated on a time budget — when the projected section would
    # overrun, it is skipped and recorded in extra["skipped_sections"]
    # so the final line still appears well inside the driver's timeout.
    t_start = time.monotonic()
    budget = float(os.environ.get("BENCH_TIME_BUDGET", "2700"))
    deadline = t_start + budget
    skipped: list = []
    extra["time_budget_s"] = budget

    def _ok(name: str, est: float = 120.0) -> bool:
        """True if `name` (rough cost `est` s) fits in the budget."""
        if time.monotonic() + est <= deadline:
            return True
        skipped.append(name)
        print(f"[bench] budget: skipping {name} "
              f"({time.monotonic() - t_start:.0f}s elapsed of {budget:.0f}s)",
              file=sys.stderr)
        return False

    # The budget gates SECTION STARTS; a section stuck inside a device
    # call cannot be interrupted from Python. Past budget + 300 s grace,
    # a watchdog thread force-finishes: emit the best measurement that
    # landed and hard-exit NON-ZERO — a parsed line, and an exit code
    # that says the run did not finish. `final_lock`/`finishing`
    # serialize it against the normal final-emit paths (no interleaved
    # stdout).
    final_lock = threading.Lock()
    finishing = threading.Event()

    def _final_emit(value: float, ex: dict, **kw) -> int:
        """Emit the final line; -> the process's exit code. A section
        that recorded an error, or a run without any measurement
        (`error`), is a failed run. Budget skips are not failures."""
        with final_lock:
            finishing.set()
            failed = sorted(
                k for k, v in ex.items()
                if (isinstance(v, dict) and "error" in v)
                or (isinstance(v, list)
                    and any(isinstance(r, dict) and "error" in r for r in v)))
            if failed:
                ex["failed_sections"] = failed
            _emit(value, ex, **kw)
            return 1 if failed or "error" in ex else 0

    def _watchdog():
        time.sleep(max(0.0, deadline + 300 - time.monotonic()))
        with final_lock:
            if finishing.is_set():
                return  # normal completion beat us; let main finish
            try:
                # The snapshot races the main thread's section-key inserts
                # ({**extra} can raise "dict changed size during
                # iteration"); ANY failure here must still leave a parsed
                # line — that is the watchdog's whole guarantee.
                snap = {**extra}
                snap.setdefault("skipped_sections", list(skipped))
                snap["watchdog"] = (
                    "a section ran past budget+300s; force-emitted "
                    "partial results")
                ab = snap.get("anakin_breakout", {})
                if isinstance(ab, dict) and ab.get("frames_per_s", 0) > 0:
                    _emit(ab["frames_per_s"], snap,
                          metric="anakin_breakout_env_frames_per_s")
                else:
                    _emit(0.0, {**snap,
                                "error": "stuck before any measurement"})
                sys.stdout.flush()
            except Exception:  # noqa: BLE001 — minimal fallback line
                try:
                    # Print-only: touching bench_detail.json here would
                    # overwrite whatever full detail the early headline
                    # emit already persisted.
                    print(json.dumps({
                        "metric": "impala_e2e_env_frames_per_s",
                        "value": 0.0, "unit": "frames/s",
                        "vs_baseline": 0.0,
                        "extra": {"watchdog": "emit failed"}}))
                    sys.stdout.flush()
                except Exception:  # noqa: BLE001
                    pass
            finally:
                os._exit(1)

    threading.Thread(target=_watchdog, daemon=True).start()
    stall_s = float(os.environ.get("BENCH_TEST_STALL_S", "0"))
    if stall_s > 0:
        # Test hook (tests/test_bench_contract.py): park the main thread
        # the way a section stuck in an uninterruptible device call
        # does, so the watchdog path is actually exercised.
        time.sleep(stall_s)

    # Headline section first (accelerator only — a conv learn step per
    # update on the 1-core host is minutes). On success, emit the parsed
    # headline NOW: even if the driver kills everything after this
    # point, the artifact carries a real number.
    ab_early: dict = {}
    if os.environ.get("BENCH_ANAKIN_BREAKOUT", "1" if on_accel else "0") == "1":
        try:
            ab_early = bench_anakin_breakout(
                int(os.environ.get("BENCH_AB_ENVS", "256" if on_accel else "4")),
                int(os.environ.get("BENCH_AB_CHUNK", "20" if on_accel else "2")),
                max(iters // 30, 3))
            extra["anakin_breakout"] = ab_early
        except Exception as e:  # noqa: BLE001
            extra["anakin_breakout"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] anakin_breakout failed: {e}", file=sys.stderr)
    if on_accel and ab_early.get("frames_per_s", 0) > 0:
        extra["headline"] = ("anakin_breakout: on-device pixel-env "
                             "training, frames collected AND learned per "
                             "second; host-loop e2e + stage budget in "
                             "e2e_pipeline_*/stage_budget")
        # Lock-shared with the watchdog (WITHOUT setting `finishing`): a
        # watchdog firing concurrently must not interleave its line with
        # this print and corrupt the last stdout line.
        with final_lock:
            _emit(ab_early["frames_per_s"],
                  {**extra, "partial": "headline-only early emit; "
                   "the full-detail line (if present below) supersedes this"},
                  metric="anakin_breakout_env_frames_per_s")
            sys.stdout.flush()

    results = []
    for B in sweep:
        if not _ok(f"learn_step_B{B}", 90.0):
            continue
        try:
            results.append(bench_learn_step(cfg, B, iters))
        except Exception as e:  # noqa: BLE001 — an unmeasurable B is excluded, not 1e-9
            results.append({"B": B, "error": f"{type(e).__name__}: {e}"})
            print(f"[bench] learn B={B} failed: {e}", file=sys.stderr)
    extra["learn_step_sweep"] = results
    valid = [r for r in results if "frames_per_s" in r]
    if not valid:
        if ab_early.get("frames_per_s", 0) > 0:
            # The headline already landed; finish with it rather than
            # clobbering the round's number with a 0.0 error line.
            extra["skipped_sections"] = skipped
            extra["error_learn_step"] = "no learn-step measurement landed"
            return _final_emit(ab_early["frames_per_s"], extra,
                               metric="anakin_breakout_env_frames_per_s")
        return _final_emit(
            0.0, {**extra, "error": "no learn-step measurement landed",
                  "phase": "learn_step", "skipped_sections": skipped})
    best = max(valid, key=lambda r: r["frames_per_s"])

    # K steps per dispatch: the honest device rate with the per-step
    # dispatch gap stripped (and the rate a learner running
    # updates_per_call=K actually sustains). Accelerator-default: XLA
    # CPU runs while-loop bodies single-threaded, so a CPU scan-of-learn
    # measures that quirk (~60x slow), not the framework.
    if os.environ.get("BENCH_SCAN", "1" if on_accel else "0") == "1" and _ok("learn_scan", 90):
        try:
            extra["learn_scan"] = bench_learn_scan(
                cfg, best["B"], int(os.environ.get("BENCH_SCAN_K", "8")),
                max(iters // 8, 8) if on_accel else 2)
            extra["learn_scan"]["speedup_vs_per_step"] = round(
                extra["learn_scan"]["frames_per_s"] / best["frames_per_s"], 2)
        except Exception as e:  # noqa: BLE001
            extra["learn_scan"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] learn_scan failed: {e}", file=sys.stderr)

    # Folded /255 path: same math, minus the full-frame normalize pass.
    if os.environ.get("BENCH_FOLD", "1") == "1" and _ok("fold_normalize", 90):
        try:
            import dataclasses as _dc

            r = bench_learn_step(_dc.replace(cfg, fold_normalize=True),
                                 best["B"], iters)
            r["speedup_vs_plain"] = round(
                r["frames_per_s"] / best["frames_per_s"], 3)
            extra["fold_normalize"] = r
        except Exception as e:  # noqa: BLE001
            extra["fold_normalize"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] fold_normalize failed: {e}", file=sys.stderr)

    if on_accel:  # a device metric: a CPU run reports none
        try:
            extra["roofline"] = impala_roofline(cfg, best["B"], best["step_ms"] / 1e3)
            scan = extra.get("learn_scan", {})
            if scan.get("step_ms", 0) > 0:
                # The scan-measured step is the honest device time (no
                # dispatch gap), so this is the truer attainable fraction.
                extra["roofline"]["scan_measured_step_ms"] = scan["step_ms"]
                extra["roofline"]["mfu_attainable_scan"] = round(
                    extra["roofline"]["attainable_step_ms"] / scan["step_ms"], 3)
        except Exception as e:  # noqa: BLE001
            extra["roofline"] = {"error": f"{type(e).__name__}: {e}"}

    # MXU-dense variant: the IMPALA-paper deep ResNet
    # torso at width 4 — 3x3 convs with 64/128 output channels and
    # 576/1152-deep contractions that fill the 128-wide MXU. Proves the
    # chip-side framework path sustains high MFU when the MODEL is dense;
    # Nature-CNN's low MFU is its 32/64-channel geometry, not dispatch.
    # Accelerator-only: a width-4 ResNet learn step on 1 CPU core is
    # minutes per step.
    if os.environ.get("BENCH_RESNET", "1" if on_accel else "0") == "1" and _ok("resnet", 300):
        try:
            import dataclasses as _dc

            rcfg = _dc.replace(cfg, torso="resnet",
                               torso_width=int(os.environ.get("BENCH_RESNET_WIDTH", "4")),
                               fold_normalize=True)
            # B=32: ~4.7 GB of bf16 activations for the width-4 stack
            # (B*T=640 frames x ~7.3 MB/frame) — comfortably inside v5e
            # HBM without remat, whose recompute would inflate the
            # cost-analysis FLOPs and with them the reported MFU.
            rB = int(os.environ.get("BENCH_RESNET_BATCH", "32"))
            r = bench_learn_step(rcfg, rB, max(iters // 4, 8) if on_accel else 2)
            # Scan-timed step (dispatch gap stripped) for the honest MFU,
            # like the headline sweep's learn_scan.
            try:
                rs = bench_learn_scan(rcfg, rB,
                                      int(os.environ.get("BENCH_SCAN_K", "8")),
                                      max(iters // 8, 8) if on_accel else 2)
                r["scan"] = rs
            except Exception as e:  # noqa: BLE001
                r["scan"] = {"error": f"{type(e).__name__}: {e}"}
            roof = impala_roofline(rcfg, rB, r["step_ms"] / 1e3)
            if r.get("scan", {}).get("step_ms", 0) > 0:
                roof["scan_measured_step_ms"] = r["scan"]["step_ms"]
                roof["mfu_attainable_scan"] = round(
                    roof["attainable_step_ms"] / r["scan"]["step_ms"], 3)
            r["roofline"] = roof
            r["torso_width"] = rcfg.torso_width
            extra["resnet"] = r
        except Exception as e:  # noqa: BLE001
            extra["resnet"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] resnet failed: {e}", file=sys.stderr)

    # End-to-end IS the headline: the reference's operating
    # mode is the full actors -> queue -> learner -> weights loop, so the
    # `value` must be a pipeline number, with the learn step as detail.
    e2e_fps = 0.0
    if os.environ.get("BENCH_E2E", "1") == "1":
        e2e_B = int(os.environ.get("BENCH_E2E_BATCH", str(best["B"] if on_accel else 8)))
        e2e_updates = int(os.environ.get("BENCH_E2E_UPDATES", "30" if on_accel else "3"))
        for mode in ("shm", "tcp"):
            if not _ok(f"e2e_{mode}", 420):
                continue
            try:
                r = bench_e2e(cfg, e2e_B, e2e_updates, mode=mode)
                extra[f"e2e_pipeline_{mode}"] = r
                e2e_fps = max(e2e_fps, r["frames_per_s"])
            except Exception as e:  # noqa: BLE001 — one mode failing must not cost the other
                extra[f"e2e_pipeline_{mode}"] = {"error": f"{type(e).__name__}: {e}"}
                print(f"[bench] e2e[{mode}] failed: {e}", file=sys.stderr)

    if os.environ.get("BENCH_BUDGET", "1") == "1" and _ok("stage_budget", 420):
        try:
            extra["stage_budget"] = bench_stage_budget(
                cfg, int(os.environ.get("BENCH_BUDGET_BATCH",
                                        "128" if on_accel else "8")),
                best["frames_per_s"])
        except Exception as e:  # noqa: BLE001
            extra["stage_budget"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] stage budget failed: {e}", file=sys.stderr)

    # Host-only TCP-vs-shm-ring PUT A/B (the auto-enable adjudication for
    # runtime/shm_ring.py); cheap and link-independent, so it runs by
    # default on every platform.
    if os.environ.get("BENCH_TRANSPORT", "1") == "1" and _ok("transport_compare", 120):
        try:
            r = bench_transport_compare(cfg)
            extra["transport_compare"] = r
            if "verdict" in r:
                extra["transport_verdict"] = r["verdict"]
        except Exception as e:  # noqa: BLE001
            extra["transport_compare"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] transport_compare failed: {e}", file=sys.stderr)

    # Host-only encode+PUT A/B (the auto-enable adjudication for the
    # codec schema cache and frame-stack dedup, data/codec.py).
    if os.environ.get("BENCH_CODEC", "1") == "1" and _ok("codec_compare", 120):
        try:
            r = bench_codec_compare(cfg)
            extra["codec_compare"] = r
            if "verdict" in r:
                extra["codec_verdict"] = r["verdict"]
        except Exception as e:  # noqa: BLE001
            extra["codec_compare"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] codec_compare failed: {e}", file=sys.stderr)

    # Two-process weight-plane A/B (the auto-enable adjudication for the
    # shm weight board, runtime/weight_board.py).
    if os.environ.get("BENCH_WEIGHTS", "1") == "1" and _ok("weights_compare", 120):
        try:
            r = bench_weights_compare(cfg)
            extra["weights_compare"] = r
            if "verdict" in r:
                extra["weights_verdict"] = r["verdict"]
        except Exception as e:  # noqa: BLE001
            extra["weights_compare"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] weights_compare failed: {e}", file=sys.stderr)

    # Whole-blob vs sharded vs sharded+bf16 weight-plane A/B at two
    # policy shapes (the auto-enable adjudication for per-shard
    # publication + the quantized broadcast, runtime/weight_shards.py).
    if os.environ.get("BENCH_WEIGHTS_SHARD", "1") == "1" and \
            _ok("weights_shard_compare", 240):
        try:
            r = bench_weights_shard_compare(cfg)
            extra["weights_shard_compare"] = r
            if "verdict" in r:
                extra["weights_shard_verdict"] = r["verdict"]
        except Exception as e:  # noqa: BLE001
            extra["weights_shard_compare"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] weights_shard_compare failed: {e}", file=sys.stderr)

    # Two-process Ape-X ingest-plane A/B (the auto-enable adjudication
    # for the sharded replay service, data/replay_service.py).
    if os.environ.get("BENCH_REPLAY", "1") == "1" and _ok("replay_compare", 150):
        try:
            r = bench_replay_compare()
            extra["replay_compare"] = r
            if "verdict" in r:
                extra["replay_verdict"] = r["verdict"]
        except Exception as e:  # noqa: BLE001
            extra["replay_compare"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] replay_compare failed: {e}", file=sys.stderr)

    # Two-process sample-at-source A/B (the auto-enable adjudication
    # for actor-side priority stamping + priority-mass admission,
    # data/admission.py).
    if os.environ.get("BENCH_ADMISSION", "1") == "1" and \
            _ok("admission_compare", 150):
        try:
            r = bench_admission_compare()
            extra["admission_compare"] = r
            if "verdict" in r:
                extra["admission_verdict"] = r["verdict"]
        except Exception as e:  # noqa: BLE001
            extra["admission_compare"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] admission_compare failed: {e}", file=sys.stderr)

    # In-process sequence-mode (R2D2) leg of the sample-at-source
    # adjudication: the LazyBlob decode-deferral win the transition-mode
    # A/B cannot reach (admission_verdict.json `rerun_sequence_mode`).
    if os.environ.get("BENCH_ADMISSION", "1") == "1" and \
            _ok("admission_sequence_compare", 60):
        try:
            extra["admission_sequence_compare"] = \
                bench_admission_sequence_compare()
        except Exception as e:  # noqa: BLE001
            extra["admission_sequence_compare"] = {
                "error": f"{type(e).__name__}: {e}"}
            print(f"[bench] admission_sequence_compare failed: {e}",
                  file=sys.stderr)

    # In-process tiered-replay A/B (the auto-enable adjudication for the
    # hot/cold spill tier, data/replay_spill.py): storage density per GB
    # of learner RAM at a spill-forcing hot budget, gated on the timed
    # sample+writeback loop staying within 10% of all-RAM.
    if os.environ.get("BENCH_REPLAY_SPILL", "1") == "1" and \
            _ok("replay_spill_compare", 120):
        try:
            r = bench_replay_spill_compare()
            extra["replay_spill_compare"] = r
            if "verdict" in r:
                extra["replay_spill_verdict"] = r["verdict"]
        except Exception as e:  # noqa: BLE001
            extra["replay_spill_compare"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] replay_spill_compare failed: {e}", file=sys.stderr)

    # Two-process host-vs-device sample-path A/B (the auto-enable
    # adjudication for the fused device-resident sample path,
    # data/device_path.py).
    if os.environ.get("BENCH_DEVICE_PATH", "1") == "1" and \
            _ok("device_path_compare", 150):
        try:
            r = bench_device_path_compare()
            extra["device_path_compare"] = r
            if "verdict" in r:
                extra["device_path_verdict"] = r["verdict"]
        except Exception as e:  # noqa: BLE001
            extra["device_path_compare"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] device_path_compare failed: {e}", file=sys.stderr)

    # Multi-process learner-tier A/B (the auto-enable adjudication for
    # the sharded learner tier, runtime/learner_tier.py): one seat vs
    # two cooperating seats with the host-collective gradient exchange.
    if os.environ.get("BENCH_LEARNER", "1") == "1" and _ok("learner_compare", 180):
        try:
            r = bench_learner_compare()
            extra["learner_compare"] = r
            if "verdict" in r:
                extra["learner_verdict"] = r["verdict"]
        except Exception as e:  # noqa: BLE001
            extra["learner_compare"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] learner_compare failed: {e}", file=sys.stderr)

    # Partition-aware collective A/B (the bf16/overlap adjudication for
    # the learner tier's gradient exchange, parallel/collective.py):
    # ring vs partitioned vs bf16-encoded rounds at the xformer gradient
    # shape, plus the backward-overlap pipeline.
    if os.environ.get("BENCH_COLLECTIVE", "1") == "1" and _ok(
            "collective_compare", 60):
        try:
            r = bench_collective_compare()
            extra["collective_compare"] = r
            if "verdict" in r:
                extra["collective_verdict"] = r["verdict"]
        except Exception as e:  # noqa: BLE001
            extra["collective_compare"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] collective_compare failed: {e}", file=sys.stderr)

    # Multi-process chaos drill (the elastic-fleet adjudication,
    # runtime/fleet.py): kill+respawn the learner mid-window, assert
    # zero corrupted trajectories, bounded throughput dip, full
    # re-promotion within the deadline, and the kill-one-of-N learner
    # SEAT drill (runtime/learner_tier.py).
    if os.environ.get("BENCH_CHAOS", "1") == "1" and _ok("chaos_compare", 200):
        try:
            r = bench_chaos_compare()
            extra["chaos_compare"] = r
            if "verdict" in r:
                extra["chaos_verdict"] = r["verdict"]
        except Exception as e:  # noqa: BLE001
            extra["chaos_compare"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] chaos_compare failed: {e}", file=sys.stderr)

    # Two-process sequential-vs-pipelined actor A/B (the auto-enable
    # adjudication for the pipelined actor data plane,
    # runtime/actor_pipeline.py).
    if os.environ.get("BENCH_ACTOR", "1") == "1" and _ok("actor_compare", 180):
        try:
            r = bench_actor_compare()
            extra["actor_compare"] = r
            if "verdict" in r:
                extra["actor_pipeline_verdict"] = r["verdict"]
        except Exception as e:  # noqa: BLE001
            extra["actor_compare"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] actor_compare failed: {e}", file=sys.stderr)

    # Multi-process act-path client-swarm A/B (the auto-enable
    # adjudication for the inference serving tier, runtime/serving.py).
    if os.environ.get("BENCH_INFER", "1") == "1" and _ok("inference_compare", 150):
        try:
            r = bench_inference_compare(
                ImpalaConfig(obs_shape=(128,), num_actions=8, trajectory=8,
                             lstm_size=128))
            extra["inference_compare"] = r
            if "verdict" in r:
                extra["inference_verdict"] = r["verdict"]
        except Exception as e:  # noqa: BLE001
            extra["inference_compare"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] inference_compare failed: {e}", file=sys.stderr)

    if os.environ.get("BENCH_KERNELS", "1") == "1" and _ok("kernel_compare", 240):
        try:
            extra["kernel_compare"] = bench_kernels(
                ImpalaConfig(), int(os.environ.get("BENCH_KERNEL_BATCH", "256")),
                max(iters, 10) if on_accel else 2)
        except Exception as e:  # noqa: BLE001
            extra["kernel_compare"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] kernels failed: {e}", file=sys.stderr)

    if os.environ.get("BENCH_R2D2", "1") == "1" and _ok("r2d2_learn", 120):
        try:
            # Default B=128 (the builders' earlier account had it well
            # ahead of B=64; not measured on the attached chip).
            extra["r2d2_learn"] = bench_r2d2_learn(
                int(os.environ.get("BENCH_R2D2_BATCH", "128")),
                iters if on_accel else 2)
        except Exception as e:  # noqa: BLE001
            extra["r2d2_learn"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] r2d2 failed: {e}", file=sys.stderr)

    if os.environ.get("BENCH_APEX", "1") == "1" and _ok("apex_learn", 120):
        try:
            extra["apex_learn"] = bench_apex_learn(
                int(os.environ.get("BENCH_APEX_BATCH", "256")),
                iters if on_accel else 2)
        except Exception as e:  # noqa: BLE001
            extra["apex_learn"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] apex failed: {e}", file=sys.stderr)

    if os.environ.get("BENCH_XIMPALA", "1") == "1" and _ok("ximpala_learn", 120):
        try:
            extra["ximpala_learn"] = bench_ximpala_learn(
                int(os.environ.get("BENCH_XIMPALA_BATCH", "64")),
                iters if on_accel else 2)
        except Exception as e:  # noqa: BLE001
            extra["ximpala_learn"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] ximpala failed: {e}", file=sys.stderr)

    if os.environ.get("BENCH_APEX_INGEST", "1") == "1" and _ok("apex_ingest", 300):
        try:
            extra["apex_ingest"] = bench_apex_ingest(
                int(os.environ.get("BENCH_APEX_INGEST_ITERS", "5")))
        except Exception as e:  # noqa: BLE001
            extra["apex_ingest"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] apex ingest failed: {e}", file=sys.stderr)

    if os.environ.get("BENCH_INGEST", "1") == "1" and _ok("ingest", 150):
        try:
            extra["ingest"] = bench_ingest(
                int(os.environ.get("BENCH_INGEST_BATCH", "32")),
                int(os.environ.get("BENCH_INGEST_ITERS", "11")))
        except Exception as e:  # noqa: BLE001
            extra["ingest"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] ingest failed: {e}", file=sys.stderr)

    if os.environ.get("BENCH_ANAKIN", "1") == "1" and _ok("anakin", 240):
        try:
            # Accel sizing saturates the chip; the CPU artifact documents
            # the schema at a size the 1-core host can time.
            extra["anakin"] = bench_anakin(
                int(os.environ.get("BENCH_ANAKIN_ENVS",
                                   "1024" if on_accel else "64")),
                int(os.environ.get("BENCH_ANAKIN_CHUNK",
                                   "100" if on_accel else "20")),
                max(iters // 30, 3))
        except Exception as e:  # noqa: BLE001
            extra["anakin"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] anakin failed: {e}", file=sys.stderr)

    if os.environ.get("BENCH_ANAKIN_APEX", "1" if on_accel else "0") == "1" and _ok("anakin_apex", 240):
        try:
            extra["anakin_apex"] = bench_anakin_apex(
                int(os.environ.get("BENCH_AA_ENVS", "64" if on_accel else "2")),
                int(os.environ.get("BENCH_AA_CHUNK", "10" if on_accel else "2")),
                max(iters // 30, 3))
        except Exception as e:  # noqa: BLE001
            extra["anakin_apex"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] anakin_apex failed: {e}", file=sys.stderr)

    if os.environ.get("BENCH_ANAKIN_R2D2", "1") == "1" and _ok("anakin_r2d2", 240):
        try:
            extra["anakin_r2d2"] = bench_anakin_r2d2(
                int(os.environ.get("BENCH_AR_ENVS", "256" if on_accel else "16")),
                int(os.environ.get("BENCH_AR_CHUNK", "50" if on_accel else "5")),
                max(iters // 30, 3))
        except Exception as e:  # noqa: BLE001
            extra["anakin_r2d2"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] anakin_r2d2 failed: {e}", file=sys.stderr)

    if os.environ.get("BENCH_LONG_CONTEXT", "1" if on_accel else "0") == "1" and _ok("long_context", 240):
        try:
            extra["long_context"] = bench_long_context(
                int(os.environ.get("BENCH_LC_ITERS", "10")))
        except Exception as e:  # noqa: BLE001
            extra["long_context"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] long-context failed: {e}", file=sys.stderr)

    extra["skipped_sections"] = skipped
    extra["elapsed_s"] = round(time.monotonic() - t_start, 1)
    ab = extra.get("anakin_breakout", {})
    if on_accel and ab.get("frames_per_s", 0) > 0:
        # The pixel-env Anakin row is a full training loop, not a learn
        # step: every frame is collected (env step + preprocessing) AND
        # learned on the chip. The host-loop e2e_pipeline_* sections and
        # the stage budget stay visible in `extra`.
        extra["headline"] = ("anakin_breakout: on-device pixel-env "
                             "training, frames collected AND learned per "
                             "second; host-loop e2e + stage budget in "
                             "e2e_pipeline_*/stage_budget")
        extra["learn_step_best_frames_per_s"] = best["frames_per_s"]
        if e2e_fps > 0:
            extra["host_loop_e2e_frames_per_s"] = e2e_fps
        return _final_emit(ab["frames_per_s"], extra,
                           metric="anakin_breakout_env_frames_per_s")
    if e2e_fps > 0:
        extra["learn_step_best_frames_per_s"] = best["frames_per_s"]
        return _final_emit(e2e_fps, extra)
    # No pipeline measurement landed: fall back to the learn-step
    # headline under its own (honest) metric name.
    return _final_emit(best["frames_per_s"], extra,
                       metric="impala_learn_env_frames_per_s")


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Digest of each benchmark cell's fused chunk as lowered for a described
TPU v5e, with every Mosaic kernel's serialized body cut out.

A refactor of the launcher, the configuration or an agent's config class
must hand the chip the SAME program. This lowers `train_chunk` of the six
cells (`perfbench/workloads/*.json`: the section of
`perfbench/configs/<config>.json` at the traffic's `num_envs` and
`chunk_updates`) the way `tests/test_tpu_compile.py`'s whole-step cases do
(shapes from `jax.eval_shape(anakin.init, ...)`, kernels chosen as on the
chip, nothing compiled or run), cuts each `tpu_custom_call`'s
`backend_config` out (a Mosaic body carries its call stack's line
numbers, ROADMAP S8; the text around it carries no locations) and prints
one JSON line a cell: the sha256 of the text, its length, its kernels.
Run it in two checkouts and compare the lines (ISSUE 45):

    python scripts/chunk_text_digest.py [--cell <name> ...] [--dump <dir>]

It needs no chip and opens no device; a cell takes 10-60 s here.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import sys
import tempfile
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BODY = re.compile(r'backend_config = "(?:[^"\\]|\\.)*"')


def _cell(name: str) -> tuple[str, dict, dict]:
    """-> (section name, {section name: section}, traffic) of a workload."""
    bench = os.path.join(ROOT, "perfbench")
    with open(os.path.join(bench, "workloads", f"{name}.json")) as f:
        workload = json.load(f)
    with open(os.path.join(bench, "configs", f"{workload['config']}.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic", f"{workload['traffic']}.json")) as f:
        traffic = {**json.load(f), **workload["overrides"]}
    section = dict(config[config["section"]])
    for key in ("updates_per_call", "train_start_factor"):
        if key in traffic:
            section[key] = traffic[key]
    return config["section"], {config["section"]: section}, traffic


def _build(path: str, section: str, traffic: dict):
    """The cell's fused loop, as `launch.train_anakin*` builds it."""
    from distributed_reinforcement_learning_tpu.runtime import launch

    cfg, rt = launch.load_config(path, section)
    n = int(traffic["num_envs"])
    algo = rt.algorithm
    if algo == "impala":
        from distributed_reinforcement_learning_tpu.runtime.anakin import AnakinImpala

        return AnakinImpala(launch.ImpalaAgent(cfg), n,
                            env=launch._jittable_env_for(cfg, rt)[0])
    if algo == "r2d2":
        from distributed_reinforcement_learning_tpu.runtime.anakin_r2d2 import (
            AnakinR2D2)

        env, transform = launch._jittable_env_for(cfg, rt)
        return AnakinR2D2(
            launch.R2D2Agent(cfg), num_envs=n, batch_size=rt.batch_size,
            capacity=int(traffic["capacity"]),
            target_sync_interval=rt.target_sync_interval,
            updates_per_collect=rt.updates_per_call,
            epsilon_floor=rt.epsilon_floor or 0.0, env=env,
            obs_transform=transform)
    from distributed_reinforcement_learning_tpu.envs.registry import make_jittable_env
    from distributed_reinforcement_learning_tpu.runtime.anakin_tokens import (
        AnakinTokens)

    env = make_jittable_env(rt.envs[0], vocab=cfg.vocab_size,
                            episode_len=cfg.trajectory,
                            distance=cfg.recall_distance)
    return AnakinTokens(launch._token_agent(cfg), n, env)


def digest(name: str, chip, dump: str | None) -> dict:
    import jax

    section, config, traffic = _cell(name)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "config.json")
        with open(path, "w") as f:
            json.dump(config, f)
        anakin = _build(path, section, traffic)
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        jax.eval_shape(anakin.init, jax.random.PRNGKey(0)))
    text = anakin.train_chunk.lower(state, int(traffic["chunk_updates"])).as_text()
    cut = BODY.sub('backend_config = ""', text)
    if dump:
        os.makedirs(dump, exist_ok=True)
        with open(os.path.join(dump, f"{name}.txt"), "w") as f:
            f.write(cut)
    return {"cell": name, "sha256": hashlib.sha256(cut.encode()).hexdigest(),
            "chars": len(cut), "chars_with_bodies": len(text),
            "tpu_custom_call": len(re.findall("tpu_custom_call", text)),
            "locations": len(re.findall(r"\bloc\(", cut))}


def main() -> None:
    cells = sorted(
        f[:-5] for f in os.listdir(os.path.join(ROOT, "perfbench", "workloads"))
        if "anakin" in f)
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--cell", nargs="+", default=cells, choices=cells)
    p.add_argument("--dump", default=None, help="write each cut text here")
    args = p.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import distributed_reinforcement_learning_tpu.ops.pallas as pallas_pkg

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    real = pallas_pkg.resolve_backend

    def as_on_chip(backend="auto", opt_in_env=None):
        with mock.patch.object(pallas_pkg.jax, "default_backend", lambda: "tpu"):
            return real(backend, opt_in_env)

    with mock.patch.object(pallas_pkg, "resolve_backend", as_on_chip):
        for name in args.cell:
            print(json.dumps(digest(name, chip, args.dump)), flush=True)


if __name__ == "__main__":
    main()

"""The precision control of `families/ssmoelm.py`'s limits: the plain
reference computed in bfloat16 THROUGHOUT (parameters, activations,
router, softmax, loss, and an optimizer step kept in bfloat16: the
nearest precision below the stated one) is put in the PROGRAM'S place in
comparison (a) and goes through the family's own `distances`, `within`,
`route_distances` and `routes_ok` against the float32 `highest` reference
on the sets the bfloat16 run chose. `ok` has to come out false, by one of
the limits and not by each.

Where it runs decides its size. On a TPU, the cell's: 2 x 2,048 tokens of
the `nemotron_h_moe` section, where the second reading of every limit comes
from (`JAX_PLATFORMS=tpu python -m pytest
perfbench/tests/test_nemotron_h_moe_control.py -s` through the chip tool; the
readings land in `chiprun_out/nemotron_h_moe_control.json`). On the CPU, the
faults test's small float32 size at the cell's learning rate, where a
step is under bfloat16's last bit and the others hide.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

import discover
from conftest import BENCH_DIR, ROOT
from distributed_reinforcement_learning_tpu.agents.ssmoelm import SSMoELMAgent

family = discover.module(BENCH_DIR, "families", "ssmoelm")
ON_CHIP = jax.default_backend() == "tpu"


def _cell():
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    path = os.path.join(ROOT, "config.json")
    with open(path) as f:
        section = json.load(f)["nemotron_h_moe"]
    return SSMoELMAgent(load_config(path, "nemotron_h_moe")[0]), section


def _small():
    from test_nemotron_h_moe_faults import CFG, SECTION

    return SSMoELMAgent(dataclasses.replace(CFG, start_learning_rate=1e-5)), SECTION


def control(agent, section: dict, seed: int) -> dict:
    """Comparison (a) with the bfloat16 reference where the program stands."""
    ref, hp = family.reference_module(), family.hyper(agent)
    theirs = ref.rekey(family.perturbed(
        jax.jit(agent.model.init)(jax.random.PRNGKey(seed % 2 ** 31)), seed),
        hp["layer_order"])
    nb = family.seeded_batch(section, family.REFERENCE_ROWS, seed)
    got, grads = family.reference_sums(ref, theirs, nb, hp, None, "bfloat16")
    got.update(routes=got["routing"]["routes"], route_scores=got["routing"]["picked"],
               stats_logp=got["logp"])
    # one optimizer step as bfloat16 parameters keep it, leaf by leaf
    scale = min(1.0, hp["gradient_clip_norm"] / max(got["grad_norm"], 1e-30))
    moved = 0.0
    for p, g in zip(jax.tree.leaves(theirs), grads):
        low = p.astype(jnp.bfloat16)
        stepped, _ = ref.rmsprop_leaf(low, 1.0, g * scale, ref.learning_rate(0, hp))
        moved += float(jnp.sum(jnp.square((stepped - low).astype(jnp.float32))))
    got["update_norm"] = moved ** 0.5
    del grads
    jax.clear_caches()
    want, grads = family.reference_sums(ref, theirs, nb, hp, got["routes"])
    _, _, want["update_norm"], _ = family.reference_step(
        ref, theirs, None, grads, hp, 0, want["grad_norm"], keep=False)
    dist = family.distances(got, want, nb["action"])
    routing = family.route_distances([want["routing"]])
    return {"ok": (family.looplm().within(dist, family.STATED)
                   and family.routes_ok(routing)),
            "refused_by": sorted(k for k in family.STATED
                                 if not dist[k] <= family.STATED[k]),
            "routes_ok": family.routes_ok(routing), "distance": dist,
            "routing": {k: v for k, v in routing.items() if k != "limits"}}


@pytest.mark.parametrize("seed", [2147483011, 3000000012])
def test_the_bfloat16_reference_in_the_programs_place_is_not_ok(seed):
    agent, section = _cell() if ON_CHIP else _small()
    got = control(agent, section, seed)
    print(f"[control] seed {seed} on {jax.default_backend()}: {json.dumps(got)}")
    if ON_CHIP:
        out = os.path.join(ROOT, "chiprun_out", "nemotron_h_moe_control.json")
        os.makedirs(os.path.dirname(out), exist_ok=True)
        said = json.load(open(out)) if os.path.exists(out) else {}
        said[str(seed)] = got
        with open(out, "w") as f:
            json.dump(said, f, indent=1)
    assert got["ok"] is False, got
    assert "update_norm" in got["refused_by"], got
    assert got["distance"]["update_norm"] > 0.5  # next to a state left unchanged
    if ON_CHIP:
        assert "logp_mean" in got["refused_by"], got

"""What every gated path of the host data plane does when nobody says.

The single statement of the system's run-time defaults: for each of the
seventeen knobs, (a) unset resolves to the default written out below,
(b) the knob forces its path on (or to N), (c) the knob forces it off
(or to 0) — each through the owning module's public resolver. The
expected values are literals here on purpose: a default that moves in
the code has to move in this table too.
"""

import platform

import pytest

from distributed_reinforcement_learning_tpu.data import admission, codec, device_path
from distributed_reinforcement_learning_tpu.runtime import (
    actor_pipeline,
    learner_tier,
    replay_shard,
    serving,
    shm_ring,
    weight_board,
    weight_shards,
)

# The seqlock's store-ordering argument holds on x86-64 only
# (runtime/weight_board.py), so the board defaults on there alone.
_X86_64 = platform.machine().lower() in ("x86_64", "amd64")

# knob, resolver, default, (forcing value, result), (forcing value, result)
GATES = [
    ("DRL_SHM_RING", shm_ring.ring_enabled, False, ("1", True), ("0", False)),
    ("DRL_SHM_WEIGHTS", weight_board.board_enabled, _X86_64,
     ("1", True), ("0", False)),
    ("DRL_CODEC_CACHE", codec.cache_enabled, False, ("1", True), ("0", False)),
    ("DRL_OBS_DEDUP", codec.obs_dedup_enabled, False,
     ("1", True), ("0", False)),
    ("DRL_WEIGHTS_SHARDED", weight_shards.sharded_enabled, False,
     ("1", True), ("0", False)),
    ("DRL_WEIGHTS_QUANT", weight_shards.quant_mode, None,
     ("1", "bf16"), ("0", None)),
    ("DRL_WEIGHTS_DELTA", weight_shards.delta_enabled, False,
     ("1", True), ("0", False)),
    ("DRL_REPLAY_SHARDS", replay_shard.shard_count, 2, ("3", 3), ("0", 0)),
    ("DRL_REPLAY_SPILL", replay_shard.spill_auto_enabled, True,
     ("1", True), ("0", False)),
    ("DRL_ACTOR_PRIORITY", admission.actor_priority_enabled, False,
     ("1", True), ("0", False)),
    ("DRL_ADMISSION", admission.admission_enabled, False,
     ("1", True), ("0", False)),
    ("DRL_DEVICE_PATH", device_path.device_path_enabled, False,
     ("1", True), ("0", False)),
    ("DRL_LEARNER_SEATS", learner_tier.seat_count, 0, ("4", 4), ("0", 0)),
    ("DRL_COLL_QUANT", learner_tier.coll_quant, "f32",
     ("1", "bf16"), ("0", "f32")),
    ("DRL_COLL_OVERLAP", learner_tier.coll_overlap, 0, ("1", 1), ("0", 0)),
    ("DRL_INFER_REPLICAS", serving.replica_count, 0, ("3", 3), ("0", 0)),
    ("DRL_ACTOR_PIPE", actor_pipeline.pipeline_enabled, False,
     ("1", True), ("0", False)),
]


def _cases():
    for knob, resolve, default, on, off in GATES:
        yield pytest.param(knob, resolve, None, default, id=f"{knob}-unset")
        yield pytest.param(knob, resolve, *on, id=f"{knob}={on[0]}")
        yield pytest.param(knob, resolve, *off, id=f"{knob}={off[0]}")


@pytest.mark.parametrize("knob,resolve,value,expected", _cases())
def test_gate_resolves(monkeypatch, knob, resolve, value, expected):
    if value is None:
        monkeypatch.delenv(knob, raising=False)
    else:
        monkeypatch.setenv(knob, value)
    got = resolve()
    assert got == expected and type(got) is type(expected), (knob, value, got)

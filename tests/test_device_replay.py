"""The stored form of the device ring (`data/device_replay.py`): byte
leaves whose entries are whole 32-bit words live as `WordRing`s
(`u32[capacity, rows, 128]`), every other leaf as it is; `ingest` and
`sample` keep the logical contract bit for bit against the numpy ring
of `reference/r2d2_atari.py`.

The last tests hold the fault of PERF.md's PRs 26-27 out: a uint8 pixel
ring that enters a chunk in its logical `[capacity, ...]` shape is laid
out capacity-innermost by the TPU compiler and walked whole by every
gather and write.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_reinforcement_learning_tpu.agents.r2d2 import R2D2Agent, R2D2Config
from distributed_reinforcement_learning_tpu.data import device_replay
from distributed_reinforcement_learning_tpu.data.device_replay import WordRing
from distributed_reinforcement_learning_tpu.parallel.mesh import make_mesh
from distributed_reinforcement_learning_tpu.reference import r2d2_atari as ref
from distributed_reinforcement_learning_tpu.runtime.anakin_r2d2 import AnakinR2D2

ENTRY = jax.ShapeDtypeStruct
CONFIG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "config.json")

# (entry shape, rows of 128 words it is stored in, or None = bypass)
BYTE_ENTRIES = [
    pytest.param((3, 84, 84, 4), 168, id="sequence_T_84_84_4"),
    pytest.param((84, 84, 4), 56, id="stack_84_84_4"),
    pytest.param((5, 8), 8, id="last_dim_not_4"),
    pytest.param((3, 5), None, id="odd_15_bytes_bypasses"),
]


def _bytes(seed, *shape):
    return np.random.RandomState(seed).randint(0, 256, shape).astype(np.uint8)


@pytest.mark.parametrize("entry, rows", BYTE_ENTRIES)
def test_pack_then_unpack_is_the_identity(entry, rows):
    assert device_replay._word_rows(entry, jnp.uint8) == rows
    if rows is None:
        return  # nothing to pack: the leaf stays uint8 (tested below)
    x = _bytes(0, 6, *entry)
    words = device_replay.pack(jnp.asarray(x), rows)
    assert (words.dtype, words.shape) == (jnp.uint32, (6, rows, 128))
    # Byte 4k+j of an entry is bits 8j.. of its word k; the tail is 0.
    flat = np.asarray(words).reshape(6, -1)
    n = x[0].size // 4
    np.testing.assert_array_equal(
        flat[:, :n], x.reshape(6, n, 4).view("<u4")[..., 0])
    assert not flat[:, n:].any()
    back = device_replay.unpack(words, entry)
    assert back.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(back), x)


@pytest.mark.parametrize("entry, rows", BYTE_ENTRIES)
def test_ingest_then_sample_is_the_numpy_ring_across_a_wrap(entry, rows):
    capacity, width = 12, 4
    entries = {"state": ENTRY(entry, jnp.uint8), "reward": ENTRY((3,), jnp.float32),
               "action": ENTRY((3,), jnp.int32), "done": ENTRY((3,), jnp.bool_)}
    replay = device_replay.make(entries, capacity)
    assert isinstance(replay.storage["state"], WordRing) == (rows is not None)
    storage = {k: np.zeros((capacity, *e.shape), e.dtype) for k, e in entries.items()}
    pri, ptr, size = np.zeros(capacity), 0, 0
    ingest = jax.jit(device_replay.ingest)
    r = np.random.RandomState(3)
    for step in range(4):  # the fourth write wraps `ptr` onto the first
        new = {"state": _bytes(step, width, *entry),
               "reward": r.normal(size=(width, 3)).astype(np.float32),
               "action": r.randint(0, 18, (width, 3)).astype(np.int32),
               "done": r.rand(width, 3) < 0.3}
        errs = np.abs(r.normal(size=width)).astype(np.float32)
        storage, pri, ptr, size = ref.ring_write(storage, pri, ptr, size, new, errs)
        replay = ingest(replay, jax.tree.map(jnp.asarray, new), jnp.asarray(errs))
        assert (int(replay.ptr), int(replay.size)) == (ptr, size)
        _, batch, idx, _ = jax.jit(device_replay.sample, static_argnums=2)(
            replay, jax.random.PRNGKey(step), 8)
        idx = np.asarray(idx)
        assert (np.diff(idx) >= 0).all() and idx.max() < size
        for k, ring in storage.items():
            assert batch[k].dtype == ring.dtype
            np.testing.assert_array_equal(np.asarray(batch[k]), ring[idx])
    assert (ptr, size) == (4, 12)


@pytest.mark.parametrize("entry, dtype", [
    ((120,), jnp.float32), ((120,), jnp.int32), ((120,), jnp.bool_),
    ((512,), jnp.float32), ((4,), jnp.float32), ((), jnp.int32),
    ((3, 5), jnp.uint8), ((8,), jnp.int8)])
def test_other_leaves_are_stored_as_they_are(entry, dtype):
    replay = device_replay.make({"x": ENTRY(entry, dtype)}, 16)
    ring = replay.storage["x"]
    assert (ring.shape, ring.dtype) == ((16, *entry), dtype)
    assert not np.asarray(ring).any()


def test_every_stored_leaf_keeps_capacity_on_axis_0():
    an = _byte_anakin()
    state = an.init(jax.random.PRNGKey(0))
    assert isinstance(state.replay.storage.state, WordRing)
    assert state.replay.storage.state.entry_shape == (6, 4)
    for leaf in jax.tree.leaves(state.replay.storage):
        assert leaf.shape[0] == an.capacity
    assert state.replay.priorities.shape == (an.capacity,)


def test_the_report_names_the_word_leaves_and_their_share():
    C, T = 32, 5
    replay = device_replay.make(
        {"state": ENTRY((T, 84, 84, 4), jnp.uint8),
         "next_state": ENTRY((84, 84, 4), jnp.uint8),
         "reward": ENTRY((T,), jnp.float32), "odd": ENTRY((3, 5), jnp.uint8)}, C)
    report = device_replay.stored_as_words(replay)
    assert report["leaves"] == ["next_state", "state"]
    # Whole tiles: 5 x 7,056 words in 280 rows of 128, 7,056 in 56.
    assert report["word_bytes"] == C * (280 + 56) * 128 * 4
    assert report["ring_bytes"] == report["word_bytes"] + C * (T * 4 + 15)
    plain = device_replay.make({"x": ENTRY((4,), jnp.float32)}, C)
    assert device_replay.stored_as_words(plain) == {
        "leaves": [], "word_bytes": 0, "ring_bytes": C * 16}
    assert "no leaf (0.0 %" in device_replay.describe_storage(plain)


def test_the_report_at_the_r2d2_atari_sizes():
    """Shapes only (`eval_shape`): 2,048 sequences of 120 stacks are one
    word leaf, 99.8 % of the ring's 6.95 GB, and never a uint8 array."""
    from distributed_reinforcement_learning_tpu.envs import breakout_jax
    from distributed_reinforcement_learning_tpu.utils.config import load_config

    cfg, rt = load_config(CONFIG, "r2d2_atari")
    an = AnakinR2D2(R2D2Agent(cfg), num_envs=256, batch_size=rt.batch_size,
                    capacity=rt.replay_capacity, env=breakout_jax)
    entries = an._sequence_entries()
    assert entries.state == ENTRY((120, 84, 84, 4), jnp.uint8)
    replay = jax.eval_shape(lambda: device_replay.make(entries, 2048))
    words = replay.storage.state.words
    assert (words.shape, words.dtype) == ((2048, 6616, 128), jnp.uint32)
    report = device_replay.stored_as_words(replay)
    assert report["leaves"] == ["state"]
    assert report["word_bytes"] == 2048 * 6616 * 128 * 4
    assert device_replay.describe_storage(replay) == (
        "replay ring 6.95 GB, stored as 32-bit words: state "
        "(99.8 % of its bytes)")


# -- a tiny fused loop whose observations are bytes ---------------------------


def _to_bytes(obs):
    """CartPole's four floats as four bytes: a 4-byte "pixel" a step."""
    return jnp.clip(obs * 32.0 + 128.0, 0, 255).astype(jnp.uint8)


def _byte_anakin(capacity=24, **kw):
    cfg = R2D2Config(obs_shape=(4,), num_actions=2, seq_len=6, burn_in=2,
                     lstm_size=16, learning_rate=1e-3, n_step=3,
                     dueling_hidden=8, priority_eta=0.9)
    kw.setdefault("num_envs", 4)
    kw.setdefault("batch_size", 2)
    return AnakinR2D2(R2D2Agent(cfg), capacity=capacity,
                      obs_transform=_to_bytes, updates_per_collect=2, **kw)


@pytest.mark.parametrize("chunk", ["collect_chunk", "train_chunk"])
def test_the_ring_enters_and_leaves_a_chunk_as_words(chunk):
    """From the StableHLO of the lowered chunk: no uint8 tensor whose
    leading dimension is the capacity exists anywhere in it (so none is
    a parameter, a result or a gather operand); the ring is the one
    `ui32[capacity, rows, 128]` argument, and it is aliased to a result."""
    an = _byte_anakin(capacity=24)  # 24: no other dimension of the program
    state = jax.eval_shape(an.init, jax.random.PRNGKey(0))
    text = getattr(an, chunk).lower(state, 2).as_text()
    assert not re.findall(r"tensor<24x[0-9x]*xui8>", text)
    main = text[text.index("func.func public @main"):]
    signature = main[:main.index("\n")]
    ring = re.findall(r"%arg\d+: tensor<24x8x128xui32> \{([^}]*)\}", signature)
    assert len(ring) == 1 and "tf.aliasing_output" in ring[0]
    # What is sampled and learned from is uint8 in its logical shape.
    if chunk == "train_chunk":
        assert "tensor<2x6x4xui8>" in text


def test_word_ring_shards_over_the_data_axis_of_a_mesh():
    """Per-device shards: `P(data)` on axis 0 of the word leaf, as on
    every other leaf, with no change to `anakin_mesh.replay_specs`."""
    mesh = make_mesh(8)
    an = _byte_anakin(capacity=64, num_envs=8, batch_size=8, mesh=mesh)
    state = an.init(jax.random.PRNGKey(0))
    words = state.replay.storage.state.words
    assert words.shape == (64, 8, 128)
    assert {s.data.shape for s in words.addressable_shards} == {(8, 8, 128)}
    state, _ = an.collect_chunk(state, 3)
    state, metrics = an.train_chunk(state, 2)
    assert np.isfinite(np.asarray(metrics["loss"])).all()
    assert float(metrics["replay_size"][-1]) == 5 * 8
    held = np.asarray(state.replay.storage.state.words)
    assert held[:, 0, :6].any() and not held[:, 0, 6:].any()  # 24 B = 6 words

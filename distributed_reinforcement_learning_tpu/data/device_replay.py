"""Prioritized replay as a device-resident ring (shared machinery).

The host topology keeps replay on the host (`data/replay.py` SumTree —
the re-design of `distributed_queue/buffer_queue.py:256-346`); the
Anakin runtimes keep it in device memory so sampling happens INSIDE the
compiled program. This module is the storage-agnostic core used by both
on-device replay families (`runtime/anakin_r2d2.py` sequences,
`runtime/anakin_apex.py` transitions): `storage` is any pytree whose
leaves are `[capacity, ...]` rings.

The stored form of a leaf follows from what its entries are. A leaf of
uint8 entries whose bytes are a multiple of 4 (pixel stacks) is kept as
a `WordRing`: `u32[capacity, rows, 128]`, four bytes to a 32-bit word,
each entry one run of whole (8, 128) tiles. The TPU packs uint8 four
ROWS to a sublane word and lays a ring like `u8[C, T, 84, 84, 4]` out
with the capacity dimension innermost, so that a gather of 64 entries
or a write of 256 walks every tile of the ring (PERF.md, PRs 26-27); as
words an entry is its own contiguous slab, the write is one
`dynamic_update_slice` of slabs and the gather one slab copy per drawn
slot. `ingest` packs, `sample` unpacks: callers see the logical shapes
and dtypes only. Every other leaf (int32 actions, float32 rewards,
LSTM states and vector observations, bool dones) is a plain array.

Math parity with `data/replay.py`: priority `(|err| + 0.001) ** 0.6`,
stratified sampling over `total/n` segments, IS weights `(N * p) **
-beta` batch-max-normalized, beta annealed 0.4 -> 1.0 by 0.001 per
sample. Writes are `write_width`-aligned (capacity must be a multiple),
overwriting oldest entries FIFO like the SumTree's write pointer.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

PER_EPS = 0.001
PER_ALPHA = 0.6
BETA0 = 0.4
BETA_INCREMENT = 0.001


_LANES = 128  # 32-bit words in a row of a tile
_SUBLANES = 8  # rows of a tile: an entry is padded to whole tiles


@jax.tree_util.register_pytree_node_class
class WordRing:
    """A ring of uint8 entries stored as words: `words` is
    `u32[capacity, rows, 128]` (the one array leaf), `entry_shape` the
    logical per-entry shape (static)."""

    def __init__(self, words: jax.Array, entry_shape: tuple[int, ...]):
        self.words = words
        self.entry_shape = tuple(entry_shape)

    def tree_flatten(self):
        return (self.words,), self.entry_shape

    @classmethod
    def tree_unflatten(cls, entry_shape, children):
        return cls(children[0], entry_shape)


def _is_word_ring(x) -> bool:
    return isinstance(x, WordRing)


def _word_rows(entry_shape, dtype) -> int | None:
    """Rows of 128 words an entry is stored in, or None for a leaf that
    stays as it is: only whole words of bytes are packed."""
    nbytes = math.prod(entry_shape)
    if jnp.dtype(dtype) != jnp.uint8 or nbytes == 0 or nbytes % 4:
        return None
    rows = -(-nbytes // (4 * _LANES))
    return -(-rows // _SUBLANES) * _SUBLANES


def pack(x: jax.Array, rows: int) -> jax.Array:
    """`u8[n, *entry]` -> `u32[n, rows, 128]`: byte `4 k + j` of an entry
    is bits `8 j ..` of its word `k`; the tail of the last tile is 0."""
    n = x.shape[0]
    words = jax.lax.bitcast_convert_type(x.reshape(n, -1, 4), jnp.uint32)
    words = jnp.pad(words, ((0, 0), (0, rows * _LANES - words.shape[1])))
    return words.reshape(n, rows, _LANES)


def unpack(words: jax.Array, entry_shape: tuple[int, ...]) -> jax.Array:
    """Inverse of `pack`: `u32[n, rows, 128]` -> `u8[n, *entry]`. Whole
    rows of padding go first: a slice of the flat words is a pass."""
    n = words.shape[0]
    nwords = math.prod(entry_shape) // 4
    flat = words[:, :-(-nwords // _LANES)].reshape(n, -1)[:, :nwords]
    return jax.lax.bitcast_convert_type(flat, jnp.uint8).reshape(
        n, *entry_shape)


class DeviceReplay(NamedTuple):
    storage: Any  # pytree of [capacity, ...] rings (arrays and WordRings)
    priorities: jax.Array  # [capacity] f32, alpha-transformed; 0 = empty
    ptr: jax.Array  # i32 next write slot (write_width-aligned)
    size: jax.Array  # i32 filled count
    beta: jax.Array  # f32 annealed IS exponent


def priority(err: jax.Array) -> jax.Array:
    """`(|err| + eps) ** alpha` (`data/replay.py` PrioritizedReplay)."""
    return jnp.power(jnp.abs(err) + PER_EPS, PER_ALPHA)


def make(entries: Any, capacity: int) -> DeviceReplay:
    """An empty ring of `capacity` entries. `entries` is a pytree of
    `jax.ShapeDtypeStruct`, the shape and dtype of ONE entry of each
    leaf: the stored zeros are built from them, so a byte leaf never
    exists in its logical `[capacity, ...]` form."""

    def zeros(entry):
        rows = _word_rows(entry.shape, entry.dtype)
        if rows is None:
            return jnp.zeros((capacity, *entry.shape), entry.dtype)
        return WordRing(jnp.zeros((capacity, rows, _LANES), jnp.uint32),
                        entry.shape)

    return DeviceReplay(
        storage=jax.tree.map(zeros, entries),
        priorities=jnp.zeros((capacity,), jnp.float32),
        ptr=jnp.int32(0),
        size=jnp.int32(0),
        beta=jnp.float32(BETA0),
    )


def ingest(replay: DeviceReplay, batch: Any, errs: jax.Array) -> DeviceReplay:
    """Write `W` new entries (the leading dim of `batch`'s leaves) at
    `ptr` with priorities from raw errors `errs [W]`. Capacity is the
    ring's own (priorities.shape[0], static under jit) — never passed,
    so it cannot disagree with the arrays."""
    capacity = replay.priorities.shape[0]
    width = errs.shape[0]

    def write(ring, new):
        if _is_word_ring(ring):
            return WordRing(write(ring.words, pack(new, ring.words.shape[1])),
                            ring.entry_shape)
        return jax.lax.dynamic_update_slice(
            ring, new.astype(ring.dtype),
            (replay.ptr,) + (0,) * (ring.ndim - 1))

    storage = jax.tree.map(write, replay.storage, batch,
                           is_leaf=_is_word_ring)
    priorities = jax.lax.dynamic_update_slice(
        replay.priorities, priority(errs), (replay.ptr,))
    return replay._replace(
        storage=storage,
        priorities=priorities,
        ptr=(replay.ptr + width) % capacity,
        size=jnp.minimum(replay.size + width, capacity),
    )


def sample(replay: DeviceReplay, rng: jax.Array, n: int,
           axis_name: str | None = None):
    """-> (replay', batch, idx [n], is_weights [n]). Stratified over
    `total/n` segments; empty slots carry zero priority and are never
    drawn (the ring must hold at least one entry).

    `axis_name`: set by shard_map callers holding PER-DEVICE replay
    shards (the Anakin mesh runtimes). Sampling stays local — each shard
    stratifies over its own priorities with its own size N, the correct
    IS weight for the per-shard sampler — but the batch-max
    normalization runs over the GLOBAL batch (pmax over the axis) so the
    weight scale matches the single-device semantics."""
    capacity = replay.priorities.shape[0]
    p = replay.priorities
    cum = jnp.cumsum(p)
    total = cum[-1]
    seg = total / n
    u = (jnp.arange(n, dtype=jnp.float32) + jax.random.uniform(rng, (n,))) * seg
    idx = jnp.clip(jnp.searchsorted(cum, u, side="right"), 0, capacity - 1)
    probs = p[idx] / total
    weights = jnp.power(replay.size.astype(jnp.float32) * probs, -replay.beta)
    wmax = jnp.max(weights)
    if axis_name is not None:
        wmax = jax.lax.pmax(wmax, axis_name)
    weights = weights / wmax

    def gather(ring):
        if not _is_word_ring(ring):
            return ring[idx]
        # One slab copy per drawn slot: a general gather of the word
        # table compiles to passes over the whole ring.
        slabs = jax.lax.map(
            lambda i: jax.lax.dynamic_index_in_dim(ring.words, i,
                                                   keepdims=False), idx)
        return unpack(slabs, ring.entry_shape)

    batch = jax.tree.map(gather, replay.storage, is_leaf=_is_word_ring)
    new_replay = replay._replace(
        beta=jnp.minimum(1.0, replay.beta + BETA_INCREMENT))
    return new_replay, batch, idx, weights.astype(jnp.float32)


def update_priorities(replay: DeviceReplay, idx: jax.Array,
                      errs: jax.Array) -> DeviceReplay:
    """Refresh every sampled priority (the `update_batch` fix of
    `train_r2d2.py:159`)."""
    return replay._replace(
        priorities=replay.priorities.at[idx].set(priority(errs)))


def stored_as_words(replay: DeviceReplay) -> dict:
    """Which leaves of the ring are stored as 32-bit words (static: it
    reads shapes only) -> {"leaves": their names, "word_bytes" and
    "ring_bytes": stored bytes of those and of every leaf}."""
    leaves, word_bytes, ring_bytes = [], 0, 0
    flat, _ = jax.tree_util.tree_flatten_with_path(
        replay.storage, is_leaf=_is_word_ring)
    for path, ring in flat:
        array = ring.words if _is_word_ring(ring) else ring
        nbytes = math.prod(array.shape) * jnp.dtype(array.dtype).itemsize
        ring_bytes += nbytes
        if _is_word_ring(ring):
            leaves.append(jax.tree_util.keystr(path, simple=True,
                                               separator="."))
            word_bytes += nbytes
    return {"leaves": leaves, "word_bytes": word_bytes,
            "ring_bytes": ring_bytes}


def describe_storage(replay: DeviceReplay) -> str:
    """`stored_as_words` as the launchers' start-up line."""
    report = stored_as_words(replay)
    share = 100.0 * report["word_bytes"] / report["ring_bytes"]
    return (f"replay ring {report['ring_bytes'] / 1e9:.2f} GB, stored as "
            f"32-bit words: {', '.join(report['leaves']) or 'no leaf'} "
            f"({share:.1f} % of its bytes)")

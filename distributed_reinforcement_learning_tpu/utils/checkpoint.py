"""Checkpoint/resume: params + optimizer state + step, actually wired in.

The reference constructs `tf.train.Saver`s but never calls them from any
training loop (`agent/impala.py:103,105-109`, `agent/apex.py:80`; R2D2 has
none — SURVEY §5.4), so a crashed learner loses everything. Here
checkpointing is a first-class subsystem:

- the serialized unit is the learner's whole `TrainState` pytree (params,
  optimizer moments, device step counter) via flax msgpack serialization,
  plus a JSON sidecar of host-side counters (train steps, replay beta, ...),
- writes are atomic (tmp file + `os.replace`), the payload file is the
  commit marker, and the newest `retain` checkpoints are kept,
- learners expose `save_checkpoint`/`restore_checkpoint`; the multi-process
  entrypoint (`runtime/transport.run_role`) saves on an interval and
  restores on startup, which is the learner half of crash recovery
  (actors already reconnect through the transport layer).
"""

from __future__ import annotations

import json
import os
import pickle
import re
import sys
import tempfile
from pathlib import Path
from typing import Any

from flax import serialization

from distributed_reinforcement_learning_tpu.utils.environ import env_flag, env_float

_CKPT_RE = re.compile(r"^ckpt_(\d{10})\.msgpack$")


def encode_replay_snapshot(replay) -> bytes | None:
    """Pickle a replay buffer's `snapshot()` for checkpointing, or None.

    SURVEY §5.4's optional replay snapshot: without it a restarted
    Ape-X/R2D2 learner resumes with an empty Memory. Disabled with
    `DRL_CKPT_REPLAY=0`; skipped (with a log line) above
    `DRL_CKPT_REPLAY_MAX_MB` (default 512) because a full Atari replay at
    capacity 1e5 is ~5 GB and would dominate every checkpoint write.
    """
    if not env_flag("DRL_CKPT_REPLAY", True):
        return None
    cap_mb = env_float("DRL_CKPT_REPLAY_MAX_MB", 512.0)

    def over_cap(nbytes: int) -> bool:
        if nbytes > cap_mb * 1e6:
            print(f"[checkpoint] replay snapshot {nbytes / 1e6:.0f} MB exceeds "
                  f"DRL_CKPT_REPLAY_MAX_MB={cap_mb:.0f}; skipping (set higher "
                  f"to keep it)", file=sys.stderr)
            return True
        return False

    # The SoA backend can price its snapshot without materializing it —
    # reject an over-cap replay BEFORE copying ~GBs under its lock.
    estimate = getattr(replay, "approx_snapshot_nbytes", None)
    if estimate is not None and over_cap(estimate()):
        return None
    snap = replay.snapshot()
    payload = snap.get("items", snap.get("stacked"))  # list vs SoA backend
    nbytes = sum(
        x.nbytes for x in _iter_array_leaves(payload)
    ) + snap["priorities"].nbytes
    if over_cap(nbytes):
        return None
    return pickle.dumps(snap, protocol=pickle.HIGHEST_PROTOCOL)


def decode_replay_snapshot(data: bytes) -> dict:
    return pickle.loads(data)


def _iter_array_leaves(tree):
    if hasattr(tree, "nbytes"):
        yield tree
    elif isinstance(tree, dict):
        for v in tree.values():
            yield from _iter_array_leaves(v)
    elif isinstance(tree, (list, tuple)):
        for v in tree:
            yield from _iter_array_leaves(v)


def _atomic_write(path: Path, data: bytes) -> None:
    fd, tmp = tempfile.mkstemp(dir=path.parent, prefix=path.name, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
            # fsync before replace: os.replace is atomic in the namespace
            # but not on disk — without the flush a power loss can commit
            # a truncated payload under the final name.
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        dirfd = os.open(path.parent, os.O_RDONLY)
        try:
            os.fsync(dirfd)
        finally:
            os.close(dirfd)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


class Checkpointer:
    """Step-numbered, atomic, retain-N checkpoint store on a directory.

    Layout: `ckpt_{step:010d}.msgpack` (the TrainState, written last =
    commit marker) and `ckpt_{step:010d}.extra.json` (host counters,
    written first). A checkpoint is visible only once its msgpack exists.
    """

    def __init__(self, directory: str | Path, retain: int = 3):
        self.directory = Path(directory)
        self.directory.mkdir(parents=True, exist_ok=True)
        self.retain = retain
        # Sweep tmp files orphaned by a hard kill (SIGKILL/OOM between
        # mkstemp and os.replace) — nothing else ever deletes them, and a
        # crash-looping learner would otherwise accumulate one
        # TrainState-sized blob per crash.
        for stale in self.directory.glob("*.tmp"):
            try:
                stale.unlink()
            except OSError:
                pass
        # Sweep sidecars (extra.json, auxiliary blobs) without a committed
        # payload: save() writes them before the msgpack (the msgpack is
        # the commit marker), so a crash between the writes leaves orphans
        # that _prune — which iterates committed steps only — would never
        # delete.
        for side in list(self.directory.glob("ckpt_*.extra.json")) + list(
            self.directory.glob("ckpt_*.blob.*")
        ):
            m = re.match(r"^ckpt_(\d{10})\.", side.name)
            if m and not self._payload_path(int(m.group(1))).exists():
                try:
                    side.unlink()
                except OSError:
                    pass

    def _payload_path(self, step: int) -> Path:
        return self.directory / f"ckpt_{step:010d}.msgpack"

    def _extra_path(self, step: int) -> Path:
        return self.directory / f"ckpt_{step:010d}.extra.json"

    def _blob_path(self, step: int, name: str) -> Path:
        return self.directory / f"ckpt_{step:010d}.blob.{name}"

    def steps(self) -> list[int]:
        """Committed checkpoint steps, ascending."""
        out = []
        for p in self.directory.iterdir():
            m = _CKPT_RE.match(p.name)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.steps()
        return steps[-1] if steps else None

    def save(
        self,
        step: int,
        state: Any,
        extra: dict | None = None,
        blobs: dict[str, bytes] | None = None,
    ) -> Path:
        """Persist `state` (+ host `extra`, + named auxiliary `blobs` such
        as a replay-buffer snapshot) as checkpoint `step`. Sidecars are
        written first; the msgpack payload is the commit marker."""
        _atomic_write(self._extra_path(step), json.dumps(extra or {}).encode())
        for name, data in (blobs or {}).items():
            _atomic_write(self._blob_path(step, name), data)
        path = self._payload_path(step)
        _atomic_write(path, serialization.to_bytes(state))
        self._prune()
        return path

    def load_blob(self, step: int, name: str) -> bytes | None:
        path = self._blob_path(step, name)
        return path.read_bytes() if path.exists() else None

    def restore(self, template: Any, step: int | None = None) -> tuple[Any, dict, int] | None:
        """-> (state, extra, step) for `step` (default latest), or None.

        `template` must be a pytree with the same structure as the saved
        state (a freshly-initialized TrainState); flax deserializes into it.
        """
        if step is None:
            step = self.latest_step()
            if step is None:
                return None
        payload = self._payload_path(step)
        if not payload.exists():
            return None
        data = payload.read_bytes()
        try:
            state = serialization.from_bytes(template, data)
        except (ValueError, KeyError) as e:
            # Layout migration: pre-r3 image models nested conv params as
            # nn.Conv's `Conv_{i}/{kernel,bias}`; the explicit NatureConv
            # layout (models/torso.py) flattens them. Retry the restore
            # through the upgrade map before giving up — chained to the
            # original error so a genuinely corrupt checkpoint surfaces
            # both failures, not just the retry's.
            from distributed_reinforcement_learning_tpu.models.torso import (
                upgrade_nature_conv_params)

            try:
                raw = upgrade_nature_conv_params(serialization.msgpack_restore(data))
                state = serialization.from_state_dict(template, raw)
            except Exception as retry_err:
                raise retry_err from e
        extra_path = self._extra_path(step)
        extra = json.loads(extra_path.read_text()) if extra_path.exists() else {}
        return state, extra, step

    def _prune(self) -> None:
        for step in self.steps()[: -self.retain]:
            sides = list(self.directory.glob(f"ckpt_{step:010d}.blob.*"))
            for p in (self._payload_path(step), self._extra_path(step), *sides):
                try:
                    p.unlink()
                except FileNotFoundError:
                    pass

"""How a `DRL_*` environment knob is read: one grammar for the package.

Unset or empty means the caller's default. A value the grammar does not
accept raises `ValueError` naming the knob and what it takes, so a typo
(`DRL_REPLAY_SPILL=of`) never silently selects a path.
"""

from __future__ import annotations

import os

_ON = ("1", "true", "yes", "on")
_OFF = ("0", "false", "no", "off")


def env_flag(name: str, default: bool) -> bool:
    """An on/off knob: `1|true|yes|on` / `0|false|no|off`, any case."""
    raw = os.environ.get(name, "").strip().lower()
    if not raw:
        return default
    if raw in _ON:
        return True
    if raw in _OFF:
        return False
    raise ValueError(f"{name} must be one of {'|'.join(_ON)} or "
                     f"{'|'.join(_OFF)}, got {raw!r}")


def env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError as e:
        raise ValueError(f"{name} must be an integer, got {raw!r}") from e


def env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError as e:
        raise ValueError(f"{name} must be a number, got {raw!r}") from e

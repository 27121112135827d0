"""Operations and bytes from shapes: what the algorithm needs, not what
a compiler reports. Kept with the benchmark so that no PR that claims a
gain can change the numerator of a utilization.

A multiply-add counts as 2 operations. A backward pass costs twice its
forward pass (one product for the input gradient, one for the weight
gradient), so a trained frame costs 3 forwards; a target network's
forward costs 1. Recomputed operations (remat) do not count.

The pieces every network here is made of; what one family's network or
one torso adds up to is in `families/<algorithm>.py` and
`torsos/<torso>.py`, found by name.
"""

from __future__ import annotations

import discover

ACTION_EMBED_WIDTH = 256  # models/torso.py ActionEmbedding: two Dense(256)


def torso_macs(data_dir: str, section: dict) -> tuple[int, int]:
    """(multiply-adds for one frame, features out) of the section's
    torso, from `torsos/<name>.py`: the section's `torso` key for pixel
    observations (the program's default is the Nature stack), `mlp` for
    vectors."""
    pixels = len(section["model_input"]) == 3
    name = section.get("torso", "nature") if pixels else "mlp"
    return discover.module(data_dir, "torsos", name).macs(section)


def embed_macs(num_actions: int) -> int:
    return num_actions * ACTION_EMBED_WIDTH + ACTION_EMBED_WIDTH ** 2


def lstm_macs(features: int, hidden: int) -> int:
    return (features + hidden) * 4 * hidden


def vtrace_kernel_cost(t_steps: int, batch: int) -> dict:
    """One `vtrace_pallas` call on `[T, B]` float32 (ops/pallas/vtrace.py):
    reads log_rhos, discounts, rewards, values `[T,B]` and bootstrap
    `[B]`; writes vs and clipped rhos `[T,B]`. Per element: exp, two
    min, the delta (mul, add, sub, mul), the recursion (mul, mul, add)
    and the final add: 11 operations."""
    n = t_steps * batch
    return {"flops": 11 * n, "bytes": 4 * (4 * n + batch + 2 * n)}


def roofline_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """(least seconds the chip could take, which bound it is)."""
    by_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (by_bytes, "memory")

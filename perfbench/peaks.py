"""Published per-chip peaks, keyed by a substring of JAX's `device_kind`.

Copied from `bench.py` `_DEVICE_PEAKS` (PR 21) so that a later change to
that file cannot move a utilization figure. A device that is not here is
an error, not a default: add its row with its source.
"""

from __future__ import annotations

DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16 (393 is
    # the chip's int8 TOP/s), 16 GB of HBM at 819 GB/s. The attached
    # chip reports device_kind "TPU v5 lite".
    "v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "v5e": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def device_peaks(device_kind: str) -> dict:
    kind = device_kind.lower()
    for key, peaks in DEVICE_PEAKS.items():
        if key in kind:
            return peaks
    raise KeyError(
        f"no published peaks for device_kind {device_kind!r}: add a row "
        f"to perfbench/peaks.py DEVICE_PEAKS with its source")

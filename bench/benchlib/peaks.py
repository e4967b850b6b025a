"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
A kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

from typing import Dict, NamedTuple


class Peak(NamedTuple):
    bf16_flops: float        # FLOP/s per chip
    hbm_bytes: float         # bytes/s per chip
    hbm_capacity: float      # bytes per chip
    source: str


_V5E = Peak(197e12, 819e9, 16e9, 'Google Cloud documentation, "TPU v5e"')

PEAKS: Dict[str, Peak] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

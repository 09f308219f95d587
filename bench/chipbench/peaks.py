"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.  A
device missing from the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict, NamedTuple


class Peak(NamedTuple):
    flops_per_s: float      # dense bf16 MXU peak
    hbm_bytes_per_s: float  # HBM bandwidth
    hbm_bytes: float        # HBM capacity
    source: str


PEAKS: Dict[str, Peak] = {
    "TPU v5 lite": Peak(197e12, 819e9, 16e9,
                        'Google Cloud documentation, "TPU v5e"'),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

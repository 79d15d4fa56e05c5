"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error: a share of a peak is never
computed against a guessed default.
"""
from __future__ import annotations

from typing import Dict

#: device_kind -> peaks.  Source: Google Cloud documentation, "TPU v5e"
#: (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


class UnknownDevice(KeyError):
    """The device has no entry in :data:`PEAKS`."""


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None

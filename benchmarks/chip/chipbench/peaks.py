"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

A device that is not in the table is an error, never a default: a
roofline share against the wrong peak is a wrong number."""
from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
# int8, 16 GB HBM at 819 GB/s per chip.  JAX names the chip "TPU v5 lite".
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}
SOURCE = "Google Cloud documentation, TPU v5e"


class UnknownDevice(KeyError):
    """The device kind has no published peaks in ``PEAKS``."""


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None

"""Published peak rates of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (one v5e chip: 16 GB of HBM
at 819 GB/s, 197 TFLOP/s bf16, 393 TOP/s int8). JAX names that chip
"TPU v5 lite". A device that is not in the table is an error, never a
default: a roofline share against a guessed peak is no measurement.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    hbm_bytes_per_s: float
    hbm_bytes: float
    bf16_flops_per_s: float
    int8_ops_per_s: float


PEAKS = {
    "TPU v5 lite": ChipPeaks(hbm_bytes_per_s=819e9, hbm_bytes=16e9,
                             bf16_flops_per_s=197e12, int8_ops_per_s=393e12),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; raises ``KeyError`` for any other."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

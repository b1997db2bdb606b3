"""Published per-chip peaks, keyed by ``device_kind``.  A kind that is not
in the table is an error, never a default."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class DevicePeaks:
    flops: float  # bf16 FLOP/s
    hbm_bw: float  # HBM bytes/s
    hbm_bytes: float  # HBM capacity
    source: str


DEVICE_PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "16 GB HBM at 819 GB/s",
    ),
}


def peaks_for(device_kind: str) -> DevicePeaks:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to DEVICE_PEAKS with its "
                       f"source") from None

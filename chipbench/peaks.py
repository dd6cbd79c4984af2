"""Published peaks of the chips the benchmark runs on, keyed by JAX's
`device_kind`. Every roofline share and `mfu` metric divides by these.

A kind that is not in the table is an error: a share computed against a
guessed peak would read as a measurement.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float     # dense bf16 matrix FLOP/s
    hbm_bytes_s: float    # HBM bandwidth, bytes/s
    hbm_bytes: float      # HBM capacity, bytes
    ici_bits_s: float     # chip-to-chip interconnect, bits/s per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9,
        ici_bits_s=1600e9,
        source="Google Cloud documentation, 'TPU v5e' system architecture "
               "page: 197 TFLOP/s bf16, 16 GB HBM2 at 819 GB/s, "
               "1,600 Gbps ICI"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it "
            f"to chipbench/peaks.py with its source (known: "
            f"{sorted(PEAKS)})") from None


def roofline_s(flops: float, nbytes: float, peaks: Peaks) -> float:
    """The least time the chip could take for this work."""
    return max(flops / peaks.flops_bf16, nbytes / peaks.hbm_bytes_s)

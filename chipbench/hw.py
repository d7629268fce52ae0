"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (``device_kind`` "TPU v5 lite"):
197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s, and
1,600 Gbit/s of chip-to-chip interconnect per chip.

The models run float32 parameters at JAX's default matmul precision, which the
MXU executes as single bf16 passes with float32 accumulation, so the bf16 peak
is the one every utilization divides by.

This table is the benchmark's own copy, so that a change to the program's
hardware table cannot move the yardstick.  A kind that is not here is an
error, never a default.
"""
from __future__ import annotations

import dataclasses

__all__ = ["Peaks", "PEAKS", "peaks"]


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float  # bf16 FLOP/s per chip
    hbm_bytes_s: float  # HBM bytes/s per chip
    ici_bytes_s: float  # chip-to-chip bytes/s per chip, all links
    hbm_bytes: int  # device memory per chip


PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bytes_s=819e9, ici_bytes_s=1600e9 / 8,
                         hbm_bytes=16 * 10**9),
}


def peaks(kind: str) -> Peaks:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[kind]

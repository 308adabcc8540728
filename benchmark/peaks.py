"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The benchmark's own copy (the original is ``paddle_tpu/device/peaks.py``):
a later PR that changes the program cannot move the peak that its
roofline and MFU shares are divided by.  A device that is not in the
table is an error, never a default.
"""

from __future__ import annotations

from typing import NamedTuple


class ChipPeaks(NamedTuple):
    flops: float        # dense bf16 FLOP/s of one chip
    hbm_bw: float       # device-memory bytes/s of one chip


CHIP_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB of HBM at 819 GB/s
    "TPU v5 lite": ChipPeaks(197e12, 819e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(known: {sorted(CHIP_PEAKS)}); add it to "
            f"benchmark/peaks.py with its source") from None

"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

ONE table for every consumer — the engine's bytes-vs-FLOPs cost models
(``models/serving_engine.py``, ``models/disagg.py``) — so they can never
disagree about the chip.  A device that is not in the table is an
error, not a default: a number divided by an invented peak looks like a
measurement and is not one.
"""

from __future__ import annotations

from typing import NamedTuple

import jax

__all__ = ["ChipPeaks", "CHIP_PEAKS", "chip_peaks"]


class ChipPeaks(NamedTuple):
    flops: float        # dense bf16 FLOP/s of one chip
    hbm_bw: float       # device-memory bytes/s of one chip


CHIP_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB HBM at 819 GB/s
    "TPU v5 lite": ChipPeaks(197e12, 819e9),
    # nominal, NOT a measurement target: the cost models' crossover
    # arithmetic is unit-tested on the CPU backend and needs a fixed
    # figure there
    "cpu": ChipPeaks(5e10, 5e10),
}


def chip_peaks(device=None) -> ChipPeaks:
    """Peaks of ``device`` (default: the first device); raises
    ``KeyError`` for a ``device_kind`` the table does not list."""
    kind = (device if device is not None else jax.devices()[0]).device_kind
    try:
        return CHIP_PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {kind!r}; add it to "
            f"paddle_tpu.device.peaks.CHIP_PEAKS with its source "
            f"(known: {sorted(CHIP_PEAKS)})") from None

"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

``repro.obs.audit`` turns a compiled program's HLO FLOPs and bytes
(``launch.hlo_cost``) into roofline times against them.  A kind missing
from the table is an error (``peaks_for``), never a default: a roofline
computed against another chip's peaks is a wrong number, not an
approximate one.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published peaks of one chip."""

    flops: float      # dense bf16 FLOP/s
    hbm_bw: float     # HBM bytes/s
    link_bw: float    # bytes/s per chip-to-chip link
    source: str


PEAKS: Dict[str, ChipPeaks] = {
    # 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of ICI
    # over four links (= 50 GB/s each)
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9, link_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e'"),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind`` (``jax.Device.device_kind``);
    raises KeyError for a kind with no entry in ``PEAKS``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add an "
            f"entry with its source to repro.launch.roofline.PEAKS "
            f"(known: {sorted(PEAKS)})") from None

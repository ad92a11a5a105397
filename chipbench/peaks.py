"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

Copied from ``repro.launch.roofline.PEAKS`` so that the yardstick does not
move with the program.  A kind that is not in the table is an error, never
a default: a roofline share against another chip's peaks is a wrong number.

The MXU peak is the published bfloat16 rate, the chip's only published
matrix peak.  A float32 Gram at ``Precision.HIGHEST`` costs about six
bfloat16 passes, so such a kernel's share of this peak cannot read above
about a sixth.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB of HBM at 819 GB/s
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, 'TPU v5e'"},
}


def peaks_for(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})") from None

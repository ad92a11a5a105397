"""Kernel layer (repro.kernels.seg_gram): percent of the roofline,
the least time the window's sweeps need (``counts/dml_sweep.py``,
published peaks) over the device time of the Pallas kernel ops in the
trace (the fold Gram, the MM steps, the final stage)."""

from chipbench.readers import roofline_pct


def read(run):
    return roofline_pct(run)

"""Kernel layer (repro.kernels.seg_gram): percent of the roofline,
the least time the window's days need (``counts/store_daily.py``,
published peaks) over the device time of the Pallas kernel ops in the
trace (the day's fold Gram, the MM steps, the final stage)."""

from chipbench.readers import roofline_pct


def read(run):
    return roofline_pct(run)

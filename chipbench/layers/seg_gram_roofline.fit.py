"""Kernel layer (repro.kernels.seg_gram): percent of the roofline,
the least time the window's units need (work count, published peaks)
over the device time of the Pallas kernel ops in the trace."""

from chipbench.readers import roofline_pct


def read(run):
    return roofline_pct(run)

# Launch layer: cell lowerings, HLO cost and roofline, elastic sweeps, the
# two-process distributed smoke.

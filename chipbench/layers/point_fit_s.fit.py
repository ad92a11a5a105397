"""Estimator layer (repro.core): mean seconds of the point fit
(DML.fit: cross-fit nuisances and final stage), from the harness span."""

from chipbench.readers import span_mean


def read(run):
    return span_mean(run, "point_fit")

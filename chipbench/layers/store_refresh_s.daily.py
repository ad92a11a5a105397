"""Store layer (repro.store, ``MomentStore.refresh``): seconds per day
in the harness span around the refresh, up to the panel's effects and
SEs being ready: the MM logistic steps, the predictions and the final
stage over the window's rows."""

from chipbench.readers import span_mean


def read(run):
    return span_mean(run, "refresh")

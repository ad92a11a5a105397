"""Store layer (repro.store, ``MomentStore.ingest``): seconds per day
in the harness span around the day's ingest, up to its state being
ready: the day's fold Gram and its rows written into the ring."""

from chipbench.readers import span_mean


def read(run):
    return span_mean(run, "ingest")

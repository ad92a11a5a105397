"""Sweep layer (repro.sweep): host seconds per sweep in the program's
``sweep.segmented`` spans (the segmented column's host work: tracing on
a cache miss, dispatch; the spans do not wait for the device).  A
program that records no such span has nothing to read (None)."""

from chipbench.program_spans import seconds_per_unit, window_spans

SPAN = ("sweep.segmented",)


def read(run):
    if not window_spans(run, SPAN):
        return None
    return seconds_per_unit(run, SPAN)

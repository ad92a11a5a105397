"""Runtime layer (repro.runtime): seconds per fit in the scheduler's
``runtime.plan`` spans, where the memory model probes the replicate
program (probe compiles and cache loads, the HLO-text parse,
``memory_analysis``)."""

from chipbench.program_spans import seconds_per_unit


def read(run):
    return seconds_per_unit(run, ("runtime.plan",))

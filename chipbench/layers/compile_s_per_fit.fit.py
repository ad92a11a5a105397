"""Compile layer (repro.obs compile accounting): seconds per fit that
the program spent tracing, lowering, compiling and loading programs
from the persistent cache, from its ``compile.*`` spans (nested spans
counted once)."""

from chipbench.program_spans import COMPILE, seconds_per_unit


def read(run):
    return seconds_per_unit(run, COMPILE)

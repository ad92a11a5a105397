"""Compile layer (repro.obs compile accounting): programs compiled or
loaded from the persistent cache per sweep, from the program's
``compile.backend`` spans; 0 once every shape is warm."""

from chipbench.program_spans import count_per_unit


def read(run):
    return count_per_unit(run, ("compile.backend",))

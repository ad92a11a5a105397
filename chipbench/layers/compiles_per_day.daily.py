"""Compile layer (repro.obs compile accounting): programs compiled or
loaded from the persistent cache per day, from the program's
``compile.backend`` spans; 0 once every shape is warm, which holds only
while the ring position and the day's slot stay traced."""

from chipbench.program_spans import count_per_unit


def read(run):
    return count_per_unit(run, ("compile.backend",))

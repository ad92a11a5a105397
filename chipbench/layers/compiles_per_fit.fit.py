"""Compile layer (repro.obs compile accounting): programs compiled or
loaded from the persistent cache per fit, from the program's
``compile.backend`` spans (JAX times each cache load inside one)."""

from chipbench.program_spans import count_per_unit


def read(run):
    return count_per_unit(run, ("compile.backend",))

"""The program's own spans in a run's window, from the process tracer
of ``repro.obs.trace``.

The fit path records its layer-boundary spans (``runtime.plan``, ...)
and its compiles (``compile.trace``, ``compile.lower``,
``compile.backend``, ``compile.cache_load``) there when no tracer is
threaded in, as in every cell.  A span belongs to the window when it
starts inside it; the readers divide by the window's units.  A program
without a process tracer has nothing to read (None); a window with
units and no such span reads 0.
"""

from __future__ import annotations

from typing import List, Optional

COMPILE = ("compile.trace", "compile.lower", "compile.backend",
           "compile.cache_load")


def window_spans(run, names) -> Optional[List]:
    """The process tracer's spans named in ``names`` that start inside
    the window, or None when there is no unit or no process tracer."""
    if not run.units:
        return None
    try:
        from repro.obs import trace
    except ImportError:
        return None
    tracer = getattr(trace, "process_tracer", None)
    if tracer is None:
        return None
    lo, hi = run.window_start * 1e9, run.window_end * 1e9
    return [s for s in list(tracer().spans)
            if s.name in names and not s.open and lo <= s.start_ns <= hi]


def count_per_unit(run, names) -> Optional[float]:
    spans = window_spans(run, names)
    return None if spans is None else len(spans) / len(run.units)


def seconds_per_unit(run, names) -> Optional[float]:
    """Seconds covered by the spans (overlaps counted once) per unit."""
    spans = window_spans(run, names)
    if spans is None:
        return None
    covered, end = 0, None
    for s in sorted(spans, key=lambda s: s.start_ns):
        lo = s.start_ns if end is None else max(s.start_ns, end)
        if s.end_ns > lo:
            covered += s.end_ns - lo
        end = s.end_ns if end is None else max(end, s.end_ns)
    return covered / 1e9 / len(run.units)

"""repro.obs — zero-dependency observability.

The paper's argument is ultimately a measurement argument; this
package makes the same measurements first-class: a hierarchical span
tracer with ``block_until_ready``-honest durations and
Chrome-trace/Perfetto export (``trace``), a metrics registry of
counters / gauges / bounded-reservoir histograms with a plain-JSON
snapshot (``metrics``), and a predicted-vs-measured cost audit
joining traced chunks to the affine memory model and HLO roofline
probes (``audit``).  Thread ONE ``Tracer`` through
``DML(tracer=...)``, ``TaskRuntime(tracer=...)``, ``sweep(tracer=...)``,
or ``MomentStore(tracer=...)``; with ``tracer=None`` (the default
everywhere) the fit path's layer spans go to the bounded
``process_tracer()``, which syncs nothing and lowers nothing, so traced
and untraced runs execute the same compiled programs.  The process
tracer's first use installs the compile accounting (``compile.*``
spans, ``compiles[<span>]`` / ``compile_s[<span>]`` counters).
"""
#   trace.py    hierarchical span tracer (block_until_ready-honest
#               durations), Chrome trace-event / Perfetto export,
#               text tree, per-name rollups; the process tracer and
#               the compile accounting
#   metrics.py  counters / gauges / histograms with a snapshot API
#   audit.py    predicted-vs-measured cost audit joining traced chunks
#               to the affine memory model and hlo_cost roofline
# Thread ONE Tracer through DML(tracer=...), TaskRuntime(tracer=...),
# sweep(tracer=...), and crossfit; tracer=None sends the fit path's
# layer spans to the bounded process tracer.
from repro.obs.audit import ChunkAudit, CostAudit
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
    reset_default_registry,
)
from repro.obs.trace import (Span, Tracer, layer_span, maybe_span,
                             process_tracer, reset_process_tracer)

__all__ = [
    "ChunkAudit",
    "CostAudit",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Span",
    "Tracer",
    "default_registry",
    "layer_span",
    "maybe_span",
    "process_tracer",
    "reset_default_registry",
    "reset_process_tracer",
]

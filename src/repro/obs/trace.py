"""Hierarchical host-side span tracer with Chrome-trace export.

The paper's Ray case study is an observability argument: it justifies
the parallelization by *measuring* estimation times.  This tracer is
the measuring instrument for our runtime — spans open around
``TaskRuntime.map`` / per-chunk dispatches / gathered DAG nodes, sweep
columns, and crossfit targets, nest by call structure (a host-side
stack), and close with ``jax.block_until_ready`` on the produced value
so durations measure executed work, not dispatch latency.

Exports:

  chrome_trace()       Chrome trace-event JSON ("X" complete events,
                       "i" instants for RuntimeEvents) — load the file
                       in Perfetto (https://ui.perfetto.dev) or
                       chrome://tracing;
  render()             indented text tree with durations, for terminals
                       and bench logs;
  rollup()             per-span-name {count, total_s, max_s} — the
                       ``obs.spans`` section of BENCH_results.json.

A ``Tracer`` owns its :class:`~repro.obs.metrics.MetricsRegistry` and
:class:`~repro.obs.audit.CostAudit` so integrations thread ONE object.

Two kinds of tracer:

  explicit  a ``Tracer()`` threaded through ``DML(tracer=...)``,
            ``TaskRuntime(tracer=...)``, ``sweep(tracer=...)``, ...:
            spans, ``block_until_ready`` syncs, chunk histograms,
            jit-miss counters and the cost audit;
  process   ``process_tracer()``: one ``Tracer(sync=False)`` per
            process, a ring of ``PROCESS_MAX_SPANS`` spans with a
            ``dropped`` count.  The fit path's layer-boundary spans
            (``layer_span``: ``dml.fit``, ``crossfit:<nuisance>``,
            ``dml.final_stage``, ``inference.bootstrap``,
            ``runtime.map`` > ``runtime.plan`` / ``runtime.chunk``) land
            here when no tracer is threaded in.  It records spans only:
            it never syncs, never probes, never observes a histogram, so
            the same compiled programs run with it as without it.

Every span also opens ``jax.profiler.TraceAnnotation(name)``, so a
``jax.profiler`` trace (Perfetto, TensorBoard) shows the program's
spans on its host plane, on the device ops' clock.  Span timestamps
stay ``time.perf_counter_ns``.

Compile accounting: the first ``process_tracer()`` call installs one
``jax.monitoring`` listener per process.  Each compile duration JAX
reports becomes a closed span under the innermost open span of the
tracer in effect (an explicit tracer's when one has a span open, else
the process tracer's):

  compile.trace       tracing a function to a jaxpr
  compile.lower       jaxpr to an MLIR module
  compile.backend     one program compiled, or loaded from the
                      persistent cache (JAX times the cache read inside)
  compile.cache_load  the persistent-cache read, inside its backend span

and counts on ``default_registry()``: ``compiles[<span>]`` (programs
compiled or loaded: one per ``compile.backend``), ``compile_s[<span>]``
(seconds of compile work, nested events counted once),
``compile_cache_hits`` and ``compile_cache_misses``.  ``<span>`` is the
name of the innermost open span, ``(root)`` outside any.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

import jax

from repro.obs.audit import CostAudit
from repro.obs.metrics import MetricsRegistry, default_registry

PROCESS_MAX_SPANS = 65_536


def _jsonable(v: Any) -> Any:
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


@dataclasses.dataclass(slots=True)
class Span:
    """One traced interval (or instant, when ``end_ns == start_ns``)."""

    span_id: int
    name: str
    cat: str
    start_ns: int
    end_ns: int = -1  # -1 while open
    parent_id: int = -1
    depth: int = 0
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    instant: bool = False

    @property
    def open(self) -> bool:
        return self.end_ns < 0

    @property
    def duration_s(self) -> float:
        if self.open:
            return 0.0
        return max(self.end_ns - self.start_ns, 0) / 1e9


class Tracer:
    """Span stack + completed-span log + metrics + cost audit.

    ``sync=True`` (default) forces ``jax.block_until_ready`` at
    :meth:`sync` call sites so span durations are honest; set False to
    trace pure scheduling overhead without forcing device work.
    ``peaks`` (``launch.roofline.peaks_for(device_kind)``) turns on the
    audit's roofline time ratios.  ``max_spans`` bounds the span log to
    a ring keeping the newest spans (``dropped`` counts the rest); None
    keeps every span.  Each thread nests its spans on its own stack.
    """

    def __init__(self, *, sync: bool = True, clock=time.perf_counter_ns,
                 peaks=None, max_spans: Optional[int] = None):
        self._clock = clock
        self.sync_enabled = bool(sync)
        # in open order; closed in place
        self.spans = ([] if max_spans is None
                      else collections.deque(maxlen=int(max_spans)))
        self.recorded = 0  # every span ever recorded; the next span's id
        self._lock = threading.Lock()
        self._local = threading.local()
        self.metrics = MetricsRegistry()
        self.audit = CostAudit(peaks=peaks)

    @property
    def dropped(self) -> int:
        """Spans recorded but no longer held by the ring."""
        return self.recorded - len(self.spans)

    @property
    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def current(self) -> Optional[Span]:
        """The innermost span open on this thread, or None."""
        stack = self._stack
        return stack[-1] if stack else None

    # -- recording ------------------------------------------------------
    def _record(self, name: str, cat: str, start_ns: int, end_ns: int,
                attrs: Dict[str, Any], instant: bool = False) -> Span:
        parent = self.current()
        s = Span(
            span_id=-1,
            name=name,
            cat=cat,
            start_ns=start_ns,
            end_ns=end_ns,
            parent_id=parent.span_id if parent else -1,
            depth=parent.depth + 1 if parent else 0,
            attrs={k: _jsonable(v) for k, v in attrs.items()},
            instant=instant,
        )
        with self._lock:  # threads share the process tracer
            s.span_id = self.recorded
            self.recorded += 1
            self.spans.append(s)
        return s

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "runtime", **attrs) -> Iterator[Span]:
        """Open a nested span; yields it so callers can attach attrs.
        Mirrored as a ``jax.profiler.TraceAnnotation`` of the same name."""
        s = self._record(name, cat, self._clock(), -1, attrs)
        stack, active = self._stack, _open_tracers()
        stack.append(s)
        active.append(self)
        try:
            with jax.profiler.TraceAnnotation(name):
                yield s
        finally:
            active.pop()
            stack.pop()
            s.end_ns = self._clock()

    def instant(self, name: str, cat: str = "event", **attrs) -> Span:
        """Zero-duration marker (RuntimeEvents: retry, downgrade, ...)."""
        now = self._clock()
        return self._record(name, cat, now, now, attrs, instant=True)

    def add_span(self, name: str, start_ns: int, end_ns: int,
                 cat: str = "runtime", **attrs) -> Span:
        """Record an interval that has already ended (on this tracer's
        clock) under the innermost open span."""
        return self._record(name, cat, start_ns, end_ns, attrs)

    def sync(self, value: Any) -> Any:
        """``block_until_ready`` inside an open span so its duration
        covers the device work that produced ``value``."""
        if self.sync_enabled:
            try:
                jax.block_until_ready(value)
            except Exception:  # noqa: BLE001 — non-jax values pass through
                pass
        return value

    # -- export ---------------------------------------------------------
    def chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event JSON (dict; ``json.dump`` it).  Timestamps
        are microseconds relative to the first span, complete spans are
        ph="X", instants ph="i" — the schema Perfetto ingests."""
        t0 = min((s.start_ns for s in self.spans), default=0)
        events: List[Dict[str, Any]] = []
        for s in self.spans:
            base = {
                "name": s.name,
                "cat": s.cat,
                "ts": (s.start_ns - t0) / 1e3,
                "pid": 1,
                "tid": 1,
                "args": dict(s.attrs),
            }
            if s.instant:
                events.append({**base, "ph": "i", "s": "t"})
            else:
                end = s.end_ns if not s.open else s.start_ns
                events.append(
                    {**base, "ph": "X", "dur": max(end - s.start_ns, 0) / 1e3}
                )
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f, indent=1)
        return path

    def render(self) -> str:
        """Indented text tree (spans in open order, depth-indented)."""
        lines = []
        for s in self.spans:
            pad = "  " * s.depth
            if s.instant:
                lines.append(f"{pad}! {s.name} {s.attrs or ''}".rstrip())
            else:
                lines.append(
                    f"{pad}{s.name} [{s.cat}] {s.duration_s * 1e3:.2f}ms"
                    + (f" {s.attrs}" if s.attrs else "")
                )
        return "\n".join(lines)

    def rollup(self) -> Dict[str, Dict[str, float]]:
        """Per-name duration rollup over completed non-instant spans."""
        out: Dict[str, Dict[str, float]] = {}
        for s in self.spans:
            if s.instant or s.open:
                continue
            r = out.setdefault(s.name, {"count": 0, "total_s": 0.0, "max_s": 0.0})
            r["count"] += 1
            r["total_s"] += s.duration_s
            r["max_s"] = max(r["max_s"], s.duration_s)
        return out

    def span_names(self) -> List[str]:
        return [s.name for s in self.spans]


@contextlib.contextmanager
def maybe_span(tracer: Optional[Tracer], name: str, cat: str = "runtime", **attrs):
    """``tracer.span(...)`` when tracing, a free no-op otherwise — the
    one-liner integrations use so ``tracer=None`` stays zero-cost."""
    if tracer is None:
        yield None
    else:
        with tracer.span(name, cat=cat, **attrs) as s:
            yield s


def layer_span(tracer: Optional[Tracer], name: str, cat: str = "runtime",
               **attrs):
    """A span at a layer boundary of the fit path: into ``tracer`` when
    one is threaded in, else into the process tracer.  Yields the Span."""
    return (tracer if tracer is not None else process_tracer()).span(
        name, cat=cat, **attrs)


# ---------------------------------------------------------------------------
# The process tracer and the compile accounting.
# ---------------------------------------------------------------------------

_PROCESS: Optional[Tracer] = None
_LISTENING = False
# per thread: ``open``, the tracers with a span open (innermost last);
# ``compiles``, the newest compile spans not yet inside a later one
_THREAD = threading.local()

_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile.cache_load",
}
_CACHE_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "compile_cache_hits",
    "/jax/compilation_cache/cache_misses": "compile_cache_misses",
}
_RECENT_COMPILES = 4096  # a lowering can trace hundreds of primitives


def _thread_list(attr: str) -> list:
    out = getattr(_THREAD, attr, None)
    if out is None:
        out = []
        setattr(_THREAD, attr, out)
    return out


def _open_tracers() -> List[Tracer]:
    return _thread_list("open")


def process_tracer() -> Tracer:
    """The process-wide bounded tracer (created on first use, like
    ``default_registry()``); its first use installs the compile
    accounting."""
    global _PROCESS, _LISTENING
    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        jax.monitoring.register_event_listener(_on_cache_event)
        _LISTENING = True
    if _PROCESS is None:
        _PROCESS = Tracer(sync=False, max_spans=PROCESS_MAX_SPANS)
    return _PROCESS


def reset_process_tracer() -> None:
    """Drop the process tracer (a fresh one is created on next use);
    the compile listener stays installed."""
    global _PROCESS
    _PROCESS = None


def _on_compile(event: str, duration: float, **kwargs) -> None:
    """One compile duration from ``jax.monitoring``: a closed span
    ``[now - duration, now]`` and the ``compiles`` / ``compile_s``
    counters of the innermost open span.  JAX reports nested work
    (an inner function's trace, the cache read inside a backend
    compile) before the work around it, so a new span adopts the
    newest compile spans that overlap it, and its seconds count only
    the part they do not cover."""
    name = _COMPILE_SPANS.get(event)
    if name is None:
        return
    active = _open_tracers()
    tr = active[-1] if active else process_tracer()
    end = tr._clock()
    start = end - int(duration * 1e9)
    parent = tr.current()
    where = parent.name if parent else "(root)"
    s = tr.add_span(name, start, end, cat="compile",
                    fun_name=kwargs.get("fun_name", ""))
    recent = _thread_list("compiles")
    covered = 0
    while recent and recent[-1][1].end_ns > start:
        owner, inner = recent.pop()
        covered += inner.end_ns - inner.start_ns
        if owner is tr:
            inner.parent_id, inner.depth = s.span_id, s.depth + 1
    recent.append((tr, s))
    del recent[:-_RECENT_COMPILES]
    reg = default_registry()
    if name == "compile.backend":
        reg.counter(f"compiles[{where}]").inc()
    reg.counter(f"compile_s[{where}]").inc(max(end - start - covered, 0) / 1e9)


def _on_cache_event(event: str, **kwargs) -> None:
    name = _CACHE_COUNTERS.get(event)
    if name is not None:
        default_registry().counter(name).inc()

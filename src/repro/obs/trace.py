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
``tracer=None`` everywhere means: no spans, no syncs, no probe
lowerings — the traced and untraced paths run the same compiled
programs (bit-identity contracts hold by construction).
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from typing import Any, Dict, Iterator, List, Optional

import jax

from repro.obs.audit import CostAudit
from repro.obs.metrics import MetricsRegistry


def _jsonable(v: Any) -> Any:
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


@dataclasses.dataclass
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
    audit's roofline time ratios.
    """

    def __init__(self, *, sync: bool = True, clock=time.perf_counter_ns,
                 peaks=None):
        self._clock = clock
        self.sync_enabled = bool(sync)
        self.spans: List[Span] = []  # in open order; closed in place
        self._stack: List[Span] = []
        self._next_id = 0
        self.metrics = MetricsRegistry()
        self.audit = CostAudit(peaks=peaks)

    # -- recording ------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, cat: str = "runtime", **attrs) -> Iterator[Span]:
        """Open a nested span; yields it so callers can attach attrs."""
        parent = self._stack[-1] if self._stack else None
        s = Span(
            span_id=self._next_id,
            name=name,
            cat=cat,
            start_ns=self._clock(),
            parent_id=parent.span_id if parent else -1,
            depth=len(self._stack),
            attrs={k: _jsonable(v) for k, v in attrs.items()},
        )
        self._next_id += 1
        self.spans.append(s)
        self._stack.append(s)
        try:
            yield s
        finally:
            self._stack.pop()
            s.end_ns = self._clock()

    def instant(self, name: str, cat: str = "event", **attrs) -> Span:
        """Zero-duration marker (RuntimeEvents: retry, downgrade, ...)."""
        parent = self._stack[-1] if self._stack else None
        now = self._clock()
        s = Span(
            span_id=self._next_id,
            name=name,
            cat=cat,
            start_ns=now,
            end_ns=now,
            parent_id=parent.span_id if parent else -1,
            depth=len(self._stack),
            attrs={k: _jsonable(v) for k, v in attrs.items()},
            instant=True,
        )
        self._next_id += 1
        self.spans.append(s)
        return s

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

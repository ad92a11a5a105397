"""From a profiler trace to the device numbers of one run.

A TPU trace (``.xplane.pb``) holds, per chip, a plane ``/device:TPU:<i>``
with the line ``XLA Ops`` (one event per executed HLO op; ops inside a
``while`` body nest inside the ``while`` event) and the line
``XLA Modules`` (one event per program execution), on the same clock as
the host plane, where the harness's ``jax.profiler.TraceAnnotation``
marks (names starting ``bench.``) land.

``load`` turns the trace into plain lists; ``reduce`` computes, inside
the ``bench.window`` mark:

  busy_s      union of the op intervals, averaged over the chips;
  window_s    the mark's length;
  kernel_s    summed time of the Pallas kernel ops, matched by their
              custom-call target (``tpu_custom_call``: ``pl.pallas_call``
              carries no kernel name yet), averaged over the chips;
  top_ops     the ops that took most time, as [label, seconds];
  idle_gaps   the longest stretches with no op on the first chip, each
              named by the innermost harness mark around it.
"""

from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, List, Optional, Tuple

MARK = "bench."
WINDOW = "bench.window"
KERNEL_TARGET = "tpu_custom_call"

_OP = re.compile(r"^(%[^\s]+) = .*?\s([a-z][a-z0-9\-]*)\(")
_MODULE = re.compile(r"\(\d+\)$")

Event = Tuple[str, float, float]  # (name, start_ns, end_ns)


def load(path: str) -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}},
    "marks": [...]} from an .xplane.pb file."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> dict:
    devices: Dict[str, Dict[str, List[Event]]] = {}
    marks: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    dev[key].extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                marks.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name.startswith(MARK))
    return {"devices": devices, "marks": marks}


def _clip(events: List[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def _union(events: List[Event]) -> List[Tuple[float, float]]:
    spans: List[Tuple[float, float]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if spans and s <= spans[-1][1]:
            spans[-1] = (spans[-1][0], max(spans[-1][1], e))
        else:
            spans.append((s, e))
    return spans


def op_label(name: str, module: str = "") -> str:
    """``module:%op opcode`` from an HLO op event name."""
    m = _OP.match(name)
    head = f"{m.group(1)} {m.group(2)}" if m else name[:80]
    return f"{module}:{head}" if module else head


def _module_at(modules: List[Event], starts: List[float], t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][1] <= t < modules[i][2]:
        return _MODULE.sub("", modules[i][0])
    return ""


def _mark_at(marks: List[Event], t: float) -> str:
    inner: Optional[Event] = None
    for m in marks:
        if m[0] != WINDOW and m[1] <= t < m[2]:
            if inner is None or m[2] - m[1] < inner[2] - inner[1]:
                inner = m
    return inner[0][len(MARK):] if inner else "harness"


def reduce(tr: dict, *, top: int = 10) -> Optional[dict]:
    """Device numbers inside the window mark; None when the trace has no
    window mark or no device plane with ops in it."""
    win = [m for m in tr["marks"] if m[0] == WINDOW]
    if not win or not tr["devices"]:
        return None
    lo, hi = win[0][1], win[0][2]
    busy, kernel = [], []
    first = None
    for plane in sorted(tr["devices"]):
        ops = _clip(tr["devices"][plane]["ops"], lo, hi)
        if not ops:
            continue
        busy.append(sum(e - s for s, e in _union(ops)))
        kernel.append(sum(e - s for n, s, e in ops if KERNEL_TARGET in n))
        if first is None:
            first = (ops, tr["devices"][plane]["modules"])
    if first is None:
        return None
    ops, modules = first
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    per_op: Dict[str, float] = collections.defaultdict(float)
    for n, s, e in ops:
        per_op[n] += e - s
    per_label: Dict[str, float] = collections.defaultdict(float)
    first_at = {n: s for n, s, _ in reversed(ops)}
    for n, d in per_op.items():
        per_label[op_label(n, _module_at(modules, starts, first_at[n]))] += d
    holes, t = [], lo
    for s, e in _union(ops) + [(hi, hi)]:
        if s > t:
            holes.append((s - t, (s + t) / 2))
        t = max(t, e)
    holes.sort(key=lambda g: -g[0])
    gaps = [(g, _mark_at(tr["marks"], mid)) for g, mid in holes[:top]]
    ns = 1e-9
    return {
        "busy_s": sum(busy) / len(busy) * ns,
        "window_s": (hi - lo) * ns,
        "kernel_s": sum(kernel) / len(kernel) * ns,
        "top_ops": [[k, v * ns] for k, v in
                    sorted(per_label.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[name, g * ns] for g, name in gaps[:top]],
    }

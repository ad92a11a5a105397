"""Arithmetic the metric readers share.  A reader that finds nothing to
read returns None, and the harness leaves its metric out."""

from __future__ import annotations

from typing import Optional

import numpy as np


def window_to_last_unit(run) -> Optional[float]:
    """Seconds from the window's start to the end of its last unit."""
    return run.units[-1]["t1"] - run.window_start if run.units else None


def rate(run, field: str) -> Optional[float]:
    """All the work of the window's units over all its time."""
    span = window_to_last_unit(run)
    if not span:
        return None
    return sum(u[field] for u in run.units) / span


def span_mean(run, name: str) -> Optional[float]:
    s = run.span_seconds(name)
    return float(np.mean(s)) if s else None


def device_idle_pct(run) -> Optional[float]:
    """100 * (1 - busy / window) from the traced window."""
    tr = run.device_trace
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def roofline_pct(run) -> Optional[float]:
    """The least time the window's units need on this chip,
    max(flops / peak, bytes / HBM bandwidth) per unit from the cell's work
    count, over the summed device time of the Pallas kernel ops."""
    tr, work, pk = run.device_trace, run.work, run.peaks
    if not tr or not work or not pk or tr["kernel_s"] <= 0 or not run.units:
        return None
    least = max(work["flops"] / pk["flops"], work["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * len(run.units) * least / tr["kernel_s"]

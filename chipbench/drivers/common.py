"""Helpers the drivers share: the program's config, budgets, gaps."""

from __future__ import annotations

import numpy as np


def causal_config(ctx, **extra):
    """The program's ``CausalConfig`` from the configuration file."""
    from repro.config import CausalConfig
    return CausalConfig(**ctx.config["causal_config"], **extra)


def memory_budget(dev, share: float) -> int:
    """``share`` of the device memory still free (the runtime's replicate
    budget); a fixed small budget off the chip, to force chunks."""
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit")
    if not limit:
        return 64 << 20
    return int(share * (limit - stats.get("bytes_in_use", 0)))


def gap_se(got, ref, se) -> float:
    """max |got - ref| / se, elementwise, in float64."""
    got, ref, se = (np.asarray(a, np.float64) for a in (got, ref, se))
    return float(np.max(np.abs(got - ref) / np.maximum(se, 1e-30)))


def rel(got, ref) -> float:
    """|got - ref| / |ref| in float64."""
    got, ref = float(got), float(ref)
    return abs(got - ref) / max(abs(ref), 1e-30)


def fro_rel(got, ref) -> float:
    """||got - ref||_F / ||ref||_F in float64."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))

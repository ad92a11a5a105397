"""The many-cohorts sweep as a dry-run cell: E per-segment DML fits
lowered against the production mesh — the paper's case-study workload
shape (many effect estimates per run, not one) at the §5.3 scale.

Two lowerings of the same estimation:

  mode="segmented"  the one-pass segment×fold Gram kernels
                    (repro.sweep.segmented): rows shard over every
                    chip, the (E·K, q, q) segmented Gram is the one
                    cross-chip reduction — the many-effects-cheaply
                    execution, and the cell most representative of the
                    sweep subsystem's technique;
  mode="cells"      E masked weighted single fits batched on a leading
                    cell axis (the certified-bitwise execution),
                    lowered for cross-checking the segmented cell's
                    collectives.

Like launch/dml_cell.py these lower compile-only (no device buffers):
the dry-run/roofline tooling reads cost + memory off the HLO.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import CausalConfig
from repro.core.final_stage import cate_basis

N_ROWS = 1_048_576  # the paper's "1 Million", padded to 2^20 (see dml_cell)
N_COVARIATES = 500
N_SEGMENTS = 64


def make_sweep_step(cfg: CausalConfig, n_segments: int = N_SEGMENTS,
                    mode: str = "segmented"):
    """One full E-segment sweep column as a single jittable program.
    Segment ids come in as data (host-computed, like fold assignments
    in the DML cell)."""
    if mode == "segmented":
        from repro.sweep.segmented import segmented_dml_sweep

        def sweep_fit(X, y, t, sids):
            out = segmented_dml_sweep(cfg, X, y, t, sids, n_segments,
                                      jax.random.PRNGKey(0))
            return out["theta"], out["se"]

        return sweep_fit
    if mode != "cells":
        raise ValueError(f"unknown sweep cell mode {mode!r}")

    from repro.core.registry import get_spec
    from repro.sweep.engine import column_keys
    cell = get_spec("dml").weighted_fit(cfg)

    def sweep_fit(X, y, t, sids):
        keys = column_keys(jax.random.PRNGKey(0), 0, n_segments)
        data = {"X": X, "y": y, "t": t, "phi": cate_basis(
            X, cfg.cate_features)}

        def one(key, sid):
            w = (sids == sid).astype(jnp.float32)
            return cell(key, w, data)

        out = jax.vmap(one)(keys, jnp.arange(n_segments, dtype=jnp.int32))
        return out["theta"], out["se"]

    return sweep_fit


def input_specs(n: int = N_ROWS, p: int = N_COVARIATES):
    f32, i32 = jnp.float32, jnp.int32
    return {
        "X": jax.ShapeDtypeStruct((n, p), f32),
        "y": jax.ShapeDtypeStruct((n,), f32),
        "t": jax.ShapeDtypeStruct((n,), f32),
        "sids": jax.ShapeDtypeStruct((n,), i32),
    }


def row_sharding(mesh: Mesh) -> Dict[str, NamedSharding]:
    """Rows shard over EVERY mesh axis jointly (the paper's one giant
    data axis; segments batch inside the program)."""
    axes = tuple(mesh.axis_names)
    return {
        "X": NamedSharding(mesh, P(axes, None)),
        "y": NamedSharding(mesh, P(axes)),
        "t": NamedSharding(mesh, P(axes)),
        "sids": NamedSharding(mesh, P(axes)),
    }


def lower_sweep_cell(mesh: Mesh, cfg: CausalConfig = None,
                     n: int = N_ROWS, p: int = N_COVARIATES,
                     n_segments: int = N_SEGMENTS,
                     mode: str = "segmented"):
    cfg = cfg or CausalConfig(n_folds=5, cate_features=1)
    step = make_sweep_step(cfg, n_segments, mode)
    specs = input_specs(n, p)
    sh = row_sharding(mesh)
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            step,
            in_shardings=(sh["X"], sh["y"], sh["t"], sh["sids"]),
        ).lower(specs["X"], specs["y"], specs["t"], specs["sids"])
    return lowered

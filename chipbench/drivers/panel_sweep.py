"""Segmented sweeps back to back: ``sweep(spec, ..., mode="segmented")``.

Each sweep in the window gets its own key (the shared fold split
differs), on the same data made in set-up, through one ``SweepSpec``
built in set-up as a user would keep it.  One unit is one sweep and
counts ``n`` rows: each row enters its own cohort's fit once.  A sweep
whose column failed, or did not take the segmented path, counts as
failed.

Check, once the window has closed: one sweep of the window, drawn from
the seed, against the plain reference of the same estimand on the same
rows and fold key, re-derived from the program's key lineage (column 0
folds with ``fold_in(sweep_key, 0)``): every (cohort, fold) nuisance
model of the visit (``beta_y_rel``) and of the treatment
(``beta_t_rel``), Frobenius-relative; the per-cohort effects in units
of the reference SE (``theta_gap_se``, the worst cohort) and their SEs
(``se_rel``, the worst cohort); and the (cohort, fold) cells with no
rows (``empty_cells``).  The program computes in float32 throughout, so
the reference does too; the control rounds every factor of the
reference's data-sized products to bfloat16.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from chipbench.drivers import common
from chipbench.refs import uplift


def setup(ctx):
    from repro.sweep import SweepSpec
    c = ctx.config
    ctx.state["data"] = jax.block_until_ready(
        uplift.make_panel(jax.random.fold_in(ctx.key, 1), c))
    ctx.state["spec"] = SweepSpec.grid(n_segments=c["segments"],
                                       configs=(common.causal_config(ctx),))
    ctx.state["sweep_root"] = jax.random.fold_in(ctx.key, 2)
    out = _sweep(ctx, jax.random.fold_in(ctx.key, 3))  # every shape once
    if out is None:
        raise RuntimeError(f"the warm-up sweep failed: {ctx.state['error']}")


def _sweep(ctx, key):
    """One sweep; its column's arrays, or None when it failed or left
    the segmented path."""
    from repro.sweep import sweep
    d = ctx.state["data"]
    panel = sweep(ctx.state["spec"], X=d["X"], y=d["y"], t=d["t"],
                  segment_ids=d["sids"], key=key, mode="segmented")
    col = panel.columns[0]
    if col.failed or col.events != ("segmented",):
        ctx.state["error"] = col.error or f"events {col.events}"
        return None
    return jax.block_until_ready({
        "theta": col.thetas[:, 0], "se": col.ses[:, 0], "beta_y": col.beta_y,
        "beta_t": col.beta_t, "cell_rows": col.cell_rows})


def window(ctx):
    sweeps = []
    while ctx.window_open():
        t0 = time.perf_counter()
        ctx.attempted += 1
        out = _sweep(ctx, jax.random.fold_in(ctx.state["sweep_root"],
                                             ctx.attempted - 1))
        if out is None:
            ctx.failed += 1
            continue
        sweeps.append((ctx.attempted - 1, out))
        ctx.unit(t0, rows=ctx.config["n"])
    ctx.out["sweeps"] = sweeps


def release(ctx):
    ctx.state.pop("spec", None)


def check(ctx, control: bool) -> dict:
    c, d = ctx.config, ctx.state["data"]
    cc = c["causal_config"]
    rng = np.random.default_rng(ctx.seed)
    j, got = ctx.out["sweeps"][int(rng.integers(len(ctx.out["sweeps"])))]
    fold_key = jax.random.fold_in(
        jax.random.fold_in(ctx.state["sweep_root"], j), 0)

    def estimate(lowp):
        out = uplift.segmented_dml(
            d["X"], d["y"], d["t"], d["sids"], fold_key, E=c["segments"],
            k=cc["n_folds"], lam=cc["ridge_lambda"], iters=2 * cc["newton_iters"],
            chunks=c["ref_chunks"], lowp=lowp)
        return dict(zip(("theta", "se", "beta_y", "beta_t"),
                        (np.asarray(a) for a in out)))

    ref = estimate(False)
    if control:
        got = dict(estimate(True), cell_rows=got["cell_rows"])
    return {"beta_y_rel": common.fro_rel(got["beta_y"], ref["beta_y"]),
            "beta_t_rel": common.fro_rel(got["beta_t"], ref["beta_t"]),
            "theta_gap_se": common.gap_se(got["theta"], ref["theta"], ref["se"]),
            "se_rel": float(np.max(np.abs(np.asarray(got["se"], np.float64)
                                          - ref["se"]) / ref["se"])),
            "empty_cells": float(np.sum(np.asarray(got["cell_rows"]) == 0))}

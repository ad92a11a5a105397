"""A trailing-window daily refresh: ``MomentStore(spec, window=...)``,
one ``ingest`` and one ``refresh`` per day.

Set-up makes the store first (a program without a windowed store fails
there, at once), then draws the days from the seed on the device and
keeps them on the host: ``days`` history days and a pool of
``pool_days`` more, day ``d`` from ``fold_in(fold_in(seed key, 0), d)``.
It ingests the history, refreshes, then ingests and refreshes one more
day, so that the first retirement has run before the window opens.

One unit is one day, back to back: the day's block arrives from the
host (``device_put``), ``ingest`` retires the oldest day, ``refresh``,
then the panel's effects and SEs are ready.  A unit counts the rows the
refreshed panel is fitted on, the window's.  Past the pool the days
cycle through it while the day index still advances, so every day's
rows take new global indices and new folds.  A day whose column failed,
or was not refreshed from the window's rows, counts as failed.  The
spans ``ingest`` and ``refresh`` (in the window only) each end when
their outputs are ready.

Check, once the window has closed: the last refreshed panel against the
plain reference (``refs/daily.py``) over the days the store held then,
each row in the fold the documented rule gives it: every (cohort, fold)
visit model (``beta_y_rel``) and treatment model (``beta_t_rel``),
Frobenius-relative; the per-cohort effects in units of the reference SE
(``theta_gap_se``, the worst cohort) and their SEs (``se_rel``, the
worst cohort); the (cohort, fold) cells with no rows (``empty_cells``).
The control rounds every factor of the reference's data-sized products
to bfloat16.
"""

from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

from chipbench.drivers import common
from chipbench.refs import daily


def setup(ctx):
    from repro.store import MomentStore, Window
    from repro.sweep import SweepSpec
    c, pool = ctx.config, int(ctx.traffic["pool_days"])
    days, day_rows = int(c["days"]), int(c["day_rows"])
    if days * day_rows != int(c["n"]):
        raise ValueError(f"n {c['n']} != days x day_rows {days * day_rows}")
    spec = SweepSpec.grid(n_segments=c["segments"],
                          configs=(common.causal_config(ctx),))
    ctx.state["store_key"] = jax.random.fold_in(ctx.key, 1)
    ctx.state["store"] = store = MomentStore(
        spec, n_features=c["p"], key=ctx.state["store_key"],
        window=Window(days=days, day_rows=day_rows))
    root = jax.random.fold_in(ctx.key, 0)
    ctx.state["days"] = [jax.device_get(daily.make_day(jax.random.fold_in(root, d), c))
                         for d in range(days + pool)]
    for d in range(days + 1):  # a full window, then the first retirement
        _ingest(ctx, d, spans=False)
        if d >= days - 1 and _refresh(ctx, spans=False) is None:
            raise RuntimeError(f"the warm-up refresh failed: {ctx.state['error']}")
    ctx.state["next_day"] = days + 1


def _host_day(ctx, d: int) -> dict:
    days, held = int(ctx.config["days"]), ctx.state["days"]
    return held[d if d < len(held) else days + (d - days) % (len(held) - days)]


def _span(ctx, name: str, spans: bool):
    """The harness span, in the window only: set-up's days would weigh
    its compiles into the per-day means."""
    return ctx.span(name) if spans else contextlib.nullcontext()


def _ingest(ctx, d: int, spans: bool = True):
    block = jax.device_put(_host_day(ctx, d))
    store = ctx.state["store"]
    with _span(ctx, "ingest", spans):
        store.ingest(X=block["X"], y=block["y"], t=block["t"],
                     segment_ids=block["sids"])
        jax.block_until_ready(store.state_dict())


def _refresh(ctx, spans: bool = True):
    """One refresh; its column's arrays, or None when it failed or did
    not re-read the window's rows."""
    with _span(ctx, "refresh", spans):
        panel = ctx.state["store"].refresh()
        col = panel.columns[0]
        if col.failed or col.events[-1:] != ("rows",):
            ctx.state["error"] = col.error or f"events {col.events}"
            return None
        return jax.block_until_ready({
            "theta": col.thetas[:, 0], "se": col.ses[:, 0],
            "beta_y": col.beta_y, "beta_t": col.beta_t,
            "cell_rows": col.cell_rows})


def window(ctx):
    store = ctx.state["store"]
    while ctx.window_open():
        t0 = time.perf_counter()
        ctx.attempted += 1
        d = ctx.state["next_day"]
        ctx.state["next_day"] += 1
        _ingest(ctx, d)
        out = _refresh(ctx)
        if out is None:
            ctx.failed += 1
            continue
        ctx.out["last"] = (d, out)
        ctx.unit(t0, rows=store.resident_rows)


def release(ctx):
    ctx.state.pop("store", None)


def check(ctx, control: bool) -> dict:
    c = ctx.config
    cc = c["causal_config"]
    k, day_rows = cc["n_folds"], int(c["day_rows"])
    last, got = ctx.out["last"]
    held = range(last - int(c["days"]) + 1, last + 1)
    column_key = jax.random.fold_in(ctx.state["store_key"], 0)
    hd = [_host_day(ctx, d) for d in held]
    Xt = np.concatenate([h["X"].T for h in hd], axis=1)
    rows = {f: np.concatenate([h[f] for h in hd]) for f in ("y", "t", "sids")}
    folds = np.concatenate([np.asarray(daily.row_folds(
        column_key, d * day_rows, n=day_rows, k=k)) for d in held])

    def estimate(lowp):
        out = daily.window_dml(
            Xt, rows["y"], rows["t"], rows["sids"], folds, E=c["segments"],
            k=k, lam=cc["ridge_lambda"], iters=2 * cc["newton_iters"],
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

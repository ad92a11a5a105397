"""DML fits back to back: ``DML.fit``, then ``ate_interval()`` when the
traffic asks for bootstrap CIs.

Each fit in the window gets its own key (folds and bootstrap draws
differ), on the same data, through one ``DML`` object built in set-up
as a user would keep it.  One unit is one fit and counts ``n`` rows,
plus ``n`` for each of its B replicates.

Traffic parameters: ``bootstrap`` (run ``ate_interval()`` after each
fit) and ``check_replicates`` (how many replicates the check compares).

Check, once the window has closed: one fit of the window, drawn from
the seed, against the plain reference of the same estimand on the same
rows and fold key: the fold models' ridge coefficients of both
nuisances (``beta_rel``, decided by the kernel's Grams), theta
(``theta_gap_se``, in units of the reference SE) and its SE
(``se_rel``).  With CIs, also ``check_replicates`` of that fit's
replicates, drawn from the seed, each against the reference refit on
the replicate's row counts and fold key, re-derived from the program's
key lineage (``replicate_gap_se``).

The configuration keeps XLA's default matmul precision for the
out-of-fold predictions X @ beta, and on the TPU that is one bfloat16
pass.  The reference computes those predictions at the same precision
on the TPU (factors rounded to bfloat16, float32 sums), and everything
else in float32 (PERF.md gives the readings).
"""

from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

from chipbench.drivers import common
from chipbench.refs import estimands as ref
from chipbench.refs.dgp import make_data

BOOT_SALT = 0x0B00  # the program's bootstrap key: fold_in(fit_key, 0x0B00)


def setup(ctx):
    from repro.core.dml import DML
    c = ctx.config
    ctx.state["data"] = jax.block_until_ready(make_data(
        jax.random.fold_in(ctx.key, 1), c["n"], c["p"],
        discrete=c["discrete_treatment"], heterogeneous=False))
    budget = common.memory_budget(ctx.device, c["runtime_memory_budget_share"])
    ctx.state["est"] = DML(common.causal_config(ctx, runtime_memory_budget=budget))
    ctx.state["fit_root"] = jax.random.fold_in(ctx.key, 2)
    _fit(ctx, jax.random.fold_in(ctx.key, 3), spans=False)  # every shape once


def _fit(ctx, key, spans=True):
    est, d = ctx.state["est"], ctx.state["data"]
    span = ctx.span if spans else (lambda name: contextlib.nullcontext())
    with span("point_fit"):
        res = est.fit(d["y"], d["t"], d["X"], key=key)
        theta, se = jax.block_until_ready((res.theta, res.stderr))
    out = {"theta": float(np.asarray(theta)[0]), "se": float(np.asarray(se)[0]),
           "beta": np.stack([np.asarray(res.crossfit.states_y["beta"]),
                             np.asarray(res.crossfit.states_t["beta"])])}
    if ctx.traffic["bootstrap"]:
        with span("bootstrap"):
            res.ate_interval()
            reps = jax.block_until_ready(res.inference().replicates)
        out["reps"] = np.asarray(reps)[:, 0]
    return out


def window(ctx):
    c = ctx.config
    boots = c["causal_config"]["n_bootstrap"] if ctx.traffic["bootstrap"] else 0
    rows = c["n"] * (1 + boots)
    fits = []
    while ctx.window_open():
        t0 = time.perf_counter()
        ctx.attempted += 1
        fits.append(_fit(ctx, jax.random.fold_in(ctx.state["fit_root"], len(fits))))
        ctx.unit(t0, rows=rows)
    ctx.out["fits"] = fits


def release(ctx):
    ctx.state.pop("est", None)


def check(ctx, control: bool) -> dict:
    c, d = ctx.config, ctx.state["data"]
    fits = ctx.out["fits"]
    rng = np.random.default_rng(ctx.seed)
    j = int(rng.integers(len(fits)))
    fit_key = jax.random.fold_in(ctx.state["fit_root"], j)
    kw = dict(k=c["causal_config"]["n_folds"],
              lam=c["causal_config"]["ridge_lambda"], chunks=c["ref_chunks"],
              pred_lowp=ctx.device.platform == "tpu")

    def estimate(key, w, lowp):
        th, se, beta = ref.dml(d["X"], d["y"], d["t"], key, w, lowp=lowp, **kw)
        return float(th), float(se), np.asarray(beta)

    th_ref, se_ref, beta_ref = estimate(
        fit_key, jax.numpy.ones((c["n"],), jax.numpy.float32), False)
    if control:
        theta, se, beta = estimate(
            fit_key, jax.numpy.ones((c["n"],), jax.numpy.float32), True)
    else:
        theta, se, beta = fits[j]["theta"], fits[j]["se"], fits[j]["beta"]
    out = {"beta_rel": max(common.fro_rel(beta[i], beta_ref[i]) for i in (0, 1)),
           "theta_gap_se": common.gap_se(theta, th_ref, se_ref),
           "se_rel": common.rel(se, se_ref)}
    if ctx.traffic["bootstrap"]:
        B = c["causal_config"]["n_bootstrap"]
        picks = sorted(rng.choice(B, size=min(ctx.traffic["check_replicates"], B),
                                  replace=False).tolist())
        boot_key = jax.random.fold_in(fit_key, BOOT_SALT)
        rep_ref, rep_ctl = [], []
        for b in picks:
            w, kfit = ref.bootstrap_weights(jax.random.fold_in(boot_key, b), c["n"])
            rep_ref.append(estimate(kfit, w, False)[0])
            if control:
                rep_ctl.append(estimate(kfit, w, True)[0])
        reps = rep_ctl if control else fits[j]["reps"][picks]
        out["replicate_gap_se"] = common.gap_se(reps, rep_ref, se_ref)
    return out

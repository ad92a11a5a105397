"""Refutation tests — NEXUS's 'integrated validation' (paper §4), the
dowhy-style robustness checks re-run through the fold-parallel engine:

  placebo_treatment      permuted T  -> estimate should collapse to ~0
  random_common_cause    X + noise covariate -> estimate should be stable
  data_subset            random half of rows -> estimate should be stable

Each refuter is R independent re-fits — iterative steps of a causal
algorithm, i.e. exactly the concurrency class the paper parallelizes
(§5.1 fold fits, §5.2 tuning trials, and these replicates).  The R
re-fits are dispatched through ``repro.inference.executor`` — the same
pluggable Executor that runs bootstrap replicates — so by default they
execute as ONE vmapped program instead of a Python loop (pass
``executor="serial"`` for the loop baseline; per-replicate estimates are
bit-identical across the two).  Each replicate derives its permutation /
noise / subset mask AND its fold assignment from ``fold_in(key, r)``,
the lineage that makes any single replicate exactly replayable.

``data_subset`` keeps rows in place and zeroes their training + moment
weights (the weighted-fit path bootstrap replicates use), which is
estimation-equivalent to physically dropping the rows but keeps every
replicate the same shape — the requirement for batching them into one
program.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.config import CausalConfig
from repro.core.dml import DML
from repro.core.final_stage import cate_basis
from repro.inference.bootstrap import dml_theta_once, replicate_keys
from repro.runtime import as_runtime


@dataclasses.dataclass(frozen=True)
class RefutationReport:
    name: str
    original_ate: float
    refuted_ates: Tuple[float, ...]
    expectation: str  # "zero" | "stable"

    @property
    def mean(self) -> float:
        return float(jnp.mean(jnp.asarray(self.refuted_ates)))

    @property
    def passed(self) -> bool:
        m = jnp.asarray(self.refuted_ates)
        if self.expectation == "zero":
            # placebo effects should be ~0 relative to the real effect
            return bool(jnp.abs(m.mean()) < 0.25 * abs(self.original_ate)
                        + 3 * m.std() + 1e-6)
        rel = jnp.abs(m.mean() - self.original_ate) / max(
            abs(self.original_ate), 1e-9)
        return bool(rel < 0.25)

    def row(self) -> str:
        return (f"{self.name:>22}: original={self.original_ate:+.4f} "
                f"refuted_mean={self.mean:+.4f} "
                f"[{'PASS' if self.passed else 'FAIL'}]")


def _run_replicates(fn, key, n_reps: int, executor, *arrays,
                    label: str = "refute") -> Tuple[float, ...]:
    """Dispatch ``n_reps`` refit replicates through the task runtime and
    extract the leading (ATE) coefficient of each — shared by the DML
    refuters (y, t, X, phi) and the IV refuters (y, t, z, X, phi)."""
    rt = as_runtime(executor)
    thetas = rt.map(fn, replicate_keys(key, n_reps), *arrays,
                    label=label)["theta"]
    return tuple(float(a) for a in thetas[:, 0])


def placebo_treatment(est: DML, y, t, X, *, original_ate: float,
                      n_reps: int = 3, key=None,
                      executor="vmap") -> RefutationReport:
    key = key if key is not None else jax.random.PRNGKey(7)
    phi = cate_basis(X, est.cfg.cate_features)

    def refit(kr, y_, t_, X_, phi_):
        t_fake = jax.random.permutation(kr, t_)
        ones = jnp.ones((X_.shape[0],), jnp.float32)
        return dml_theta_once(est.nuis_y, est.nuis_t, est.cfg.n_folds,
                              X_, y_, t_fake, phi_, kr, ones,
                              with_se=False)

    ates = _run_replicates(refit, key, n_reps, executor, y, t, X, phi)
    return RefutationReport("placebo_treatment", original_ate, ates, "zero")


def random_common_cause(est: DML, y, t, X, *, original_ate: float,
                        n_reps: int = 3, key=None,
                        executor="vmap") -> RefutationReport:
    key = key if key is not None else jax.random.PRNGKey(8)
    phi = cate_basis(X, est.cfg.cate_features)

    def refit(kr, y_, t_, X_, phi_):
        n = X_.shape[0]
        extra = jax.random.normal(kr, (n, 1), X_.dtype)
        Xr = jnp.concatenate([X_, extra], axis=1)
        ones = jnp.ones((n,), jnp.float32)
        return dml_theta_once(est.nuis_y, est.nuis_t, est.cfg.n_folds,
                              Xr, y_, t_, phi_, kr, ones, with_se=False)

    ates = _run_replicates(refit, key, n_reps, executor, y, t, X, phi)
    return RefutationReport("random_common_cause", original_ate, ates,
                            "stable")


def data_subset(est: DML, y, t, X, *, original_ate: float,
                frac: float = 0.5, n_reps: int = 3, key=None,
                executor="vmap") -> RefutationReport:
    key = key if key is not None else jax.random.PRNGKey(9)
    m = int(X.shape[0] * frac)
    phi = cate_basis(X, est.cfg.cate_features)

    def refit(kr, y_, t_, X_, phi_):
        # weight-out (1-frac) of the rows instead of slicing them away:
        # identical moments, static shapes (batchable)
        n = X_.shape[0]
        w = (jax.random.permutation(kr, jnp.arange(n)) < m
             ).astype(jnp.float32)
        return dml_theta_once(est.nuis_y, est.nuis_t, est.cfg.n_folds,
                              X_, y_, t_, phi_, kr, w, with_se=False)

    ates = _run_replicates(refit, key, n_reps, executor, y, t, X, phi)
    return RefutationReport("data_subset", original_ate, ates, "stable")


# ---------------------------------------------------------------------------
# Instrument-side refuters (repro.core.iv).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WeakInstrumentReport:
    """First-stage F screen (Stock-Yogo rule of thumb: F < 10 ⇒ weak
    instrument ⇒ 2SLS point estimates and CIs are unreliable)."""

    f_stat: float
    threshold: float
    instrument_corr: float

    @property
    def passed(self) -> bool:
        return self.f_stat >= self.threshold

    def row(self) -> str:
        return (f"{'weak_instrument':>22}: F={self.f_stat:.1f} "
                f"(threshold {self.threshold:.0f}) corr(rz,rt)="
                f"{self.instrument_corr:+.3f} "
                f"[{'PASS' if self.passed else 'FAIL'}]")


def weak_instrument(res, *, threshold: float = 10.0
                    ) -> WeakInstrumentReport:
    """Screen a fitted OrthoIV/DRIV result's first stage: the robust F
    of ``rt ~ rz`` recomputed from the result's out-of-fold residuals
    (repro.core.estimands.first_stage_f)."""
    from repro.core.estimands import first_stage_f
    cf = res.fit_ctx
    if cf is None or not hasattr(res, "crossfit"):
        # DRIVResult (no stored crossfit) or a context-free result:
        # the fit-time diagnostics already carry the same F
        d = res.diagnostics
        return WeakInstrumentReport(f_stat=d.first_stage_f,
                                    threshold=threshold,
                                    instrument_corr=d.instrument_corr)
    rt_res = cf.t - res.crossfit.oof_t
    rz_res = cf.z - res.crossfit.oof_z
    f = first_stage_f(rt_res, rz_res)
    corr = float(jnp.corrcoef(jnp.stack(
        [jnp.asarray(rz_res, jnp.float32),
         jnp.asarray(rt_res, jnp.float32)]))[0, 1])
    return WeakInstrumentReport(f_stat=f, threshold=threshold,
                                instrument_corr=corr)


def placebo_instrument(est, y, t, z, X, *, original_ate: float,
                       n_reps: int = 3, key=None,
                       executor="vmap") -> RefutationReport:
    """Permute Z: a scrambled instrument carries no first-stage signal,
    so the 2SLS numerator AND denominator collapse toward 0/0 — the
    replicate estimates should scatter around zero effect with no
    systematic drift toward the original.  Each replicate is one
    weighted OrthoIV refit through the task runtime (the same
    replicate-closure machinery as the bootstrap)."""
    from repro.inference.bootstrap import iv_theta_once
    key = key if key is not None else jax.random.PRNGKey(17)
    phi = cate_basis(X, est.cfg.cate_features)

    def refit(kr, y_, t_, z_, X_, phi_):
        z_fake = jax.random.permutation(kr, z_)
        ones = jnp.ones((X_.shape[0],), jnp.float32)
        return iv_theta_once(est.nuis_y, est.nuis_t, est.nuis_z,
                             est.cfg.n_folds, X_, y_, t_, z_fake, phi_,
                             kr, ones, with_se=False)

    ates = _run_replicates(refit, key, n_reps, executor, y, t, z,
                           X, phi, label="placebo_instrument")
    return RefutationReport("placebo_instrument", original_ate, ates,
                            "zero")


def run_all(cfg: CausalConfig, y, t, X, *, key=None, executor="vmap"
            ) -> Tuple[RefutationReport, ...]:
    """The refuter panel on ONE shared task runtime (configured from
    cfg.runtime_*): the three refuters are independent branches of a
    task graph gathered together, each branch's replicate map going
    through the same chunked, fault-tolerant scheduler."""
    key = key if key is not None else jax.random.PRNGKey(0)
    est = DML(cfg)
    base = est.fit(y, t, X, key=key)
    a0 = base.ate
    rt = as_runtime(executor, memory_budget=cfg.runtime_memory_budget,
                    chunk=cfg.runtime_chunk,
                    max_retries=cfg.runtime_max_retries)
    p = rt.call(lambda: placebo_treatment(
        est, y, t, X, original_ate=a0, key=key, executor=rt),
        label="placebo_treatment")
    r = rt.call(lambda: random_common_cause(
        est, y, t, X, original_ate=a0, key=key, executor=rt),
        label="random_common_cause")
    d = rt.call(lambda: data_subset(
        est, y, t, X, original_ate=a0, key=key, executor=rt),
        label="data_subset")
    return tuple(rt.gather([p, r, d]))

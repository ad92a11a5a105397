"""Double/Debiased ML estimator (Chernozhukov et al. 2018) — the
algorithm the paper scales.  ``DML(engine="parallel")`` is the paper's
DML_Ray translated to SPMD; ``engine="sequential"`` is the EconML
baseline it benchmarks against (both produce identical estimates up to
fold-init PRNG; tests assert the equivalence).

Usage (mirrors the paper's §5.1 listing):

    est = DML(CausalConfig(n_folds=5, nuisance_y="ridge",
                           nuisance_t="logistic", engine="parallel"))
    res = est.fit(y, t, X=X, key=jax.random.PRNGKey(0))
    res.ate, res.stderr, res.cate(X_new)
    res.ate_interval()            # B=cfg.n_bootstrap replicates, one
    res.cate_interval(X_new)      # vmapped program (repro.inference)

Spans: ``dml.fit`` around the fit, with ``crossfit:<nuisance>`` and
``dml.final_stage`` inside, and ``inference.bootstrap`` around the
replicates; they go to ``DML(tracer=...)`` when one is given (which
then sees the whole fit, its replicates included), else to the process
tracer (repro.obs.trace).

The fit -> inference plumbing (interval methods, replicate caching,
analytic fallbacks) lives in the shared base layer
``repro.core.estimator``; this module supplies only the DML-specific
pieces: the fit program and the replicate-inference dispatch.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.config import CausalConfig
from repro.core.crossfit import CrossfitResult, crossfit
from repro.core.estimands import Diagnostics, compute_diagnostics
from repro.core.estimator import (SandwichEffectResult, inf_cache_field,
                                  resolve_scheme)
from repro.core.final_stage import FinalStageResult, cate_basis, fit_final_stage
from repro.core.nuisance import Nuisance, make_nuisance
from repro.obs.trace import layer_span


@dataclasses.dataclass(frozen=True)
class FitContext:
    """Everything needed to re-run the estimation as one batched program
    (bootstrap replicates re-derive folds from ``key`` for exact replay)."""

    y: jax.Array
    t: jax.Array
    XW: jax.Array     # nuisance covariates (X ++ W)
    phi: jax.Array    # (n, p_phi) CATE basis
    key: jax.Array
    nuis_y: Nuisance
    nuis_t: Nuisance
    tracer: Any = None  # the DML's explicit repro.obs Tracer, if any
    # the DML's replicate closures (dml_bootstrap's ``replicate_fns``)
    replicate_fns: Optional[Dict[Any, Any]] = dataclasses.field(
        default=None, repr=False, compare=False)


@dataclasses.dataclass(frozen=True)
class DMLResult(SandwichEffectResult):
    theta: jax.Array             # (p_phi,) final-stage coefficients
    cov: jax.Array               # (p_phi, p_phi)
    cfg: CausalConfig
    crossfit: CrossfitResult
    final: FinalStageResult
    diagnostics: Diagnostics
    fit_ctx: Optional[FitContext] = None
    _inf_cache: Dict[Any, Any] = inf_cache_field()

    estimator_name = "DML"

    def _runtime_kwargs(self) -> Dict[str, Any]:
        tracer = self.fit_ctx.tracer if self.fit_ctx is not None else None
        return {**super()._runtime_kwargs(), "tracer": tracer}

    def _replicate_inference(self, method, n_boot, exe, alpha):
        """Replicate re-estimation through the task runtime: delete-fold
        jackknife off the existing fold states, or B weighted refits
        (pairs/multiplier bootstrap) as one batched program."""
        from repro.inference import delete_fold_jackknife, dml_bootstrap
        ctx = self.fit_ctx
        rt_kw = self._runtime_kwargs()
        if method == "jackknife":
            cf = self.crossfit
            return delete_fold_jackknife(
                ctx.y, ctx.t, cf.oof_y, cf.oof_t, cf.folds, ctx.phi,
                self.cfg.n_folds, alpha=alpha, executor=exe,
                point=self.theta, point_se=self.stderr,
                row_block=self.cfg.row_block, **rt_kw)
        return dml_bootstrap(
            ctx.nuis_y, ctx.nuis_t, n_folds=self.cfg.n_folds,
            XW=ctx.XW, y=ctx.y, t=ctx.t, phi=ctx.phi,
            key=jax.random.fold_in(ctx.key, 0x0b00), alpha=alpha,
            n_replicates=n_boot, scheme=resolve_scheme(method),
            executor=exe, point=self.theta, point_se=self.stderr,
            row_block=self.cfg.row_block,
            strategy=self.cfg.row_block_strategy,
            replicate_fns=ctx.replicate_fns, **rt_kw)

    def _summary_extra(self):
        d = self.diagnostics
        return (f"ortho-moment |E[e·rt]| = {d.ortho_moment:.2e}",
                f"overlap: propensity in [{d.min_propensity:.3f}, "
                f"{d.max_propensity:.3f}]",
                f"nuisance R²(y) = {d.nuisance_r2_y:.3f}")


class DML:
    """The estimator facade.  Nuisances default from the CausalConfig;
    pass explicit ``Nuisance`` objects to override (e.g. tuned models
    from repro.core.tuning).  ``tracer`` (a repro.obs Tracer) records
    the fit and its replicate inference.

    The estimator keeps the bootstrap's replicate closures for its own
    life (``_replicate_fns``, keyed by what each closure bakes in), so
    every fit after the first reuses the memory model and the compiled
    chunk programs the runtime cached on them."""

    def __init__(self, cfg: CausalConfig,
                 nuisance_y: Optional[Nuisance] = None,
                 nuisance_t: Optional[Nuisance] = None, tracer=None):
        self.cfg = cfg
        t_task = "clf" if cfg.discrete_treatment else "reg"
        self.nuis_y = nuisance_y or make_nuisance(cfg.nuisance_y, "reg", cfg)
        self.nuis_t = nuisance_t or make_nuisance(cfg.nuisance_t, t_task, cfg)
        self.tracer = tracer
        self._replicate_fns: Dict[Any, Any] = {}

    def fit(self, y: jax.Array, t: jax.Array, X: jax.Array,
            W: Optional[jax.Array] = None,
            key: Optional[jax.Array] = None) -> DMLResult:
        """y, t: (n,); X: (n, p) effect-relevant covariates; W: optional
        extra controls (concatenated for nuisance fitting only, exactly
        EconML's X/W split)."""
        with layer_span(self.tracer, "dml.fit", cat="estimator",
                        n=int(y.shape[0]), engine=self.cfg.engine):
            key = key if key is not None else jax.random.PRNGKey(0)
            XW = X if W is None else jnp.concatenate([X, W], axis=1)
            cf = crossfit(self.nuis_y, self.nuis_t, key, XW, y, t,
                          self.cfg.n_folds, self.cfg.engine,
                          tracer=self.tracer)
            with layer_span(self.tracer, "dml.final_stage", cat="estimator"):
                phi = cate_basis(X, self.cfg.cate_features)
                fs = fit_final_stage(y, t, cf.oof_y, cf.oof_t, phi,
                                     row_block=self.cfg.row_block,
                                     strategy=self.cfg.row_block_strategy)
                diag = compute_diagnostics(y, t, cf.oof_y, cf.oof_t,
                                           phi @ fs.theta)
        ctx = FitContext(y=y, t=t, XW=XW, phi=phi, key=key,
                         nuis_y=self.nuis_y, nuis_t=self.nuis_t,
                         tracer=self.tracer,
                         replicate_fns=self._replicate_fns)
        return DMLResult(theta=fs.theta, cov=fs.cov, cfg=self.cfg,
                         crossfit=cf, final=fs, diagnostics=diag,
                         fit_ctx=ctx)

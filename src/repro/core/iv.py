"""Orthogonal instrumental-variable estimators — the paper's remaining
EconML workload (OrthoIV / DMLIV / DRIV are the estimators its case
study parallelizes alongside DML and DRLearner).

Two estimators on the SAME substrate every other estimand uses
(streaming moments engine + crossfit engine + task runtime):

  OrthoIV   partially-linear IV: cross-fit m_y = E[Y|X], m_t = E[T|X],
            m_z = E[Z|X]; solve the residual-on-residual 2SLS moment

                E[ rz · φ(x) · (ry - <θ, φ(x)>·rt) ] = 0
                ⇒  (Σ rz·rt·φφᵀ) θ = Σ rz·ry·φ

            via ONE instrumented augmented Gram (moments.iv_gram, the
            M = [rz·φ | rt·φ | ry] form — bit-identical chunked vs
            whole).  With the constant basis θ is the classic Wald /
            2SLS ratio of residual covariances; under binary-Z
            compliance designs it targets the LATE.

  DRIV      doubly-robust IV CATE (Syrgkanis et al. 2019; EconML's
            DRIV): one more cross-fit nuisance β(x) = E[rt·rz|X] (the
            conditional compliance covariance), a preliminary constant
            OrthoIV estimate θ_pre, and the pseudo-outcome

                ψ = θ_pre + (ry - θ_pre·rt) · rz / clip(β(x))

            regressed on φ(x).  Consistent if either the residual
            nuisances or the preliminary estimate is good; mean ψ is
            the LATE functional with its own bootstrap draws.

Inference mirrors DML: analytic HC0 sandwich CIs for free, replicate
inference (pairs/multiplier bootstrap, delete-fold jackknife) routed
through ``repro.runtime`` chunked scheduling, every replicate closure
built from the replicate-invariant vocabulary so serial ≡ vmap holds
bitwise per replicate.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.config import CausalConfig
from repro.core import moments
from repro.core.crossfit import crossfit_one, fold_ids
from repro.core.estimands import IVDiagnostics, compute_iv_diagnostics
from repro.core.estimator import (PseudoOutcomeEffectResult,
                                  SandwichEffectResult, inf_cache_field,
                                  resolve_scheme)
from repro.core.final_stage import cate_basis
from repro.core.nuisance import Nuisance, make_nuisance, make_ridge
from repro.inference.numerics import det_inv, det_solve


# ---------------------------------------------------------------------------
# Three-nuisance cross-fitting (shared folds, shared engine dispatch).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class IVCrossfitResult:
    oof_y: jax.Array      # (n,) out-of-fold E[Y|X]
    oof_t: jax.Array      # (n,) out-of-fold E[T|X]
    oof_z: jax.Array      # (n,) out-of-fold E[Z|X]
    folds: jax.Array      # (n,) fold assignment
    states_y: Any
    states_t: Any
    states_z: Any


def iv_crossfit(nuis_y: Nuisance, nuis_t: Nuisance, nuis_z: Nuisance,
                key: jax.Array, X: jax.Array, y: jax.Array, t: jax.Array,
                z: jax.Array, k: int, engine: str = "parallel"
                ) -> IVCrossfitResult:
    """Cross-fit the three IV nuisances over ONE fold assignment — three
    ``crossfit_one`` dispatches through whichever engine cfg selects
    (parallel / sequential / parallel_loo / an Executor instance)."""
    kf, ky, kt, kz = jax.random.split(key, 4)
    folds = fold_ids(kf, X.shape[0], k)
    oof_y, st_y = crossfit_one(nuis_y, ky, X, y, folds, k, engine)
    oof_t, st_t = crossfit_one(nuis_t, kt, X, t, folds, k, engine)
    oof_z, st_z = crossfit_one(nuis_z, kz, X, z, folds, k, engine)
    return IVCrossfitResult(oof_y=oof_y, oof_t=oof_t, oof_z=oof_z,
                            folds=folds, states_y=st_y, states_t=st_t,
                            states_z=st_z)


# ---------------------------------------------------------------------------
# Instrumented final stage.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class IVFinalStageResult:
    theta: jax.Array       # (p_phi,)
    cov: jax.Array         # (p_phi, p_phi) HC0 sandwich
    j_gram: jax.Array      # (p_phi, p_phi) Σ rz·rt·φφᵀ / n
    n: int

    @property
    def stderr(self) -> jax.Array:
        return jnp.sqrt(jnp.diag(self.cov))


def fit_iv_final_stage(ry: jax.Array, rt: jax.Array, rz: jax.Array,
                       phi: jax.Array, *, w: Optional[jax.Array] = None,
                       ridge: float = 1e-8, row_block: int = 0,
                       strategy: Optional[str] = None
                       ) -> IVFinalStageResult:
    """Solve the instrumented orthogonal moment Jθ = b with HC0
    sandwich covariance — all statistics off one ``iv_gram`` pass plus
    one meat pass, streamed in fixed-order row blocks when
    ``row_block > 0``.  Deterministic Gauss-Jordan solves (no LAPACK),
    so the point fit is bitwise the w=1 replicate."""
    n, p = phi.shape
    f32 = jnp.float32
    ws = jnp.ones((n,), f32) if w is None else w.astype(f32)
    Gaug, n_eff = moments.iv_gram(ry, rt, rz, phi, ws,
                                  row_block=row_block, strategy=strategy)
    J, b, _, _ = moments.iv_slices(Gaug, p)
    n_eff = jnp.maximum(n_eff, 1.0)
    A = J + ridge * n_eff * jnp.eye(p, dtype=f32)
    theta = det_solve(A, b)
    meat = moments.iv_meat(ry, rt, rz, phi, theta, w=w,
                           row_block=row_block, strategy=strategy)
    Ainv = det_inv(A)
    cov = jnp.einsum("ia,ab,bj->ij", Ainv, meat, Ainv)
    return IVFinalStageResult(theta=theta, cov=cov, j_gram=J / n, n=n)


# ---------------------------------------------------------------------------
# OrthoIV — the partially-linear IV estimator facade.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class IVFitContext:
    """Replay context for replicate inference (bootstrap replicates
    re-derive folds from ``key``, exactly like DML's FitContext)."""

    y: jax.Array
    t: jax.Array
    z: jax.Array
    XW: jax.Array     # nuisance covariates (X ++ W)
    phi: jax.Array    # (n, p_phi) CATE basis
    key: jax.Array
    nuis_y: Nuisance
    nuis_t: Nuisance
    nuis_z: Nuisance
    compliance: Optional[Nuisance] = None   # DRIV's β(x) model


@dataclasses.dataclass(frozen=True)
class OrthoIVResult(SandwichEffectResult):
    theta: jax.Array             # (p_phi,) final-stage coefficients
    cov: jax.Array               # (p_phi, p_phi)
    cfg: CausalConfig
    crossfit: IVCrossfitResult
    final: IVFinalStageResult
    diagnostics: IVDiagnostics
    fit_ctx: Optional[IVFitContext] = None
    _inf_cache: Dict[Any, Any] = inf_cache_field()

    estimator_name = "OrthoIV"

    def _replicate_inference(self, method, n_boot, exe, alpha):
        """Replicate inference through the task runtime: delete-fold
        jackknife off ONE segmented instrumented-Gram pass, or B
        weighted 2SLS refits as one batched program."""
        from repro.inference import iv_bootstrap
        from repro.inference.jackknife import delete_fold_jackknife_iv
        ctx = self.fit_ctx
        rt_kw = self._runtime_kwargs()
        if method == "jackknife":
            cf = self.crossfit
            return delete_fold_jackknife_iv(
                ctx.y, ctx.t, ctx.z, cf.oof_y, cf.oof_t, cf.oof_z,
                cf.folds, ctx.phi, self.cfg.n_folds, alpha=alpha,
                executor=exe, point=self.theta, point_se=self.stderr,
                row_block=self.cfg.row_block, **rt_kw)
        return iv_bootstrap(
            ctx.nuis_y, ctx.nuis_t, ctx.nuis_z,
            n_folds=self.cfg.n_folds, XW=ctx.XW, y=ctx.y, t=ctx.t,
            z=ctx.z, phi=ctx.phi,
            key=jax.random.fold_in(ctx.key, 0x1b00), alpha=alpha,
            n_replicates=n_boot, scheme=resolve_scheme(method),
            executor=exe, point=self.theta, point_se=self.stderr,
            row_block=self.cfg.row_block, **rt_kw)

    def _summary_extra(self):
        d = self.diagnostics
        flag = "WEAK" if d.weak_instrument else "ok"
        return (f"IV-moment |E[e·rz]| = {d.ortho_moment:.2e}",
                f"first-stage F = {d.first_stage_f:.1f} [{flag}]",
                f"corr(rz, rt) = {d.instrument_corr:+.3f}",
                f"instrument overlap: E[Z|X] in "
                f"[{d.min_instrument_propensity:.3f}, "
                f"{d.max_instrument_propensity:.3f}]")


class OrthoIV:
    """Partially-linear IV via the residual-on-residual 2SLS moment.
    Nuisances default from the CausalConfig (``nuisance_z`` selects the
    instrument model: logistic for a binary instrument, ridge/mlp
    otherwise); pass explicit ``Nuisance`` objects to override (tuned
    models from repro.core.tuning)."""

    def __init__(self, cfg: CausalConfig,
                 nuisance_y: Optional[Nuisance] = None,
                 nuisance_t: Optional[Nuisance] = None,
                 nuisance_z: Optional[Nuisance] = None):
        self.cfg = cfg
        t_task = "clf" if cfg.discrete_treatment else "reg"
        z_task = "clf" if cfg.discrete_instrument else "reg"
        z_kind = cfg.nuisance_z if cfg.discrete_instrument else (
            "ridge" if cfg.nuisance_z == "logistic" else cfg.nuisance_z)
        self.nuis_y = nuisance_y or make_nuisance(cfg.nuisance_y, "reg", cfg)
        self.nuis_t = nuisance_t or make_nuisance(cfg.nuisance_t, t_task, cfg)
        self.nuis_z = nuisance_z or make_nuisance(z_kind, z_task, cfg)

    def fit(self, y: jax.Array, t: jax.Array, z: jax.Array,
            X: jax.Array, W: Optional[jax.Array] = None,
            key: Optional[jax.Array] = None) -> OrthoIVResult:
        """y, t, z: (n,); X: (n, p) effect covariates; W: optional extra
        controls (nuisance fitting only, EconML's X/W split)."""
        key = key if key is not None else jax.random.PRNGKey(0)
        XW = X if W is None else jnp.concatenate([X, W], axis=1)
        cf = iv_crossfit(self.nuis_y, self.nuis_t, self.nuis_z, key, XW,
                         y, t, z, self.cfg.n_folds, self.cfg.engine)
        f32 = jnp.float32
        ry = y.astype(f32) - cf.oof_y
        rt = t.astype(f32) - cf.oof_t
        rz = z.astype(f32) - cf.oof_z
        phi = cate_basis(X, self.cfg.cate_features)
        fs = fit_iv_final_stage(ry, rt, rz, phi,
                                row_block=self.cfg.row_block,
                                strategy=self.cfg.row_block_strategy)
        e = ry - (rt[:, None] * phi * fs.theta[None, :]).sum(axis=1)
        diag = compute_iv_diagnostics(t, z, cf.oof_t, cf.oof_z, e)
        ctx = IVFitContext(y=y, t=t, z=z, XW=XW, phi=phi, key=key,
                           nuis_y=self.nuis_y, nuis_t=self.nuis_t,
                           nuis_z=self.nuis_z)
        return OrthoIVResult(theta=fs.theta, cov=fs.cov, cfg=self.cfg,
                             crossfit=cf, final=fs, diagnostics=diag,
                             fit_ctx=ctx)


# ---------------------------------------------------------------------------
# DRIV — doubly-robust IV CATE.
# ---------------------------------------------------------------------------

def clip_compliance(beta: jax.Array, clip: float) -> jax.Array:
    """Sign-preserving magnitude floor on the compliance denominator
    β(x) = E[rt·rz|X] (EconML's cov_clip): zero crossings clamp to
    +clip."""
    return jnp.where(beta >= 0, jnp.maximum(beta, clip),
                     jnp.minimum(beta, -clip))


@dataclasses.dataclass(frozen=True)
class DRIVResult(PseudoOutcomeEffectResult):
    ate: float                # mean pseudo-outcome: the LATE functional
    stderr: float
    theta: jax.Array          # CATE coefficients on phi(x)
    pseudo: jax.Array         # (n,) DRIV pseudo-outcomes
    theta_pre: float          # the preliminary constant OrthoIV estimate
    diagnostics: IVDiagnostics
    cfg: Optional[CausalConfig] = None
    fit_ctx: Optional[IVFitContext] = None
    _inf_cache: Dict[Any, Any] = inf_cache_field()

    estimator_name = "DRIV"

    late = property(lambda self: self.ate)

    def _resolve_method(self, method):
        if method == "jackknife":
            # unlike OrthoIV, the DRIV pipeline has no LOO-identity
            # shortcut (the pseudo-outcome depends on every fold's
            # nuisances); silently substituting a bootstrap would make
            # jackknife-vs-jackknife comparisons lie
            raise ValueError(
                "DRIV has no delete-fold jackknife; use "
                "method='bootstrap'|'multiplier', or OrthoIV for a "
                "jackknife over the instrumented moment")
        return method

    def _replicate_inference(self, method, n_boot, exe, alpha):
        """Bootstrap the whole DRIV pipeline (nuisances, compliance,
        preliminary estimate, pseudo-outcome regression) as one
        runtime-scheduled program (the LATE functional's own draws ride
        along)."""
        from repro.inference import driv_bootstrap
        cfg = self._config()
        ctx = self.fit_ctx
        return driv_bootstrap(
            ctx.nuis_y, ctx.nuis_t, ctx.nuis_z, ctx.compliance,
            n_folds=cfg.n_folds, XW=ctx.XW, y=ctx.y, t=ctx.t, z=ctx.z,
            phi=ctx.phi, key=jax.random.fold_in(ctx.key, 0x1b00),
            alpha=alpha, n_replicates=n_boot,
            scheme=resolve_scheme(method), executor=exe,
            cov_clip=cfg.iv_cov_clip, point=self.theta,
            ate_point=self.ate,
            row_block=cfg.row_block, **self._runtime_kwargs())


class DRIV:
    """fit(y, t, z, X): 4 cross-fit nuisances (m_y, m_t, m_z, β) + the
    doubly-robust pseudo-outcome regression."""

    def __init__(self, cfg: CausalConfig,
                 nuisance_y: Optional[Nuisance] = None,
                 nuisance_t: Optional[Nuisance] = None,
                 nuisance_z: Optional[Nuisance] = None,
                 compliance: Optional[Nuisance] = None):
        self.cfg = cfg
        base = OrthoIV(cfg, nuisance_y, nuisance_t, nuisance_z)
        self.nuis_y, self.nuis_t, self.nuis_z = (base.nuis_y, base.nuis_t,
                                                 base.nuis_z)
        # β(x) = E[rt·rz|X] is a regression whatever Z/T are
        self.compliance = compliance or make_ridge(
            cfg.ridge_lambda, row_block=cfg.row_block,
            strategy=cfg.row_block_strategy)

    def fit(self, y: jax.Array, t: jax.Array, z: jax.Array,
            X: jax.Array, W: Optional[jax.Array] = None,
            key: Optional[jax.Array] = None) -> DRIVResult:
        cfg = self.cfg
        key = key if key is not None else jax.random.PRNGKey(0)
        XW = X if W is None else jnp.concatenate([X, W], axis=1)
        n = X.shape[0]
        f32 = jnp.float32
        cf = iv_crossfit(self.nuis_y, self.nuis_t, self.nuis_z, key, XW,
                         y, t, z, cfg.n_folds, cfg.engine)
        ry = y.astype(f32) - cf.oof_y
        rt = t.astype(f32) - cf.oof_t
        rz = z.astype(f32) - cf.oof_z

        # compliance nuisance on the SAME folds: β(x) = E[rt·rz | X]
        kb = jax.random.fold_in(key, 0xbe7a)
        oof_b, _ = crossfit_one(self.compliance, kb, XW, rt * rz,
                                cf.folds, cfg.n_folds, cfg.engine)
        beta = clip_compliance(oof_b, cfg.iv_cov_clip)

        # preliminary constant OrthoIV estimate (same moment, phi = 1)
        ones = jnp.ones((n, 1), f32)
        pre = fit_iv_final_stage(ry, rt, rz, ones,
                                 row_block=cfg.row_block,
                                 strategy=cfg.row_block_strategy)
        theta_pre = pre.theta[0]

        psi = theta_pre + (ry - theta_pre * rt) * rz / beta
        ate = float(psi.mean())
        se = float(psi.std(ddof=1) / jnp.sqrt(n))

        # pseudo-outcome regression: one augmented-moments pass
        phi = cate_basis(X, cfg.cate_features)
        q = phi.shape[1]
        Gaug, _ = moments.weighted_gram(phi, jnp.ones((n,), f32),
                                        append=psi,
                                        row_block=cfg.row_block,
                                        strategy=cfg.row_block_strategy)
        G = Gaug[:q, :q] + 1e-8 * n * jnp.eye(q)
        theta = det_solve(G, Gaug[:q, q])

        # the orthogonality diagnostic checks the moment that was
        # actually zeroed — the preliminary 2SLS solve's residual (the
        # pseudo-outcome-regression theta is a projection of ψ, not a
        # solution of E[e·rz·φ] = 0)
        e = ry - theta_pre * rt
        diag = compute_iv_diagnostics(t, z, cf.oof_t, cf.oof_z, e)
        ctx = IVFitContext(y=y, t=t, z=z, XW=XW, phi=phi, key=key,
                           nuis_y=self.nuis_y, nuis_t=self.nuis_t,
                           nuis_z=self.nuis_z, compliance=self.compliance)
        return DRIVResult(ate=ate, stderr=se, theta=theta, pseudo=psi,
                          theta_pre=float(theta_pre), diagnostics=diag,
                          cfg=cfg, fit_ctx=ctx)

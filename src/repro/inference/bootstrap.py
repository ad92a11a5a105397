"""Bootstrap re-estimation as ONE batched program.

EconML's ``BootstrapInference(n_bootstrap_samples=B)`` re-runs the whole
estimator B times — the most expensive iterative step the paper's Ray
translation targets.  Here each replicate is a *weighted* refit (pairs
bootstrap = multinomial row counts; multiplier/Bayesian = Exp(1) row
weights), which reuses the weighted-fit path that ``fold_weights``
already exercises for C1: replicate weights multiply the fold-complement
masks, so the (B, k, n) weight tensor turns B full re-estimations into
one stacked program dispatched by an Executor.

Replay: replicate b derives all of its randomness (resampling weights
AND fold assignment) from ``fold_in(base_key, b)`` — any replicate can
be re-run alone, bit-identically, which is the SPMD translation of Ray's
lineage-based reconstruction.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.crossfit import _oof_select, fold_ids, fold_weights
from repro.core.nuisance import Nuisance
from repro.inference.intervals import InferenceResult
from repro.inference.numerics import (logistic_fit_folds_w,
                                      predict_folds_linear,
                                      predict_folds_logistic,
                                      ridge_fit_folds_w,
                                      weighted_iv_theta, weighted_theta)

SCHEMES = ("pairs", "multiplier", "bayesian")


def bootstrap_weights(key: jax.Array, n: int, scheme: str) -> jax.Array:
    """Per-row resampling weights, mean ≈ 1.

    pairs       multinomial counts (classic resample-with-replacement);
                integer counts -> exactly batch-invariant;
    multiplier  i.i.d. Exp(1) multipliers (= Bayesian bootstrap /
                Rubin's Dirichlet weights up to normalization).
    """
    if scheme == "pairs":
        idx = jax.random.randint(key, (n,), 0, n)
        return jnp.bincount(idx, length=n).astype(jnp.float32)
    if scheme in ("multiplier", "bayesian"):
        return jax.random.exponential(key, (n,), jnp.float32)
    raise ValueError(f"unknown bootstrap scheme {scheme!r}")


def replicate_keys(key: jax.Array, n_replicates: int) -> jax.Array:
    """(B, key) stack where replicate b's key is ``fold_in(base, b)`` —
    NOT ``split(base, B)``, so replicate b is independent of B: a B=100
    run is a bit-exact prefix of a B=200 run, and any single replicate
    can be replayed alone (the lineage property)."""
    return jax.vmap(lambda b: jax.random.fold_in(key, b))(
        jnp.arange(n_replicates, dtype=jnp.uint32))


def _hyper(nuis: Nuisance, name: str, default):
    h = getattr(nuis, "hyper", None) or {}
    return h.get(name, default)


def fit_predict_folds(nuis: Nuisance, key: jax.Array, X: jax.Array,
                      target: jax.Array, Wk: jax.Array,
                      row_block: int = 0) -> jax.Array:
    """(k, n) fold-model predictions under weighted training.

    ridge/logistic take the replicate-invariant fold-batched kernels
    (serial == vmap bitwise), streamed in row blocks when the nuisance
    carries a ``row_block`` hyper (or one is passed) through the
    nuisance's ``strategy`` hyper (the pallas strategy takes the fused
    fold-weighted kernel, like the point fit); other nuisances
    (MLP, custom) fall back to vmapping ``nuis.fit`` over folds —
    statistically identical, but LAPACK-free bit-identity is not
    guaranteed there.
    """
    rb = row_block or int(_hyper(nuis, "row_block", 0))
    st = _hyper(nuis, "strategy", None)
    if nuis.name == "ridge":
        lam = _hyper(nuis, "lam", 1e-3)
        return predict_folds_linear(
            ridge_fit_folds_w(lam, X, target, Wk, row_block=rb,
                              strategy=st), X)
    if nuis.name == "logistic":
        lam = _hyper(nuis, "lam", 1e-3)
        iters = int(_hyper(nuis, "iters", 16))
        return predict_folds_logistic(
            logistic_fit_folds_w(lam, iters, X, target, Wk,
                                 row_block=rb, strategy=st), X)
    k = Wk.shape[0]
    keys = jax.random.split(key, k)
    st0 = jax.vmap(nuis.init, in_axes=(0, None))(keys, X.shape[1])
    st = jax.vmap(nuis.fit, in_axes=(0, None, None, 0))(st0, X, target, Wk)
    return jax.vmap(nuis.predict, in_axes=(0, None))(st, X)


def shares_nuisance_gram(nuis_y: Nuisance, nuis_t: Nuisance) -> bool:
    """Whether a DML re-estimation fits both nuisances from ONE
    fold-weighted Gram over [X | 1 | y | t]: both are ridge, streamed
    alike (the same ``row_block`` and ``strategy`` hypers).  Any other
    pair fits each nuisance on its own."""
    return (nuis_y.name == nuis_t.name == "ridge"
            and all(_hyper(nuis_y, h, None) == _hyper(nuis_t, h, None)
                    for h in ("row_block", "strategy")))


def _ridge_pair_predict_folds(nuis_y: Nuisance, nuis_t: Nuisance,
                              X: jax.Array, y: jax.Array, t: jax.Array,
                              Wk: jax.Array, row_block: int = 0):
    """The (k, n) fold-model predictions of the y and t ridges from one
    Gram and, when the penalties agree, one elimination — the bits of
    two ``fit_predict_folds`` calls.  Each ridge predicts on its own: a
    stacked (2k, n) prediction leaves one of its halves in HBM, where
    the out-of-fold gather reads it at half the speed (v5e)."""
    f32 = jnp.float32
    beta_y, beta_t = ridge_fit_folds_w(
        (_hyper(nuis_y, "lam", 1e-3), _hyper(nuis_t, "lam", 1e-3)), X,
        jnp.stack([y.astype(f32), t.astype(f32)], axis=1), Wk,
        row_block=row_block or int(_hyper(nuis_y, "row_block", 0)),
        strategy=_hyper(nuis_y, "strategy", None))
    return predict_folds_linear(beta_y, X), predict_folds_linear(beta_t, X)


def dml_residuals_once(nuis_y: Nuisance, nuis_t: Nuisance, n_folds: int,
                       XW: jax.Array, y: jax.Array, t: jax.Array,
                       key: jax.Array, w: jax.Array, *,
                       row_block: int = 0) -> Dict[str, jax.Array]:
    """The nuisance prefix of one weighted DML re-estimation: folds
    re-derived from ``key``, both nuisances cross-fit under
    ``fold_weights * w``, returning the orthogonal residuals
    {ry, rt}.  Split out so sweep cells that differ only in final
    stage can share one nuisance pass (repro.sweep).  Two ridges share
    their Gram (``shares_nuisance_gram``)."""
    kf, ky, kt = jax.random.split(key, 3)
    folds = fold_ids(kf, XW.shape[0], n_folds)
    Wk = fold_weights(folds, n_folds) * w[None, :]
    if shares_nuisance_gram(nuis_y, nuis_t):
        pred_y, pred_t = _ridge_pair_predict_folds(nuis_y, nuis_t, XW, y,
                                                   t, Wk, row_block)
        oof_y = _oof_select(pred_y, folds)
        oof_t = _oof_select(pred_t, folds)
    else:
        oof_y = _oof_select(fit_predict_folds(nuis_y, ky, XW, y, Wk,
                                              row_block), folds)
        oof_t = _oof_select(fit_predict_folds(nuis_t, kt, XW, t, Wk,
                                              row_block), folds)
    return {"ry": y.astype(jnp.float32) - oof_y,
            "rt": t.astype(jnp.float32) - oof_t}


def dml_theta_once(nuis_y: Nuisance, nuis_t: Nuisance, n_folds: int,
                   XW: jax.Array, y: jax.Array, t: jax.Array,
                   phi: jax.Array, key: jax.Array, w: jax.Array,
                   *, with_se: bool = True, row_block: int = 0,
                   strategy: Optional[str] = None
                   ) -> Dict[str, jax.Array]:
    """One full weighted DML re-estimation (the replicate closure body):
    fold keys re-derived from ``key``, nuisances cross-fit under
    ``fold_weights * w``, weighted orthogonal final stage.  Pure and
    jit/vmap-compatible."""
    r = dml_residuals_once(nuis_y, nuis_t, n_folds, XW, y, t, key, w,
                           row_block=row_block)
    with jax.named_scope("dml.final_stage"):
        theta, se = weighted_theta(r["ry"], r["rt"], phi, w,
                                   with_se=with_se, row_block=row_block,
                                   strategy=strategy)
    out = {"theta": theta}
    if se is not None:
        out["se"] = se
    return out


def make_dml_replicate_fn(nuis_y: Nuisance, nuis_t: Nuisance,
                          n_folds: int, *, scheme: str = "pairs",
                          with_se: bool = True, row_block: int = 0,
                          strategy: Optional[str] = None):
    """The bootstrap replicate closure: (key, XW, y, t, phi) ->
    {theta[, se]}.  The data tensors arrive as executor pass-through
    arguments (not closure constants) so compiled programs take them as
    real inputs.  The runtime keys its memory probes, its compiled
    chunk programs and the executors' jit caches on the closure object,
    so the closure is built once per estimator and reused by every fit:
    ``DML`` owns a dict of them (handed to ``dml_bootstrap`` as
    ``replicate_fns`` through its ``FitContext``), and the closures,
    with the programs cached on them, live as long as the estimator or
    one of its results does."""

    def replicate(kb, XW, y, t, phi):
        with jax.named_scope("inference.replicate"):
            kw, kfit = jax.random.split(kb)
            w = bootstrap_weights(kw, XW.shape[0], scheme)
            return dml_theta_once(nuis_y, nuis_t, n_folds, XW, y, t, phi,
                                  kfit, w, with_se=with_se,
                                  row_block=row_block, strategy=strategy)

    return replicate


def dml_bootstrap(nuis_y: Nuisance, nuis_t: Nuisance, *, n_folds: int,
                  XW: jax.Array, y: jax.Array, t: jax.Array,
                  phi: jax.Array, key: jax.Array,
                  n_replicates: int = 200, scheme: str = "pairs",
                  executor="vmap", alpha: float = 0.05,
                  with_se: bool = True,
                  point: Optional[jax.Array] = None,
                  point_se: Optional[jax.Array] = None,
                  mesh=None,
                  row_block: int = 0, strategy: Optional[str] = None,
                  memory_budget: int = 0, chunk: int = 0,
                  max_retries: int = 2, tracer=None,
                  replicate_fns: Optional[dict] = None) -> InferenceResult:
    """B weighted DML refits scheduled by the task runtime: the
    replicate axis streams in memory-budgeted chunks (repro.runtime),
    each chunk retrying down the backend ladder on failure — results
    are replicate-ordered and bit-identical across all of it.  The
    ``inference.bootstrap`` span goes to ``tracer`` (a repro.obs
    Tracer) or the runtime's, else to the process tracer.

    ``replicate_fns`` is the caller's cache of replicate closures (the
    estimator's, so a warm fit finds its memory model and compiled
    chunks in the runtime's caches); without it every call builds a
    fresh closure.  Each call counts ``inference.replicate_fn[built]``
    or ``[reused]`` on the process registry, and the span carries the
    same word as ``replicate_fn``; likewise
    ``inference.nuisance_gram[shared|separate]`` and ``nuisance_gram``
    say whether the replicate fits both nuisances from one Gram."""
    from repro.obs.metrics import default_registry
    from repro.obs.trace import layer_span
    from repro.runtime import as_runtime
    rt = as_runtime(executor, mesh=mesh,
                    memory_budget=memory_budget, chunk=chunk,
                    max_retries=max_retries, tracer=tracer)
    with layer_span(rt.tracer, "inference.bootstrap", cat="inference",
                    b=n_replicates, scheme=scheme) as sp:
        keys = replicate_keys(key, n_replicates)
        # everything the closure bakes in
        fn_key = (nuis_y, nuis_t, n_folds, scheme, with_se, row_block,
                  strategy)
        replicate = (replicate_fns or {}).get(fn_key)
        status = "built" if replicate is None else "reused"
        if replicate is None:
            replicate = make_dml_replicate_fn(nuis_y, nuis_t, n_folds,
                                              scheme=scheme,
                                              with_se=with_se,
                                              row_block=row_block,
                                              strategy=strategy)
            if replicate_fns is not None:
                replicate_fns[fn_key] = replicate
        default_registry().counter(f"inference.replicate_fn[{status}]").inc()
        sp.attrs["replicate_fn"] = status
        gram = ("shared" if shares_nuisance_gram(nuis_y, nuis_t)
                else "separate")
        default_registry().counter(f"inference.nuisance_gram[{gram}]").inc()
        sp.attrs["nuisance_gram"] = gram
        out = rt.map(replicate, keys, XW, y, t, phi, label="dml_bootstrap")
        thetas = out["theta"]
        se = jnp.std(thetas, axis=0, ddof=1)
    return InferenceResult(
        method=scheme, executor=rt.name,
        point=thetas.mean(axis=0) if point is None else point,
        replicates=thetas, se=se, alpha=alpha, point_se=point_se,
        replicate_se=out.get("se"))


def iv_residuals_once(nuis_y: Nuisance, nuis_t: Nuisance,
                      nuis_z: Nuisance, n_folds: int, XW: jax.Array,
                      y: jax.Array, t: jax.Array, z: jax.Array,
                      key: jax.Array, w: jax.Array, *,
                      row_block: int = 0) -> Dict[str, jax.Array]:
    """The nuisance prefix of one weighted OrthoIV re-estimation: folds
    re-derived from ``key``, the THREE nuisances cross-fit under
    ``fold_weights * w``, returning the residual triple {ry, rt, rz}
    (shared by sweep cells that differ only in final stage)."""
    kf, ky, kt, kz = jax.random.split(key, 4)
    folds = fold_ids(kf, XW.shape[0], n_folds)
    Wk = fold_weights(folds, n_folds) * w[None, :]
    oof_y = _oof_select(fit_predict_folds(nuis_y, ky, XW, y, Wk,
                                          row_block), folds)
    oof_t = _oof_select(fit_predict_folds(nuis_t, kt, XW, t, Wk,
                                          row_block), folds)
    oof_z = _oof_select(fit_predict_folds(nuis_z, kz, XW, z, Wk,
                                          row_block), folds)
    return {"ry": y.astype(jnp.float32) - oof_y,
            "rt": t.astype(jnp.float32) - oof_t,
            "rz": z.astype(jnp.float32) - oof_z}


def iv_theta_once(nuis_y: Nuisance, nuis_t: Nuisance, nuis_z: Nuisance,
                  n_folds: int, XW: jax.Array, y: jax.Array,
                  t: jax.Array, z: jax.Array, phi: jax.Array,
                  key: jax.Array, w: jax.Array, *, with_se: bool = True,
                  row_block: int = 0) -> Dict[str, jax.Array]:
    """One full weighted OrthoIV re-estimation (the replicate closure
    body): folds re-derived from ``key``, the THREE nuisances cross-fit
    under ``fold_weights * w``, weighted instrumented final stage.
    Pure, jit/vmap-compatible, built only from the replicate-invariant
    vocabulary."""
    r = iv_residuals_once(nuis_y, nuis_t, nuis_z, n_folds, XW, y, t, z,
                          key, w, row_block=row_block)
    theta, se = weighted_iv_theta(r["ry"], r["rt"], r["rz"], phi, w,
                                  with_se=with_se, row_block=row_block)
    out = {"theta": theta}
    if se is not None:
        out["se"] = se
    return out


def iv_bootstrap(nuis_y: Nuisance, nuis_t: Nuisance, nuis_z: Nuisance,
                 *, n_folds: int, XW: jax.Array, y: jax.Array,
                 t: jax.Array, z: jax.Array, phi: jax.Array,
                 key: jax.Array, n_replicates: int = 200,
                 scheme: str = "pairs", executor="vmap",
                 alpha: float = 0.05, with_se: bool = True,
                 point: Optional[jax.Array] = None,
                 point_se: Optional[jax.Array] = None,
                 mesh=None, row_block: int = 0,
                 memory_budget: int = 0, chunk: int = 0,
                 max_retries: int = 2) -> InferenceResult:
    """B weighted OrthoIV refits through the task runtime — the same
    chunked, fault-tolerant, replicate-ordered scheduling as
    dml_bootstrap."""
    from repro.runtime import as_runtime
    rt_ = as_runtime(executor, mesh=mesh,
                     memory_budget=memory_budget, chunk=chunk,
                     max_retries=max_retries)
    keys = replicate_keys(key, n_replicates)

    def replicate(kb, XW_, y_, t_, z_, phi_):
        kw, kfit = jax.random.split(kb)
        w = bootstrap_weights(kw, XW_.shape[0], scheme)
        return iv_theta_once(nuis_y, nuis_t, nuis_z, n_folds, XW_, y_,
                             t_, z_, phi_, kfit, w, with_se=with_se,
                             row_block=row_block)

    out = rt_.map(replicate, keys, XW, y, t, z, phi, label="iv_bootstrap")
    thetas = out["theta"]
    return InferenceResult(
        method=scheme, executor=rt_.name,
        point=thetas.mean(axis=0) if point is None else point,
        replicates=thetas, se=jnp.std(thetas, axis=0, ddof=1),
        alpha=alpha, point_se=point_se, replicate_se=out.get("se"))


def driv_theta_once(nuis_y: Nuisance, nuis_t: Nuisance, nuis_z: Nuisance,
                    compliance: Nuisance, n_folds: int, XW: jax.Array,
                    y: jax.Array, t: jax.Array, z: jax.Array,
                    phi: jax.Array, key: jax.Array, w: jax.Array, *,
                    cov_clip: float = 0.1, with_se: bool = True,
                    row_block: int = 0) -> Dict[str, jax.Array]:
    """One weighted DRIV re-estimation (mirrors DRIV.fit): weighted
    residual nuisances + weighted compliance fit β(x) = E[rt·rz|X],
    preliminary weighted constant OrthoIV, pseudo-outcome regression on
    phi.  Draws the LATE functional (weighted mean ψ) alongside
    theta."""
    from repro.core.iv import clip_compliance
    f32 = jnp.float32
    n = XW.shape[0]
    kf, ky, kt, kz, kb = jax.random.split(key, 5)
    folds = fold_ids(kf, n, n_folds)
    Wk = fold_weights(folds, n_folds) * w[None, :]
    oof_y = _oof_select(fit_predict_folds(nuis_y, ky, XW, y, Wk,
                                          row_block), folds)
    oof_t = _oof_select(fit_predict_folds(nuis_t, kt, XW, t, Wk,
                                          row_block), folds)
    oof_z = _oof_select(fit_predict_folds(nuis_z, kz, XW, z, Wk,
                                          row_block), folds)
    ry = y.astype(f32) - oof_y
    rt = t.astype(f32) - oof_t
    rz = z.astype(f32) - oof_z
    oof_b = _oof_select(fit_predict_folds(compliance, kb, XW, rt * rz,
                                          Wk, row_block), folds)
    beta = clip_compliance(oof_b, cov_clip)
    ones = jnp.ones((n, 1), f32)
    th_pre, _ = weighted_iv_theta(ry, rt, rz, ones, w, with_se=False,
                                  row_block=row_block)
    psi = th_pre[0] + (ry - th_pre[0] * rt) * rz / beta
    theta, se = weighted_theta(psi, jnp.ones((n,), f32), phi, w,
                               with_se=with_se, row_block=row_block)
    wf = w.astype(f32)
    ate = (wf * psi).sum() / jnp.maximum(wf.sum(), 1.0)
    out = {"theta": theta, "ate": ate}
    if se is not None:
        out["se"] = se
    return out


def driv_bootstrap(nuis_y: Nuisance, nuis_t: Nuisance, nuis_z: Nuisance,
                   compliance: Nuisance, *, n_folds: int, XW: jax.Array,
                   y: jax.Array, t: jax.Array, z: jax.Array,
                   phi: jax.Array, key: jax.Array,
                   n_replicates: int = 200, scheme: str = "pairs",
                   executor="vmap", alpha: float = 0.05,
                   cov_clip: float = 0.1, with_se: bool = True,
                   point: Optional[jax.Array] = None,
                   point_se: Optional[jax.Array] = None,
                   ate_point: Optional[float] = None,
                   mesh=None, row_block: int = 0,
                   memory_budget: int = 0, chunk: int = 0,
                   max_retries: int = 2) -> InferenceResult:
    """B weighted DRIV refits through the task runtime; the LATE
    functional's own draws ride along (ate_interval centers on mean ψ,
    not theta[0], exactly like dr_bootstrap)."""
    from repro.runtime import as_runtime
    rt_ = as_runtime(executor, mesh=mesh,
                     memory_budget=memory_budget, chunk=chunk,
                     max_retries=max_retries)
    keys = replicate_keys(key, n_replicates)

    def replicate(kb, XW_, y_, t_, z_, phi_):
        kw, kfit = jax.random.split(kb)
        w = bootstrap_weights(kw, XW_.shape[0], scheme)
        return driv_theta_once(nuis_y, nuis_t, nuis_z, compliance,
                               n_folds, XW_, y_, t_, z_, phi_, kfit, w,
                               cov_clip=cov_clip, with_se=with_se,
                               row_block=row_block)

    out = rt_.map(replicate, keys, XW, y, t, z, phi,
                  label="driv_bootstrap")
    thetas = out["theta"]
    return InferenceResult(
        method=scheme, executor=rt_.name,
        point=thetas.mean(axis=0) if point is None else point,
        replicates=thetas, se=jnp.std(thetas, axis=0, ddof=1),
        alpha=alpha, point_se=point_se, replicate_se=out.get("se"),
        ate_replicates=out["ate"], ate_point=ate_point)


def dr_theta_once(outcome: Nuisance, propensity: Nuisance, n_folds: int,
                  X: jax.Array, y: jax.Array, t: jax.Array,
                  phi: jax.Array, key: jax.Array, w: jax.Array,
                  *, clip: float = 0.01, with_se: bool = True,
                  row_block: int = 0) -> Dict[str, jax.Array]:
    """One weighted AIPW re-estimation (mirrors DRLearner.fit): weighted
    arm-wise outcome fits + weighted propensity, weighted pseudo-outcome
    regression on phi.  With the constant basis theta[0] IS the weighted
    ATE."""
    kf, k0, k1, ke = jax.random.split(key, 4)
    n = X.shape[0]
    folds = fold_ids(kf, n, n_folds)
    W = fold_weights(folds, n_folds)
    tt = t.astype(jnp.float32)
    arm0 = (1.0 - tt)[None, :]
    arm1 = tt[None, :]
    wk = w[None, :]
    m0 = _oof_select(fit_predict_folds(outcome, k0, X, y,
                                       W * arm0 * wk, row_block), folds)
    m1 = _oof_select(fit_predict_folds(outcome, k1, X, y,
                                       W * arm1 * wk, row_block), folds)
    e = _oof_select(fit_predict_folds(propensity, ke, X, tt, W * wk,
                                      row_block), folds)
    e = jnp.clip(e, clip, 1.0 - clip)
    psi = (m1 - m0
           + tt * (y - m1) / e
           - (1.0 - tt) * (y - m0) / (1.0 - e))
    theta, se = weighted_theta(psi, jnp.ones((n,), jnp.float32), phi, w,
                               with_se=with_se, row_block=row_block)
    # the ATE functional itself (DRResult.ate = mean psi), weighted —
    # theta[0] only equals it for the constant basis, so draw it too
    wf = w.astype(jnp.float32)
    ate = (wf * psi).sum() / jnp.maximum(wf.sum(), 1.0)
    out = {"theta": theta, "ate": ate}
    if se is not None:
        out["se"] = se
    return out


def dr_bootstrap(outcome: Nuisance, propensity: Nuisance, *, n_folds: int,
                 X: jax.Array, y: jax.Array, t: jax.Array, phi: jax.Array,
                 key: jax.Array, n_replicates: int = 200,
                 scheme: str = "pairs", executor="vmap",
                 alpha: float = 0.05, clip: float = 0.01,
                 with_se: bool = True,
                 point: Optional[jax.Array] = None,
                 point_se: Optional[jax.Array] = None,
                 ate_point: Optional[float] = None,
                 mesh=None, row_block: int = 0, memory_budget: int = 0,
                 chunk: int = 0, max_retries: int = 2) -> InferenceResult:
    """B weighted AIPW refits through the task runtime (same chunked,
    fault-tolerant scheduling as dml_bootstrap)."""
    from repro.runtime import as_runtime
    rt = as_runtime(executor, mesh=mesh,
                    memory_budget=memory_budget, chunk=chunk,
                    max_retries=max_retries)
    keys = replicate_keys(key, n_replicates)

    def replicate(kb, X_, y_, t_, phi_):
        kw, kfit = jax.random.split(kb)
        w = bootstrap_weights(kw, X_.shape[0], scheme)
        return dr_theta_once(outcome, propensity, n_folds, X_, y_, t_,
                             phi_, kfit, w, clip=clip, with_se=with_se,
                             row_block=row_block)

    out = rt.map(replicate, keys, X, y, t, phi, label="dr_bootstrap")
    thetas = out["theta"]
    return InferenceResult(
        method=scheme, executor=rt.name,
        point=thetas.mean(axis=0) if point is None else point,
        replicates=thetas, se=jnp.std(thetas, axis=0, ddof=1),
        alpha=alpha, point_se=point_se, replicate_se=out.get("se"),
        ate_replicates=out["ate"], ate_point=ate_point)

"""Metalearners (Künzel et al. 2019) — the S/T/X baselines the paper
cites in §2.2, built on the nuisance zoo so the same fold/population
batching applies.

  S-learner: one model of E[Y | X, T];  τ(x) = f(x,1) - f(x,0)
  T-learner: per-arm models;            τ(x) = m1(x) - m0(x)
  X-learner: imputed per-arm effects blended by the propensity

Every learner body is a *weighted* core ``(key, y, t, X, w) -> (ate,
cate)``: the public fits run it at w = 1, bootstrap replicates
(``meta_bootstrap``) at resampling weights, and the sweep subsystem
(repro.sweep) at per-segment masks — one program shape for all three.
Ridge/logistic stages route through the replicate-invariant kernels of
``repro.inference.numerics`` (a singleton fold axis), so metalearner
replicates and sweep cells hold the same serial ≡ vmap bit-identity
contract as every other estimator; custom nuisances fall back to
``nuis.fit`` (statistically identical, bit-identity not guaranteed).

Fits return ``MetaResult`` (an ``EffectResult``): metalearners now
carry ``ate_interval`` / ``inference`` like the rest of the catalogue.
Their CATE is not linear in a phi basis, so only the ATE functional has
replicate intervals.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.config import CausalConfig
from repro.core.estimator import EffectResult, inf_cache_field, resolve_scheme
from repro.core.nuisance import Nuisance, make_logistic, make_ridge


def _hyper(nuis: Nuisance, name: str, default):
    h = getattr(nuis, "hyper", None) or {}
    return h.get(name, default)


def _wfit_predict(nuis: Nuisance, key, X, target, w):
    """Weighted single fit -> predict callable.  ridge/logistic take the
    replicate-invariant fold-batched kernels with a singleton fold axis
    (serial == vmap bitwise — what lets sweep cells and bootstrap
    replicates batch); other nuisances fall back to ``nuis.fit``."""
    from repro.inference.numerics import (logistic_fit_folds_w,
                                          predict_folds_linear,
                                          predict_folds_logistic,
                                          ridge_fit_folds_w)
    rb = int(_hyper(nuis, "row_block", 0))
    if nuis.name == "ridge":
        beta = ridge_fit_folds_w(_hyper(nuis, "lam", 1e-3), X, target,
                                 w[None, :], row_block=rb)
        return lambda Xe: predict_folds_linear(beta, Xe)[0]
    if nuis.name == "logistic":
        beta = logistic_fit_folds_w(_hyper(nuis, "lam", 1e-3),
                                    int(_hyper(nuis, "iters", 16)),
                                    X, target, w[None, :], row_block=rb)
        return lambda Xe: predict_folds_logistic(beta, Xe)[0]
    st = nuis.fit(nuis.init(key, X.shape[1]), X, target, w)
    return lambda Xe: nuis.predict(st, Xe)


def _wmean(x, w):
    wf = w.astype(jnp.float32)
    return (wf * x).sum() / jnp.maximum(wf.sum(), 1.0)


# ---------------------------------------------------------------------------
# Weighted learner cores: (key, y, t, X, w) -> (ate, cate).
# ---------------------------------------------------------------------------

def _s_core(nuis, key, y, t, X, w):
    tt = t.astype(jnp.float32)[:, None]
    Xt = jnp.concatenate([X, tt, X * tt], axis=1)  # treatment interactions
    predict = _wfit_predict(nuis, key, Xt, y, w)
    X1 = jnp.concatenate([X, jnp.ones_like(tt), X], axis=1)
    X0 = jnp.concatenate([X, jnp.zeros_like(tt), jnp.zeros_like(X)], axis=1)
    cate = predict(X1) - predict(X0)
    return _wmean(cate, w), cate


def _t_core(nuis, key, y, t, X, w):
    k0, k1 = jax.random.split(key)
    tt = t.astype(jnp.float32)
    m1 = _wfit_predict(nuis, k1, X, y, w * tt)(X)
    m0 = _wfit_predict(nuis, k0, X, y, w * (1.0 - tt))(X)
    cate = m1 - m0
    return _wmean(cate, w), cate


def _x_core(nuis, prop, key, y, t, X, w, clip):
    k0, k1, k2, k3, ke = jax.random.split(key, 5)
    tt = t.astype(jnp.float32)

    # stage 1: per-arm outcome models
    m1 = _wfit_predict(nuis, k1, X, y, w * tt)(X)
    m0 = _wfit_predict(nuis, k0, X, y, w * (1.0 - tt))(X)

    # stage 2: imputed individual effects, learned per arm
    d_treated = y - m0          # valid on treated rows
    d_control = m1 - y          # valid on control rows
    tau1 = _wfit_predict(nuis, k2, X, d_treated, w * tt)(X)
    tau0 = _wfit_predict(nuis, k3, X, d_control, w * (1.0 - tt))(X)

    # stage 3: propensity-weighted blend
    e = jnp.clip(_wfit_predict(prop, ke, X, tt, w)(X), clip, 1 - clip)
    cate = e * tau0 + (1.0 - e) * tau1
    return _wmean(cate, w), cate


def make_meta_core(learner: str, cfg: Optional[CausalConfig] = None,
                   nuisance: Optional[Nuisance] = None,
                   propensity: Optional[Nuisance] = None,
                   clip: float = 0.01) -> Callable:
    """Build one learner's weighted core ``(key, y, t, X, w) -> (ate,
    cate)`` with nuisances defaulted from the CausalConfig (row_block /
    strategy thread through the nuisance hypers) — the unit the sweep
    subsystem masks per segment and ``meta_bootstrap`` reweights per
    replicate."""
    cfg = cfg or CausalConfig()
    nuis = nuisance or make_ridge(cfg.ridge_lambda, row_block=cfg.row_block,
                                  strategy=cfg.row_block_strategy)
    if learner == "s":
        return lambda key, y, t, X, w: _s_core(nuis, key, y, t, X, w)
    if learner == "t":
        return lambda key, y, t, X, w: _t_core(nuis, key, y, t, X, w)
    if learner == "x":
        prop = propensity or make_logistic(cfg.ridge_lambda,
                                           cfg.newton_iters,
                                           row_block=cfg.row_block,
                                           strategy=cfg.row_block_strategy)
        return lambda key, y, t, X, w: _x_core(nuis, prop, key, y, t, X,
                                               w, clip)
    raise ValueError(f"unknown metalearner {learner!r} (expected s|t|x)")


# ---------------------------------------------------------------------------
# Replicate inference: B weighted learner refits as one batched program.
# ---------------------------------------------------------------------------

def meta_bootstrap(core: Callable, *, y: jax.Array, t: jax.Array,
                   X: jax.Array, key: jax.Array, n_replicates: int = 200,
                   scheme: str = "pairs", executor="vmap",
                   alpha: float = 0.05, ate_point: Optional[float] = None,
                   mesh=None, memory_budget: int = 0,
                   chunk: int = 0, max_retries: int = 2):
    """B weighted metalearner refits through the task runtime (chunked,
    fault-tolerant, replicate-ordered — same scheduling as
    dml_bootstrap).  Only the ATE functional's draws are kept:
    metalearner CATEs are not phi-linear, so there is no (B, p_phi)
    coefficient matrix to quantile."""
    from repro.inference import InferenceResult
    from repro.inference.bootstrap import bootstrap_weights, replicate_keys
    from repro.runtime import as_runtime
    rt = as_runtime(executor, mesh=mesh,
                    memory_budget=memory_budget, chunk=chunk,
                    max_retries=max_retries)
    keys = replicate_keys(key, n_replicates)

    def replicate(kb, y_, t_, X_):
        kw, kfit = jax.random.split(kb)
        w = bootstrap_weights(kw, X_.shape[0], scheme)
        ate, _ = core(kfit, y_, t_, X_, w)
        return {"ate": ate}

    out = rt.map(replicate, keys, y, t, X, label="meta_bootstrap")
    draws = out["ate"][:, None]                       # (B, 1)
    point = (jnp.asarray([draws.mean()]) if ate_point is None
             else jnp.asarray([ate_point], jnp.float32))
    return InferenceResult(
        method=scheme, executor=rt.name, point=point, replicates=draws,
        se=jnp.std(draws, axis=0, ddof=1), alpha=alpha,
        ate_replicates=out["ate"], ate_point=ate_point)


@dataclasses.dataclass(frozen=True)
class MetaResult(EffectResult):
    ate: float
    cate: jax.Array  # (n,) pointwise CATE at the training rows
    learner: str = ""
    cfg: Optional[CausalConfig] = None
    fit_ctx: Optional[Dict[str, Any]] = None
    _inf_cache: Dict[Any, Any] = inf_cache_field()

    estimator_name = "metalearner"

    def _resolve_method(self, method):
        # no fold states to jackknife: substitute the bootstrap
        return "bootstrap" if method == "jackknife" else method

    def _replicate_inference(self, method, n_boot, exe, alpha):
        ctx = self.fit_ctx
        cfg = self._config()
        return meta_bootstrap(
            ctx["core"], y=ctx["y"], t=ctx["t"], X=ctx["X"],
            key=jax.random.fold_in(ctx["key"], 0x0b00), alpha=alpha,
            n_replicates=n_boot, scheme=resolve_scheme(method),
            executor=exe, ate_point=self.ate, **self._runtime_kwargs())

    def cate_interval(self, X, alpha=None):
        raise ValueError(
            "metalearner CATEs are not linear in a phi basis; only the "
            "ATE functional carries replicate intervals (ate_interval)")

    def summary(self) -> str:
        name = self.learner or self.estimator_name
        lines = [f"{name}_learner result", "-" * 46,
                 f"ATE = {self.ate:+.4f} (n = {self.cate.shape[0]})"]
        cfg = self._config()
        # only quote a CI that was already computed: summary() must not
        # silently dispatch cfg.n_bootstrap learner refits (the other
        # estimators' summaries are analytic-only for the same reason)
        if self._inf_cache:
            res = next(iter(self._inf_cache.values()))
            lo, hi = res.ate_interval(cfg.alpha)
            lines.append(f"bootstrap {100 * (1 - cfg.alpha):.0f}% CI "
                         f"[{lo:+.4f}, {hi:+.4f}]")
        return "\n".join(lines)


def _meta_fit(learner: str, y, t, X, nuisance, propensity, key, cfg,
              clip: float = 0.01) -> MetaResult:
    key = key if key is not None else jax.random.PRNGKey(0)
    core = make_meta_core(learner, cfg, nuisance, propensity, clip)
    ones = jnp.ones((X.shape[0],), jnp.float32)
    ate, cate = core(key, y, t, X, ones)
    ctx = {"core": core, "y": y, "t": t, "X": X, "key": key}
    return MetaResult(ate=float(ate), cate=cate, learner=learner, cfg=cfg,
                      fit_ctx=ctx)


def s_learner(y, t, X, *, nuisance: Optional[Nuisance] = None,
              key=None, cfg: Optional[CausalConfig] = None) -> MetaResult:
    return _meta_fit("s", y, t, X, nuisance, None, key, cfg)


def t_learner(y, t, X, *, nuisance: Optional[Nuisance] = None,
              key=None, cfg: Optional[CausalConfig] = None) -> MetaResult:
    return _meta_fit("t", y, t, X, nuisance, None, key, cfg)


def x_learner(y, t, X, *, nuisance: Optional[Nuisance] = None,
              propensity: Optional[Nuisance] = None, key=None,
              cfg: Optional[CausalConfig] = None,
              clip: float = 0.01) -> MetaResult:
    return _meta_fit("x", y, t, X, nuisance, propensity, key, cfg, clip)

"""The segmented DML fast path: all E segments' cross-fit estimates
from ONE segment×fold-segmented pass over the data.

A masked sweep cell re-reads every row per cell — E cells touch E·n
rows.  But each row belongs to exactly one (segment, fold) pair, so one
``moments.fold_gram`` pass over the combined id ``segment·K + fold``
yields every per-(segment, fold) held-out Gram at once, and the
leave-one-out identity (the repo's ``parallel_loo`` trick, here
generalized over segments)

    G_complement[s, j] = (Σ_j' Gh[s, j']) - Gh[s, j]

turns them into all E·K fold-complement normal equations with NO
second data pass.  Ridge nuisances stay EXACT; the logistic treatment
nuisance uses the Böhning-Lindsay fixed majorizer (H0 = Gram/4 + λI
factored once per (s, j), then matvec-cheap MM steps — the same
substitution ``crossfit_parallel_loo`` makes), converging to the same
optimum as Newton.  The orthogonal final stage and its HC0 meat are
per-segment one-hot Grams over the residuals.

Everything streams through ``core.moments`` (``fold_gram`` honors
``cfg.row_block``), so no per-segment data copy and no (E, n) weight
tensor ever materializes.  ONE fold Gram pass over [X | 1 | y] serves
both nuisances: the y ridge solves and the logistic majorizer's design
Gram are blocks of it.  ``cfg.row_block_strategy="pallas"`` swaps the
one-hot einsums (the fold Grams, the MM gradient terms, the
per-segment final stage) for the fused segment-Gram kernels of
``repro.kernels.seg_gram`` — the (n, E·k) masks never materialize at
all — and runs each MM step as one lane-major kernel pass with the
(E, k, q) coefficients resident (``ops.mm_logistic_grad``), so no
(n, k) residual or (n, k, q) per-row coefficients reach HBM either.
This is the "software that estimates many effects cheaply" execution
(Wong 2020): benchmarks/bench_sweep.py measures ~10x over the serial
loop at E=64 on CPU.

Contract: a *different execution* of the same estimator, not the same
bits — like ``engine="parallel_loo"`` vs ``"parallel"``, it shares one
fold assignment across cells and swaps Newton for MM, so tests assert
tolerance-equality against gathered per-segment references, while the
bitwise panel ≡ loop contract stays on the default cells mode.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.config import CausalConfig
from repro.core import moments
from repro.core.crossfit import fold_ids
from repro.core.final_stage import cate_basis
from repro.core.registry import EstimatorSpec
from repro.inference.numerics import det_inv, det_solve

_F32 = jnp.float32


def segmented_supported(rspec: EstimatorSpec, cfg: CausalConfig) -> bool:
    """The one-pass kernels cover the linear-nuisance DML family."""
    if cfg.discrete_treatment:
        t_kind_ok = cfg.nuisance_t == "logistic"
    else:
        # continuous T is ridge-fit here; a logistic nuisance_t would
        # silently become a different estimator than cells mode
        t_kind_ok = cfg.nuisance_t == "ridge"
    return rspec.name.startswith("dml") and cfg.nuisance_y == "ridge" and t_kind_ok


def _fold_grams(X, target, comb, n_segments, k, row_block, strategy):
    """One fold_gram pass over the combined segment×fold id with the
    target riding as an appended design column: the (E, k, q + 1, q + 1)
    held-out Grams of [X | 1 | target] and the (E, k) row counts."""
    q = X.shape[1] + 1
    Gh, counts = moments.fold_gram(
        X,
        comb,
        n_segments * k,
        intercept=True,
        append=target,
        row_block=row_block,
        strategy=strategy,
    )
    return Gh.reshape(n_segments, k, q + 1, q + 1), counts.reshape(n_segments, k)


def _complement(Gh, counts):
    """LOO identity: fold-complement Grams and their row counts."""
    Gc = Gh.sum(axis=1, keepdims=True) - Gh
    n_eff = jnp.maximum(counts.sum(1, keepdims=True) - counts, 1.0)
    return Gc, n_eff


def _ridge(Gc, n_eff, lam):
    """EXACT per-(segment, fold-complement) ridge: E·K tiny solves of
    the complement normal equations (target in the last column)."""
    q = Gc.shape[-1] - 1
    A = Gc[..., :q, :q] / n_eff[..., None, None] + lam * jnp.eye(q, dtype=_F32)
    b = Gc[..., :q, q] / n_eff[..., None]
    return jax.vmap(jax.vmap(det_solve))(A, b)  # (E, k, q)


def _oof_predict(xa_t, beta, comb):
    """(n,) out-of-fold predictions: each row read once by its own
    (segment, fold) model, Σ_j xa[j] · β[comb, j] in float32.  Lane-major
    (``xa_t`` is [X | 1]ᵀ), one (n,) gather per coefficient: an (n, q)
    gather would pad each row to 128 lanes in HBM."""
    E, k, q = beta.shape
    table = beta.reshape(E * k, q).T
    return sum(xa_t[j] * table[j][comb] for j in range(q))


def _segment_fold_logistic(xa_t, tt, sids, folds, Gc, n_eff, lam, iters, strategy):
    """Per-(segment, fold-complement) logistic via the Böhning-Lindsay
    fixed majorizer: H0 = Gram/4 + λI from the complement Grams of the
    design (no pass of its own), then ``iters`` MM steps, each one pass
    over the rows for the complement gradient.

    Under strategy="pallas" a step is ONE seg_gram pass
    (``mm_logistic_grad``) over lane-major rows, [X | 1]ᵀ and [t;
    cohort + 1; fold], with the (E, k, q) coefficients resident in VMEM:
    the logits, mu - t and the fold complement form in registers, so no
    (n, k) residual or (n, k, q) per-row coefficients ever reach HBM.
    Otherwise the one-hot einsums: held-in sums per segment (t1) minus
    own-fold sums (t2)."""
    n_segments, k, q = Gc.shape[0], Gc.shape[1], xa_t.shape[0]
    H0 = Gc[..., :q, :q] / (4.0 * n_eff[..., None, None]) + lam * jnp.eye(
        q, dtype=_F32
    )
    if strategy == "pallas":
        from repro.kernels.seg_gram import ops as sg_ops

        # lane-major, packed once per sweep: 64 + 32 bytes a row in HBM
        # where an (n, q + 3) operand would pad each row to 512
        meta_t = jnp.stack([tt, (sids + 1).astype(_F32), folds.astype(_F32)])

        def grad(beta):
            return sg_ops.mm_logistic_grad(xa_t, meta_t, beta)

    else:
        Xa = xa_t.T
        oh_seg = jax.nn.one_hot(sids, n_segments, dtype=_F32)  # (n, E)
        oh_comb = jax.nn.one_hot(sids * k + folds, n_segments * k, dtype=_F32)

        def grad(beta):
            bs = beta[sids]  # (n, k, q)
            mu = jax.nn.sigmoid(jnp.einsum("np,nkp->nk", Xa, bs))
            r = mu - tt[:, None]  # (n, k)
            rr = jnp.take_along_axis(r, folds[:, None], axis=1)[:, 0]
            t1 = jnp.einsum("ns,nk,np->skp", oh_seg, r, Xa)
            t2 = jnp.einsum("nc,n,np->cp", oh_comb, rr, Xa)
            return t1 - t2.reshape(n_segments, k, q)

    def _step(_, beta):  # beta: (E, k, q)
        g = grad(beta) / n_eff[..., None] + lam * beta
        return beta - jax.vmap(jax.vmap(det_solve))(H0, g)

    return jax.lax.fori_loop(0, iters, _step, jnp.zeros((n_segments, k, q), _F32))


def _segment_final_stage(
    ry, rt, phi, sids, n_segments, ridge=1e-8, row_block=0, strategy=None
):
    """Per-segment orthogonal final stage + HC0 sandwich, all E
    segments from segment-Grams over the residuals (one data pass:
    one-hot einsums by default, the fused seg_gram kernels under
    strategy="pallas")."""
    pf = phi.shape[1]
    z = rt[:, None] * phi
    m = jnp.concatenate([z, ry[:, None]], axis=1)
    if strategy == "pallas":
        from repro.kernels.seg_gram import ops as sg_ops

        gaug = sg_ops.segment_outer(m, m, sids, n_segments, row_block=row_block)
        nseg = jnp.maximum(sg_ops.segment_counts(sids, n_segments), 1.0)
    else:
        oh_seg = jax.nn.one_hot(sids, n_segments, dtype=_F32)
        gaug = jnp.einsum("ns,ni,nj->sij", oh_seg, m, m)  # (E, pf+1, pf+1)
        nseg = jnp.maximum(oh_seg.sum(0), 1.0)
    a = gaug[:, :pf, :pf] + ridge * nseg[:, None, None] * jnp.eye(pf, dtype=_F32)
    theta = jax.vmap(det_solve)(a, gaug[:, :pf, pf])
    e = ry - (z * theta[sids]).sum(axis=1)
    me = e[:, None] * z
    if strategy == "pallas":
        meat = sg_ops.segment_outer(me, me, sids, n_segments, row_block=row_block)
    else:
        meat = jnp.einsum("ns,ni,nj->sij", oh_seg, me, me)
    ainv = jax.vmap(det_inv)(a)
    cov = jnp.einsum("sia,sab,sbj->sij", ainv, meat, ainv,
                     precision=jax.lax.Precision.HIGHEST)
    se = jnp.sqrt(jnp.clip(jnp.diagonal(cov, axis1=1, axis2=2), 0.0, None))
    return theta, se


def segmented_dml_sweep(
    cfg: CausalConfig,
    X: jax.Array,
    y: jax.Array,
    t: jax.Array,
    sids: jax.Array,
    n_segments: int,
    key: jax.Array,
) -> Dict[str, jax.Array]:
    """All E per-segment DML fits from one segmented pass: shared fold
    assignment, LOO-identity ridge + MM logistic nuisances, per-segment
    final stage.  Returns {"theta" (E, p), "se" (E, p), "ate" (E,),
    "beta_y", "beta_t" (E, K, p + 1): every (segment, fold-complement)
    nuisance model, intercept last, "cell_rows" (E, K): rows per
    (segment, fold)}."""
    n = X.shape[0]
    k = cfg.n_folds
    folds = fold_ids(key, n, k)
    comb = sids * k + folds  # (n,) in [0, E·k)

    # one pass serves the y ridge and the logistic majorizer's design Gram
    Gh, counts = _fold_grams(X, y, comb, n_segments, k, cfg.row_block,
                             cfg.row_block_strategy)
    return segmented_stages(cfg, Gh, counts, n_segments, y=y, t=t, sids=sids,
                            folds=folds, comb=comb, X=X)


def segmented_stages(
    cfg: CausalConfig,
    Gh: jax.Array,
    counts: jax.Array,
    n_segments: int,
    *,
    y: jax.Array,
    t: jax.Array,
    sids: jax.Array,
    folds: jax.Array,
    comb: jax.Array,
    X: Optional[jax.Array] = None,
    xa_t: Optional[jax.Array] = None,
) -> Dict[str, jax.Array]:
    """Everything after the fold Gram, shared by the sweep and the
    moment store's windowed refresh: the fold complements, the ridge
    visit models, the treatment models (MM logistic, or ridge for a
    continuous treatment), the out-of-fold predictions and the
    per-cohort final stage with its HC0 SE.

    ``Gh`` (E, k, q + 1, q + 1) holds the held-out Grams of
    [X | 1 | y] and ``counts`` (E, k) their rows.  The rows come either
    row-major as ``X`` (the sweep, which packs [X | 1]ᵀ here) or
    already lane-major as ``xa_t`` = [X | 1]ᵀ (the store's ring).  A
    continuous treatment needs ``X`` (its ridge takes a fold Gram pass
    of its own).  A row whose cohort is -1 (padding) adds nothing: its
    ``xa_t`` column is zero, its MM cohort tag is 0 and every segment
    sum drops it; the caller gives it a ``comb`` in range, so the
    prediction gathers never wrap a negative index."""
    k = cfg.n_folds
    lam = cfg.ridge_lambda
    rb, st = cfg.row_block, cfg.row_block_strategy
    Gc, n_eff = _complement(Gh, counts)
    beta_y = _ridge(Gc, n_eff, lam)
    if xa_t is None:
        n = X.shape[0]
        xa_t = jnp.concatenate([X.astype(_F32).T, jnp.ones((1, n), _F32)])  # [X | 1]ᵀ
    tt = t.astype(_F32)
    mm_iters = 2 * cfg.newton_iters  # MM trades per-step cost for steps
    if cfg.discrete_treatment:
        beta_t = _segment_fold_logistic(
            xa_t, tt, sids, folds, Gc, n_eff, lam, mm_iters, st
        )
        mt = jax.nn.sigmoid(_oof_predict(xa_t, beta_t, comb))
    else:
        grams = _fold_grams(X, t, comb, n_segments, k, rb, st)
        beta_t = _ridge(*_complement(*grams), lam)
        mt = _oof_predict(xa_t, beta_t, comb)
    my = _oof_predict(xa_t, beta_y, comb)
    ry = y.astype(_F32) - my
    rt = tt - mt
    if X is None:  # the basis needs only the first few features
        X = xa_t[: min(max(cfg.cate_features - 1, 0), xa_t.shape[0] - 1)].T
    phi = cate_basis(X, cfg.cate_features)
    theta, se = _segment_final_stage(
        ry, rt, phi, sids, n_segments, row_block=rb, strategy=st
    )
    return {"theta": theta, "se": se, "ate": theta[:, 0], "beta_y": beta_y,
            "beta_t": beta_t, "cell_rows": counts}


_JITTED: Dict[Any, Any] = {}


def segmented_column(
    cfg: CausalConfig,
    base_data: Dict[str, Any],
    n_segments: int,
    key: jax.Array,
) -> Dict[str, jax.Array]:
    """Engine adapter: jit the segmented sweep per (config, E) so
    repeated sweeps hit the compile cache."""
    ck = (cfg, n_segments)
    fn = _JITTED.get(ck)
    if fn is None:
        fn = jax.jit(
            lambda X, y, t, sids, key_: segmented_dml_sweep(
                cfg, X, y, t, sids, n_segments, key_
            )
        )
        _JITTED[ck] = fn
    return fn(base_data["X"], base_data["y"], base_data["t"], base_data["sids"], key)

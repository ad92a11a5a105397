"""Replicate-invariant weighted estimation kernels.

The Executor contract promises that ``serial`` and ``vmap`` backends
produce *bit-identical* per-replicate estimates.  XLA does not give that
for free: LAPACK solves (``jnp.linalg.solve``, Cholesky) and mat-vec
einsums change their reduction order when a leading batch dimension is
added, so a vmapped replicate differs from the same replicate run alone
by a few ulps.  Empirically (see tests/test_inference.py) the operations
that ARE invariant under an added batch axis:

  * gram-shaped einsums with explicit fold index: ``ni,kn,nj->kij`` and
    ``kp,np->kn`` — XLA loops the batch over the same per-matrix
    contraction (the thinner ``kn,np->kp`` is NOT safe once XLA fuses an
    elementwise producer into it, so gradients are read off augmented
    Grams instead);
  * elementwise ops, plain sums, ``fold_in``/``permutation`` PRNG;
  * Gauss-Jordan elimination written as broadcast updates (fori_loop of
    rank-1 outer products) — no LAPACK, no pivot-order ambiguity.

Every function here is built ONLY from that vocabulary.  The mat-vec
RHS of the normal equations is folded into an *augmented* Gram (append
the target as an extra column of X), so the one bad shape class —
``ni,n->i`` — never appears.  Gauss-Jordan without pivoting is safe
because every system we solve is SPD plus an explicit ridge.

These kernels double as the weighted-fit path for bootstrap replicates:
``Wk`` carries fold-complement masks multiplied by per-row bootstrap
weights, the same mechanism ``crossfit.fold_weights`` uses for C1.

The Gram-shaped reductions themselves live in the streaming moments
engine (``repro.core.moments``): this module no longer re-implements
the weighted normal equations — it supplies the deterministic solves
and the fold-batched *protocols* on top of the engine's augmented-Gram
passes.  A ``row_block`` argument streams every pass in fixed-order
row blocks (bounded memory at industrial n); at the default
``row_block=0`` the einsum forms below are byte-for-byte the legacy
whole-array ones, which is what keeps serial == vmap bit-identity.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import moments


def _gauss_jordan_step(i, M):
    """Eliminate column ``i`` of the augmented system ``M``."""
    piv = M[i] / M[i, i]
    factors = M[:, i].at[i].set(0.0)
    M = M - factors[:, None] * piv[None, :]
    return M.at[i].set(piv)


def det_solve(A: jax.Array, b: jax.Array) -> jax.Array:
    """Deterministic (p,p) @ x = (p,) solve via Gauss-Jordan without
    pivoting.  Elementwise broadcast updates only — bit-identical under
    any number of leading vmap axes.  Requires A SPD-ish (ridge added by
    every caller).  Its ops carry the ``det_solve`` scope."""
    with jax.named_scope("det_solve"):
        M = jnp.concatenate([A, b[:, None]], axis=1)
        M = jax.lax.fori_loop(0, A.shape[0], _gauss_jordan_step, M)
        return M[:, -1]


def det_inv(A: jax.Array) -> jax.Array:
    """Gauss-Jordan inverse (same invariance properties as det_solve;
    its ops carry the ``det_inv`` scope)."""
    with jax.named_scope("det_inv"):
        p = A.shape[0]
        M = jnp.concatenate([A, jnp.eye(p, dtype=A.dtype)], axis=1)
        M = jax.lax.fori_loop(0, p, _gauss_jordan_step, M)
        return M[:, p:]


def _aug(X: jax.Array) -> jax.Array:
    return jnp.concatenate([X, jnp.ones((X.shape[0], 1), X.dtype)], axis=1)


# ---------------------------------------------------------------------------
# Fold-batched weighted nuisance fits.  Wk is (k, n): fold-complement
# mask times per-row replicate weights.  All einsums carry the fold
# index explicitly — vmap-of-gram ("ni,n,nj->ij" under vmap) is NOT
# batch-invariant, the explicit "ni,kn,nj->kij" form is.
# ---------------------------------------------------------------------------

def ridge_fit_folds_w(lam: jax.Array, X: jax.Array, y: jax.Array,
                      Wk: jax.Array, *, row_block: int = 0,
                      strategy: Optional[str] = None,
                      rules=None) -> jax.Array:
    """Weighted per-fold ridge, one augmented fold-weighted Gram from
    the moments engine.  Returns beta (k, p+1) (intercept last,
    matching nuisance.make_ridge's column order)."""
    f32 = jnp.float32
    p = X.shape[1] + 1
    Gaug, n_eff = moments.fold_weighted_gram(X, Wk, intercept=True,
                                             append=y,
                                             row_block=row_block,
                                             strategy=strategy,
                                             rules=rules)
    n_eff = jnp.maximum(n_eff, 1.0)                             # (k,)
    A = Gaug[:, :p, :p] / n_eff[:, None, None] \
        + lam * jnp.eye(p, dtype=f32)[None]
    b = Gaug[:, :p, p] / n_eff[:, None]
    return jax.vmap(det_solve)(A, b)


def logistic_fit_folds_w(lam: jax.Array, iters: int, X: jax.Array,
                         t: jax.Array, Wk: jax.Array, *,
                         row_block: int = 0, strategy: Optional[str] = None,
                         rules=None) -> jax.Array:
    """Weighted per-fold Newton/IRLS logistic (same math as
    nuisance.make_logistic, fold axis explicit).  Returns beta (k, p+1).

    The gradient mat-vec Σ_n r_kn·Xa_n is read off an augmented Gram
    (ones column appended): the 2-operand "kn,np->kp" einsum changes
    its reduction order when XLA fuses the elementwise residual into
    it under vmap; the engine's 3-operand Gram form does not."""
    f32 = jnp.float32
    Xa = _aug(X.astype(f32))
    k, p = Wk.shape[0], Xa.shape[1]
    Wk = Wk.astype(f32)
    tt = t.astype(f32)
    n_eff = jnp.maximum(Wk.sum(axis=1), 1.0)                    # (k,)
    lam_eye = lam * jnp.eye(p, dtype=f32)
    ones = jnp.ones((Xa.shape[0],), f32)

    def newton(_, beta):                                        # beta (k, p)
        z = jnp.einsum("kp,np->kn", beta, Xa)
        mu = jax.nn.sigmoid(z)
        s = jnp.clip(mu * (1.0 - mu), 1e-6, None) * Wk
        Gr, _ = moments.fold_weighted_gram(
            Xa, Wk * (mu - tt[None, :]), append=ones,
            row_block=row_block, strategy=strategy, rules=rules)
        g = Gr[:, :p, p] / n_eff[:, None] + lam * beta
        H, _ = moments.fold_weighted_gram(X, s, intercept=True,
                                          row_block=row_block,
                                          strategy=strategy, rules=rules)
        H = H / n_eff[:, None, None] + lam_eye[None]
        return beta - jax.vmap(det_solve)(H, g)

    beta = jax.lax.fori_loop(0, iters, newton, jnp.zeros((k, p), f32))
    return beta


def predict_folds_linear(beta: jax.Array, X: jax.Array) -> jax.Array:
    """(k, p+1) coefficients -> (k, n) linear predictions."""
    return jnp.einsum("kp,np->kn", beta, _aug(X.astype(jnp.float32)))


def predict_folds_logistic(beta: jax.Array, X: jax.Array) -> jax.Array:
    return jax.nn.sigmoid(predict_folds_linear(beta, X))


# ---------------------------------------------------------------------------
# Weighted orthogonal final stage (weighted analogue of
# final_stage.fit_final_stage, replicate-invariant form).
# ---------------------------------------------------------------------------

def weighted_theta(ry: jax.Array, rt: jax.Array, phi: jax.Array,
                   w: jax.Array, *, ridge: float = 1e-8,
                   with_se: bool = True, row_block: int = 0,
                   strategy: Optional[str] = None, rules=None
                   ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Solve the weighted orthogonal moment
    ``theta = argmin Σ w_i (ry_i - <theta, phi_i> rt_i)²`` and (optionally)
    its weighted HC0 sandwich stderr.  ry, rt, w: (n,); phi: (n, p_phi).

    Both the augmented Gram and the meat stream through the moments
    engine: with ``row_block > 0`` neither the (n, p_phi) moment matrix
    Z nor the residual vector materializes."""
    f32 = jnp.float32
    p = phi.shape[1]
    Gaug, n_eff = moments.residual_weighted_gram(ry, rt, phi, w,
                                                 row_block=row_block,
                                                 strategy=strategy,
                                                 rules=rules)
    n_eff = jnp.maximum(n_eff, 1.0)
    A = Gaug[:p, :p] + ridge * n_eff * jnp.eye(p, dtype=f32)
    theta = det_solve(A, Gaug[:p, p])
    if not with_se:
        return theta, None
    # weighted HC0: cov = A⁻¹ (Zᵀ diag(w² e²) Z) A⁻¹ — elementwise resid
    # (no mat-vec: (Z * theta).sum over the tiny p_phi axis is invariant)
    meat = moments.residual_meat(ry, rt, jnp.zeros_like(ry),
                                 jnp.zeros_like(rt), phi, theta, w=w,
                                 row_block=row_block, strategy=strategy,
                                 rules=rules)
    Ainv = det_inv(A)
    cov = jnp.einsum("ia,ab,bj->ij", Ainv, meat, Ainv)
    se = jnp.sqrt(jnp.clip(jnp.diagonal(cov), 0.0, None))
    return theta, se


def weighted_iv_theta(ry: jax.Array, rt: jax.Array, rz: jax.Array,
                      phi: jax.Array, w: jax.Array, *,
                      ridge: float = 1e-8, with_se: bool = True,
                      row_block: int = 0, strategy: Optional[str] = None,
                      rules=None
                      ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Solve the weighted instrumented orthogonal moment
    ``Σ w_i rz_i φ_i (ry_i - <theta, φ_i> rt_i) = 0`` (the residual-on-
    residual 2SLS condition) plus its weighted HC0 sandwich stderr.
    ry, rt, rz, w: (n,); phi: (n, p_phi).

    All sufficient statistics come off ONE instrumented augmented Gram
    (``moments.iv_gram``) and one meat pass — replicate-invariant forms
    only (serial ≡ vmap bitwise, certified on the row-blocked canonical
    path by tests/test_conformance.py), and w=1 reproduces the point
    fit exactly."""
    f32 = jnp.float32
    p = phi.shape[1]
    Gaug, n_eff = moments.iv_gram(ry, rt, rz, phi, w,
                                  row_block=row_block,
                                  strategy=strategy, rules=rules)
    J, b, _, _ = moments.iv_slices(Gaug, p)
    n_eff = jnp.maximum(n_eff, 1.0)
    # J = Σ w·rz·rt·φφᵀ is symmetric (a signed-weight Gram) but not
    # PSD; with a relevant instrument its pivots are bounded away from
    # zero, which is all Gauss-Jordan needs (the weak-instrument F
    # check in core.refutation screens the degenerate case).
    A = J + ridge * n_eff * jnp.eye(p, dtype=f32)
    theta = det_solve(A, b)
    if not with_se:
        return theta, None
    meat = moments.iv_meat(ry, rt, rz, phi, theta, w=w,
                           row_block=row_block, strategy=strategy,
                           rules=rules)
    Ainv = det_inv(A)
    cov = jnp.einsum("ia,ab,bj->ij", Ainv, meat, Ainv)
    se = jnp.sqrt(jnp.clip(jnp.diagonal(cov), 0.0, None))
    return theta, se

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
  * Gauss-Jordan elimination written as broadcast updates — no LAPACK,
    no pivot-order ambiguity.  Past ``_UNBLOCKED_MAX`` unknowns it runs
    in panels of ``_NB`` columns with a delayed update: a panel's rank-1
    updates are found on its column slab and pivot rows, then applied to
    the whole system as one elementwise chain of multiply-subtracts.
    Every entry sees the same multiply-subtracts, in the same order, as
    the column-at-a-time loop, and no reduction adds two of them (the
    only reductions pick one entry by a max over -inf), so the bits
    equal that loop's under any batch axes.

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
from repro.obs.metrics import default_registry


# Columns per panel of the delayed-update elimination, and the most
# unknowns the unblocked loop keeps (the final stage's p_phi = 1, the
# sweep's cells).  Timed on a v5e: panels of 16 beat 32 and 64 at every
# size tried; the loop wins up to about 96 unknowns over a batch of 320
# systems, the panels from about 24 over a batch of 10.
_NB = 16
_UNBLOCKED_MAX = 64


def _gauss_jordan_step(i, M):
    """Eliminate column ``i`` of the augmented system ``M``."""
    piv = M[i] / M[i, i]
    factors = M[:, i].at[i].set(0.0)
    M = M - factors[:, None] * piv[None, :]
    return M.at[i].set(piv)


def _gauss_jordan(M: jax.Array, p: int) -> jax.Array:
    """Gauss-Jordan elimination without pivoting of the augmented
    ``(p, p + r)`` system ``M``; returns the reduced right-hand block
    ``(p, r)``.  Systems with more than ``_UNBLOCKED_MAX`` unknowns go
    through ``_panels``, the rest through the unblocked loop; the choice
    is counted as ``det_solve.path[panelled|unblocked]`` at trace
    time."""
    panelled = p > _UNBLOCKED_MAX
    default_registry().counter(
        f"det_solve.path[{'panelled' if panelled else 'unblocked'}]").inc()
    if not panelled:
        return jax.lax.fori_loop(0, p, _gauss_jordan_step, M)[:, p:]
    # pad the p x p block to a multiple of _NB with an identity block,
    # the right-hand columns after it.  The padded rows and columns stay
    # +0 off the identity, so a padded step subtracts +0 x +0 from every
    # original entry: exact
    q = -(-p // _NB) * _NB
    if q > p:
        r = M.shape[1] - p
        M = jnp.concatenate([
            jnp.concatenate([M[:, :p], jnp.zeros((p, q - p), M.dtype),
                             M[:, p:]], axis=1),
            jnp.concatenate([jnp.zeros((q - p, p), M.dtype),
                             jnp.eye(q - p, dtype=M.dtype),
                             jnp.zeros((q - p, r), M.dtype)], axis=1)])
    return _panels(M[None])[0, :p, q:]


@jax.custom_batching.custom_vmap
def _panels(M: jax.Array) -> jax.Array:
    """Panelled elimination of the padded systems ``M`` (B, q, w).

    Per panel of ``_NB`` columns, an inner loop eliminates the panel's
    columns on the column slab and the pivot-row block alone, and
    records each step's factors ``f_s`` and pivot row ``piv_s``; then
    every row gets the panel's multiply-subtracts
    ``((M - f_0 piv_0) - f_1 piv_1) ...`` in one elementwise chain, and
    the pivot rows are written back.  Every entry sees the same
    multiply-subtracts in the same order as the unblocked loop, and no
    reduction sums two of them, so the bits are the same.

    The panel's pivot rows and columns are always the first ``_NB``
    (each panel ends by rotating them to the back), and the step's
    column and pivot row are picked and written through masks: dynamic
    offsets into both of M's matrix axes make XLA lay the batch axes
    minor (on a TPU, a (2, 128) tile over a (2, 5) batch), and under
    vmap a dynamic index becomes a gather or scatter.  Written in lax
    with one explicit batch axis (``_panels_vmap`` folds vmapped axes
    into it) to keep the traced program small: the replicate's memory
    probes lower it on every fit."""
    lax = jax.lax
    B, q, w = M.shape
    nb = _NB
    iota_nb = lax.iota(jnp.int32, nb)
    iota_q = lax.iota(jnp.int32, q)
    zeros_q = jnp.zeros((B, q), M.dtype)

    def bcast(x, shape, dims):
        return lax.broadcast_in_dim(x, shape, dims)

    def step(s, carry):
        slab, rows, F, P = carry                   # (B, q, nb), (B, nb, w)
        at = iota_nb == s
        col = iota_q == s
        m_w = bcast(at, (B, nb, w), (1,))
        row = _select(m_w, rows, 1)                                 # (B, w)
        d = _select(bcast(at, (B, nb), (1,)),
                    lax.slice_in_dim(row, 0, nb, axis=1), 1)        # (B,)
        piv = lax.div(row, bcast(d, (B, w), (0,)))
        f = _select(bcast(at, (B, q, nb), (2,)), slab, 2)           # (B, q)
        f = lax.select(bcast(col, (B, q), (1,)), zeros_q, f)
        piv_q = bcast(lax.slice_in_dim(piv, 0, nb, axis=1), (B, q, nb),
                      (0, 2))
        slab = lax.select(bcast(col, (B, q, nb), (1,)), piv_q,
                          lax.sub(slab, lax.mul(bcast(f, (B, q, nb), (0, 1)),
                                                piv_q)))
        piv_w = bcast(piv, (B, nb, w), (0, 2))
        f_w = bcast(lax.slice_in_dim(f, 0, nb, axis=1), (B, nb, w), (0, 1))
        rows = lax.select(m_w, piv_w, lax.sub(rows, lax.mul(f_w, piv_w)))
        # F and P keep the step axis first, for the chain's split
        return (slab, rows,
                lax.select(bcast(at, (nb, B, q), (0,)),
                           bcast(f, (nb, B, q), (1, 2)), F),
                lax.select(bcast(at, (nb, B, w), (0,)),
                           bcast(piv, (nb, B, w), (1, 2)), P))

    def panel(_, M):
        slab = lax.slice_in_dim(M, 0, nb, axis=2)
        rows = lax.slice_in_dim(M, 0, nb, axis=1)
        carry = (slab, rows, jnp.zeros((nb, B, q), M.dtype),
                 jnp.zeros((nb, B, w), M.dtype))
        _, rows, F, P = lax.fori_loop(0, nb, step, carry)
        # the delayed update, one elementwise chain over M
        shape = (nb, B, q, w)
        Fs = lax.split(bcast(F, shape, (0, 1, 2)), (1,) * nb)
        Ps = lax.split(bcast(P, shape, (0, 1, 3)), (1,) * nb)
        M = lax.reshape(M, (1, B, q, w))
        for f_s, p_s in zip(Fs, Ps):
            M = lax.sub(M, lax.mul(f_s, p_s))
        M = lax.reshape(M, (B, q, w))
        M = lax.concatenate([lax.slice_in_dim(M, nb, q, axis=1), rows], 1)
        return lax.concatenate([lax.slice_in_dim(M, nb, q, axis=2),
                                lax.slice_in_dim(M, 0, nb, axis=2),
                                lax.slice_in_dim(M, q, w, axis=2)], 2)

    return lax.fori_loop(0, q // nb, panel, M)


@_panels.def_vmap
def _panels_vmap(axis_size, in_batched, M):
    # fold the vmapped axis into the explicit batch axis, so the loops
    # are traced once at the full batch and never batched by vmap (the
    # rule only runs with M batched: it is the one argument)
    folded = _panels(M.reshape((-1,) + M.shape[2:]))
    return folded.reshape(M.shape), True


def _select(mask: jax.Array, X: jax.Array, axis: int) -> jax.Array:
    """The slice of ``X`` along ``axis`` where ``mask`` (X's shape)
    holds: a max over the others set to -inf, so exact for every value,
    -0 and nan included."""
    return jax.lax.reduce_max(
        jax.lax.select(mask, X, jnp.full_like(X, -jnp.inf)), (axis,))


def det_solve(A: jax.Array, b: jax.Array) -> jax.Array:
    """Deterministic (p,p) @ x = b solve via Gauss-Jordan without
    pivoting, panelled for large p (``_gauss_jordan``).  ``b`` is one
    right-hand side (p,) or r of them (p, r), eliminated together: a
    column only ever meets the multiply-subtracts of its own entries, so
    each comes out with the bits of its own one-column solve.
    Elementwise broadcast updates only — bit-identical under any number
    of leading vmap axes, and to the unblocked loop.  Requires A SPD-ish
    (ridge added by every caller).  Its ops carry the ``det_solve``
    scope."""
    with jax.named_scope("det_solve"):
        if b.ndim == 2:
            return _gauss_jordan(jnp.concatenate([A, b], axis=1),
                                 A.shape[0])
        M = jnp.concatenate([A, b[:, None]], axis=1)
        return _gauss_jordan(M, A.shape[0])[:, 0]


def det_inv(A: jax.Array) -> jax.Array:
    """Gauss-Jordan inverse (the same elimination and invariance
    properties as det_solve; its ops carry the ``det_inv`` scope)."""
    with jax.named_scope("det_inv"):
        p = A.shape[0]
        M = jnp.concatenate([A, jnp.eye(p, dtype=A.dtype)], axis=1)
        return _gauss_jordan(M, p)


def _aug(X: jax.Array) -> jax.Array:
    return jnp.concatenate([X, jnp.ones((X.shape[0], 1), X.dtype)], axis=1)


# ---------------------------------------------------------------------------
# Fold-batched weighted nuisance fits.  Wk is (k, n): fold-complement
# mask times per-row replicate weights.  All einsums carry the fold
# index explicitly — vmap-of-gram ("ni,n,nj->ij" under vmap) is NOT
# batch-invariant, the explicit "ni,kn,nj->kij" form is.
# ---------------------------------------------------------------------------

def ridge_fit_folds_w(lam, X: jax.Array, y: jax.Array,
                      Wk: jax.Array, *, row_block: int = 0,
                      strategy: Optional[str] = None) -> jax.Array:
    """Weighted per-fold ridge, one augmented fold-weighted Gram from
    the moments engine.  Returns beta (k, p+1) (intercept last,
    matching nuisance.make_ridge's column order).

    ``y`` (n, m) fits m targets on the same X and weights from ONE Gram
    over [X | 1 | y_1 .. y_m], returning (m, k, p+1); ``lam`` is then
    the m penalties (Python numbers).  When they agree, the targets
    share one elimination (``det_solve``'s right-hand sides); each
    target's bits are those of its own one-target fit."""
    f32 = jnp.float32
    p = X.shape[1] + 1
    Gaug, n_eff = moments.fold_weighted_gram(X, Wk, intercept=True,
                                             append=y,
                                             row_block=row_block,
                                             strategy=strategy)
    n_eff = jnp.maximum(n_eff, 1.0)                             # (k,)
    if y.ndim == 1:
        A = Gaug[:, :p, :p] / n_eff[:, None, None] \
            + lam * jnp.eye(p, dtype=f32)[None]
        b = Gaug[:, :p, p] / n_eff[:, None]
        return jax.vmap(det_solve)(A, b)
    G = Gaug[:, :p, :p] / n_eff[:, None, None]
    eye = jnp.eye(p, dtype=f32)[None]
    b = Gaug[:, :p, p:] / n_eff[:, None, None]                  # (k, p, m)
    if len(set(lam)) == 1:
        return jnp.moveaxis(jax.vmap(det_solve)(G + lam[0] * eye, b), 2, 0)
    return jnp.stack([jax.vmap(det_solve)(G + l * eye, b[..., j])
                      for j, l in enumerate(lam)])


def logistic_fit_folds_w(lam: jax.Array, iters: int, X: jax.Array,
                         t: jax.Array, Wk: jax.Array, *,
                         row_block: int = 0, strategy: Optional[str] = None
                         ) -> jax.Array:
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
            row_block=row_block, strategy=strategy)
        g = Gr[:, :p, p] / n_eff[:, None] + lam * beta
        H, _ = moments.fold_weighted_gram(X, s, intercept=True,
                                          row_block=row_block,
                                          strategy=strategy)
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
                   strategy: Optional[str] = None
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
                                                 strategy=strategy)
    n_eff = jnp.maximum(n_eff, 1.0)
    A = Gaug[:p, :p] + ridge * n_eff * jnp.eye(p, dtype=f32)
    theta = det_solve(A, Gaug[:p, p])
    if not with_se:
        return theta, None
    # weighted HC0: cov = A⁻¹ (Zᵀ diag(w² e²) Z) A⁻¹ — elementwise resid
    # (no mat-vec: (Z * theta).sum over the tiny p_phi axis is invariant)
    meat = moments.residual_meat(ry, rt, jnp.zeros_like(ry),
                                 jnp.zeros_like(rt), phi, theta, w=w,
                                 row_block=row_block, strategy=strategy)
    Ainv = det_inv(A)
    cov = jnp.einsum("ia,ab,bj->ij", Ainv, meat, Ainv)
    se = jnp.sqrt(jnp.clip(jnp.diagonal(cov), 0.0, None))
    return theta, se


def weighted_iv_theta(ry: jax.Array, rt: jax.Array, rz: jax.Array,
                      phi: jax.Array, w: jax.Array, *,
                      ridge: float = 1e-8, with_se: bool = True,
                      row_block: int = 0, strategy: Optional[str] = None
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
                                  strategy=strategy)
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
                           row_block=row_block, strategy=strategy)
    Ainv = det_inv(A)
    cov = jnp.einsum("ia,ab,bj->ij", Ainv, meat, Ainv)
    se = jnp.sqrt(jnp.clip(jnp.diagonal(cov), 0.0, None))
    return theta, se

"""Builders + pure-jnp oracle for the fused segment-Gram family.

Every moment form in ``repro.core.moments`` is an instance of ONE shape:

    G[s] = sum_{n: seg_n = s}  w_n * L_n (x) R_n

where the per-row factors (L, R) are assembled from raw inputs by a
*builder* — residualize, multiply by phi, append the target column —
and ``seg`` is a segment/fold id (one segment means a plain Gram).  The
builders below are plain jnp functions over 2-D fp32 blocks, so the
SAME builder body is traced inside the Pallas kernel (registers), the
XLA scatter lowering, and this one-hot einsum oracle: the three
backends differ only in how the segmented sum is realized.

Builder contract: inputs are 2-D arrays — row-shaped ``(rows, d)`` or
broadcast ``(1, d)`` (e.g. theta) — and the output pair (L, R) is
row-linear in the data, with all-zero input rows mapping to all-zero
L/R rows (that is what makes zero-padding the row tail an exact no-op
in every accumulator).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array
Pair = Tuple[Array, Array]


def build_pair(U: Array, V: Array) -> Pair:
    """Plain segmented outer product: L = U, R = V."""
    return U, V


def _cat(parts: Sequence[Array]) -> Array:
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def build_design(*D: Array) -> Pair:
    """Symmetric Gram over a design ``[X | 1? | y?]``, whole or as its
    column parts (assembled per row block, so the kernel path never
    copies X into an (n, q) design)."""
    D = _cat(D)
    return D, D


def build_residual(y: Array, t: Array, my: Array, mt: Array, phi: Array) -> Pair:
    """DML final stage: M = [(t - mt) * phi | (y - my)], G = M^T M."""
    ry = y - my
    rt = t - mt
    M = jnp.concatenate([rt * phi, ry], axis=1)
    return M, M


def build_residual_direct(ry: Array, rt: Array, phi: Array) -> Pair:
    """Residuals already formed (inference.numerics): M = [rt*phi | ry]."""
    M = jnp.concatenate([rt * phi, ry], axis=1)
    return M, M


def build_iv(ry: Array, rt: Array, rz: Array, phi: Array) -> Pair:
    """Instrumented augmented Gram: M = [rz*phi | rt*phi | ry]."""
    M = jnp.concatenate([rz * phi, rt * phi, ry], axis=1)
    return M, M


def build_fold_weighted(Wt: Array, *D: Array) -> Pair:
    """Dense per-fold weight matrix (moments.fold_weighted_gram):
    L_n = Wt_n ⊗ d_n (the k per-fold weights kron the design row, the
    design whole or as its column parts), so G = L^T R reshapes to the
    (k, q, q) stack Σ_n Wk[k, n] d_n d_nᵀ.  Zero rows give zero L/R
    rows (both factors vanish)."""
    D = _cat(D)
    r = Wt.shape[0]
    L = (Wt[:, :, None] * D[:, None, :]).reshape(r, Wt.shape[1] * D.shape[1])
    return L, D


def build_gram_and_vec(D: Array, wg: Array, v: Array) -> Pair:
    """Two-weight Gram + cross-moment (moments.weighted_gram_and_vec):
    L = [wg·d | v], R = d — the top q rows of L^T R are Σ wg d dᵀ and
    the trailing row is Σ v dᵀ (the augmented form; the thin ni,n->i
    mat-vec is not chunk-stable — see core.moments)."""
    return jnp.concatenate([wg * D, v], axis=1), D


def build_residual_meat(
    y: Array,
    t: Array,
    my: Array,
    mt: Array,
    phi: Array,
    theta: Array,
    w: Optional[Array] = None,
) -> Pair:
    """HC0 meat of the orthogonal moment: m = (w *) e * z with
    z = rt*phi, e = ry - <z, theta> (theta rides as a (1, p) broadcast
    row so the residual forms in registers alongside z)."""
    ry = y - my
    rt = t - mt
    z = rt * phi
    e = ry - jnp.sum(z * theta, axis=1, keepdims=True)
    if w is not None:
        e = w * e
    m = e * z
    return m, m


def build_iv_meat(
    ry: Array,
    rt: Array,
    rz: Array,
    phi: Array,
    theta: Array,
    w: Optional[Array] = None,
) -> Pair:
    """HC0 meat of the instrumented moment: score zc = rz*phi, residual
    e = ry - <rt*phi, theta>."""
    z = rt * phi
    e = ry - jnp.sum(z * theta, axis=1, keepdims=True)
    if w is not None:
        e = w * e
    m = e * (rz * phi)
    return m, m


def _dot(a: Array, b: Array) -> Array:
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def build_mm_logistic(xa: Array, meta: Array, coef: Array) -> Pair:
    """The segmented sweep's MM logistic gradient, lane-major (rows on
    lanes, see ``ops.lane_gram``).  ``xa`` (q, r) is [X | 1]ᵀ, ``meta``
    (3, r) is [t; cohort + 1; fold] (cohort 0 marks a padded row), and
    the resident ``coef`` (K·q, E) holds every (cohort, fold) model,
    cohort e's fold-k model in column e, rows k·q .. k·q + q - 1.  Per
    row the cohort's one-hot picks its K models, and the logits, mu - t
    and the fold complement (the row's own fold zeroed) form in
    registers:

        Lᵀ = onehot(cohort) (E, r),   Rᵀ[k·q + j] = rc[k] · xa[j],

    so ``L^T R`` reshapes to the (E, K, q) complement gradient
    ``Σ_{cohort e, fold ≠ k} (mu_k - t) xa``: the one-hot path's t1 - t2
    as one sum, with no (n, K) or (n, K, q) array in HBM.  The products
    with 0/1 matrices (the pick, the tiling, the per-model sums) run at
    HIGHEST, so they are exact; the real products stay elementwise in
    float32.  Zero rows give zero Lᵀ (cohort 0) and zero Rᵀ (xa = 0)."""
    q, r = xa.shape
    kq, E = coef.shape
    K = kq // q
    iota = jax.lax.broadcasted_iota
    t = meta[0:1]
    cohort = meta[1:2].astype(jnp.int32)
    fold = meta[2:3].astype(jnp.int32)
    L = (cohort == iota(jnp.int32, (E, r), 0) + 1).astype(jnp.float32)
    # tile (K·q, q): xa repeated per fold model; blk (K, K·q) sums each
    # model's q rows, blk_t (K·q, K) spreads a model's residual over them
    c, j = iota(jnp.int32, (kq, q), 0), iota(jnp.int32, (kq, q), 1)
    tile = functools.reduce(
        jnp.logical_or, [c == j + m * q for m in range(K)]).astype(jnp.float32)
    m, c = iota(jnp.int32, (K, kq), 0), iota(jnp.int32, (K, kq), 1)
    blk = ((c >= m * q) & (c < m * q + q)).astype(jnp.float32)
    c, m = iota(jnp.int32, (kq, K), 0), iota(jnp.int32, (kq, K), 1)
    blk_t = ((c >= m * q) & (c < m * q + q)).astype(jnp.float32)
    xk = _dot(tile, xa)  # (K·q, r)
    logit = _dot(blk, xk * _dot(coef, L))  # (K, r)
    res = jax.nn.sigmoid(logit) - t
    res = jnp.where(fold == iota(jnp.int32, (K, r), 0), 0.0, res)
    return L, _dot(blk_t, res) * xk


def seg_gram_ref(
    builder,
    arrays,
    *,
    seg: Optional[Array] = None,
    w: Optional[Array] = None,
    n_segments: int = 1,
) -> Array:
    """One-hot einsum oracle (whole-array, no blocking): the reference
    the kernel and scatter lowerings are tested against."""
    L, R = builder(*arrays)
    Lw = L if w is None else L * w
    if n_segments == 1:
        return jnp.einsum("ni,nj->ij", Lw, R)
    oh = jax.nn.one_hot(seg[:, 0], n_segments, dtype=L.dtype)
    return jnp.einsum("ns,ni,nj->sij", oh, Lw, R)

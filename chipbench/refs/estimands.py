"""Plain ``jax.numpy`` reference of the estimand the fit cells compute.

Adapted from ``chip_smoke.py`` (``_ref_dml``): straight from the
estimand's definition, no kernels, no batching, chunked over rows so
that it fits on one chip.

Every data-sized product goes through ``fold_weighted_gram`` or ``mm``.  With
``lowp=False`` (the reference) the products are float32 and every
contraction runs at ``precision=HIGHEST``.  With ``lowp=True`` (the
control) both factors are first rounded to bfloat16, which is what one
bfloat16 MXU pass with float32 accumulation computes: the product of two
bfloat16 values is exact in float32.  The small solves stay float32 at
HIGHEST in both, so the control differs from the reference only in the
precision of the data passes, the step a later change could be tempted
to take.  On any backend the two differ, so the control also fails on
the CPU.  Each public function runs under
``jax.default_matmul_precision("highest")``, so that the contractions
inside the solves (on the TPU the default is one bfloat16 pass) are
float32 too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def highest(fn):
    """Run ``fn`` (and trace what it jits) at float32 matmul precision."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


def rnd(a, lowp: bool):
    """``a`` as float32, rounded through bfloat16 when ``lowp``."""
    a = a.astype(F32)
    return a.astype(jnp.bfloat16).astype(F32) if lowp else a


def mm(a, b, lowp: bool):
    """``a @ b`` from float32 (or bfloat16-rounded) factors, exact
    accumulation order left to XLA at HIGHEST."""
    return jnp.matmul(rnd(a, lowp), rnd(b, lowp), precision=HI)


def _pad_rows(a, m):
    pad = (-a.shape[0]) % m
    if not pad:
        return a
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))


def fold_weighted_gram(Wc, D, *, lowp: bool, chunks: int):
    """``G[j] = sum_n Wc[n, j] D_n (x) D_n`` -> (k, q, q): per row chunk
    the weighted rows ``Wc[n, j] * D_n`` (float32 products, rounded
    through bfloat16 when ``lowp``) against ``D``."""
    n, k, q = D.shape[0], Wc.shape[1], D.shape[1]
    m = -(-n // chunks)
    Wc, D = _pad_rows(Wc.astype(F32), m), _pad_rows(D.astype(F32), m)

    def step(acc, c):
        wc = jax.lax.dynamic_slice_in_dim(Wc, c * m, m)
        d = jax.lax.dynamic_slice_in_dim(D, c * m, m)
        L = rnd((wc[:, :, None] * d[:, None, :]).reshape(m, k * q), lowp)
        return acc + jnp.matmul(L.T, rnd(d, lowp), precision=HI), None

    G, _ = jax.lax.scan(step, jnp.zeros((k * q, q), F32), jnp.arange(chunks))
    return G.reshape(k, q, q)


def fold_ids(key, n: int, k: int):
    """The program's balanced fold assignment: a permutation of
    ``arange(n) % k`` under ``key``."""
    return jax.random.permutation(key, jnp.arange(n, dtype=jnp.int32) % k)


@highest
@functools.partial(jax.jit, static_argnames=("k", "chunks", "lowp", "pred_lowp"))
def dml(X, y, t, key, w, *, k: int, lam: float, chunks: int, lowp: bool,
        pred_lowp: bool = False):
    """Weighted cross-fit DML with ridge nuisances for y and t, directly
    from its definition: folds from ``key``'s first split, per fold a
    ridge fit on the complement rows (row weights ``w``), out-of-fold
    predictions, then the orthogonal final stage and its HC0 standard
    error.  ``pred_lowp`` rounds the factors of the out-of-fold
    predictions X @ beta through bfloat16 (as one bfloat16 MXU pass does)
    whatever ``lowp`` is.  Returns (theta, se, beta (2, k, q)): the fold
    models' ridge coefficients for y and for t, intercept last."""
    n = X.shape[0]
    kf, _, _ = jax.random.split(key, 3)
    folds = fold_ids(kf, n, k)
    D = jnp.concatenate([X, jnp.ones((n, 1), F32), y[:, None], t[:, None]],
                        axis=1)
    q = X.shape[1] + 1
    Wc = w[:, None] * (folds[:, None] != jnp.arange(k)[None, :])  # (n, k)
    G = fold_weighted_gram(Wc, D, lowp=lowp, chunks=chunks)
    ne = jnp.maximum(Wc.sum(0), 1.0)[:, None]
    A = G[:, :q, :q] / ne[..., None] + lam * jnp.eye(q, dtype=F32)
    B = jnp.linalg.solve(A, G[:, :q, q:] / ne[..., None])  # (k, q, 2)
    pred = mm(D[:, :q], jnp.transpose(B, (1, 0, 2)).reshape(q, 2 * k),
              lowp or pred_lowp)
    pred = pred.reshape(n, k, 2)
    own = jnp.take_along_axis(pred, folds[:, None, None], 1)[:, 0]  # (n, 2)
    ry, rt = y - own[:, 0], t - own[:, 1]
    a = (w * rt * rt).sum() + 1e-8 * jnp.maximum(w.sum(), 1.0)
    theta = (w * rt * ry).sum() / a
    e = ry - theta * rt
    se = jnp.sqrt(((w * e * rt) ** 2).sum()) / a
    return theta, se, jnp.moveaxis(B, 2, 0)


def bootstrap_weights(kb, n: int):
    """Pairs-bootstrap row counts of replicate key ``kb`` and the key of
    its refit, as the program derives them: ``split(kb)`` gives the
    resampling key and the fit key."""
    kw, kfit = jax.random.split(kb)
    idx = jax.random.randint(kw, (n,), 0, n)
    return jnp.bincount(idx, length=n).astype(F32), kfit

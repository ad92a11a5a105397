"""Property-based tests (hypothesis) on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import moments
from repro.core.crossfit import fold_ids, fold_weights
from repro.inference.numerics import _UNBLOCKED_MAX, det_solve
from repro.kernels.seg_gram import ops as sg_ops

SETTINGS = dict(max_examples=20, deadline=None)


def _close(got, want, rtol=1e-5):
    """fp32 reassociation: agreement to ``rtol`` of the largest entry."""
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=rtol * max(1.0, np.abs(want).max()))


def _segment_rows(seed, n, q, s):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    U = jax.random.normal(ks[0], (n, q))
    V = jax.random.normal(ks[1], (n, q + 1))
    seg = jax.random.randint(ks[2], (n,), 0, s)
    w = jax.random.exponential(ks[3], (n,))
    return U, V, seg, w


def _scatter_outer(U, V, seg, s, w):
    return sg_ops.segment_outer(U, V, seg, s, w=w, backend="scatter")


# few distinct shapes, so the examples share compiled programs
ROWS = st.sampled_from([1, 37, 256, 300])
WIDTHS = st.sampled_from([1, 4])
SEGMENTS = st.sampled_from([2, 5, 9])   # S = 1 returns an unstacked Gram


@settings(**SETTINGS)
@given(n=st.integers(10, 500), k=st.integers(2, 8), seed=st.integers(0, 99))
def test_fold_partition_invariants(n, k, seed):
    folds = fold_ids(jax.random.PRNGKey(seed), n, k)
    W = fold_weights(folds, k)
    f = np.asarray(folds)
    assert f.min() >= 0 and f.max() < k
    # balanced within 1
    counts = np.bincount(f, minlength=k)
    assert counts.max() - counts.min() <= 1
    # every sample trains k-1 models and is held out of exactly 1
    np.testing.assert_array_equal(np.asarray(W.sum(0)), (k - 1.0))


@settings(**SETTINGS)
@given(cut=st.sampled_from([1, 100, 255, 299]), q=WIDTHS, s=SEGMENTS,
       seed=st.integers(0, 999))
def test_segment_outer_additive_over_row_splits(cut, q, s, seed):
    """The store's additivity: the segmented Gram over rows A ∪ B is its
    sum over A plus its sum over B, wherever the rows are split."""
    U, V, seg, w = _segment_rows(seed, 300, q, s)
    whole = _scatter_outer(U, V, seg, s, w)
    parts = (_scatter_outer(U[:cut], V[:cut], seg[:cut], s, w[:cut])
             + _scatter_outer(U[cut:], V[cut:], seg[cut:], s, w[cut:]))
    _close(parts, whole)


@settings(**SETTINGS)
@given(n=ROWS, q=WIDTHS, s=SEGMENTS, c=st.floats(-4.0, 4.0),
       seed=st.integers(0, 999))
def test_segment_outer_linear_in_row_weights(n, q, s, c, seed):
    """G(w1 + c·w2) = G(w1) + c·G(w2): a row weight enters once and
    linearly (a bootstrap or segment mask is just another weight)."""
    U, V, seg, w1 = _segment_rows(seed, n, q, s)
    w2 = jax.random.normal(jax.random.PRNGKey(seed + 1), (n,))
    got = _scatter_outer(U, V, seg, s, w1 + c * w2)
    want = (_scatter_outer(U, V, seg, s, w1)
            + c * _scatter_outer(U, V, seg, s, w2))
    _close(got, want, rtol=1e-4)


@settings(**SETTINGS)
@given(n=ROWS, q=WIDTHS, s=SEGMENTS, seed=st.integers(0, 999))
def test_segment_outer_equivariant_under_relabelling(n, q, s, seed):
    """Relabelling the segments by a permutation π moves slab s to
    π(s) and changes nothing else."""
    U, V, seg, w = _segment_rows(seed, n, q, s)
    perm = jax.random.permutation(jax.random.PRNGKey(seed + 7), s)
    base = _scatter_outer(U, V, seg, s, w)
    moved = _scatter_outer(U, V, perm[seg], s, w)
    _close(np.asarray(moved)[np.asarray(perm)], base)


@settings(**SETTINGS)
@given(p=st.sampled_from([1, 7, _UNBLOCKED_MAX, _UNBLOCKED_MAX + 1, 96]),
       seed=st.integers(0, 999))
def test_det_solve_matches_linalg_solve_on_spd(p, seed):
    """det_solve agrees with jnp.linalg.solve on random SPD systems on
    both sides of its unblocked/panelled threshold."""
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    B = jax.random.normal(ka, (p, p))
    A = B @ B.T / p + jnp.eye(p)
    b = jax.random.normal(kb, (p,))
    with jax.default_matmul_precision("highest"):
        want = jnp.linalg.solve(A, b)
    _close(jax.jit(det_solve)(A, b), want, rtol=1e-4)


@settings(**SETTINGS)
@given(n=ROWS, p=WIDTHS, k=st.sampled_from([2, 5]),
       row_block=st.sampled_from([0, 64]), seed=st.integers(0, 999))
def test_fold_complement_gram_is_total_less_own_fold(n, p, k, row_block,
                                                     seed):
    """The leave-one-out identity behind ``parallel_loo`` and the
    segmented sweep: the fold-weighted Gram under ``fold_weights``'
    complement weights (times row weights) is, for each fold, the total
    Gram less that fold's ``fold_gram``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    X = jax.random.normal(ks[0], (n, p))
    folds = jax.random.randint(ks[1], (n,), 0, k)
    w = jax.random.exponential(ks[2], (n,))
    Wk = fold_weights(folds, k) * w[None, :]
    G, n_eff = moments.fold_weighted_gram(X, Wk, row_block=row_block,
                                          strategy="chunked")
    Gh, _ = moments.fold_gram(X * jnp.sqrt(w)[:, None], folds, k,
                              row_block=row_block, strategy="chunked")
    _close(G, Gh.sum(0)[None] - Gh)
    _close(n_eff, Wk.sum(1))

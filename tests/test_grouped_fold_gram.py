"""The ridge fold Grams of a weighted re-estimation from rows grouped by
fold.

``moments.grouped_fold_weighted_gram`` reduces each fold's rows on
their own (a stable sort of the fold ids groups them) and sums the other
folds' Grams into each complement, where ``fold_weighted_gram`` widens
every row by the k fold weights.  Both compute
``Σ_n fold_weights(folds, k)[c, n] · w_n · d_n d_nᵀ``; the sums run in
another order, so they agree to float32 rounding, and ``n_eff`` bitwise.
A bootstrap replicate with ridge nuisances takes the grouped form; each
``dml_bootstrap`` call counts ``inference.fold_gram[grouped|kron]``.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import CausalConfig
from repro.core import moments
from repro.core.crossfit import fold_ids, fold_weights
from repro.core.dml import DML
from repro.core.nuisance import make_logistic, make_ridge
from repro.data.causal_dgp import make_causal_data
from repro.inference import bootstrap, dml_bootstrap
from repro.kernels.seg_gram import ops as sg_ops
from repro.obs.metrics import default_registry

K, ROW_BLOCK = 5, 128
# row_block 0 is the whole-array einsum; pallas runs the kernel in
# interpret mode, the CPU stand-in for the chip's lowering
STRATEGIES = {"whole_array": (0, None, None),
              "chunked": (ROW_BLOCK, "chunked", None),
              "pallas": (ROW_BLOCK, "pallas", "interpret")}
# the widths of test_shared_nuisance_gram.py
WIDTHS = (37, 130)
# the Grams sum the same float32 products in another order: the largest
# entry's relative gap stays within a few float32 roundings of the
# n-term sums
GRAM_RTOL = 2e-6
# n: a multiple of K and of ROW_BLOCK's fold slices (2560 = 5 x 512),
# n % K != 0 (2051), and a fold tail that is not a multiple of
# ROW_BLOCK (2051 / 5 -> 411 and 410 rows)
SIZES = (2560, 2051)


def _backend(name):
    return sg_ops.force_backend(name) if name else contextlib.nullcontext()


def _data(n, p, seed=0):
    kx, ky, kf, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    X = jax.random.normal(kx, (n, p), jnp.float32)
    Y = jax.random.normal(ky, (n, 2), jnp.float32)
    folds = fold_ids(kf, n, K)
    w = bootstrap.bootstrap_weights(kw, n, "pairs")
    return X, Y, folds, w


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("p", WIDTHS)
@pytest.mark.parametrize("strategy", sorted(STRATEGIES))
def test_grouped_equals_kron_form(strategy, p, n):
    """The grouped Gram against the kron form on the same weights, with
    pairs-bootstrap counts (about a third of the rows count zero)."""
    rb, st, backend = STRATEGIES[strategy]
    X, Y, folds, w = _data(n, p)
    assert int((w == 0).sum()) > n // 4
    with _backend(backend):
        G, n_eff = moments.grouped_fold_weighted_gram(
            X, folds, w, K, intercept=True, append=Y, row_block=rb,
            strategy=st)
        G_ref, n_ref = moments.fold_weighted_gram(
            X, fold_weights(folds, K) * w[None, :], intercept=True,
            append=Y, row_block=rb, strategy=st)
    assert G.shape == G_ref.shape == (K, p + 3, p + 3)
    np.testing.assert_array_equal(np.asarray(n_eff), np.asarray(n_ref))
    gap = np.abs(np.asarray(G) - np.asarray(G_ref)).max(axis=(1, 2))
    scale = np.abs(np.asarray(G_ref)).max(axis=(1, 2))
    assert (gap <= GRAM_RTOL * scale).all(), gap / scale


@pytest.mark.parametrize("strategy", sorted(STRATEGIES))
def test_zero_weight_rows_and_folds_change_nothing(strategy):
    """Rows of count zero add nothing: dropping the weight of every row
    of fold 0 leaves fold 0's complement Gram (the other four folds)
    exactly as it was and zeroes fold 0's share of the others."""
    rb, st, backend = STRATEGIES[strategy]
    X, Y, folds, w = _data(2051, WIDTHS[0], seed=1)
    w0 = jnp.where(folds == 0, 0.0, w)
    kw = dict(intercept=True, append=Y, row_block=rb, strategy=st)
    with _backend(backend):
        G, _ = moments.grouped_fold_weighted_gram(X, folds, w, K, **kw)
        G0, n0 = moments.grouped_fold_weighted_gram(X, folds, w0, K, **kw)
        G_ref, n_ref = moments.fold_weighted_gram(
            X, fold_weights(folds, K) * w0[None, :], **kw)
    np.testing.assert_array_equal(np.asarray(G0[0]), np.asarray(G[0]))
    np.testing.assert_array_equal(np.asarray(n0), np.asarray(n_ref))
    np.testing.assert_allclose(np.asarray(G0), np.asarray(G_ref),
                               rtol=0, atol=GRAM_RTOL
                               * float(np.abs(np.asarray(G_ref)).max()))


@pytest.mark.parametrize("n", [7, 1000, 2051, 4096, 1_700_003])
def test_fold_sizes_are_those_of_fold_ids(n):
    """``fold_sizes`` is the static layout of ``fold_ids``' draw at every
    n, across the shuffle's one to three sort rounds; the grouped form
    relies on it, and leaves the fold ids themselves as drawn."""
    folds = np.asarray(fold_ids(jax.random.PRNGKey(n), n, K))
    sizes = moments.fold_sizes(n, K)
    np.testing.assert_array_equal(np.bincount(folds, minlength=K), sizes)
    assert sum(sizes) == n and sizes[0] - sizes[-1] <= 1


def _bits(tree):
    return [np.asarray(x).view(np.uint32)
            for x in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("pair", ["ridge_ridge", "ridge_logistic"])
@pytest.mark.parametrize("strategy", sorted(STRATEGIES))
def test_replicates_serial_equal_vmap_bitwise(strategy, pair):
    """Replicate thetas and SEs do not depend on the executor's batch
    axis on the grouped path, shared Gram or not."""
    rb, st, backend = STRATEGIES[strategy]
    d = make_causal_data(jax.random.PRNGKey(2), 2051, WIDTHS[0], effect=1.0)
    ny = make_ridge(1e-3, rb, st)
    nt = (make_ridge(1e-3, rb, st) if pair == "ridge_ridge"
          else make_logistic(1e-3, 4, rb, st))
    with _backend(backend):
        reps = [dml_bootstrap(ny, nt, n_folds=K, XW=d.X, y=d.y, t=d.t,
                              phi=d.X[:, :1], key=jax.random.PRNGKey(1),
                              n_replicates=3, executor=ex, row_block=rb,
                              strategy=st)
                for ex in ("serial", "vmap")]
    for a, b in zip(*(_bits((r.replicates, r.replicate_se)) for r in reps)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("pair,form", [
    ((make_ridge(1e-3, ROW_BLOCK, "chunked"),
      make_ridge(1e-3, ROW_BLOCK, "chunked")), "grouped"),
    ((make_ridge(1e-3, ROW_BLOCK, "chunked"),
      make_logistic(1e-3, 4, ROW_BLOCK, "chunked")), "kron"),
], ids=["ridge_pair", "ridge_logistic"])
def test_fold_gram_counted_once_per_bootstrap_call_and_on_the_span(pair,
                                                                  form):
    from repro.obs.trace import Tracer
    ny, nt = pair
    d = make_causal_data(jax.random.PRNGKey(4), 1024, 6, effect=1.0)
    tracer = Tracer(sync=False)
    for i in range(2):
        dml_bootstrap(ny, nt, n_folds=K, XW=d.X, y=d.y, t=d.t,
                      phi=d.X[:, :1], key=jax.random.PRNGKey(i),
                      n_replicates=2, tracer=tracer)
    counters = default_registry().snapshot()["counters"]
    other = {"grouped": "kron", "kron": "grouped"}[form]
    assert counters[f"inference.fold_gram[{form}]"] == 2
    assert f"inference.fold_gram[{other}]" not in counters
    spans = [s for s in tracer.spans if s.name == "inference.bootstrap"]
    assert [s.attrs["fold_gram"] for s in spans] == [form] * 2


def test_estimator_bootstrap_counts_grouped_once_per_fit():
    d = make_causal_data(jax.random.PRNGKey(5), 1024, 6, effect=1.0)
    cfg = CausalConfig(n_folds=K, nuisance_y="ridge", nuisance_t="ridge",
                       inference="bootstrap", n_bootstrap=2,
                       row_block=ROW_BLOCK)
    est = DML(cfg)
    for i in range(2):
        res = est.fit(d.y, d.t, d.X, key=jax.random.PRNGKey(i))
        res.ate_interval()
        res.ate_interval()          # cached: no second bootstrap
    counters = default_registry().snapshot()["counters"]
    assert counters["inference.fold_gram[grouped]"] == 2
    assert "inference.fold_gram[kron]" not in counters

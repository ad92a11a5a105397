"""Both DML nuisances from one fold-weighted Gram.

When the outcome and treatment nuisances are both ridge, streamed alike,
a bootstrap replicate (and every other ``dml_residuals_once`` caller)
builds ONE fold-weighted Gram over [X | 1 | y | t], reads both ridges'
normal equations off it and solves them in one elimination when their
penalties agree.  Every number it returns has the bits of the two
separate fits; any other pair of
nuisances fits each on its own.  Each ``dml_bootstrap`` call counts
``inference.nuisance_gram[shared|separate]``.
"""
import contextlib

import jax
import numpy as np
import pytest

from repro.config import CausalConfig
from repro.core import moments
from repro.core.dml import DML
from repro.core.nuisance import make_logistic, make_ridge
from repro.data.causal_dgp import make_causal_data
from repro.inference import bootstrap, dml_bootstrap
from repro.kernels.seg_gram import ops as sg_ops
from repro.obs.metrics import default_registry

N, K, B, ROW_BLOCK = 2048, 5, 3, 512
# row_block 0 is the whole-array einsum; pallas runs the kernel in
# interpret mode, the CPU stand-in for the chip's lowering
STRATEGIES = {"whole_array": (0, None, None),
              "chunked": (ROW_BLOCK, "chunked", None),
              "pallas": (ROW_BLOCK, "pallas", "interpret")}
# below and above the panelled elimination's threshold of 64 unknowns
WIDTHS = (37, 130)


@pytest.fixture(scope="module")
def datasets():
    return {p: make_causal_data(jax.random.PRNGKey(p), N, p, effect=1.0)
            for p in WIDTHS}


def _bits(tree):
    return [np.asarray(x).view(np.uint32)
            for x in jax.tree_util.tree_leaves(tree)]


def _backend(name):
    return sg_ops.force_backend(name) if name else contextlib.nullcontext()


def _replicates(ny, nt, d, shared, *, final_stage=True):
    """ry, rt (and theta, se) of two pairs-bootstrap replicates, vmapped,
    on the shared path or (``shared`` False) the separate one."""

    def one(kb):
        kw, kfit = jax.random.split(kb)
        w = bootstrap.bootstrap_weights(kw, N, "pairs")
        out = bootstrap.dml_residuals_once(ny, nt, K, d.X, d.y, d.t, kfit,
                                           w)
        if final_stage:
            out.update(bootstrap.dml_theta_once(
                ny, nt, K, d.X, d.y, d.t, d.X[:, :2], kfit, w))
        return out

    with pytest.MonkeyPatch.context() as mp:
        if not shared:
            mp.setattr(bootstrap, "shares_nuisance_gram",
                       lambda a, b: False)
        return jax.jit(jax.vmap(one))(
            bootstrap.replicate_keys(jax.random.PRNGKey(3), 2))


@pytest.mark.parametrize("strategy", sorted(STRATEGIES))
@pytest.mark.parametrize("p", WIDTHS)
def test_shared_gram_equals_separate_fits_bitwise(datasets, p, strategy):
    rb, st, backend = STRATEGIES[strategy]
    ny, nt = make_ridge(1e-3, rb, st), make_ridge(1e-3, rb, st)
    assert bootstrap.shares_nuisance_gram(ny, nt)
    with _backend(backend):
        shared = _replicates(ny, nt, datasets[p], True)
        separate = _replicates(ny, nt, datasets[p], False)
    assert sorted(shared) == ["rt", "ry", "se", "theta"]
    for a, b in zip(_bits(shared), _bits(separate)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("strategy", sorted(STRATEGIES))
def test_shared_gram_serial_equals_vmap_bitwise(datasets, strategy):
    """The replicate thetas on the shared path do not depend on the
    executor's batch axis."""
    rb, st, backend = STRATEGIES[strategy]
    d = datasets[WIDTHS[0]]
    ny, nt = make_ridge(1e-3, rb, st), make_ridge(1e-3, rb, st)
    with _backend(backend):
        reps = {ex: dml_bootstrap(ny, nt, n_folds=K, XW=d.X, y=d.y, t=d.t,
                                  phi=d.X[:, :1], key=jax.random.PRNGKey(1),
                                  n_replicates=B, executor=ex,
                                  row_block=rb, strategy=st).replicates
                for ex in ("serial", "vmap")}
    np.testing.assert_array_equal(*_bits(reps))
    assert default_registry().snapshot()["counters"][
        "inference.nuisance_gram[shared]"] == 2


@pytest.mark.parametrize("pair", [
    (make_ridge(1e-3, ROW_BLOCK, "chunked"),
     make_logistic(1e-3, 4, ROW_BLOCK, "chunked")),
    (make_ridge(1e-3, ROW_BLOCK, "chunked"), make_ridge(1e-3, 0, None)),
    (make_ridge(1e-3, ROW_BLOCK, "chunked"),
     make_ridge(1e-3, ROW_BLOCK, "whole")),
], ids=["ridge_logistic", "row_block_differs", "strategy_differs"])
def test_other_pairs_fit_apart_and_count_separate(datasets, pair):
    from repro.obs.trace import Tracer
    ny, nt = pair
    assert not bootstrap.shares_nuisance_gram(ny, nt)
    d = datasets[WIDTHS[0]]
    grams = []
    real = moments.fold_weighted_gram

    def counted(*a, **kw):
        grams.append(kw.get("append"))
        return real(*a, **kw)

    tracer = Tracer(sync=False)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moments, "fold_weighted_gram", counted)
        dml_bootstrap(ny, nt, n_folds=K, XW=d.X, y=d.y, t=d.t,
                      phi=d.X[:, :1], key=jax.random.PRNGKey(1),
                      n_replicates=B, tracer=tracer)
    # each nuisance traces its own Grams, none with two targets
    assert grams and all(a is None or a.ndim == 1 for a in grams)
    counters = default_registry().snapshot()["counters"]
    assert counters["inference.nuisance_gram[separate]"] == 1
    assert "inference.nuisance_gram[shared]" not in counters
    (span,) = [s for s in tracer.spans if s.name == "inference.bootstrap"]
    assert span.attrs["nuisance_gram"] == "separate"


@pytest.mark.parametrize("p", WIDTHS)
def test_unequal_penalties_share_the_gram_and_solve_apart(datasets,
                                                          monkeypatch, p):
    ny = make_ridge(1e-3, ROW_BLOCK, "chunked")
    nt = make_ridge(3e-2, ROW_BLOCK, "chunked")
    assert bootstrap.shares_nuisance_gram(ny, nt)
    grams = []
    real = moments.grouped_fold_weighted_gram

    def counted(*a, **kw):
        grams.append(kw["append"].shape)
        return real(*a, **kw)

    monkeypatch.setattr(moments, "grouped_fold_weighted_gram", counted)
    shared = _replicates(ny, nt, datasets[p], True, final_stage=False)
    assert grams == [(N, 2)]
    path = "panelled" if p > 64 else "unblocked"
    assert default_registry().snapshot()["counters"][
        f"det_solve.path[{path}]"] == 2
    separate = _replicates(ny, nt, datasets[p], False, final_stage=False)
    for a, b in zip(_bits(shared), _bits(separate)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("p", WIDTHS)
def test_equal_penalties_share_one_elimination(datasets, p):
    ny, nt = (make_ridge(1e-3, ROW_BLOCK, "chunked") for _ in range(2))
    _replicates(ny, nt, datasets[p], True, final_stage=False)
    path = "panelled" if p > 64 else "unblocked"
    assert default_registry().snapshot()["counters"][
        f"det_solve.path[{path}]"] == 1


def test_counted_once_per_bootstrap_call_and_named_on_the_span(datasets):
    from repro.obs.trace import Tracer
    d = datasets[WIDTHS[0]]
    cfg = CausalConfig(n_folds=K, nuisance_y="ridge", nuisance_t="ridge",
                       inference="bootstrap", n_bootstrap=B,
                       row_block=ROW_BLOCK)
    tracer = Tracer(sync=False)
    est = DML(cfg, tracer=tracer)
    for i in range(2):
        res = est.fit(d.y, d.t, d.X, key=jax.random.PRNGKey(i))
        res.ate_interval()
        res.ate_interval()          # cached: no second bootstrap
    counters = default_registry().snapshot()["counters"]
    assert counters["inference.nuisance_gram[shared]"] == 2
    assert "inference.nuisance_gram[separate]" not in counters
    spans = [s for s in tracer.spans if s.name == "inference.bootstrap"]
    assert [s.attrs["nuisance_gram"] for s in spans] == ["shared"] * 2

"""The segmented sweep's fused MM logistic step and its binary uplift
panel, on the CPU at n = 4,096, p = 12, E = 8.

Contracts:
  * one fused step (``seg_gram.ops.mm_logistic_grad``: lane-major rows,
    the (E, K, q) coefficients resident) equals the one-hot einsum
    step's t1 - t2, on Zipf cohorts, 85% treated, with one empty
    (cohort, fold) cell, under the "scatter" and "interpret" lowerings
    and across padded kernel row blocks;
  * ``sweep(mode="segmented")`` equals ``chipbench/refs/uplift.py``'s
    plain reference of the estimand, nuisance models included;
  * the segmented column records its spans (``sweep.column[i]`` >
    ``sweep.segmented``) on the process tracer and counts
    ``sweep.path[...]``, ``sweep.cells``, ``sweep.mm_steps`` and
    ``sweep.empty_cells`` on the process registry.
"""
import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import CausalConfig
from repro.kernels.seg_gram import kernel as sg_kernel
from repro.kernels.seg_gram import ops as sg_ops
from repro.kernels.seg_gram import ref as sg_ref
from repro.obs import trace
from repro.obs.metrics import default_registry
from repro.sweep import SweepSpec, sweep
from repro.sweep import segmented

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench.refs import uplift  # noqa: E402

N, P, E, K = 4096, 12, 8, 5
Q = P + 1
ITERS = 32
# The fused step sums (mu_k - t) xa over each cohort's other folds in one
# term; the einsum step sums the cohort (t1) and subtracts its own fold
# (t2).  Same products, different association: f32 rounding of sums of
# about 4,096 terms of size ~1, relative to the gradient's largest entry.
STEP_RTOL = 2e-6
# 32 MM steps and the final stage on top of it: each step's rounding
# passes through a well-conditioned solve (H0 = Gram / 4 + lam I), so the
# models agree to a few ulps of float32 times the steps.
FIT_RTOL = 2e-5


def _data(key=jax.random.PRNGKey(5)):
    config = {"n": N, "p": P, "segments": E,
              "data": {"zipf_s": 1.0, "treated_share": 0.85,
                       "visit_rate": 0.047, "uplift_mean": 0.01,
                       "uplift_sd": 0.005, "visit_feature_norm": 0.5}}
    return uplift.make_panel(key, config)


@pytest.fixture(scope="module")
def data():
    return _data()


def _cfg(**kw):
    return CausalConfig(n_folds=K, nuisance_y="ridge", nuisance_t="logistic",
                        discrete_treatment=True, cate_features=1,
                        row_block=512, row_block_strategy="pallas",
                        inference="none", **kw)


def _einsum_grad(Xa, t, sids, folds, beta):
    """The one-hot path's step gradient, t1 - t2, in float64 numpy."""
    Xa, t, beta = (np.asarray(a, np.float64) for a in (Xa, t, beta))
    sids, folds = np.asarray(sids), np.asarray(folds)
    r = 1.0 / (1.0 + np.exp(-np.einsum("np,nkp->nk", Xa, beta[sids]))) - t[:, None]
    t1 = np.zeros((E, K, Q))
    t2 = np.zeros((E, K, Q))
    np.add.at(t1, sids, r[:, :, None] * Xa[:, None, :])
    np.add.at(t2, (sids, folds), r[np.arange(N), folds][:, None] * Xa)
    return t1 - t2


@pytest.fixture(scope="module")
def step_inputs(data):
    sids = np.asarray(data["sids"]).copy()
    folds = np.array(jax.random.randint(jax.random.PRNGKey(8), (N,), 0, K))
    # one empty (cohort, fold) cell: cohort E - 1 has no row in fold 2
    folds[(sids == E - 1) & (folds == 2)] = 3
    beta = 0.5 * jax.random.normal(jax.random.PRNGKey(9), (E, K, Q))
    Xa = jnp.concatenate([data["X"], jnp.ones((N, 1))], axis=1)
    return Xa, data["t"], jnp.asarray(sids), jnp.asarray(folds), beta


# explicit block_n tilings of the 4,096 rows: 384 gives ten full blocks
# and a padded tail, 128 gives 32 blocks and no tail, 1,024 an exact
# multiple, 8,192 one block wider than n
@pytest.mark.parametrize("backend,block_n", [
    ("scatter", None), ("interpret", None), ("interpret", 384),
    ("interpret", 128), ("interpret", 1024), ("interpret", 8192)])
def test_fused_step_matches_einsum_step(step_inputs, backend, block_n):
    Xa, t, sids, folds, beta = step_inputs
    assert 0.8 < float(t.mean()) < 0.9
    counts = np.bincount(np.asarray(sids) * K + np.asarray(folds), minlength=E * K)
    assert counts[(E - 1) * K + 2] == 0 and counts.min() == 0
    xa_t = Xa.T
    meta_t = jnp.stack([t, (sids + 1).astype(jnp.float32),
                        folds.astype(jnp.float32)])
    if block_n is None:
        got = sg_ops.mm_logistic_grad(xa_t, meta_t, beta, backend=backend)
    else:
        table = jnp.transpose(beta, (1, 2, 0)).reshape(K * Q, E)
        got = sg_kernel.seg_gram_lanes(
            sg_ref.build_mm_logistic, [xa_t, meta_t, table], interpret=True,
            block_n=block_n).reshape(E, K, Q)
    want = _einsum_grad(Xa, t, sids, folds, beta)
    np.testing.assert_allclose(np.asarray(got), want,
                               atol=STEP_RTOL * np.abs(want).max(), rtol=0)
    assert np.isfinite(np.asarray(got)).all()


def test_build_mm_logistic_zero_rows_are_zero():
    """The padding contract: all-zero input columns give zero L and R."""
    L, R = sg_ref.build_mm_logistic(jnp.zeros((Q, 16)), jnp.zeros((3, 16)),
                                    jnp.ones((K * Q, E)))
    assert np.all(np.asarray(L) == 0.0) and np.all(np.asarray(R) == 0.0)


@pytest.mark.parametrize("backend", ["scatter", "interpret"])
def test_fused_mm_fit_matches_einsum_fit(data, step_inputs, backend):
    """32 MM steps from the fused step = 32 from the one-hot einsums."""
    Xa, t, sids, folds, _ = step_inputs
    comb = sids * K + folds
    Gh, counts = segmented._fold_grams(data["X"], data["y"], comb, E, K, 0,
                                       "chunked")
    Gc, n_eff = segmented._complement(Gh, counts)
    args = (Xa.T, t, sids, folds, Gc, n_eff, 1e-3, ITERS)
    want = segmented._segment_fold_logistic(*args, "chunked")
    with sg_ops.force_backend(backend):
        got = segmented._segment_fold_logistic(*args, "pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=FIT_RTOL * float(jnp.abs(want).max()), rtol=0)


def test_segmented_sweep_matches_uplift_reference(data):
    cfg = _cfg()
    key = jax.random.PRNGKey(21)
    panel = sweep(SweepSpec.grid(n_segments=E, configs=(cfg,)), X=data["X"],
                  y=data["y"], t=data["t"], segment_ids=data["sids"], key=key,
                  mode="segmented")
    col = panel.columns[0]
    assert col.events == ("segmented",) and not col.failed
    theta, se, beta_y, beta_t = uplift.segmented_dml(
        data["X"], data["y"], data["t"], data["sids"], jax.random.fold_in(key, 0),
        E=E, k=K, lam=cfg.ridge_lambda, iters=ITERS, chunks=8, lowp=False)
    assert col.beta_y.shape == col.beta_t.shape == (E, K, Q)
    for got, want in ((col.beta_y, beta_y), (col.beta_t, beta_t)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=FIT_RTOL * float(jnp.abs(want).max()),
                                   rtol=0)
    # theta within a hundred-thousandth of its SE, SE to 1e-5 relative
    gap = np.abs(np.asarray(col.thetas[:, 0]) - np.asarray(theta)) / np.asarray(se)
    assert gap.max() < 1e-5, gap
    np.testing.assert_allclose(np.asarray(col.ses[:, 0]), np.asarray(se), rtol=1e-5)
    assert np.asarray(col.cell_rows).min() > 0


def _counters():
    return dict(default_registry().snapshot()["counters"])


def _delta(c0, c1, name):
    return c1.get(name, 0) - c0.get(name, 0)


def test_segmented_column_spans_and_counters(data):
    """Spans on the process tracer, counters on the process registry;
    a cohort with no rows leaves its K (cohort, fold) cells empty."""
    sids = jnp.where(data["sids"] == E - 1, 0, data["sids"])
    cfg = _cfg(ridge_lambda=2e-3)
    trace.reset_process_tracer()
    c0 = _counters()
    panel = sweep(SweepSpec.grid(n_segments=E, configs=(cfg,)), X=data["X"],
                  y=data["y"], t=data["t"], segment_ids=sids,
                  key=jax.random.PRNGKey(2), mode="segmented")
    c1 = _counters()
    assert panel.columns[0].events == ("segmented",)
    assert _delta(c0, c1, "sweep.path[segmented]") == 1
    assert _delta(c0, c1, "sweep.cells") == E * K
    assert _delta(c0, c1, "sweep.mm_steps") == ITERS
    assert _delta(c0, c1, "sweep.empty_cells") == K
    spans = {s.name: s for s in trace.process_tracer().spans}
    assert spans["sweep.segmented"].parent_id == spans["sweep.column[0]"].span_id
    assert not spans["sweep.segmented"].open
    trace.reset_process_tracer()


def test_cells_and_shared_paths_count(data):
    cfg = dataclasses.replace(_cfg(), row_block_strategy="chunked",
                              nuisance_t="ridge", discrete_treatment=False)
    trace.reset_process_tracer()
    c0 = _counters()
    cfg2 = dataclasses.replace(cfg, cate_features=2)
    spec = SweepSpec(n_segments=E, columns=(("dml", cfg), ("dml", cfg2)))
    sweep(spec, X=data["X"][:512], y=data["y"][:512], t=data["t"][:512],
          segment_ids=data["sids"][:512], key=jax.random.PRNGKey(4))
    c1 = _counters()
    assert _delta(c0, c1, "sweep.path[shared]") == 2
    assert _delta(c0, c1, "sweep.cells") == 2 * E * K
    names = [s.name for s in trace.process_tracer().spans]
    assert "sweep.group:dml" in names and "sweep.column[1]" in names
    trace.reset_process_tracer()

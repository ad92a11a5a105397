"""Orthogonal-IV family (repro.core.iv): LATE recovery on the
compliance DGP (the acceptance bar: within 2 standard errors), naive-DML
bias as the control, DRIV agreement, CATE recovery, weak-instrument
screening, replicate inference, and the IV cell lowered as one
program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import CausalConfig
from repro.core.dml import DML
from repro.core.iv import DRIV, OrthoIV
from repro.data.causal_dgp import make_iv_data

N, P = 8000, 10


@pytest.fixture(scope="module")
def data():
    return make_iv_data(jax.random.PRNGKey(42), N, P, effect=1.5,
                        compliance=0.7)


@pytest.fixture(scope="module")
def fitted(data):
    cfg = CausalConfig(n_folds=5, n_bootstrap=32)
    return OrthoIV(cfg).fit(data.y, data.t, data.z, data.X,
                            key=jax.random.PRNGKey(0))


def test_orthoiv_recovers_late_within_2se(data, fitted):
    """The acceptance criterion: the known LATE within 2 stderr."""
    assert abs(fitted.late - data.true_late) < 2 * float(fitted.stderr[0])
    assert not fitted.diagnostics.weak_instrument
    assert fitted.diagnostics.ortho_moment < 1e-3


def test_naive_dml_is_biased_iv_is_not(data, fitted, key):
    """The reason the IV family exists: the DGP's unobserved confounder
    drives noncompliers' treatment, so DML (no instrument) lands far
    from the truth while OrthoIV straddles it."""
    cfg = CausalConfig(n_folds=5, inference="none")
    naive = DML(cfg).fit(data.y, data.t, data.X, key=key)
    iv_err = abs(fitted.late - data.true_late)
    naive_err = abs(naive.ate - data.true_late)
    assert naive_err > 0.15          # materially confounded
    assert iv_err < 0.5 * naive_err  # and the instrument removes it


def test_driv_agrees_with_orthoiv(data, fitted, key):
    cfg = CausalConfig(n_folds=5, inference="none")
    dr = DRIV(cfg).fit(data.y, data.t, data.z, data.X, key=key)
    assert abs(dr.late - data.true_late) < 2.5 * dr.stderr + 0.05
    assert abs(dr.late - fitted.late) < 0.1
    # the preliminary estimate is the constant OrthoIV solve
    assert abs(dr.theta_pre - fitted.late) < 0.05


def test_iv_cate_recovery_heterogeneous(key):
    d = make_iv_data(jax.random.PRNGKey(7), N, P, effect=1.0,
                     heterogeneous=True, compliance=0.8)
    cfg = CausalConfig(n_folds=5, cate_features=2, inference="none")
    res = OrthoIV(cfg).fit(d.y, d.t, d.z, d.X, key=key)
    # theta ~ [1.0, 0.5] (effect = 1 + 0.5 x0), IV noise is real
    np.testing.assert_allclose(np.asarray(res.theta), [1.0, 0.5],
                               atol=0.2)
    rmse = float(jnp.sqrt(jnp.mean(
        (res.cate(d.X) - d.true_cate) ** 2)))
    assert rmse < 0.25


def test_continuous_instrument(key):
    d = make_iv_data(jax.random.PRNGKey(5), N, P, effect=0.8,
                     discrete_instrument=False, compliance=0.9)
    cfg = CausalConfig(n_folds=5, discrete_instrument=False,
                       discrete_treatment=False, nuisance_t="ridge",
                       inference="none")
    res = OrthoIV(cfg).fit(d.y, d.t, d.z, d.X, key=key)
    assert abs(res.ate - 0.8) < 0.1
    assert not res.diagnostics.weak_instrument


def test_weak_instrument_is_flagged(key):
    """Near-zero compliance -> no first stage -> the F screen fires."""
    d = make_iv_data(jax.random.PRNGKey(11), 3000, 6, effect=1.0,
                     compliance=0.02)
    cfg = CausalConfig(n_folds=3, inference="none")
    res = OrthoIV(cfg).fit(d.y, d.t, d.z, d.X, key=key)
    assert res.diagnostics.weak_instrument
    from repro.core.refutation import weak_instrument
    rep = weak_instrument(res)
    assert not rep.passed
    assert "FAIL" in rep.row()


def test_weak_instrument_report_on_strong_design(fitted):
    from repro.core.refutation import weak_instrument
    rep = weak_instrument(fitted)
    assert rep.passed
    assert rep.f_stat > 100.0


def test_placebo_instrument_executor_equivalence(data, key):
    from repro.core.refutation import placebo_instrument
    est = OrthoIV(CausalConfig(n_folds=3, inference="none"))
    kw = dict(original_ate=1.5, n_reps=2, key=jax.random.PRNGKey(19))
    r_ser = placebo_instrument(est, data.y, data.t, data.z, data.X,
                               executor="serial", **kw)
    r_vec = placebo_instrument(est, data.y, data.t, data.z, data.X,
                               executor="vmap", **kw)
    assert r_ser.refuted_ates == r_vec.refuted_ates
    assert r_ser.name == "placebo_instrument"


def test_iv_bootstrap_interval_api(data, fitted):
    lo, hi = fitted.late_interval()
    assert lo < fitted.late < hi
    assert np.isfinite([lo, hi]).all()
    lo2, hi2 = fitted.ate_interval(alpha=0.5)
    assert (hi2 - lo2) < (hi - lo)
    blo, bhi = fitted.cate_interval(data.X[:5])
    assert blo.shape == (5,) and bool((blo < bhi).all())


def test_iv_jackknife_agrees_with_if_stderr(fitted):
    jk = fitted.inference(method="jackknife")
    if_se = float(fitted.stderr[0])
    jk_se = float(jk.se[0])
    assert 0.3 * if_se < jk_se < 3.0 * if_se, (jk_se, if_se)


def test_iv_jackknife_matches_direct_delete_fold(key):
    """LOO-identity jackknife (one segmented instrumented Gram) vs
    re-solving each delete-fold weighted IV moment directly."""
    from repro.core.crossfit import fold_ids
    from repro.core.final_stage import cate_basis
    from repro.inference import delete_fold_jackknife_iv
    from repro.inference.numerics import weighted_iv_theta
    n, k = 2000, 4
    d = make_iv_data(jax.random.PRNGKey(13), n, 6, effect=1.0,
                     compliance=0.75)
    my = 0.1 * d.y
    mt = jnp.full((n,), 0.5, jnp.float32)
    mz = jnp.full((n,), 0.5, jnp.float32)
    folds = fold_ids(key, n, k)
    phi = cate_basis(d.X, 2)
    jk = delete_fold_jackknife_iv(d.y, d.t, d.z, my, mt, mz, folds, phi,
                                  k)
    ry, rt, rz = d.y - my, d.t - mt, d.z - mz
    direct = jnp.stack([
        weighted_iv_theta(ry, rt, rz, phi,
                          (folds != j).astype(jnp.float32),
                          with_se=False)[0]
        for j in range(k)])
    np.testing.assert_allclose(np.asarray(jk.replicates),
                               np.asarray(direct), rtol=1e-4, atol=1e-5)
    jk_rb = delete_fold_jackknife_iv(d.y, d.t, d.z, my, mt, mz, folds,
                                     phi, k, row_block=300)
    np.testing.assert_allclose(np.asarray(jk_rb.replicates),
                               np.asarray(jk.replicates), rtol=1e-4,
                               atol=1e-5)


def test_iv_inference_cache_ignores_alpha(fitted):
    r1 = fitted.inference(n_bootstrap=8)
    r2 = fitted.inference(n_bootstrap=8, alpha=0.2)
    assert r1 is r2


def test_iv_inference_none_falls_back_to_sandwich(data):
    cfg = CausalConfig(n_folds=3, inference="none")
    res = OrthoIV(cfg).fit(data.y, data.t, data.z, data.X,
                           key=jax.random.PRNGKey(0))
    lo, hi = res.ate_interval()
    clo, chi = res.conf_int()
    assert lo == pytest.approx(float(clo[0]))
    assert hi == pytest.approx(float(chi[0]))


def test_driv_interval_centers_on_late(data, key):
    cfg = CausalConfig(n_folds=3, n_bootstrap=24)
    res = DRIV(cfg).fit(data.y, data.t, data.z, data.X, key=key)
    lo, hi = res.late_interval()
    assert lo <= res.late <= hi
    blo, bhi = res.cate_interval(data.X[:4])
    assert blo.shape == (4,)


def test_tuned_iv_nuisances(data, key):
    from repro.core.tuning import tuned_iv_nuisances
    cfg = CausalConfig(n_folds=3, inference="none")
    ny, nt, nz = tuned_iv_nuisances(cfg, data.X[:2000], data.y[:2000],
                                    data.t[:2000], data.z[:2000], key)
    assert ny.name == "ridge" and nt.name == "logistic"
    assert nz.name == "logistic"
    res = OrthoIV(cfg, nuisance_y=ny, nuisance_t=nt,
                  nuisance_z=nz).fit(data.y, data.t, data.z, data.X,
                                     key=key)
    assert abs(res.late - data.true_late) < 0.2


def test_iv_summary_renders(fitted):
    s = fitted.summary()
    assert "OrthoIV result" in s and "first-stage F" in s


def _run_cell(lowered, arrays):
    """Compile a lowered cell, run it, and check its (theta, cov)."""
    theta, cov = lowered.compile()(*arrays)
    assert theta.shape == (1,) and cov.shape == (1, 1)
    assert np.isfinite(np.asarray(theta)).all()
    assert float(cov[0, 0]) > 0.0
    return float(theta[0])


@pytest.mark.parametrize("engine", ["parallel", "parallel_loo"])
def test_dml_cell_lowers(engine):
    """The DML workload lowers as one program against a one-device
    mesh per cross-fit engine, and the compiled cell recovers the
    effect (smoke shape: n = 512, p = 8)."""
    from jax.sharding import Mesh
    from repro.core.crossfit import fold_ids
    from repro.configs.dml_synthetic import CAUSAL
    from repro.data.causal_dgp import make_causal_data
    from repro.launch.dml_cell import lower_dml_cell
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    lowered = lower_dml_cell(mesh, CAUSAL, n=512, p=8, engine=engine)
    d = make_causal_data(jax.random.PRNGKey(5), 512, 8, effect=1.0)
    folds = fold_ids(jax.random.PRNGKey(6), 512, CAUSAL.n_folds)
    theta = _run_cell(lowered, (d.X, d.y, d.t, folds))
    assert abs(theta - d.true_ate) < 0.5


@pytest.mark.parametrize("engine", ["parallel", "parallel_loo"])
def test_iv_cell_lowers(engine):
    """The IV workload lowers against a mesh exactly like the DML cell,
    per cross-fit engine, and the compiled cell runs (smoke shape)."""
    from jax.sharding import Mesh
    from repro.core.crossfit import fold_ids
    from repro.launch.dml_cell import lower_iv_cell
    from repro.configs.iv_synthetic import IV_CAUSAL
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    lowered = lower_iv_cell(mesh, IV_CAUSAL, n=512, p=8, engine=engine)
    d = make_iv_data(jax.random.PRNGKey(5), 512, 8, effect=1.0)
    folds = fold_ids(jax.random.PRNGKey(6), 512, IV_CAUSAL.n_folds)
    _run_cell(lowered, (d.X, d.y, d.t, d.z, folds))


def test_iv_data_ground_truth_properties():
    """DGP invariants: complier fraction ~ compliance, exclusion (Z
    enters Y only through T), and the Wald estimand equals the LATE."""
    # instrument_strength=0 -> Z ~ Bern(1/2) independent of X, so the
    # UNCONDITIONAL Wald ratio is the LATE (with X-driven assignment
    # only the X-conditional moment is; that's what OrthoIV solves)
    d = make_iv_data(jax.random.PRNGKey(3), 50_000, 4, effect=2.0,
                     compliance=0.6, instrument_strength=0.0)
    assert abs(float(d.complier.mean()) - 0.6) < 0.02
    # population Wald check: E[Y|Z=1]-E[Y|Z=0] / E[T|Z=1]-E[T|Z=0]
    z = np.asarray(d.z)
    y = np.asarray(d.y)
    t = np.asarray(d.t)
    wald = ((y[z == 1].mean() - y[z == 0].mean())
            / (t[z == 1].mean() - t[z == 0].mean()))
    assert abs(wald - d.true_late) < 0.15

"""repro.inference: executor equivalence (serial == vmap to float
reassociation at the legacy shape), jackknife-vs-IF stderr agreement, and the
estimator-facing interval API.  Cross-estimator bit-identity and
row_block conformance live in tests/test_conformance.py; nominal CI
coverage lives in tests/test_oracle_properties.py (slow tier)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import CausalConfig
from repro.core.dml import DML
from repro.core.drlearner import DRLearner
from repro.data.causal_dgp import make_causal_data
from repro.inference import (SerialExecutor, ShardMapExecutor,
                             VmapExecutor, delete_fold_jackknife,
                             dml_bootstrap, make_executor)

N, P, K = 3000, 8, 4


@pytest.fixture(scope="module")
def data():
    return make_causal_data(jax.random.PRNGKey(42), N, P, effect=1.5)


@pytest.fixture(scope="module")
def fitted(data):
    cfg = CausalConfig(n_folds=K, n_bootstrap=32)
    return DML(cfg).fit(data.y, data.t, data.X, key=jax.random.PRNGKey(0))


# serial vs vmap replicates: an added batch axis may retile an f32
# n-contraction (XLA-build dependent), a reassociation of tens of ulps
SERIAL_VMAP_RTOL = 1e-5


def _boot(ctx, executor, scheme="pairs", B=6):
    return dml_bootstrap(ctx.nuis_y, ctx.nuis_t, n_folds=K, XW=ctx.XW,
                         y=ctx.y, t=ctx.t, phi=ctx.phi,
                         key=jax.random.PRNGKey(5), n_replicates=B,
                         scheme=scheme, executor=executor)


@pytest.mark.parametrize("scheme", ["pairs", "multiplier"])
def test_serial_vmap_bit_identical_legacy_shape(fitted, scheme):
    """The PR-1 engine-equivalence anchor: per-replicate estimates from
    the loop baseline and the batched program agree at the legacy
    whole-array p_phi=1 shape to float reassociation.  Bitwise equality
    there rested on XLA's CPU tiling of the row_block=0 einsums, which
    the vmap batch axis may change (the installed XLA does for the HC0
    meat), so the bound is SERIAL_VMAP_RTOL — tens of f32 ulps."""
    ctx = fitted.fit_ctx
    r_ser = _boot(ctx, "serial", scheme=scheme)
    r_vec = _boot(ctx, "vmap", scheme=scheme)
    np.testing.assert_allclose(np.asarray(r_ser.replicates),
                               np.asarray(r_vec.replicates),
                               rtol=SERIAL_VMAP_RTOL)
    np.testing.assert_allclose(np.asarray(r_ser.replicate_se),
                               np.asarray(r_vec.replicate_se),
                               rtol=SERIAL_VMAP_RTOL)


def test_shard_map_matches_vmap(fitted):
    """Replicate axis sharded over the (1-device here) data mesh axis:
    same program, same bits — including the non-divisible-B padding."""
    ctx = fitted.fit_ctx
    r_vec = _boot(ctx, "vmap", B=5)
    r_shm = _boot(ctx, "shard_map", B=5)
    np.testing.assert_array_equal(np.asarray(r_vec.replicates),
                                  np.asarray(r_shm.replicates))


def test_vmap_microbatch_bit_identical(fitted):
    """Chunked vmap (bounded-memory mode for industrial n) returns the
    same bits as the full-batch program."""
    ctx = fitted.fit_ctx
    r_full = _boot(ctx, VmapExecutor(), B=7)
    r_chunk = _boot(ctx, VmapExecutor(microbatch=3), B=7)
    np.testing.assert_array_equal(np.asarray(r_full.replicates),
                                  np.asarray(r_chunk.replicates))


def test_replicates_replay_from_base_key(fitted):
    """Lineage: replicate b depends only on fold_in(base, b), so a
    3-replicate run is a prefix of a 6-replicate run."""
    ctx = fitted.fit_ctx
    r6 = _boot(ctx, "vmap", B=6)
    r3 = _boot(ctx, "vmap", B=3)
    np.testing.assert_array_equal(np.asarray(r3.replicates),
                                  np.asarray(r6.replicates)[:3])


def test_jackknife_agrees_with_if_stderr():
    """Delete-fold jackknife se vs the influence-function (HC0 sandwich)
    se computed in estimands/final_stage: same asymptotic target."""
    d = make_causal_data(jax.random.PRNGKey(3), 8000, 10, effect=1.0)
    res = DML(CausalConfig(n_folds=5)).fit(d.y, d.t, d.X,
                                           key=jax.random.PRNGKey(0))
    jk = res.inference(method="jackknife")
    if_se = float(res.stderr[0])
    jk_se = float(jk.se[0])
    assert 0.4 * if_se < jk_se < 2.5 * if_se, (jk_se, if_se)


def test_jackknife_reuses_fold_states(fitted):
    """Direct call on the crossfit artifacts (no refit whatsoever)."""
    cf = fitted.crossfit
    ctx = fitted.fit_ctx
    jk = delete_fold_jackknife(ctx.y, ctx.t, cf.oof_y, cf.oof_t,
                               cf.folds, ctx.phi, K)
    assert jk.replicates.shape == (K, ctx.phi.shape[1])
    assert np.isfinite(np.asarray(jk.se)).all()


def test_ate_interval_api(data, fitted):
    lo, hi = fitted.ate_interval()
    assert lo < fitted.ate < hi
    assert np.isfinite([lo, hi]).all()
    # width shrinks with alpha
    lo2, hi2 = fitted.ate_interval(alpha=0.5)
    assert (hi2 - lo2) < (hi - lo)
    # normal + studentized kinds work
    for kind in ("normal", "studentized"):
        lo3, hi3 = fitted.ate_interval(kind=kind)
        assert lo3 < hi3


def test_cate_interval_api(data, fitted):
    lo, hi = fitted.cate_interval(data.X[:7])
    assert lo.shape == (7,) and hi.shape == (7,)
    assert bool((lo < hi).all())


def test_interval_default_config_is_b200():
    """Acceptance: plain DML.fit(...).ate_interval() draws B=200
    bootstrap replicates through the vmap executor by default."""
    cfg = CausalConfig()
    assert cfg.inference == "bootstrap"
    assert cfg.n_bootstrap == 200
    assert cfg.inference_executor == "vmap"


def test_inference_none_falls_back_to_sandwich(data):
    cfg = CausalConfig(n_folds=3, inference="none")
    res = DML(cfg).fit(data.y, data.t, data.X, key=jax.random.PRNGKey(0))
    lo, hi = res.ate_interval()
    clo, chi = res.conf_int()
    assert lo == pytest.approx(float(clo[0]))
    assert hi == pytest.approx(float(chi[0]))
    blo, bhi = res.cate_interval(data.X[:3])
    assert bool((blo < bhi).all())


def test_dr_learner_interval(data):
    cfg = CausalConfig(n_folds=3, n_bootstrap=24)
    res = DRLearner(cfg).fit(data.y, data.t, data.X,
                             key=jax.random.PRNGKey(0))
    lo, hi = res.ate_interval()
    assert lo < hi
    assert abs((lo + hi) / 2 - res.ate) < 0.2
    blo, bhi = res.cate_interval(data.X[:4])
    assert blo.shape == (4,)


def test_dr_interval_centers_on_ate_with_heterogeneous_basis():
    """The ATE CI must cover res.ate (= mean pseudo-outcome) even when
    the CATE basis is heterogeneous and covariates are NOT centered —
    theta[0] is then the effect at x=0, far from the ATE."""
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    n = 3000
    X = 5.0 + jax.random.normal(ks[0], (n, 3))   # non-centered
    prop = jax.nn.sigmoid(0.3 * (X[:, 0] - 5.0))
    t = jax.random.bernoulli(ks[1], prop).astype(jnp.float32)
    tau = 1.0 + 0.5 * X[:, 0]
    y = tau * t + X[:, 0] + 0.5 * jax.random.normal(ks[2], (n,))
    cfg = CausalConfig(n_folds=3, cate_features=2, n_bootstrap=32)
    res = DRLearner(cfg).fit(y, t, X, key=ks[3])
    lo, hi = res.ate_interval()
    assert abs(res.ate - float(tau.mean())) < 0.3
    assert lo <= res.ate <= hi, (lo, res.ate, hi)


def test_dr_inference_none_is_respected(data):
    """inference='none' must not silently launch a bootstrap."""
    cfg = CausalConfig(n_folds=3, inference="none")
    res = DRLearner(cfg).fit(data.y, data.t, data.X,
                             key=jax.random.PRNGKey(0))
    lo, hi = res.ate_interval()      # analytic normal CI, no refits
    assert lo < res.ate < hi
    with pytest.raises(ValueError):
        res.cate_interval(data.X[:2])
    with pytest.raises(ValueError):
        res.inference()


def test_inference_cache_ignores_alpha(fitted):
    """Replicates are alpha-independent: a new level must re-quantile
    the cached draws, not re-run B re-estimations."""
    r1 = fitted.inference(n_bootstrap=8)
    r2 = fitted.inference(n_bootstrap=8, alpha=0.2)
    assert r1 is r2


def test_mlp_nuisance_bootstrap_runs(data):
    """Non-linear nuisances take the generic vmapped-fit fallback."""
    from repro.core.nuisance import make_mlp
    from repro.inference import dml_bootstrap as boot
    ny = make_mlp("reg", hidden=(8,), steps=10, lr=1e-2)
    nt = make_mlp("clf", hidden=(8,), steps=10, lr=1e-2)
    phi = jnp.ones((N, 1), jnp.float32)
    r = boot(ny, nt, n_folds=3, XW=data.X, y=data.y, t=data.t, phi=phi,
             key=jax.random.PRNGKey(2), n_replicates=3, with_se=False)
    assert r.replicates.shape == (3, 1)
    assert np.isfinite(np.asarray(r.replicates)).all()


def test_make_executor_factory():
    assert isinstance(make_executor("serial"), SerialExecutor)
    assert isinstance(make_executor("vmap"), VmapExecutor)
    assert isinstance(make_executor("shard_map"), ShardMapExecutor)
    exe = VmapExecutor()
    assert make_executor(exe) is exe
    with pytest.raises(ValueError):
        make_executor("ray")


def test_executor_maps_pytrees():
    exe = make_executor("vmap")
    xs = {"a": jnp.arange(4.0), "b": jnp.ones((4, 2))}
    out = exe.map(lambda x: {"s": x["a"] + x["b"].sum()}, xs)
    np.testing.assert_allclose(np.asarray(out["s"]),
                               np.asarray(jnp.arange(4.0) + 2.0))


def test_executor_passthrough_args():
    """Extra map args ride along un-mapped (compiled-program inputs, not
    baked constants) on every backend."""
    data = jnp.arange(6.0)
    for name in ("serial", "vmap", "shard_map"):
        exe = make_executor(name)
        out = exe.map(lambda i, d: d[i] * 2.0,
                      jnp.arange(3, dtype=jnp.int32), data)
        np.testing.assert_allclose(np.asarray(out), [0.0, 2.0, 4.0],
                                   err_msg=name)


def test_refutation_executor_equivalence(data):
    """Refuters route their replicate loops through the same Executor:
    serial and vmap dispatch give identical replicate ATEs."""
    from repro.core import refutation
    est = DML(CausalConfig(n_folds=3))
    kw = dict(original_ate=1.5, n_reps=2, key=jax.random.PRNGKey(11))
    for refuter in (refutation.placebo_treatment,
                    refutation.random_common_cause,
                    refutation.data_subset):
        r_ser = refuter(est, data.y, data.t, data.X, executor="serial",
                        **kw)
        r_vec = refuter(est, data.y, data.t, data.X, executor="vmap",
                        **kw)
        assert r_ser.refuted_ates == r_vec.refuted_ates, refuter.__name__


def test_tuning_executor_equivalence(key):
    """tune_penalty through serial vs vmap executors: same scores."""
    from repro.core.tuning import tune_penalty
    n, p = 500, 6
    ks = jax.random.split(key, 2)
    X = jax.random.normal(ks[0], (n, p))
    y = X @ jax.random.normal(ks[1], (p,))
    lams = jnp.asarray([1e-4, 1e-2, 1.0], jnp.float32)
    r_vec = tune_penalty("reg", lams, X, y, n_folds=3, key=key,
                         executor="vmap")
    r_ser = tune_penalty("reg", lams, X, y, n_folds=3, key=key,
                         executor="serial")
    assert r_vec.best_index == r_ser.best_index
    # tune_penalty rides the legacy LAPACK-solve nuisances, so serial
    # vs batched agree to float32 noise, not bitwise
    np.testing.assert_allclose(np.asarray(r_vec.scores),
                               np.asarray(r_ser.scores),
                               rtol=1e-4, atol=1e-9)

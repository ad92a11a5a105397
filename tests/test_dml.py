"""Estimator faithfulness (the paper's §5.1 demo + §2 theory): ATE/CATE
recovery on the dowhy-style DGP, parallel == sequential engines, W
controls, and tuned nuisances."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import CausalConfig
from repro.core.dml import DML
from repro.core.nuisance import make_mlp
from repro.data.causal_dgp import make_causal_data, paper_demo_data

N, P = 8000, 20


@pytest.fixture(scope="module")
def data():
    return make_causal_data(jax.random.PRNGKey(42), N, P, effect=1.5)


def test_ate_recovery_parallel(data, key):
    cfg = CausalConfig(n_folds=5, engine="parallel")
    res = DML(cfg).fit(data.y, data.t, data.X, key=key)
    assert abs(res.ate - data.true_ate) < 3 * float(res.stderr[0]) + 0.05
    assert res.diagnostics.ortho_moment < 1e-3


def test_parallel_equals_sequential(data, key):
    """C1 is an execution-strategy change, not a statistical one."""
    r1 = DML(CausalConfig(n_folds=5, engine="parallel")).fit(
        data.y, data.t, data.X, key=key)
    r2 = DML(CausalConfig(n_folds=5, engine="sequential")).fit(
        data.y, data.t, data.X, key=key)
    np.testing.assert_allclose(r1.theta, r2.theta, rtol=1e-4, atol=1e-5)


def test_cate_recovery_heterogeneous(key):
    data = make_causal_data(jax.random.PRNGKey(7), N, P,
                            heterogeneous=True, effect=1.0)
    cfg = CausalConfig(n_folds=5, cate_features=2, engine="parallel")
    res = DML(cfg).fit(data.y, data.t, data.X, key=key)
    rmse = float(jnp.sqrt(jnp.mean((res.cate(data.X) - data.true_cate) ** 2)))
    assert rmse < 0.15
    # theta ~ [1.0, 0.5] (effect = 1 + 0.5 x0)
    np.testing.assert_allclose(res.theta, [1.0, 0.5], atol=0.12)


def test_paper_demo_listing(key):
    """The exact §5.1 code-listing DGP: y=(1+.5 x0)T + x0 + eps."""
    data = paper_demo_data(jax.random.PRNGKey(0), n=20_000, p=50)
    cfg = CausalConfig(n_folds=5, cate_features=2, engine="parallel")
    res = DML(cfg).fit(data.y, data.t, data.X, key=key)
    assert abs(res.ate_of(data.X) - float(data.true_cate.mean())) < 0.08


def test_w_controls_are_used(key):
    """Confounding lives in W only: omitting W biases the estimate,
    including it recovers the truth."""
    data = make_causal_data(jax.random.PRNGKey(3), N, P, effect=1.0,
                            confounding_strength=2.0)
    W, X = data.X[:, :10], data.X[:, 10:]  # confounders are in cols < 10
    cfg = CausalConfig(n_folds=5, engine="parallel")
    biased = DML(cfg).fit(data.y, data.t, X, key=key)
    adjusted = DML(cfg).fit(data.y, data.t, X, W=W, key=key)
    assert abs(adjusted.ate - 1.0) < abs(biased.ate - 1.0)
    assert abs(adjusted.ate - 1.0) < 0.1


@pytest.mark.slow
def test_mlp_nuisances(key):
    """Nonlinear confounding needs a nonlinear nuisance."""
    n = 4000
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    X = jax.random.normal(ks[0], (n, 5))
    g = jnp.sin(2 * X[:, 0]) + X[:, 1] ** 2
    prop = jax.nn.sigmoid(g - 1.0)
    t = jax.random.bernoulli(ks[1], prop).astype(jnp.float32)
    y = 1.0 * t + g + 0.3 * jax.random.normal(ks[2], (n,))
    cfg = CausalConfig(n_folds=4, engine="parallel")
    nuis_y = make_mlp("reg", hidden=(64,), steps=300, lr=3e-3)
    nuis_t = make_mlp("clf", hidden=(64,), steps=300, lr=3e-3)
    res = DML(cfg, nuisance_y=nuis_y, nuisance_t=nuis_t).fit(y, t, X,
                                                             key=key)
    linear = DML(cfg).fit(y, t, X, key=key)
    assert abs(res.ate - 1.0) < abs(linear.ate - 1.0) + 0.02
    assert abs(res.ate - 1.0) < 0.15


def test_continuous_treatment(key):
    data = make_causal_data(jax.random.PRNGKey(5), N, P, effect=0.7,
                            discrete_treatment=False)
    cfg = CausalConfig(n_folds=5, discrete_treatment=False,
                       nuisance_t="ridge", engine="parallel")
    res = DML(cfg).fit(data.y, data.t, data.X, key=key)
    assert abs(res.ate - 0.7) < 0.05


def test_summary_renders(data, key):
    res = DML(CausalConfig(n_folds=3)).fit(data.y, data.t, data.X, key=key)
    s = res.summary()
    assert "DML result" in s and "overlap" in s


def test_loo_engine_matches_parallel(data, key):
    """Beyond-paper leave-one-out-Gram engine: identical estimates (ridge
    exact by identity; logistic MM converges to the same optimum)."""
    r1 = DML(CausalConfig(n_folds=5, engine="parallel")).fit(
        data.y, data.t, data.X, key=key)
    r2 = DML(CausalConfig(n_folds=5, engine="parallel_loo")).fit(
        data.y, data.t, data.X, key=key)
    assert abs(r1.ate - r2.ate) < 2e-3
    np.testing.assert_allclose(r1.theta, r2.theta, atol=2e-3)


@pytest.mark.parametrize("slot", ["nuisance_y", "nuisance_t", "nuisance_z"])
def test_removed_backbone_kind_is_rejected(slot):
    """The nuisance kinds are ridge | logistic | mlp; the retired
    "backbone" alias is refused in every nuisance slot, not quietly
    read as a linear head."""
    from repro.core.iv import OrthoIV

    cfg = CausalConfig(**{slot: "backbone"})
    est = OrthoIV if slot == "nuisance_z" else DML
    with pytest.raises(ValueError, match="unknown nuisance kind 'backbone'"):
        est(cfg)

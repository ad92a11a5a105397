"""The bootstrap's replicate closure lives as long as its estimator.

``DML`` keeps the closures ``dml_bootstrap`` maps over the replicate
axis, keyed by what each bakes in, so the runtime's caches (the memory
model's probes, the compiled chunk programs, the executors' jit caches),
all keyed on the closure object, hit on every fit after the first: a
warm fit plans and compiles no replicate program, and runs the very
programs the first fit compiled.  The closures, and the programs cached
on them, die with the estimator and its results.
"""
import gc
import weakref

import jax
import numpy as np
import pytest

from repro.config import CausalConfig
from repro.core.dml import DML
from repro.data.causal_dgp import make_causal_data
from repro.inference import dml_bootstrap
from repro.inference.bootstrap import make_dml_replicate_fn, replicate_keys
from repro.obs.metrics import default_registry
from repro.runtime import memory

N, P, K, B, ROW_BLOCK = 2048, 8, 3, 5, 512
METHODS = {"pairs": "bootstrap", "multiplier": "multiplier"}


@pytest.fixture(scope="module")
def data():
    return make_causal_data(jax.random.PRNGKey(0), N, P, effect=1.0)


@pytest.fixture(scope="module")
def budget(data):
    """A memory budget that holds two replicates and not three, read
    from the memory model of a throwaway closure on the same shapes:
    the five replicates run as chunks 2, 2, 1, each a probed program."""
    est = DML(_cfg("bootstrap", 0))
    fn = make_dml_replicate_fn(est.nuis_y, est.nuis_t, K,
                               row_block=ROW_BLOCK)
    d = data
    keys = replicate_keys(jax.random.PRNGKey(0), B)
    model = memory.memory_model(fn, keys, (d.X, d.y, d.t, d.X[:, :1]), B)
    assert model is not None and model.slope > 0
    return int(model.base + 2.5 * model.slope)


def _cfg(method, budget):
    return CausalConfig(n_folds=K, nuisance_y="ridge", nuisance_t="ridge",
                        inference=method, n_bootstrap=B,
                        row_block=ROW_BLOCK, runtime_memory_budget=budget)


def _counters():
    return dict(default_registry().snapshot()["counters"])


def _delta(c0, name):
    return _counters().get(name, 0) - c0.get(name, 0)


def _replicates(res, scheme, with_se):
    """The fit's B replicate thetas: through ``ate_interval()`` (which
    runs with SEs), or through ``dml_bootstrap`` with the estimator's
    own closures and the fit's context when ``with_se`` is off."""
    if with_se:
        res.ate_interval()
        return np.asarray(res.inference().replicates)
    ctx, cfg = res.fit_ctx, res.cfg
    return np.asarray(dml_bootstrap(
        ctx.nuis_y, ctx.nuis_t, n_folds=cfg.n_folds, XW=ctx.XW, y=ctx.y,
        t=ctx.t, phi=ctx.phi, key=jax.random.fold_in(ctx.key, 0x0b00),
        n_replicates=cfg.n_bootstrap, scheme=scheme, with_se=False,
        row_block=cfg.row_block, strategy=cfg.row_block_strategy,
        memory_budget=cfg.runtime_memory_budget,
        replicate_fns=ctx.replicate_fns).replicates)


@pytest.mark.parametrize("with_se", [True, False], ids=["se", "no_se"])
@pytest.mark.parametrize("scheme", sorted(METHODS))
def test_warm_fits_plan_and_compile_no_replicate_program(data, budget,
                                                         scheme, with_se):
    d = data
    est = DML(_cfg(METHODS[scheme], budget))
    for i in range(3):
        res = est.fit(d.y, d.t, d.X, key=jax.random.PRNGKey(i))
        c0 = _counters()
        reps = _replicates(res, scheme, with_se)
        first = i == 0
        assert _delta(c0, "runtime.probe_compiles") == (2 if first else 0)
        assert _delta(c0, "inference.replicate_fn[built]") == int(first)
        assert _delta(c0, "inference.replicate_fn[reused]") == int(not first)
        assert default_registry().snapshot()["gauges"][
            "runtime.chunk_size[dml_bootstrap]"] == 2
        fresh = DML(_cfg(METHODS[scheme], budget)).fit(
            d.y, d.t, d.X, key=jax.random.PRNGKey(i))
        ref = _replicates(fresh, scheme, with_se)
        assert reps.shape == (B, 1)
        np.testing.assert_array_equal(reps, ref)
    assert len(est._replicate_fns) == 1


def test_each_key_gets_its_own_closure(data, budget):
    d = data
    est = DML(_cfg("bootstrap", budget))
    res = est.fit(d.y, d.t, d.X, key=jax.random.PRNGKey(0))
    cases = [(s, se) for s in sorted(METHODS) for se in (True, False)]
    for scheme, with_se in cases * 2:
        if with_se:
            res.inference(method=METHODS[scheme])
        else:
            _replicates(res, scheme, with_se=False)
    fns = list(est._replicate_fns.values())
    assert len(fns) == len(cases) == len({id(f) for f in fns})
    c = _counters()
    assert c["inference.replicate_fn[built]"] == len(cases)
    # the second pass over the cases: the results cache the
    # with-SE draws, so only the without-SE calls reach the closures
    assert c["inference.replicate_fn[reused]"] == len(cases) // 2


def test_bootstrap_span_says_whether_the_closure_was_reused(data, budget):
    from repro.obs.trace import Tracer
    d = data
    tracer = Tracer(sync=False)
    est = DML(_cfg("bootstrap", budget), tracer=tracer)
    for i in range(2):
        est.fit(d.y, d.t, d.X, key=jax.random.PRNGKey(i)).ate_interval()
    spans = [s for s in tracer.spans if s.name == "inference.bootstrap"]
    assert [s.attrs["replicate_fn"] for s in spans] == ["built", "reused"]


def test_without_a_cache_every_call_builds(data, budget):
    """``dml_bootstrap`` called as the benchmarks call it: no cache, a
    fresh closure each time, as before."""
    res = DML(_cfg("bootstrap", budget)).fit(data.y, data.t, data.X)
    object.__setattr__(res.fit_ctx, "replicate_fns", None)
    c0 = _counters()
    for method in ("bootstrap", "multiplier"):
        res.inference(method=method)
    assert _delta(c0, "inference.replicate_fn[built]") == 2
    assert _delta(c0, "inference.replicate_fn[reused]") == 0
    assert _delta(c0, "runtime.probe_compiles") == 4


@pytest.mark.parametrize("sized", [True, False], ids=["budget", "no_budget"])
def test_closures_die_with_the_estimator(data, budget, sized):
    """Nothing process-wide pins the closure: not the memory model's
    probe cache (budgeted chunks) and not the vmap executor's jit
    cache (one unsized map)."""
    d = data
    gc.collect()
    probes0 = len(memory._PROBE_CACHE)
    est = DML(_cfg("bootstrap", budget if sized else 0))
    res = est.fit(d.y, d.t, d.X, key=jax.random.PRNGKey(0))
    res.ate_interval()
    res2 = est.fit(d.y, d.t, d.X, key=jax.random.PRNGKey(1))
    res2.ate_interval()
    (fn,) = est._replicate_fns.values()
    ref = weakref.ref(fn)
    assert (fn in memory._PROBE_CACHE) == sized
    del fn, est, res, res2
    gc.collect()
    assert ref() is None
    assert len(memory._PROBE_CACHE) <= probes0

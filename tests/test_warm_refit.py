"""Warm refits are bit-identical, for every registry estimator.

An estimator object keeps state across fits: ``DML`` keeps its
bootstrap replicate closures, and the runtime caches memory-model
probes, compiled chunk programs and cost audits on those closures.  A
second fit of the same object on the same data and key must reproduce
the first exactly — point estimate, SE and bootstrap replicates — so no
cache keyed on an estimator's closures can hand back stale state.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core.dml import DML
from repro.core.drlearner import DRLearner
from repro.core.iv import DRIV, OrthoIV
from repro.core.registry import REGISTRY, SPEC_IDS

# spec -> (estimator class, data columns its fit takes); the
# metalearners are functions, refit through the spec's own adapter
_ESTIMATORS = {
    "dml": (DML, ("y", "t", "X")),
    "dml_p2_rb": (DML, ("y", "t", "X")),
    "dml_loo": (DML, ("y", "t", "X")),
    "drlearner": (DRLearner, ("y", "t", "X")),
    "orthoiv": (OrthoIV, ("y", "t", "z", "X")),
    "orthoiv_p2_rb": (OrthoIV, ("y", "t", "z", "X")),
    "driv": (DRIV, ("y", "t", "z", "X")),
}


def _bits(x):
    return np.asarray(x).tobytes()


def _refitter(name, cfg):
    """One estimator object whose ``fit`` is called twice."""
    if name not in _ESTIMATORS:
        spec = REGISTRY[name]
        return lambda data, key: spec.fit(data, cfg, key)
    cls, cols = _ESTIMATORS[name]
    est = cls(cfg)
    return lambda data, key: est.fit(*(getattr(data, c) for c in cols),
                                     key=key)


@pytest.mark.parametrize("name", SPEC_IDS)
def test_warm_refit_bit_identical(name):
    spec = REGISTRY[name]
    cfg = dataclasses.replace(spec.boot_cfg or spec.base_cfg,
                              inference="bootstrap", n_bootstrap=4)
    data = spec.make_data(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    fit = _refitter(name, cfg)
    runs = []
    for _ in range(2):
        res = fit(data, key)
        inf = res.inference()
        runs.append((spec.point(res), getattr(res, "stderr", None),
                     inf.replicates))
    (p1, se1, reps1), (p2, se2, reps2) = runs
    assert _bits(p1) == _bits(p2), (name, p1, p2)
    if se1 is not None:
        assert _bits(se1) == _bits(se2), (name, se1, se2)
    assert _bits(reps1) == _bits(reps2), name

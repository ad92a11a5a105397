"""repro.core — the estimation substrate: distributed Double-ML.

The paper's primary contribution, translated from Ray task pools to
batched SPMD programs.  Everything bottoms out in the streaming
sufficient-statistics engine (``moments``); on top of it sit the
shared estimator base layer (``estimator``), fold-parallel
cross-fitting (``crossfit``, paper C1), population-axis tuning
(``tuning``, C2), the DML / DR / metalearner / orthogonal-IV
estimator facades, the refutation suite, and the registry
(``registry``) that tests, benchmarks, ``repro.sweep``, and
``repro.store`` all consume as the single source of truth.
Uncertainty quantification lives in ``repro.inference``; segment
panels in ``repro.sweep``; incremental refresh in ``repro.store``.
"""
#   moments.py      streaming sufficient-statistics engine (the single
#                   estimation substrate: whole-array or row-chunked,
#                   bit-identical by construction)
#   estimator.py    the shared estimator base layer (EffectResult: one
#                   copy of the fit -> inference plumbing)
#   registry.py     the estimator registry (one source of truth for
#                   tests, benchmarks, and repro.sweep)
#   crossfit.py     C1 fold-parallel cross-fitting (+ sequential baseline)
#   tuning.py       C2 population-axis hyper-parameter search
#   dml.py          the estimator facade (DML / DML_Ray translation)
#   nuisance.py     MXU-native nuisance zoo (ridge/logistic/MLP)
#   final_stage.py  orthogonal moment via the fused residual_gram kernel
#   iv.py           orthogonal-IV family (OrthoIV / DRIV) on the same
#                   moments + crossfit + runtime substrate
#   metalearners.py S/T/X learners as weighted cores (EffectResult fits)
#   refutation.py   NEXUS validation suite (placebo / RCC / subset /
#                   weak-instrument F screen)
#   estimands.py    ATE/ATT/CATE summaries + diagnostics
# Uncertainty quantification (bootstrap/jackknife CIs) lives in
# repro.inference; tuning + refutation replicate loops dispatch through
# its Executor.  Segment-parallel many-cohorts estimation lives in
# repro.sweep (it consumes the registry).
from repro.core import moments  # noqa: F401
from repro.core.estimator import (CausalEstimator, EffectResult,  # noqa: F401
    PseudoOutcomeEffectResult, SandwichEffectResult)
from repro.core.dml import DML, DMLResult  # noqa: F401
from repro.core.crossfit import (crossfit, crossfit_parallel,  # noqa: F401
    crossfit_parallel_loo, crossfit_sequential)
from repro.core.nuisance import Nuisance, make_nuisance, make_ridge, make_logistic, make_mlp  # noqa: F401
from repro.core.final_stage import cate_basis, fit_final_stage  # noqa: F401
from repro.core.drlearner import DRLearner  # noqa: F401
from repro.core.metalearners import (MetaResult, meta_bootstrap,  # noqa: F401
    make_meta_core, s_learner, t_learner, x_learner)
# iv last: it pulls repro.inference.numerics, whose package __init__
# imports the core submodules above (all satisfied from sys.modules by
# this point — no cycle)
from repro.core.iv import DRIV, OrthoIV  # noqa: F401
# the registry imports the estimator facades above, so it comes last
from repro.core.registry import (REGISTRY, EstimatorSpec,  # noqa: F401
    get_spec)

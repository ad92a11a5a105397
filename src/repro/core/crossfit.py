"""Distributed cross-fitting — the paper's §5.1 contribution (C1).

EconML runs the K out-of-fold nuisance fits as a sequential loop (or
joblib threads); the paper's DML_Ray turns each fold into a Ray task.
On a TPU pod the equivalent concurrency is *SPMD batching*: the K fits
are stacked on a leading fold axis and batched into one compiled
program — every fold trains simultaneously, sharing each row's bandwidth
(fold masks select the complement), with GSPMD sharding rows over the
``data`` mesh axis.

"How the K fold fits run" is dispatched through the same ``Executor``
protocol (repro.inference.executor) that schedules tuning trials and
bootstrap replicates — ONE swappable knob for every paper-parallelized
step class.  ``engine="parallel"`` maps the fold axis through the
``vmap`` executor (the Ray-task-pool translation); ``"sequential"``
maps it through ``serial`` — the EconML-style baseline for
benchmarks/bench_crossfit (paper Fig. 6) — with no bespoke Python loop
of its own.

Determinism: fold assignment and per-fold init keys derive from one base
key — the lineage that makes checkpoint-restart replay exact (DESIGN §7).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.core.nuisance import Nuisance


def fold_ids(key: jax.Array, n: int, k: int) -> jax.Array:
    """Balanced random fold assignment in [0, k)."""
    base = jnp.arange(n, dtype=jnp.int32) % k
    return jax.random.permutation(key, base)


def fold_weights(folds: jax.Array, k: int) -> jax.Array:
    """(k, n) training weights: w[j, i] = 1.0 iff sample i is OUTSIDE
    fold j (cross-fitting trains on the complement)."""
    return (folds[None, :] != jnp.arange(k, dtype=folds.dtype)[:, None]
            ).astype(jnp.float32)


def _oof_select(preds_kn: jax.Array, folds: jax.Array) -> jax.Array:
    """preds_kn: (k, n) predictions of every fold-model on every row.
    Row i keeps the prediction of model folds[i] — its held-out model."""
    return jnp.take_along_axis(preds_kn, folds[None, :], axis=0)[0]


@functools.lru_cache(maxsize=128)
def _fold_fit_fn(nuis: Nuisance):
    """The per-fold fit closure mapped by the Executor.  Cached per
    Nuisance so repeated crossfit calls hand the SAME closure object to
    the executor — its compiled-program cache is keyed on it (a fresh
    lambda per call would re-trace every fit)."""

    def fold_fit(xs, X, target):
        st = nuis.fit(nuis.init(xs["key"], X.shape[1]), X, target,
                      xs["w"])
        return nuis.predict(st, X), st

    return fold_fit


def _crossfit_engine(nuis: Nuisance, keys: jax.Array, X: jax.Array,
                     target: jax.Array, folds: jax.Array, k: int,
                     executor, tracer=None) -> Tuple[jax.Array, Any]:
    """The shared fold-fit dispatch: the fold axis (init keys + fold-
    complement weights) maps through the task runtime, so fold fits,
    tuning trials, and bootstrap replicates all run through one "how
    iterative steps run" knob — with the runtime's chunking and
    backend-downgrade ladder available to the fold axis too (pass a
    TaskRuntime as ``executor`` to set a budget, or a repro.obs Tracer
    as ``tracer`` — or a TaskRuntime carrying one — to get labelled
    crossfit spans with the fold-fit chunk spans nested inside; with
    neither, the span goes to the process tracer)."""
    from repro.obs.trace import layer_span
    from repro.runtime import as_runtime
    rt = as_runtime(executor, tracer=tracer)
    W = fold_weights(folds, k)                      # (k, n)
    label = f"crossfit:{nuis.name}"
    with layer_span(rt.tracer, label, cat="crossfit", k=k,
                    n=int(X.shape[0]), backend=rt.name):
        preds, states = rt.map(_fold_fit_fn(nuis), {"key": keys, "w": W},
                               X, target, label=label)
        if rt.tracer is not None:
            rt.tracer.sync((preds, states))
    return _oof_select(preds, folds), states


def crossfit_parallel(nuis: Nuisance, key: jax.Array, X: jax.Array,
                      target: jax.Array, folds: jax.Array, k: int,
                      executor="vmap", tracer=None
                      ) -> Tuple[jax.Array, Any]:
    """C1: all K fold-fits in ONE batched program (the Ray-tasks
    translation).  Returns (out-of-fold predictions (n,), states)."""
    keys = jax.random.split(key, k)
    return _crossfit_engine(nuis, keys, X, target, folds, k, executor, tracer)


def crossfit_parallel_loo(nuis: Nuisance, key: jax.Array, X: jax.Array,
                          target: jax.Array, folds: jax.Array, k: int,
                          mm_iters: int = 32):
    """C1+ (beyond-paper, EXPERIMENTS §Perf): the leave-one-out Gram
    identity collapses the K complement fits to ONE fold-segmented
    moments pass over X (row-blocked when the nuisance carries a
    ``row_block`` hyper).  Exact for ridge; fixed-majorizer MM for
    logistic (same optimum).  Falls back to the vmap engine for
    non-linear nuisances."""
    from repro.core.nuisance import logistic_fit_folds, ridge_fit_folds
    p = X.shape[1]
    lam = (nuis.init(key, p)["lam"]
           if nuis.name in ("ridge", "logistic") else 0.0)
    rb = (nuis.hyper or {}).get("row_block", 0)
    st = (nuis.hyper or {}).get("strategy", None)
    if nuis.name == "ridge":
        states = ridge_fit_folds(lam, X, target, folds, k, row_block=rb,
                                 strategy=st)
    elif nuis.name == "logistic":
        states = logistic_fit_folds(lam, mm_iters, X, target, folds, k,
                                    row_block=rb, strategy=st)
    else:
        return crossfit_parallel(nuis, key, X, target, folds, k)
    preds = jax.vmap(nuis.predict, in_axes=(0, None))(states, X)
    return _oof_select(preds, folds), states


def crossfit_sequential(nuis: Nuisance, key: jax.Array, X: jax.Array,
                        target: jax.Array, folds: jax.Array, k: int,
                        tracer=None) -> Tuple[jax.Array, Any]:
    """EconML-style baseline: one fit per fold, strictly in sequence —
    the ``serial`` Executor (one compiled program per fold, like K
    Ray-less workers); the bespoke Python loop this function used to
    carry is gone.  Per-fold init keys keep the legacy
    ``fold_in(key, j)`` lineage."""
    keys = jax.vmap(lambda j: jax.random.fold_in(key, j))(
        jnp.arange(k, dtype=jnp.uint32))
    return _crossfit_engine(nuis, keys, X, target, folds, k, "serial", tracer)


@dataclasses.dataclass(frozen=True)
class CrossfitResult:
    oof_y: jax.Array      # (n,) out-of-fold E[Y|X]
    oof_t: jax.Array      # (n,) out-of-fold E[T|X] (propensity if binary)
    folds: jax.Array      # (n,) fold assignment
    states_y: Any
    states_t: Any


def crossfit_one(nuis: Nuisance, key: jax.Array, X: jax.Array,
                 target: jax.Array, folds: jax.Array, k: int,
                 engine: str = "parallel", tracer=None
                 ) -> Tuple[jax.Array, Any]:
    """Engine dispatch for ONE cross-fit target over a fixed fold
    assignment — the unit `crossfit` composes twice and the IV
    estimators (three nuisances: E[Y|X], E[T|X], E[Z|X]) compose three
    or four times.  engine: "parallel" (paper C1) maps the fold axis
    through ``vmap``; "sequential" through ``serial``; "parallel_loo"
    takes the one-pass LOO-Gram fast path; any other executor name or
    Executor/TaskRuntime instance maps the fold axis directly.
    ``tracer`` (a repro.obs Tracer) records the fold fits' spans."""
    if engine == "parallel_loo":
        return crossfit_parallel_loo(nuis, key, X, target, folds, k)
    if engine == "sequential":
        return crossfit_sequential(nuis, key, X, target, folds, k, tracer)
    exe = "vmap" if engine == "parallel" else engine
    return crossfit_parallel(nuis, key, X, target, folds, k,
                             executor=exe, tracer=tracer)


def crossfit(nuis_y: Nuisance, nuis_t: Nuisance, key: jax.Array,
             X: jax.Array, y: jax.Array, t: jax.Array, k: int,
             engine: str = "parallel", tracer=None) -> CrossfitResult:
    """Cross-fit both nuisances.  engine: "parallel" (paper) dispatches
    the 2·K fits through the ``vmap`` Executor; "sequential" (EconML
    baseline) through ``serial``; "parallel_loo" takes the one-pass
    LOO-Gram fast path.  Any other executor name (e.g. "shard_map") or
    Executor instance maps the fold axis directly."""
    kf, ky, kt = jax.random.split(key, 3)
    folds = fold_ids(kf, X.shape[0], k)
    oof_y, st_y = crossfit_one(nuis_y, ky, X, y, folds, k, engine, tracer)
    oof_t, st_t = crossfit_one(nuis_t, kt, X, t, folds, k, engine, tracer)
    return CrossfitResult(oof_y=oof_y, oof_t=oof_t, folds=folds,
                          states_y=st_y, states_t=st_t)

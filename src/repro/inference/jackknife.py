"""Delete-fold jackknife — uncertainty almost for free.

Cross-fitting already partitions the rows into k folds and computes
out-of-fold nuisance predictions for every row.  The delete-group
jackknife is a *pure reweighted-moments pass*: ONE fold-segmented
augmented residual Gram over the data (repro.core.moments, optionally
streamed in row blocks), after which each delete-fold estimate is the
LOO identity

    G_(-j) = G_total - G_fold_j

plus a (p_phi, p_phi) deterministic solve — no nuisance refits, no
dataset re-indexing, k tiny solves on top of a finished DML fit.  This
is the cheap end of the inference spectrum (bootstrap being the
expensive end), and the k delete-fold solves go through the same
Executor as bootstrap replicates (elementwise subtraction + the
Gauss-Jordan solve are replicate-invariant, so serial == vmap holds
bitwise here too).

Variance: the delete-group jackknife estimator with k groups,

    se² = (k-1)/k · Σ_j (θ_(-j) - θ̄)²,

is a consistent estimate of the same asymptotic variance the influence-
function (HC0 sandwich) stderr targets — tests assert agreement.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import moments
from repro.inference.intervals import InferenceResult
from repro.inference.numerics import det_solve


def delete_fold_jackknife(y: jax.Array, t: jax.Array, oof_y: jax.Array,
                          oof_t: jax.Array, folds: jax.Array,
                          phi: jax.Array, n_folds: int, *,
                          alpha: float = 0.05, executor="vmap",
                          point=None, point_se=None,
                          mesh=None, rules=None, ridge: float = 1e-8,
                          row_block: int = 0, memory_budget: int = 0,
                          chunk: int = 0, max_retries: int = 2,
                          tracer=None) -> InferenceResult:
    """Jackknife over the existing fold partition.  y, t: (n,);
    oof_y/oof_t: (n,) out-of-fold nuisance predictions from the fit;
    folds: (n,) fold ids.  The k delete-fold solves go through the task
    runtime like bootstrap replicates (chunking is moot at k solves,
    but the fault-tolerance ladder still applies)."""
    from repro.runtime import as_runtime
    sched = as_runtime(executor, mesh=mesh, rules=rules,
                       memory_budget=memory_budget, chunk=chunk,
                       max_retries=max_retries, tracer=tracer)
    f32 = jnp.float32
    n, p = phi.shape
    ry = y.astype(f32) - oof_y
    rt = t.astype(f32) - oof_t

    # one segmented pass: Gh[j] = Σ_{i in fold j} m_i m_iᵀ, m = [Z | ry]
    def block(ryb, rtb, phib, fb):
        Z = rtb[:, None] * phib.astype(f32)
        M = jnp.concatenate([Z, ryb[:, None]], axis=1)
        oh = jax.nn.one_hot(fb, n_folds, dtype=f32)
        return jnp.einsum("nk,ni,nj->kij", oh, M, M), oh.sum(0)

    Gh, counts = moments.blocked_reduce(
        block, (ry, rt, phi, folds), row_block=row_block, rules=rules,
        pad_values=(0, 0, 0, -1))
    G_tot = Gh.sum(0)
    n_eff = jnp.maximum(n - counts, 1.0)                     # (k,)

    def drop_fold(seg, G_tot_):
        Gd = G_tot_ - seg["G"]
        A = Gd[:p, :p] + ridge * seg["n_eff"] * jnp.eye(p, dtype=f32)
        return det_solve(A, Gd[:p, p])

    thetas = sched.map(drop_fold, {"G": Gh, "n_eff": n_eff}, G_tot,
                       label="jackknife")
    return _jackknife_result(thetas, n_folds, point, point_se, alpha,
                             sched.name)


def _jackknife_result(thetas, n_folds: int, point, point_se,
                      alpha: float, executor_name: str) -> InferenceResult:
    theta_bar = thetas.mean(axis=0)
    center = theta_bar if point is None else point
    k = float(n_folds)
    se = jnp.sqrt(jnp.clip(
        (k - 1.0) / k * jnp.square(thetas - theta_bar[None, :]).sum(axis=0),
        0.0, None))
    return InferenceResult(method="jackknife", executor=executor_name,
                           point=center, replicates=thetas, se=se,
                           alpha=alpha, point_se=point_se)


def delete_fold_jackknife_iv(y: jax.Array, t: jax.Array, z: jax.Array,
                             oof_y: jax.Array, oof_t: jax.Array,
                             oof_z: jax.Array, folds: jax.Array,
                             phi: jax.Array, n_folds: int, *,
                             alpha: float = 0.05, executor="vmap",
                             point=None, point_se=None, mesh=None,
                             rules=None, ridge: float = 1e-8,
                             row_block: int = 0, memory_budget: int = 0,
                             chunk: int = 0,
                             max_retries: int = 2) -> InferenceResult:
    """Delete-fold jackknife for the instrumented moment: ONE
    fold-segmented instrumented Gram (``moments.fold_iv_gram``,
    optionally row-blocked), then each delete-fold 2SLS estimate is the
    LOO identity ``G_(-j) = G_total - G_fold_j`` plus one (p, p)
    deterministic solve — no nuisance refits, exactly the DML
    jackknife's cost structure on the IV moment."""
    from repro.runtime import as_runtime
    sched = as_runtime(executor, mesh=mesh, rules=rules,
                       memory_budget=memory_budget, chunk=chunk,
                       max_retries=max_retries)
    f32 = jnp.float32
    n, p = phi.shape
    ry = y.astype(f32) - oof_y
    rt = t.astype(f32) - oof_t
    rz = z.astype(f32) - oof_z
    Gh, counts = moments.fold_iv_gram(ry, rt, rz, phi, folds, n_folds,
                                      row_block=row_block, rules=rules)
    G_tot = Gh.sum(0)
    n_eff = jnp.maximum(n - counts, 1.0)

    def drop_fold(seg, G_tot_):
        Gd = G_tot_ - seg["G"]
        J, b, _, _ = moments.iv_slices(Gd, p)
        A = J + ridge * seg["n_eff"] * jnp.eye(p, dtype=f32)
        return det_solve(A, b)

    thetas = sched.map(drop_fold, {"G": Gh, "n_eff": n_eff}, G_tot,
                       label="jackknife_iv")
    return _jackknife_result(thetas, n_folds, point, point_se, alpha,
                             sched.name)

"""The paper's workloads as dry-run cells: fold-parallel DML (5-fold
ridge + logistic cross-fit, orthogonal final stage) and its
orthogonal-IV sibling (three cross-fit nuisances + the instrumented
final stage), at the §5.3 scale — n = 1M rows x p = 500 covariates —
lowered against the production mesh with rows sharded over every chip.

These are the cells "most representative of the paper's technique" for
the §Perf hillclimb: C1's K simultaneous fold-fits appear as a leading
vmap axis; the Gram/Newton reductions are the collectives.  The IV cell
lowers the SAME shared engines (crossfit_one ×3 + moments.iv_gram), so
the two estimands differ only in which moments the final stage reads.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import CausalConfig
from repro.core.crossfit import crossfit_parallel, crossfit_parallel_loo
from repro.core.final_stage import cate_basis, fit_final_stage
from repro.core.iv import fit_iv_final_stage
from repro.core.nuisance import make_nuisance

N_ROWS = 1_048_576  # the paper's "1 Million", padded to 2^20 so rows
# shard evenly over 256/512 chips (extra rows carry zero weight)
N_COVARIATES = 500


def make_dml_step(cfg: CausalConfig, engine: str = "parallel",
                  rules=None):
    """One full DML fit as a single jittable program, lowering the SAME
    shared estimation engine the host estimator runs (no inline
    re-implementation of cross-fitting).  Fold assignment comes in as
    data (host-computed, deterministic).

    engine="parallel"      paper-faithful C1 (vmapped complement fits)
    engine="parallel_loo"  beyond-paper leave-one-out-Gram fast path

    cfg.row_block > 0 streams every moments pass (nuisance normal
    equations, LOO fold Grams, final stage) in row blocks constrained
    on the ``rows`` mesh axis — the (k, n) complement-fit activations
    and the (n, p_phi) final-stage moment matrix never materialize.
    """
    ridge = make_nuisance(cfg.nuisance_y, "reg", cfg)
    logit = make_nuisance(cfg.nuisance_t,
                          "clf" if cfg.discrete_treatment else "reg", cfg)

    def dml_fit(X, y, t, folds):
        k = cfg.n_folds
        key = jax.random.PRNGKey(0)
        cf = (crossfit_parallel_loo if engine == "parallel_loo"
              else crossfit_parallel)
        my, _ = cf(ridge, key, X, y, folds, k, rules)
        mt, _ = cf(logit, key, X, t, folds, k, rules)
        phi = cate_basis(X, cfg.cate_features)
        fs = fit_final_stage(y, t, my, mt, phi,
                             row_block=cfg.row_block, rules=rules)
        return fs.theta, fs.cov

    return dml_fit


def make_iv_step(cfg: CausalConfig, engine: str = "parallel",
                 rules=None):
    """One full OrthoIV fit as a single jittable program: the same
    shared crossfit engine run for THREE nuisances (E[Y|X], E[T|X],
    E[Z|X]) plus the instrumented final stage (moments.iv_gram /
    iv_meat) — the IV workload lowered the exact way the DML cell is."""
    ridge = make_nuisance(cfg.nuisance_y, "reg", cfg)
    logit_t = make_nuisance(cfg.nuisance_t,
                            "clf" if cfg.discrete_treatment else "reg",
                            cfg)
    logit_z = make_nuisance(cfg.nuisance_z,
                            "clf" if cfg.discrete_instrument else "reg",
                            cfg)

    def iv_fit(X, y, t, z, folds):
        k = cfg.n_folds
        key = jax.random.PRNGKey(0)
        cf = (crossfit_parallel_loo if engine == "parallel_loo"
              else crossfit_parallel)
        my, _ = cf(ridge, key, X, y, folds, k, rules)
        mt, _ = cf(logit_t, key, X, t, folds, k, rules)
        mz, _ = cf(logit_z, key, X, z, folds, k, rules)
        f32 = jnp.float32
        ry = y.astype(f32) - my
        rt = t.astype(f32) - mt
        rz = z.astype(f32) - mz
        phi = cate_basis(X, cfg.cate_features)
        fs = fit_iv_final_stage(ry, rt, rz, phi,
                                row_block=cfg.row_block,
                                strategy=cfg.row_block_strategy,
                                rules=rules)
        return fs.theta, fs.cov

    return iv_fit


def input_specs(n: int = N_ROWS, p: int = N_COVARIATES,
                with_instrument: bool = False):
    f32, i32 = jnp.float32, jnp.int32
    specs = {
        "X": jax.ShapeDtypeStruct((n, p), f32),
        "y": jax.ShapeDtypeStruct((n,), f32),
        "t": jax.ShapeDtypeStruct((n,), f32),
        "folds": jax.ShapeDtypeStruct((n,), i32),
    }
    if with_instrument:
        specs["z"] = jax.ShapeDtypeStruct((n,), f32)
    return specs


def row_sharding(mesh: Mesh, with_instrument: bool = False
                 ) -> Dict[str, NamedSharding]:
    """Rows shard over EVERY mesh axis jointly (the paper's one giant
    data axis; folds batch inside the program)."""
    axes = tuple(mesh.axis_names)
    sh = {
        "X": NamedSharding(mesh, P(axes, None)),
        "y": NamedSharding(mesh, P(axes)),
        "t": NamedSharding(mesh, P(axes)),
        "folds": NamedSharding(mesh, P(axes)),
    }
    if with_instrument:
        sh["z"] = NamedSharding(mesh, P(axes))
    return sh


def lower_dml_cell(mesh: Mesh, cfg: CausalConfig = None,
                   n: int = N_ROWS, p: int = N_COVARIATES,
                   engine: str = "parallel", rules=None):
    cfg = cfg or CausalConfig(n_folds=5, cate_features=1)
    step = make_dml_step(cfg, engine, rules)
    specs = input_specs(n, p)
    sh = row_sharding(mesh)
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            step,
            in_shardings=(sh["X"], sh["y"], sh["t"], sh["folds"]),
        ).lower(specs["X"], specs["y"], specs["t"], specs["folds"])
    return lowered


def lower_iv_cell(mesh: Mesh, cfg: CausalConfig = None,
                  n: int = N_ROWS, p: int = N_COVARIATES,
                  engine: str = "parallel", rules=None):
    """The OrthoIV workload against the production mesh: identical row
    sharding plus the instrument column."""
    cfg = cfg or CausalConfig(n_folds=5, cate_features=1)
    step = make_iv_step(cfg, engine, rules)
    specs = input_specs(n, p, with_instrument=True)
    sh = row_sharding(mesh, with_instrument=True)
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            step,
            in_shardings=(sh["X"], sh["y"], sh["t"], sh["z"],
                          sh["folds"]),
        ).lower(specs["X"], specs["y"], specs["t"], specs["z"],
                specs["folds"])
    return lowered

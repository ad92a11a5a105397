"""Paper Fig. 6: DML (sequential EconML-style) vs DML_Ray (parallel)
runtime at growing data scales.

On this host the mesh is one CPU device, so the measured speedup isolates
the paper's MECHANISM — K sequential fit programs vs one batched
fold-parallel program (dispatch overhead, compile reuse, shared data
passes) — rather than multi-node scaling.

Defaults are CPU-friendly; ``--full`` runs the paper's exact
10k/100k/1M x 500 sweep.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.config import CausalConfig
from repro.core.dml import DML
from repro.data.causal_dgp import make_causal_data


def time_fit(est: DML, data, key, reps: int = 1) -> float:
    # warm-up/compile
    est.fit(data.y, data.t, data.X, key=key)
    t0 = time.perf_counter()
    for _ in range(reps):
        res = est.fit(data.y, data.t, data.X, key=key)
        jax.block_until_ready(res.theta)
    return (time.perf_counter() - t0) / reps


def run(sizes, p, n_folds=5, key=None, csv=print):
    key = key if key is not None else jax.random.PRNGKey(0)
    rows = []
    for n in sizes:
        data = make_causal_data(jax.random.fold_in(key, n), n, p,
                                effect=1.0)
        seq = DML(CausalConfig(n_folds=n_folds, engine="sequential"))
        par = DML(CausalConfig(n_folds=n_folds, engine="parallel"))
        loo = DML(CausalConfig(n_folds=n_folds, engine="parallel_loo"))
        t_seq = time_fit(seq, data, key)
        t_par = time_fit(par, data, key)
        t_loo = time_fit(loo, data, key)
        csv(f"crossfit_seq_n{n}_p{p},{t_seq*1e6:.0f},ate_err="
            f"{abs(seq.fit(data.y, data.t, data.X, key=key).ate-1):.4f}")
        csv(f"crossfit_par_n{n}_p{p},{t_par*1e6:.0f},speedup="
            f"{t_seq/t_par:.2f}x")
        csv(f"crossfit_loo_n{n}_p{p},{t_loo*1e6:.0f},speedup="
            f"{t_seq/t_loo:.2f}x")
        rows.append((n, t_seq, t_par, t_loo))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-exact 10k/100k/1M x 500")
    args = ap.parse_args(argv)
    if args.full:
        run(sizes=(10_000, 100_000, 1_000_000), p=500)
    else:
        run(sizes=(10_000, 30_000, 100_000), p=50)


if __name__ == "__main__":
    main()

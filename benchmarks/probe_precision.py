"""Gram accuracy and speed of the seg_gram kernel's dot precision (TPU).

    python benchmarks/probe_precision.py [--n 262144] [--q 502]

Builds one S = 1 design Gram ``D^T D`` (the cross-fit's shape at the
paper's width) with the Pallas kernel at ``Precision.HIGHEST`` and at
``Precision.DEFAULT``, and with a plain XLA ``d.T @ d`` under
``default_matmul_precision("default")`` and ``("highest")``.  Each is
compared with a float64 host Gram: ``rel_fro`` is the relative Frobenius
error, ``max_rel_corr`` the largest error scaled by
``sqrt(G_ii G_jj)``.  Times are the median of five runs after a warm-up.

This is the measurement behind ``kernel.PRECISION``: the moments must
match a float64 Gram to about 1e-5 (the smoke's moment tolerance), and a
single bf16 pass (DEFAULT) does not.  It needs a TPU: the kernel is
compiled with Mosaic, never interpreted.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels.seg_gram import kernel as K
from repro.kernels.seg_gram import ref as R


def _median_s(f, D, reps=5):
    jax.block_until_ready(f(D))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(D))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _errors(G, G64):
    G = np.asarray(G, np.float64)
    fro = np.linalg.norm(G - G64) / np.linalg.norm(G64)
    scale = np.sqrt(np.outer(np.diag(G64), np.diag(G64)))
    return fro, float(np.max(np.abs(G - G64) / scale))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=262_144)
    ap.add_argument("--q", type=int, default=502)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("probe_precision: needs a TPU", file=sys.stderr)
        return 2
    n, q = args.n, args.q
    D = jax.random.normal(jax.random.PRNGKey(0), (n, q), jnp.float32)
    D = D.at[:, q - 2].set(1.0)  # the intercept column
    D64 = np.asarray(D, np.float64)
    G64 = D64.T @ D64
    # the MXU's work: the q x q Gram padded to (8, 128) fp32 tiles
    flops = 2.0 * n * K._round_up(q, 8) * K._round_up(q, 128)
    prior = K.PRECISION
    try:
        for name, prec in (("HIGHEST", lax.Precision.HIGHEST),
                           ("DEFAULT", lax.Precision.DEFAULT)):
            K.PRECISION = prec
            f = jax.jit(lambda d: K.seg_gram_pallas(R.build_design, [d],
                                                    interpret=False))
            fro, corr = _errors(f(D), G64)
            s = _median_s(f, D)
            print(f"kernel {name}: rel_fro={fro:.3g} max_rel_corr={corr:.3g} "
                  f"ms={1e3 * s:.3f} TFLOP/s={flops / s / 1e12:.2f}")
    finally:
        K.PRECISION = prior
    for name in ("default", "highest"):
        with jax.default_matmul_precision(name):
            f = jax.jit(lambda d: d.T @ d)
            fro, corr = _errors(f(D), G64)
            s = _median_s(f, D)
        print(f"xla {name}: rel_fro={fro:.3g} max_rel_corr={corr:.3g} "
              f"ms={1e3 * s:.3f}")
    print("device", jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main())

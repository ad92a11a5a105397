"""Kernel micro-benchmark: wall time of the ``residual_gram`` jnp
reference path on this host (the Pallas variant is TPU-target; its
interpret-mode execution measures Python, not hardware, so we report the
ref path + the derived streaming bandwidth)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels.residual_gram import ops as rg_ops


def _timeit(f, *args, reps=5):
    out = f(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def bench_residual_gram(csv=print):
    n, p = 200_000, 128
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    y, t_, my, mt = (jax.random.normal(ks[i], (n,)) for i in range(4))
    phi = jax.random.normal(ks[4], (n, p))
    f = jax.jit(lambda *a: rg_ops.residual_gram(*a, backend="ref"))
    dt = _timeit(f, y, t_, my, mt, phi)
    bytes_moved = n * p * 4  # one streaming pass over phi
    csv(f"residual_gram_ref_n{n}_p{p},{dt*1e6:.0f},"
        f"stream_gbps={bytes_moved/dt/1e9:.2f}")


def main(csv=print):
    bench_residual_gram(csv)


if __name__ == "__main__":
    main()

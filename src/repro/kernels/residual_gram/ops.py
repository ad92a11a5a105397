"""Jit'd dispatch wrapper for the fused residual-Gram kernel.

Used by repro.core.final_stage: local (per-shard) moments are computed
here, then psum'd over the data axis — the distributed normal equations
of the DML final stage.  The kernel path routes through the unified
segment-Gram kernel (repro.kernels.seg_gram), whose wrapper zero-pads
the row tail (exact no-op) — no n % block_n divisibility requirement.
"pallas" compiles with Mosaic and raises off TPU; "interpret" runs the
same kernel in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax

from repro.kernels.residual_gram import kernel as _kernel
from repro.kernels.residual_gram import ref as _ref


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


@functools.partial(jax.jit, static_argnames=("backend", "block_n"))
def residual_gram(
    y: jax.Array,
    t: jax.Array,
    my: jax.Array,
    mt: jax.Array,
    phi: jax.Array,
    *,
    backend: str = "",
    block_n: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused residualize->moments. Returns (G (p,p), b (p,)), fp32.
    ``block_n`` overrides the kernel's VMEM-planned row block."""
    be = backend or default_backend()
    if be == "ref":
        return _ref.residual_gram_ref(y, t, my, mt, phi)
    if be not in ("pallas", "interpret"):
        raise ValueError(f"unknown residual_gram backend {be!r}")
    if be == "pallas" and jax.default_backend() != "tpu":
        raise RuntimeError(
            "residual_gram: the 'pallas' lowering runs on TPU only (the "
            f"backend is {jax.default_backend()!r}); use 'interpret' or 'ref'")
    return _kernel.residual_gram_pallas(
        y,
        t,
        my,
        mt,
        phi,
        block_n=block_n,
        interpret=be == "interpret",
    )

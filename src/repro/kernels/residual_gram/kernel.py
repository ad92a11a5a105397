"""Fused residualize -> Gram for the DML final stage — now a thin
wrapper over the unified segment-Gram kernel (repro.kernels.seg_gram),
which generalizes this form to fold/IV/segment-masked Grams.  One
fused implementation; this module keeps the historical entry point.

The augmented Gram M = [rt*phi | ry] comes out of one rolled pass over
(block_n, p) tiles (residuals and Z form in registers, accumulators
stay VMEM-resident); (G, b) are slices of it.

Padding contract (no divisibility requirement): the row tail is
zero-padded inside the kernel wrapper — all-zero rows produce all-zero
M rows, contributing exactly 0.0 to G and b (tested bitwise in
tests/test_kernels_seg_gram.py).

``interpret`` is explicit: False compiles with Mosaic (TPU only),
True runs the same grid in interpret mode.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.kernels.seg_gram import kernel as sg_kernel
from repro.kernels.seg_gram import ref as sg_ref


def residual_gram_pallas(
    y: jax.Array,
    t: jax.Array,
    my: jax.Array,
    mt: jax.Array,
    phi: jax.Array,
    *,
    interpret: bool,
    block_n: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """y,t,my,mt: (n,); phi: (n,p). Returns (G (p,p), b (p,)) in fp32."""
    p = phi.shape[1]
    col = lambda x: x.astype(jax.numpy.float32).reshape(-1, 1)  # noqa: E731
    gaug = sg_kernel.seg_gram_pallas(
        sg_ref.build_residual,
        [col(y), col(t), col(my), col(mt), phi.astype(jax.numpy.float32)],
        block_n=block_n,
        interpret=interpret,
    )
    return gaug[:p, :p], gaug[:p, p]

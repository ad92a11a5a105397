"""Orthogonal final stage: the Neyman-orthogonal moment solved as
(distributed) normal equations on residuals.

    ry = y - m_y(X),  rt = t - m_t(X),  Z = rt ⊙ phi(X)
    theta = argmin  Σ (ry - <theta, phi>·rt)²   ⇒   (ZᵀZ)θ = Zᵀry

All sufficient statistics come from the streaming moments engine
(repro.core.moments).  Two memory regimes:

  row_block = 0   whole-array: the fused Pallas ``residual_gram``
                  kernel (HBM→VMEM, one pass) computes G/b, and the
                  HC0 meat is a dense einsum over the materialized
                  (n, p_phi) moment matrix Z — fastest when Z fits.
  row_block = R   chunked: a ``lax.scan`` over row blocks streams BOTH
                  passes (G/b, then the meat at the solved theta), so
                  the dense Z and the residual vector never
                  materialize — peak temporaries are O(R·p_phi), which
                  is what lets n exceed a single-allocation budget
                  (paper §5.3 "industrial scale").  The (p,p)
                  moments are the only thing reduced — the same shape
                  as Ray's driver-side aggregation but executed as one
                  psum.  ``strategy="pallas"`` keeps the same two-pass
                  structure but takes each pass through the fused
                  seg_gram kernel (one HBM pass per moment; the
                  measured CPU lowering closes the chunked-vs-whole
                  runtime gap at n=100k — benchmarks/bench_final_stage).

Inference: heteroskedasticity-robust (HC0) sandwich covariance, matching
EconML's ``StatsModelsLinearRegression`` final stage.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import moments
from repro.kernels.residual_gram import ops as rg_ops


def cate_basis(X: jax.Array, n_features: int) -> jax.Array:
    """phi(x): [1] (ATE / constant effect) or [1, x_0..x_{m-1}]."""
    n = X.shape[0]
    ones = jnp.ones((n, 1), jnp.float32)
    if n_features <= 1:
        return ones
    return jnp.concatenate([ones, X[:, : n_features - 1].astype(jnp.float32)],
                           axis=1)


@dataclasses.dataclass(frozen=True)
class FinalStageResult:
    theta: jax.Array       # (p_phi,)
    cov: jax.Array         # (p_phi, p_phi) HC0 sandwich
    gram: jax.Array        # (p_phi, p_phi) ZᵀZ / n
    n: int

    @property
    def stderr(self) -> jax.Array:
        return jnp.sqrt(jnp.diag(self.cov))


def fit_final_stage(y: jax.Array, t: jax.Array, my: jax.Array,
                    mt: jax.Array, phi: jax.Array, *,
                    ridge: float = 1e-8, backend: str = "",
                    row_block: int = 0, strategy: Optional[str] = None
                    ) -> FinalStageResult:
    """Solve the orthogonal moment.  y,t,my,mt: (n,); phi: (n, p_phi).

    ``row_block > 0`` streams every moment in fixed-order row blocks
    (see module docstring); chunked and "whole" blocked evaluation of
    the same row_block are bit-identical by construction."""
    n, p = phi.shape
    r = moments.resolve_row_block(n, row_block)
    if r > 0:
        G, b = moments.residual_moments(y, t, my, mt, phi, row_block=r,
                                        strategy=strategy, backend=backend)
        A = G + ridge * n * jnp.eye(p, dtype=jnp.float32)
        theta = jnp.linalg.solve(A, b)
        meat = moments.residual_meat(y, t, my, mt, phi, theta,
                                     row_block=r, strategy=strategy)
        Ainv = jnp.linalg.inv(A)
        cov = Ainv @ meat @ Ainv
        return FinalStageResult(theta=theta, cov=cov, gram=G / n, n=n)

    G, b = rg_ops.residual_gram(y, t, my, mt, phi, backend=backend)
    A = G + ridge * n * jnp.eye(p, dtype=jnp.float32)
    theta = jnp.linalg.solve(A, b)

    # HC0 sandwich: cov = G⁻¹ (Zᵀ diag(e²) Z) G⁻¹
    ry = (y - my).astype(jnp.float32)
    rt = (t - mt).astype(jnp.float32)
    z = rt[:, None] * phi.astype(jnp.float32)
    e = ry - z @ theta
    meat = jnp.einsum("ni,n,nj->ij", z, jnp.square(e), z)
    Ainv = jnp.linalg.inv(A)
    cov = Ainv @ meat @ Ainv
    return FinalStageResult(theta=theta, cov=cov, gram=G / n, n=n)

"""Refresh-side solves: cell accumulators → per-segment effects.

Everything here is O(p³)-per-cell linear algebra on the store's
sufficient statistics — no data pass:

  1. Cross-fit ridge nuisances come from the fold-complement of the
     nuisance Gram (the leave-one-out identity of
     ``sweep.segmented._complement`` and ``_ridge``, same scaling: complement
     Gram / n_eff + λI).
  2. Residuals are linear forms of the design, ``r = cᵀ dn`` with
     coefficient vectors like ``c_y = [-β_y | 1 at the y column]``, so
     every final-stage moment is a contraction of the degree-4 tensor
     ``vg`` with two coefficient vectors:

        G   = Σ rt²·φφᵀ      = ⟨vg, c_t ⊗ c_t⟩
        b   = Σ rt·ry·φ      = ⟨vg, c_t ⊗ c_y⟩  (φ₀ ≡ 1 carries ry)
        J   = Σ rz·rt·φφᵀ    = ⟨vg, c_z ⊗ c_t⟩  (instrumented family)
        Σe² = Σry² - 2θᵀb + θᵀGθ

  3. Solve/invert with the deterministic Gauss-Jordan kernels and the
     exact ridge scaling of the segmented sweep (``+ 1e-8·n_seg·I``).

Standard errors are the **homoskedastic** sandwich ``σ²·A⁻¹ G A⁻¹``
(σ² = Σe²/n_seg): the HC0 meat ``Σe²·zzᵀ`` is degree-6 in the design
and is NOT a contraction of any stored moment — computing it would
need a data pass, which is exactly what refresh must not do.  See
docs/ARCHITECTURE.md for the contract table entry.

``refresh_rows`` is the other path, for a windowed column whose
treatment model is logistic: the MM steps need a gradient pass over the
rows, so it re-reads the window's rows kept on the device and runs the
segmented sweep's stages on them (HC0 SEs, as the sweep's).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.config import CausalConfig
from repro.inference.numerics import det_inv, det_solve
from repro.store.stats import ColumnLayout, State, window_sum
from repro.sweep.segmented import segmented_stages

Array = jax.Array
_F32 = jnp.float32


def _coef(beta: Array, col: int, qd: int, q: int) -> Array:
    """Residual coefficient vector in dn coordinates: r = cᵀ dn."""
    c = jnp.zeros(beta.shape[:-1] + (qd,), beta.dtype)
    c = c.at[..., :q].set(-beta)
    return c.at[..., col].set(1.0)


def refresh_column(layout: ColumnLayout, state: State, n_segments: int, *,
                   ridge_lambda: float, ridge_final: float = 1e-8
                   ) -> Dict[str, Array]:
    """Re-solve one column: {"theta" (E, pf), "se" (E, pf), "ate" (E,)}.

    Zero-row cells stay finite (n_eff/n_seg floored at 1, ridge keeps
    every solve well-posed); ``EffectPanel.ok`` flags them via counts.
    """
    lo = layout
    E, k, q, qd, pf = n_segments, lo.k, lo.q, lo.qd, lo.pf
    ng = state["ng"].reshape(E, k, qd, qd)
    counts = state["counts"].reshape(E, k)

    # fold-complement ridge nuisances (LOO identity, segmented scaling)
    Gseg = ng.sum(axis=1)
    A_aug = Gseg[:, None] - ng
    n_eff = jnp.maximum(counts.sum(1, keepdims=True) - counts, 1.0)
    A = (A_aug[..., :q, :q] / n_eff[..., None, None]
         + ridge_lambda * jnp.eye(q, dtype=_F32))
    solve2 = jax.vmap(jax.vmap(det_solve))

    def _beta_for(col):
        return solve2(A, A_aug[..., :q, col] / n_eff[..., None])

    cy = _coef(_beta_for(lo.iy), lo.iy, qd, q)
    ct = _coef(_beta_for(lo.it), lo.it, qd, q)

    # final-stage statistics as contractions of the degree-4 tensor
    V6 = state["vg"].reshape(E, k, pf, qd, pf, qd)

    def _quad(ca, cb):
        return jnp.einsum("skaibj,ski,skj->sab", V6, ca, cb)

    def _qvec(ca, cb):
        return jnp.einsum("skaij,ski,skj->sa", V6[:, :, :, :, 0, :], ca, cb)

    def _qscl(ca, cb):
        return jnp.einsum("skij,ski,skj->s", V6[:, :, 0, :, 0, :], ca, cb)

    nseg = jnp.maximum(counts.sum(axis=1), 1.0)
    eye = jnp.eye(pf, dtype=_F32)
    Gtt = _quad(ct, ct)          # Σ rt²·φφᵀ per segment
    bty = _qvec(ct, cy)          # Σ rt·ry·φ
    syy = _qscl(cy, cy)          # Σ ry²

    if lo.iv:
        cz = _coef(_beta_for(lo.iz), lo.iz, qd, q)
        a = _quad(cz, ct) + ridge_final * nseg[:, None, None] * eye
        theta = jax.vmap(det_solve)(a, _qvec(cz, cy))
        meat_base = _quad(cz, cz)   # Σ rz²·φφᵀ — the instrument score Gram
    else:
        a = Gtt + ridge_final * nseg[:, None, None] * eye
        theta = jax.vmap(det_solve)(a, bty)
        meat_base = Gtt

    sse = syy - 2.0 * (theta * bty).sum(-1) + jnp.einsum(
        "sa,sab,sb->s", theta, Gtt, theta)
    sigma2 = jnp.clip(sse, 0.0, None) / nseg
    ainv = jax.vmap(det_inv)(a)
    cov = jnp.einsum("sia,sab,sbj->sij", ainv,
                     sigma2[:, None, None] * meat_base, ainv)
    se = jnp.sqrt(jnp.clip(jnp.diagonal(cov, axis1=1, axis2=2), 0.0, None))
    return {"theta": theta, "se": se, "ate": theta[:, 0]}


def refresh_rows(layout: ColumnLayout, cfg: CausalConfig, state: State,
                 start, n_segments: int, day_rows: int) -> Dict[str, Array]:
    """Re-solve one windowed column from the window's rows, oldest day
    first (slot ``start`` leads): sum the day slots' Grams, then the
    segmented sweep's stages over the ring — fold complements, ridge
    visit models, ``2·newton_iters`` MM logistic steps from zero, the
    out-of-fold predictions and the per-cohort final stage with its HC0
    SE.  Returns the sweep's {"theta", "se", "ate", "beta_y", "beta_t",
    "cell_rows"}."""
    E, k, q, qd = n_segments, layout.k, layout.q, layout.qd
    ng = window_sum(state["ng"], start).reshape(E, k, qd, qd)
    counts = window_sum(state["counts"], start).reshape(E, k)
    keep = list(range(q)) + [layout.iy]  # [X | 1 | y]
    Gh = ng[:, :, keep][:, :, :, keep]
    shift = -start * day_rows
    xa_t = jnp.roll(state["xa"], shift, axis=1)
    meta = jnp.roll(state["meta"], shift, axis=1)  # [t; cohort + 1; fold; y]
    sids = meta[1].astype(jnp.int32) - 1
    folds = meta[2].astype(jnp.int32)
    comb = jnp.where(sids >= 0, sids * k + folds, 0)
    return segmented_stages(cfg, Gh, counts, E, y=meta[3], t=meta[0],
                            sids=sids, folds=folds, comb=comb, xa_t=xa_t)

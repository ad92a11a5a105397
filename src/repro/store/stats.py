"""Ingest-side sufficient statistics of the effect store.

The store's unit of state is the per-(segment, fold) cell.  Each cell
holds two Gram-additive accumulators over the nuisance design
``dn = [X | 1 | t | y]`` (``[... | z]`` for the instrumented family):

  ng      (cells, qd, qd)   ``Σ_n dn_n dn_nᵀ`` — the nuisance fold
          Gram.  Its fold-complement (the leave-one-out identity) is
          every cross-fit ridge normal equation at once.
  vg      (cells, pf·qd, pf·qd)   ``Σ_n v_n v_nᵀ`` with
          ``v = φ(x) ⊗ dn`` — the degree-4 moment tensor.  Every
          final-stage statistic (G, b, Σrz·rt·φφᵀ, Σe² …) is a
          *contraction* of vg with per-cell residual coefficient
          vectors (a residual is linear in dn: ``ry = c_yᵀ dn`` with
          ``c_y = [-β_y | 1 at the y column]``), so refresh never
          re-reads a row.
  counts  (cells,)   exact integer row counts (f32 sums of integers
          are order-independent below 2²⁴).

Ingest folds a new row block into all three with ONE
``moments.blocked_reduce`` pass over only the new rows, seeded with the
standing accumulators (``init=``).  Because the seeded left-fold
replays exactly the addition sequence a one-shot pass over the
concatenated rows would run, incremental ingest is **bitwise** the
full rebuild whenever every earlier ingest ended on a ``row_block``
boundary — the store's fixed-order block-fold contract.
``strategy="pallas"`` routes through the fused segment-outer kernels
instead (bitwise on the scatter lowering, delta-add tolerance on the
compiled kernel).

A windowed store (``Window``: a trailing window of ``days`` day slots)
keeps each statistic per day instead, with a leading ``days`` axis:
``ng`` (days, cells, qd, qd), ``counts`` (days, cells), and ``vg``
for the columns refreshed from moments.  A day's ingest computes its
slot afresh (one pass over the day's rows, no ``init``) and overwrites
the oldest slot once the window is full; refresh sums the slots in a
fixed age order, oldest first (``window_sum``), so the sums do not
depend on where in the ring the days sit.  A column whose treatment
model is logistic (``ColumnLayout.rows``) has no ``vg``: its refresh
re-reads the window's rows, which it keeps on the device in a
lane-major ring, ``xa`` = [X | 1]ᵀ (q, days·day_rows) and ``meta`` =
[t; cohort + 1; fold; y] (4, days·day_rows); a padded row has a zero
``xa`` column and cohort tag 0.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import moments

Array = jax.Array
_F32 = jnp.float32

State = Dict[str, Array]


@dataclasses.dataclass(frozen=True)
class ColumnLayout:
    """Static shape metadata of one store column's accumulators."""

    p: int    # X feature width
    pf: int   # CATE basis width (cate_basis column count)
    k: int    # cross-fit folds
    iv: bool  # instrumented design (z column present)
    rows: bool = False  # refreshed from the window's rows (logistic t)

    @property
    def q(self) -> int:
        """Augmented nuisance design width [X | 1]."""
        return self.p + 1

    @property
    def it(self) -> int:
        """Column index of t inside dn."""
        return self.q

    @property
    def iy(self) -> int:
        """Column index of y inside dn."""
        return self.q + 1

    @property
    def iz(self) -> int:
        """Column index of z inside dn (instrumented layouts only)."""
        return self.q + 2

    @property
    def qd(self) -> int:
        """Full dn width."""
        return self.q + (3 if self.iv else 2)

    @property
    def pv(self) -> int:
        """Width of the Khatri-Rao row ``v = φ ⊗ dn``."""
        return self.pf * self.qd


def init_state(layout: ColumnLayout, n_cells: int, days: int = 0,
               day_rows: int = 0) -> State:
    """Zero accumulators for ``n_cells = n_segments · k`` cells; with
    ``days``, one slot per day and, for a ``rows`` column, the ring of
    ``days · day_rows`` rows."""
    lead = (days,) if days else ()
    state = {
        "ng": jnp.zeros(lead + (n_cells, layout.qd, layout.qd), _F32),
        "counts": jnp.zeros(lead + (n_cells,), _F32),
    }
    if layout.rows:
        state["xa"] = jnp.zeros((layout.q, days * day_rows), _F32)
        state["meta"] = jnp.zeros((4, days * day_rows), _F32)
    else:
        state["vg"] = jnp.zeros(lead + (n_cells, layout.pv, layout.pv), _F32)
    return state


def window_sum(slots: Array, start) -> Array:
    """Σ over the leading day axis of ``slots``, oldest day first: the
    slots rolled so that slot ``start`` leads, then a left fold."""
    rolled = jnp.roll(slots, -start, axis=0)
    acc = rolled[0]
    for d in range(1, slots.shape[0]):
        acc = acc + rolled[d]
    return acc


def ingest_day(layout: ColumnLayout, state: State, slot, X: Array,
               t: Array, y: Array, z: Optional[Array], phi: Array,
               sids: Array, folds: Array, n_cells: int, *,
               row_block: int = 0, strategy: Optional[str] = None) -> State:
    """Write one day into slot ``slot`` of a windowed column: its cell
    statistics from one pass over the day's rows alone, and for a
    ``rows`` column its rows into the ring.  ``sids`` is -1 on padded
    rows, which add nothing."""
    comb = jnp.where(sids >= 0, sids * layout.k + folds, -1)
    day = {key: jnp.zeros(v.shape[1:], _F32) for key, v in state.items()
           if key in ("ng", "vg", "counts")}
    day = ingest_cells(layout, day, X, t, y, z, phi, comb, n_cells,
                       row_block=row_block, strategy=strategy)
    out = {key: state[key].at[slot].set(v) for key, v in day.items()}
    if layout.rows:
        r = X.shape[0]
        real = (sids >= 0).astype(_F32)
        xa = jnp.concatenate([X.astype(_F32).T, real[None]])
        meta = jnp.stack([t.astype(_F32), (sids + 1).astype(_F32),
                          folds.astype(_F32), y.astype(_F32)])
        at = (0, slot * r)
        out["xa"] = jax.lax.dynamic_update_slice(state["xa"], xa, at)
        out["meta"] = jax.lax.dynamic_update_slice(state["meta"], meta, at)
    return out


def _dn(layout: ColumnLayout, X: Array, t: Array, y: Array,
        z: Optional[Array]) -> Array:
    n = X.shape[0]
    cols = [
        X.astype(_F32),
        jnp.ones((n, 1), _F32),
        t.astype(_F32).reshape(n, 1),
        y.astype(_F32).reshape(n, 1),
    ]
    if layout.iv:
        cols.append(z.astype(_F32).reshape(n, 1))
    return jnp.concatenate(cols, axis=1)


def _vrow(layout: ColumnLayout, phi: Array, dn: Array) -> Array:
    v = phi.astype(_F32)[:, :, None] * dn[:, None, :]
    return v.reshape(dn.shape[0], layout.pv)


def ingest_cells(layout: ColumnLayout, state: State, X: Array, t: Array,
                 y: Array, z: Optional[Array], phi: Array, comb: Array,
                 n_cells: int, *, row_block: int = 0,
                 strategy: Optional[str] = None) -> State:
    """Fold a row block into the standing cell accumulators.

    ``comb`` is the combined cell id ``segment·k + fold`` per row.  One
    blocked pass over ONLY the new rows; history is never re-touched.
    """
    if strategy == "pallas":
        from repro.kernels.seg_gram import ops as sg_ops

        dn = _dn(layout, X, t, y, z)
        if layout.rows:
            return {
                "ng": sg_ops.segment_outer(dn, dn, comb, n_cells,
                                           row_block=row_block,
                                           init=state["ng"]),
                "counts": state["counts"] + sg_ops.segment_counts(comb,
                                                                  n_cells),
            }
        v = _vrow(layout, phi, dn)
        return {
            "ng": sg_ops.segment_outer(dn, dn, comb, n_cells,
                                       row_block=row_block,
                                       init=state["ng"]),
            "vg": sg_ops.segment_outer(v, v, comb, n_cells,
                                       row_block=row_block,
                                       init=state["vg"]),
            "counts": state["counts"] + sg_ops.segment_counts(comb, n_cells),
        }

    def _block(Xb, tb, yb, *rest):
        if layout.iv:
            zb, phib, cb = rest
        else:
            (phib, cb), zb = rest, None
        dn = _dn(layout, Xb, tb, yb, zb)
        if layout.rows:
            oh = jax.nn.one_hot(cb, n_cells, dtype=_F32)
            return {"ng": jnp.einsum("nc,ni,nj->cij", oh, dn, dn),
                    "counts": oh.sum(0)}
        v = _vrow(layout, phib, dn)
        oh = jax.nn.one_hot(cb, n_cells, dtype=_F32)
        return {
            "ng": jnp.einsum("nc,ni,nj->cij", oh, dn, dn),
            "vg": jnp.einsum("nc,ni,nj->cij", oh, v, v),
            "counts": oh.sum(0),
        }

    arrays = (X, t, y) + ((z,) if layout.iv else ()) + (phi, comb)
    pad_values = (0,) * (len(arrays) - 1) + (-1,)
    return moments.blocked_reduce(_block, arrays, row_block=row_block,
                                  strategy=strategy, pad_values=pad_values,
                                  init=state, form="store_ingest")

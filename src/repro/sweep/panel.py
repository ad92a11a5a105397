"""EffectPanel: the result container of one sweep — E × C estimates
with CIs, diagnostics, and per-cell failure status.

Per-cell validity is a first-class output, not an exception: a segment
with no rows (or a non-finite solve) flags its cells ``ok = False``
while every other cell keeps its bit-exact estimate, and a column whose
dispatch fails even after the runtime's backend-downgrade ladder is
recorded as a failed column without poisoning its neighbors.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import CausalConfig


@dataclasses.dataclass(frozen=True)
class ColumnResult:
    """One (estimator, config) column of the panel: per-segment arrays,
    or an error string when the whole column's dispatch failed."""

    estimator: str
    cfg: CausalConfig
    thetas: Optional[jax.Array] = None  # (E, p_phi)
    ates: Optional[jax.Array] = None  # (E,)
    ses: Optional[jax.Array] = None  # (E, p_phi)
    ci_lo: Optional[jax.Array] = None  # (E,) replicate ATE CI
    ci_hi: Optional[jax.Array] = None  # (E,)
    replicates: Optional[jax.Array] = None  # (E, B, p_phi)
    # segmented columns only: every (segment, fold-complement) nuisance
    # model, intercept last, and the rows of each (segment, fold) cell
    beta_y: Optional[jax.Array] = None  # (E, K, p + 1)
    beta_t: Optional[jax.Array] = None  # (E, K, p + 1)
    cell_rows: Optional[jax.Array] = None  # (E, K)
    key_index: int = 0  # column index of the key lineage
    shared_nuisance: bool = False  # residuals reused from key_index
    events: Tuple[str, ...] = ()  # runtime chunk/downgrade events
    error: Optional[str] = None
    # store-refreshed columns only: True = every ingest of this column
    # ended on a row_block boundary (bitwise regime), False = at least
    # one misaligned ingest (tolerance regime), None = not applicable
    # (sweep columns, failed columns)
    aligned: Optional[bool] = None

    @property
    def failed(self) -> bool:
        """Whether this column errored (its cells carry no estimates)."""
        return self.error is not None

    def ok(self, counts: jax.Array) -> jax.Array:
        """(E,) per-cell validity: the column ran, the segment has rows,
        and the estimate is finite."""
        e = counts.shape[0]
        if self.failed or self.thetas is None:
            return jnp.zeros((e,), bool)
        finite = jnp.isfinite(self.thetas).all(axis=-1)
        return (counts > 0) & finite


@dataclasses.dataclass(frozen=True)
class EffectPanel:
    """E segments × C estimator-config columns of effect estimates."""

    columns: Tuple[ColumnResult, ...]
    counts: jax.Array  # (E,) rows per segment
    n_segments: int
    segment_key: str = ""

    @property
    def n_columns(self) -> int:
        """Number of estimator-config columns C."""
        return len(self.columns)

    def ok(self) -> jax.Array:
        """(E, C) per-cell validity mask."""
        return jnp.stack([c.ok(self.counts) for c in self.columns], axis=1)

    def ate_table(self) -> jax.Array:
        """(E, C) ATE/LATE point estimates; failed columns are NaN."""
        e = self.n_segments
        cols = [
            c.ates if c.ates is not None else jnp.full((e,), jnp.nan, jnp.float32)
            for c in self.columns
        ]
        return jnp.stack(cols, axis=1)

    def failures(self) -> Tuple[Tuple[int, str], ...]:
        """(column index, error) for every failed column."""
        return tuple((i, c.error) for i, c in enumerate(self.columns) if c.failed)

    def summary(self) -> str:
        """Human-readable panel overview (shape, validity, failures)."""
        ok = self.ok()
        head = f"EffectPanel: {self.n_segments} segments x {self.n_columns} columns"
        if self.segment_key:
            head += f" (segment_key={self.segment_key!r})"
        lines = [
            head,
            f"rows/segment: min {int(self.counts.min())}, "
            f"max {int(self.counts.max())}; "
            f"valid cells {int(ok.sum())}/{ok.size}",
            "-" * 60,
        ]
        table = self.ate_table()
        for j, col in enumerate(self.columns):
            if col.failed:
                lines.append(f"[{j}] {col.estimator}: FAILED ({col.error})")
                continue
            ates = table[:, j]
            good = ok[:, j]
            denom = jnp.maximum(good.sum(), 1)
            mean = float(jnp.where(good, ates, 0.0).sum() / denom)
            tag = " (shared nuisances)" if col.shared_nuisance else ""
            if col.aligned is False:
                tag += " (misaligned ingest: tolerance regime)"
            lines.append(
                f"[{j}] {col.estimator} p_phi={col.cfg.cate_features}: "
                f"mean ATE {mean:+.4f} over {int(good.sum())} segments{tag}"
            )
        return "\n".join(lines)

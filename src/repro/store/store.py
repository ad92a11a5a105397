"""MomentStore: the persistent, incrementally-updatable effect store.

Lifecycle::

    store = MomentStore(spec, n_features=p, key=key)
    store.ingest(X=day0.X, y=day0.y, t=day0.t, segment_ids=sids0)
    panel_v1 = store.refresh()            # EffectPanel, O(p³) per cell
    store.save(manager)                   # versioned snapshot (v1)
    store.ingest(X=day1.X, ...)           # one pass over ONLY new rows
    panel_v2 = store.refresh()
    store.restore(manager, step=1)        # rollback / hot-swap

A trailing window of days (``window=Window(days, day_rows)``) makes
each ingest one day: the day fills a slot of its own, and once the
window is full it retires the oldest day (its slot is overwritten).
A refresh then estimates over the window's rows only::

    store = MomentStore(spec, n_features=p, key=key,
                        window=Window(days=28, day_rows=499_712))
    store.ingest(X=day.X, y=day.y, t=day.t, segment_ids=day.sids)
    panel = store.refresh()               # over the last 28 days

Contracts (certified by tests/test_store.py and
tests/test_store_window.py):

  * **Bitwise ingest invariance** (no window) — at canonical
    row-blocked shapes (``cfg.row_block = R > 0``, every ingest except
    the last a multiple of R), any partition of the rows into ingest
    blocks yields bit-identical accumulators AND a bit-identical
    refreshed panel to the single-ingest full rebuild.  This follows
    from the fixed-order block-fold of ``moments.blocked_reduce`` seeded
    with the standing accumulator (``init=``) plus the index-keyed fold
    assignment below.  Misaligned ingests and ``row_block = 0`` remain
    correct but only tolerance-equal; alignment is tracked PER COLUMN
    (``store.column_aligned``, plus each refreshed ``ColumnResult``'s
    ``aligned`` flag — one misaligned ingest into one column never
    downgrades a neighbor's reported regime), with ``store.aligned``
    as the all-columns rollup.
  * **Windowed refresh** — with a window, every column's estimate is
    the estimand over the rows of the days the window holds, exactly
    as defined (tolerance-equal to a plain reference over those rows),
    and its bits do not depend on where in the ring the days sit: the
    day slots and the rows are read oldest day first.  Ridge-only
    columns solve from the summed day moments (path ``moments``); a
    column with a logistic treatment re-reads the window's rows, which
    the store keeps on the device (path ``rows``): the segmented sweep's
    stages (``sweep.segmented.segmented_stages``), so its cost is
    O(window rows × MM steps), not O(p³).
  * **Streaming-stable folds** — a row's fold is
    ``randint(fold_in(column_key, global_row_index), k)``: it depends
    only on the row's global arrival index, never on rows that arrive
    later (``crossfit.fold_ids``'s balanced permutation depends on
    total n and would reshuffle history on every ingest).
  * **Coverage gate** — ``store_supported`` admits the all-ridge
    continuous-treatment DML and OrthoIV families, whose estimates are
    exact functionals of the stored moments, and, in a windowed store,
    DML with a ridge visit model and a logistic treatment model
    (``discrete_treatment``).  Unsupported columns are fault-isolated:
    they land as failed ``ColumnResult``s with the gate's reason, never
    an exception.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import CausalConfig
from repro.core.final_stage import cate_basis
from repro.core.registry import EstimatorSpec, get_spec
from repro.obs.trace import maybe_span
from repro.store import stats as store_stats
from repro.store.solve import refresh_column, refresh_rows
from repro.store.stats import ColumnLayout
from repro.sweep.panel import ColumnResult, EffectPanel
from repro.sweep.spec import SweepSpec

Array = jax.Array
_F32 = jnp.float32


def store_supported(rspec: EstimatorSpec, cfg: CausalConfig,
                    windowed: bool = False) -> Tuple[bool, str]:
    """Gate: can this column be refreshed exactly by the store?

    Returns ``(ok, reason)``.  Admitted: the DML family and the
    OrthoIV family with all-ridge nuisances and continuous treatment —
    every statistic they need is a contraction of the store's Gram
    accumulators — and, when the store keeps a window of rows
    (``windowed``), the DML family with a ridge visit model and a
    logistic treatment model on a binary treatment, refreshed from the
    rows.  Excluded: logistic nuisances without a window (per-iteration
    data passes over rows the store does not keep), DRLearner/DRIV/
    metalearners (per-row pseudo-outcomes and clipped propensities are
    not Gram-additive).
    """
    if rspec.name.startswith("dml") or rspec.name.startswith("orthoiv"):
        iv = rspec.needs_instrument
        if (windowed and not iv and cfg.discrete_treatment
                and cfg.nuisance_y == "ridge"
                and cfg.nuisance_t == "logistic"):
            return True, ""
        if cfg.discrete_treatment:
            return False, (f"store: {rspec.name} with discrete_treatment "
                           "needs a logistic propensity (per-iteration "
                           "data passes): give the store a window, or use "
                           "discrete_treatment=False with nuisance_t='ridge'")
        for field, kind in (("nuisance_y", cfg.nuisance_y),
                            ("nuisance_t", cfg.nuisance_t)) + (
                                (("nuisance_z", cfg.nuisance_z),) if iv
                                else ()):
            if kind != "ridge":
                return False, (f"store: {rspec.name} requires "
                               f"{field}='ridge' (got {kind!r}) — only "
                               "ridge normal equations are exact "
                               "functionals of the stored Grams")
        return True, ""
    return False, (f"store: {rspec.name} builds per-row pseudo-outcomes/"
                   "propensities (not Gram-additive); supported families: "
                   "dml*, orthoiv* with all-ridge nuisances")


@dataclasses.dataclass(frozen=True)
class Window:
    """A trailing window of ``days`` days, each of at most ``day_rows``
    rows: one ingest is one day, a shorter day is padded to
    ``day_rows``, and the day after a full window retires the oldest."""

    days: int
    day_rows: int

    def __post_init__(self):
        if self.days < 1 or self.day_rows < 1:
            raise ValueError(f"store: a window needs days >= 1 and "
                             f"day_rows >= 1, got {self}")


def _basis_width(p: int, n_features: int) -> int:
    """Width of ``cate_basis(X, n_features)`` for X with p columns."""
    return 1 if n_features <= 1 else 1 + min(n_features - 1, p)


@dataclasses.dataclass
class _Column:
    name: str
    cfg: CausalConfig
    rspec: EstimatorSpec
    layout: Optional[ColumnLayout]
    state: Optional[store_stats.State]
    error: Optional[str]
    aligned: bool = True  # per-column: no misaligned ingest yet


class MomentStore:
    """Per-(segment, fold) sufficient-statistics store over a SweepSpec.

    ``n_features`` fixes the X width up front so every accumulator (and
    the checkpoint template) exists before the first row arrives.
    ``key`` roots the fold-assignment lineage (column i uses
    ``fold_in(key, i)``, mirroring the sweep's ``column_keys``).
    ``data_mesh`` (runtime.distributed.DataMesh) row-shards each
    ingest pass across ("hosts", "devices"): the sharded blocked
    reduction seeds the SAME ordered left fold, so aligned-ingest
    bitwise invariance carries over unchanged in "ordered" mode.
    ``window`` (a ``Window``) keeps a trailing window of days instead
    of all history; a windowed store runs on one device (no
    ``data_mesh``), and each of its logistic columns keeps the window's
    rows on the device, about 100 bytes a row.
    """

    def __init__(self, spec: SweepSpec, n_features: int,
                 key: Optional[Array] = None, *, tracer=None,
                 data_mesh=None, window: Optional[Window] = None):
        if window is not None and data_mesh is not None:
            raise ValueError("store: a windowed store runs on one device; "
                             "give it no data_mesh")
        self.spec = spec
        self.n_features = int(n_features)
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self.tracer = tracer
        self.data_mesh = data_mesh
        self.window = window
        self.n_total = 0
        self.n_ingests = 0
        self.version = 0
        self.seg_counts = jnp.zeros((spec.n_segments,), _F32)
        # windowed: days ingested so far (the next day's slot is
        # n_days % days, the oldest day once the window is full), the
        # real rows each slot holds, and each slot's rows per segment
        self.n_days = 0
        self.day_held: List[int] = [0] * (window.days if window else 0)
        self.seg_days = (jnp.zeros((window.days, spec.n_segments), _F32)
                         if window else None)
        self._cols: List[_Column] = []
        self._jit_cache: Dict[Any, Any] = {}
        for name, cfg in spec.columns:
            rspec = get_spec(name)
            ok, reason = store_supported(rspec, cfg, window is not None)
            if not ok:
                self._cols.append(_Column(name, cfg, rspec, None, None,
                                          reason))
                continue
            layout = ColumnLayout(
                p=self.n_features,
                pf=_basis_width(self.n_features, cfg.cate_features),
                k=cfg.n_folds,
                iv=rspec.needs_instrument,
                rows=cfg.nuisance_t == "logistic",
            )
            state = store_stats.init_state(
                layout, spec.n_segments * layout.k,
                *((window.days, window.day_rows) if window else ()))
            self._cols.append(_Column(name, cfg, rspec, layout, state,
                                      None))

    @property
    def days_held(self) -> int:
        """Days the window holds now (0 without a window)."""
        return min(self.n_days, len(self.day_held))

    @property
    def resident_rows(self) -> int:
        """Real rows the window holds now (0 without a window)."""
        return sum(self.day_held)

    def _path(self) -> str:
        """``rows`` when a column re-reads the window's rows, else
        ``moments``."""
        return "rows" if "rows" in map(_col_path, self._cols) else "moments"

    # ------------------------------------------------------------------
    # Alignment regime (per column — one misaligned ingest into one
    # column must not downgrade its neighbors' reported regime)
    # ------------------------------------------------------------------
    @property
    def column_aligned(self) -> Tuple[Optional[bool], ...]:
        """Per-column alignment: True = every ingest of that column
        ended on its ``row_block`` boundary (bitwise-ingest regime),
        False = tolerance regime, None = unsupported column."""
        return tuple(None if c.layout is None else c.aligned
                     for c in self._cols)

    @property
    def aligned(self) -> bool:
        """Store-wide rollup: every supported column still bitwise."""
        return all(c.aligned for c in self._cols if c.layout is not None)

    # ------------------------------------------------------------------
    # Fold lineage
    # ------------------------------------------------------------------
    def column_key(self, col_index: int) -> Array:
        """The fold-assignment key of column ``col_index``."""
        return jax.random.fold_in(self.key, col_index)

    def fold_assignment(self, col_index: int, start: int, n: int) -> Array:
        """Folds of global rows [start, start+n) for one column —
        index-keyed, so a row's fold never depends on later arrivals."""
        col = self._cols[col_index]
        if col.layout is None:
            raise ValueError(col.error)
        return _row_folds(self.column_key(col_index), start, n,
                          col.layout.k)

    # ------------------------------------------------------------------
    # Ingest
    # ------------------------------------------------------------------
    def ingest(self, *, X: Array, y: Array, t: Array, segment_ids: Array,
               z: Optional[Array] = None) -> "MomentStore":
        """Fold a new row block into every supported column's cells.

        One fused/blocked pass per column over ONLY the new rows.
        Empty blocks are exact no-ops on the accumulators (the version
        still advances).  With a window the block is one day of at most
        ``window.day_rows`` rows, written into its own slot, and the
        oldest day retires once the window is full.  Returns ``self``.
        """
        n = int(X.shape[0])
        if X.ndim != 2 or X.shape[1] != self.n_features:
            raise ValueError(f"store: X must be (n, {self.n_features}), "
                             f"got {X.shape}")
        needs_z = any(c.layout is not None and c.layout.iv
                      for c in self._cols)
        if needs_z and z is None:
            raise ValueError("store: spec has instrumented columns; "
                             "ingest requires z")
        if self.window is not None:
            return self._ingest_day(n, X, y, t, segment_ids, z)
        with maybe_span(self.tracer, "store.ingest", cat="store",
                        rows=n, version=self.version + 1,
                        days_held=0, resident_rows=0, path="moments"):
            if n:
                for i, col in enumerate(self._cols):
                    if col.layout is None:
                        continue
                    rb = col.cfg.row_block
                    if rb > 0 and self.n_total % rb != 0:
                        # prior ingests broke THIS column's block
                        # alignment: still correct, but its bitwise
                        # contract degrades to tolerance from here on
                        # (columns with a different row_block keep
                        # their own regime)
                        col.aligned = False
                    fn = self._ingest_fn(i)
                    args = (col.state, X, t, y, segment_ids,
                            jnp.uint32(self.n_total),
                            self.column_key(i))
                    col.state = fn(*args, z) if col.layout.iv else fn(*args)
                self.seg_counts = self.seg_counts + _seg_counts(
                    segment_ids, self.spec.n_segments)
                self.n_total += n
            self.version += 1
            self.n_ingests += 1
        self._count_ingest(n, retired=False)
        return self

    def _ingest_day(self, n, X, y, t, segment_ids, z) -> "MomentStore":
        """One day into the window: pad it to ``day_rows`` (cohort -1),
        write it into slot ``n_days % days``, retiring the day there."""
        w = self.window
        if n > w.day_rows:
            raise ValueError(f"store: a day holds at most {w.day_rows} rows "
                             f"(window.day_rows), got {n}")
        slot = self.n_days % w.days
        retired = self.n_days >= w.days
        held = self.day_held[:slot] + [n] + self.day_held[slot + 1:]
        pad = w.day_rows - n
        if pad:
            def _pad(a, value=0):
                a = jnp.asarray(a)
                return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                               constant_values=value)
            X, y, t = _pad(X), _pad(y), _pad(t)
            segment_ids = _pad(segment_ids, -1)
            z = None if z is None else _pad(z)
        with maybe_span(self.tracer, "store.ingest", cat="store", rows=n,
                        version=self.version + 1,
                        days_held=min(self.n_days + 1, w.days),
                        resident_rows=sum(held), path=self._path()):
            slot_t = jnp.int32(slot)
            for i, col in enumerate(self._cols):
                if col.layout is None:
                    continue
                fn = self._ingest_fn(i)
                args = (col.state, X, t, y, segment_ids,
                        jnp.uint32(self.n_total), self.column_key(i), slot_t)
                col.state = fn(*args, z) if col.layout.iv else fn(*args)
            self.seg_days, self.seg_counts = self._window_counts_fn()(
                self.seg_days, segment_ids, slot_t)
            self.n_total += n
            self.n_days += 1
            self.day_held = held
            self.version += 1
            self.n_ingests += 1
        self._count_ingest(n, retired=retired)
        return self

    def _count_ingest(self, n: int, *, retired: bool) -> None:
        if self.tracer is None:
            return
        m = self.tracer.metrics
        m.counter("store.ingests").inc()
        m.counter("store.ingest.rows").inc(n)
        m.counter("store.days_retired").inc(int(retired))
        m.gauge("store.version").set(self.version)
        m.gauge("store.resident_rows").set(self.resident_rows)

    def _window_counts_fn(self):
        """Jitted: write a day's rows per segment into its slot, and sum
        the slots oldest first (the slot is traced, so no day
        recompiles)."""
        fn = self._jit_cache.get("window_counts")
        if fn is None:
            E, days = self.spec.n_segments, self.window.days

            def _run(seg_days, sids, slot):
                seg_days = seg_days.at[slot].set(_seg_counts(sids, E))
                return seg_days, store_stats.window_sum(
                    seg_days, (slot + 1) % days)

            fn = jax.jit(_run)
            self._jit_cache["window_counts"] = fn
        return fn

    def _ingest_fn(self, col_index: int):
        col = self._cols[col_index]
        cfg, layout = col.cfg, col.layout
        ck = ("ingest", cfg, self.spec.n_segments, layout)
        fn = self._jit_cache.get(ck)
        if fn is not None:
            return fn
        n_cells = self.spec.n_segments * layout.k
        dm = self.data_mesh
        if self.window is not None:
            def _day(state, X, t, y, sids, start, col_key, slot, z=None):
                folds = _row_folds(col_key, start, X.shape[0], layout.k)
                return store_stats.ingest_day(
                    layout, state, slot, X, t, y, z,
                    cate_basis(X, cfg.cate_features), sids.astype(jnp.int32),
                    folds, n_cells, row_block=cfg.row_block,
                    strategy=cfg.row_block_strategy)

            # the day slots and the ring are updated in place
            fn = jax.jit(_day, donate_argnums=(0,))
            self._jit_cache[ck] = fn
            return fn

        def _run(state, X, t, y, sids, start, col_key, z=None):
            folds = _row_folds(col_key, start, X.shape[0], layout.k)
            comb = sids.astype(jnp.int32) * layout.k + folds
            phi = cate_basis(X, cfg.cate_features)
            if dm is not None:
                # Activate at trace time: blocked_reduce inside
                # ingest_cells routes each moment pass through
                # dist_reduce on the row mesh.  The per-instance
                # _jit_cache keeps mesh/plain traces separate.
                from repro.runtime.distributed import use_data_mesh

                with use_data_mesh(dm):
                    return store_stats.ingest_cells(
                        layout, state, X, t, y, z, phi, comb, n_cells,
                        row_block=cfg.row_block,
                        strategy=cfg.row_block_strategy)
            return store_stats.ingest_cells(
                layout, state, X, t, y, z, phi, comb, n_cells,
                row_block=cfg.row_block, strategy=cfg.row_block_strategy)

        fn = jax.jit(_run)
        self._jit_cache[ck] = fn
        return fn

    # ------------------------------------------------------------------
    # Refresh
    # ------------------------------------------------------------------
    def refresh(self) -> EffectPanel:
        """Re-solve every column and emit the refreshed ``EffectPanel``:
        from its accumulators (no data pass), or, for a windowed
        logistic column, from the window's rows."""
        path = self._path()
        with maybe_span(self.tracer, "store.refresh", cat="store",
                        version=self.version, n_total=self.n_total,
                        days_held=self.days_held,
                        resident_rows=self.resident_rows, path=path):
            columns = []
            tag = (f"store:v{self.version}",)
            start = (jnp.int32(self.n_days % self.window.days)
                     if self.window is not None else None)
            for i, col in enumerate(self._cols):
                if col.layout is None:
                    columns.append(ColumnResult(
                        estimator=col.name, cfg=col.cfg, key_index=i,
                        error=col.error))
                    continue
                fn = self._refresh_fn(i)
                out = fn(col.state) if start is None else fn(col.state, start)
                columns.append(ColumnResult(
                    estimator=col.name, cfg=col.cfg,
                    thetas=out["theta"], ates=out["ate"], ses=out["se"],
                    beta_y=out.get("beta_y"), beta_t=out.get("beta_t"),
                    cell_rows=out.get("cell_rows"), key_index=i,
                    events=tag + (_col_path(col),), aligned=col.aligned))
            panel = EffectPanel(columns=tuple(columns),
                                counts=self.seg_counts,
                                n_segments=self.spec.n_segments,
                                segment_key=self.spec.segment_key)
        if self.tracer is not None:
            m = self.tracer.metrics
            m.counter("store.refreshes").inc()
            for col in self._cols:
                if col.layout is None:
                    continue
                m.counter(f"store.refresh.path[{_col_path(col)}]").inc()
                if col.layout.rows:
                    m.counter("store.refresh.mm_steps").inc(
                        2 * col.cfg.newton_iters)
        return panel

    def _refresh_fn(self, col_index: int):
        col = self._cols[col_index]
        cfg, layout = col.cfg, col.layout
        ck = ("refresh", cfg, self.spec.n_segments, layout)
        fn = self._jit_cache.get(ck)
        if fn is not None:
            return fn
        E = self.spec.n_segments
        if self.window is None:
            fn = jax.jit(lambda state: refresh_column(
                layout, state, E, ridge_lambda=cfg.ridge_lambda))
        elif not layout.rows:
            def _moments(state, start):
                summed = {key: store_stats.window_sum(v, start)
                          for key, v in state.items()}
                return refresh_column(layout, summed, E,
                                      ridge_lambda=cfg.ridge_lambda)

            fn = jax.jit(_moments)
        else:
            day_rows = self.window.day_rows

            def _rows(state, start):
                return refresh_rows(layout, cfg, state, start, E, day_rows)

            fn = jax.jit(_rows)
        self._jit_cache[ck] = fn
        return fn

    # ------------------------------------------------------------------
    # Versioned snapshots (checkpoint/)
    # ------------------------------------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        """The checkpointable pytree: segment counts + per-supported-
        column accumulators (keyed by column index); a windowed store's
        day slots of segment counts, and in its columns the day slots
        and the ring of rows."""
        d: Dict[str, Any] = {"seg_counts": self.seg_counts}
        if self.window is not None:
            d["seg_days"] = self.seg_days
        for i, col in enumerate(self._cols):
            if col.state is not None:
                d[f"col{i}"] = col.state
        return d

    def _meta(self) -> Dict[str, Any]:
        return {
            "n_total": self.n_total,
            "n_ingests": self.n_ingests,
            "aligned": self.aligned,
            "column_aligned": list(self.column_aligned),
            "n_features": self.n_features,
            "n_segments": self.spec.n_segments,
            "segment_key": self.spec.segment_key,
            "columns": [c.name for c in self._cols],
            "window": (None if self.window is None else
                       [self.window.days, self.window.day_rows]),
            "n_days": self.n_days,
            "day_held": list(self.day_held),
        }

    def save(self, manager, *, metric: Optional[float] = None) -> int:
        """Snapshot the store at its current version through a
        ``checkpoint.CheckpointManager`` (atomic tmp+rename).  Returns
        the step (= version) written."""
        manager.save(self.version, self.state_dict(), metric=metric,
                     extra=self._meta())
        return self.version

    def restore(self, manager, *, step: Optional[int] = None
                ) -> "MomentStore":
        """Hot-swap/rollback: replace the accumulators with snapshot
        ``step`` (latest if None).  Spec provenance is checked so a
        checkpoint from a different column set fails loudly."""
        from repro.checkpoint.manager import restore_tree

        arrays, meta = manager.load(step=step)
        extra = meta.get("extra", {})
        want = [c.name for c in self._cols]
        if extra.get("columns") != want:
            raise ValueError(
                f"store: checkpoint columns {extra.get('columns')} do not "
                f"match this spec's {want}")
        if extra.get("n_features") != self.n_features:
            raise ValueError(
                f"store: checkpoint n_features {extra.get('n_features')} "
                f"!= {self.n_features}")
        want_w = (None if self.window is None else
                  [self.window.days, self.window.day_rows])
        if extra.get("window") != want_w:
            raise ValueError(
                f"store: checkpoint window {extra.get('window')} != this "
                f"store's {want_w}")
        state = restore_tree(self.state_dict(), arrays)
        self.seg_counts = state["seg_counts"]
        if self.window is not None:
            self.seg_days = state["seg_days"]
            self.n_days = int(extra["n_days"])
            self.day_held = [int(h) for h in extra["day_held"]]
        for i, col in enumerate(self._cols):
            if col.state is not None:
                col.state = state[f"col{i}"]
        self.version = int(meta["step"])
        self.n_total = int(extra.get("n_total", 0))
        self.n_ingests = int(extra.get("n_ingests", 0))
        # per-column flags when present; older snapshots carried only
        # the store-wide bool, which broadcasts conservatively
        col_aligned = extra.get(
            "column_aligned",
            [bool(extra.get("aligned", True))] * len(self._cols))
        for col, flag in zip(self._cols, col_aligned):
            if col.layout is not None:
                col.aligned = bool(flag)
        return self


def _col_path(col: _Column) -> str:
    """How a column refreshes: ``rows`` (re-reads the window's rows),
    ``moments`` (solves from the stored Grams), or ``none``."""
    if col.layout is None:
        return "none"
    return "rows" if col.layout.rows else "moments"


def _row_folds(col_key: Array, start, n: int, k: int) -> Array:
    idx = jnp.asarray(start, jnp.uint32) + jnp.arange(n, dtype=jnp.uint32)
    keys = jax.vmap(lambda i: jax.random.fold_in(col_key, i))(idx)
    return jax.vmap(
        lambda kk: jax.random.randint(kk, (), 0, k))(keys).astype(jnp.int32)


def _seg_counts(sids: Array, n_segments: int) -> Array:
    return jax.ops.segment_sum(jnp.ones((sids.shape[0],), _F32),
                               sids.astype(jnp.int32),
                               num_segments=n_segments)

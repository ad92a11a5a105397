"""The sweep engine: estimate E segments × C estimator-configs as
batched programs instead of a Python loop.

Execution model
---------------
Each cell of the grid is a *masked weighted single fit*: the segment
mask enters the estimator exactly where bootstrap resampling weights do
(``w`` of the registry's ``weighted_fit`` closures), so per-segment
sufficient statistics stream through ``core.moments`` — no per-segment
data copies are ever gathered.  Cells are built from the same
replicate-invariant closure family the bootstrap replicates run, so the
certified serial ≡ vmap bit-identity contract transfers verbatim: at
the canonical row-blocked shapes the panel is BITWISE identical to a
Python loop of the same single fits (``serial_loop``, asserted by
tests/test_sweep.py).

Scheduling
----------
The (segment × config) cell axis dispatches through the task runtime
(``runtime.map``), inheriting memory-aware chunking
(``CausalConfig.sweep_chunk`` / ``runtime_chunk`` / the HLO-probed
budget) and the per-chunk backend-downgrade ladder.  Replicate CIs add
the bootstrap axis through ``runtime.map_product`` — (cell × replicate)
flattened onto ONE batched program, subdivided by the same scheduler.

Cost sharing
------------
Two layers of reuse on top of the cell grid:

  * columns that differ only in final stage (same
    ``registry.nuisance_signature``) share one residual pass per
    segment (``spec.residual_fit`` / ``spec.final_fit``);
  * ``mode="segmented"`` (DML family) collapses the per-cell fold Grams
    into ONE segment×fold-segmented pass over the data via the
    leave-one-out identity — the many-effects-cheaply execution, ~10x
    over the loop at E=64 (see repro.sweep.segmented).

Fault isolation
---------------
A failing column (bad config, nuisance build error, dispatch failure
past the downgrade ladder) is recorded on its ``ColumnResult.error``;
every other column keeps its estimates.  Zero-row segments yield
flagged (``ok = False``) finite cells, never a crash.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import CausalConfig
from repro.core.estimator import resolve_scheme
from repro.core.final_stage import cate_basis
from repro.core.registry import EstimatorSpec, get_spec, nuisance_signature
from repro.obs.metrics import default_registry
from repro.obs.trace import layer_span
from repro.sweep.panel import ColumnResult, EffectPanel
from repro.sweep.spec import SweepSpec, segment_counts

_BOOT_SCHEMES = ("bootstrap", "multiplier", "bayesian")


def column_keys(key: jax.Array, col_index: int, n_segments: int) -> jax.Array:
    """Per-cell fit keys: fold_in(fold_in(base, column), segment) — any
    single cell can be replayed alone, bit-identically (the lineage
    property bootstrap replicates already carry)."""
    ck = jax.random.fold_in(key, col_index)
    return jax.vmap(lambda s: jax.random.fold_in(ck, s))(
        jnp.arange(n_segments, dtype=jnp.uint32)
    )


def _segment_mask(sids: jax.Array, sid) -> jax.Array:
    return (sids == sid).astype(jnp.float32)


def _runtime(cfg: CausalConfig, executor, tracer=None, data_mesh=None):
    from repro.runtime import as_runtime

    return as_runtime(
        executor if executor is not None else cfg.inference_executor,
        memory_budget=cfg.runtime_memory_budget,
        chunk=cfg.sweep_chunk or cfg.runtime_chunk,
        max_retries=cfg.runtime_max_retries,
        data_mesh=data_mesh,
        tracer=tracer,
    )


def _make_masked_cell(cell):
    def _masked_cell(xs, d):
        w = _segment_mask(d["sids"], xs["sid"])
        return cell(xs["key"], w, d)

    return _masked_cell


def _make_masked_resid(resid_fn):
    def _masked_resid(xs, d):
        w = _segment_mask(d["sids"], xs["sid"])
        return resid_fn(xs["key"], w, d)

    return _masked_resid


def _make_masked_final(final_fn):
    def _masked_final(xs, d):
        w = _segment_mask(d["sids"], xs["sid"])
        return final_fn(xs["resid"], w, d)

    return _masked_final


def _make_replicate_cell(cell, scheme: str):
    from repro.inference.bootstrap import bootstrap_weights

    def _rep_cell(xo, kb, d):
        # per-(cell, replicate) randomness: the replicate key folds in
        # the segment id, then splits into (resample, fit) keys
        kcell = jax.random.fold_in(kb, xo["sid"].astype(jnp.uint32))
        kw, kfit = jax.random.split(kcell)
        w = _segment_mask(d["sids"], xo["sid"]) * bootstrap_weights(
            kw, d["sids"].shape[0], scheme
        )
        out = cell(kfit, w, d)
        return {"theta": out["theta"], "ate": out["ate"]}

    return _rep_cell


def _column_data(base_data: Dict[str, Any], cfg: CausalConfig) -> Dict[str, Any]:
    d = dict(base_data)
    d["phi"] = cate_basis(base_data["X"], cfg.cate_features)
    return d


def _column_ci(cell, cfg: CausalConfig, rt, xs, data, key, col_index: int):
    """(cell × replicate) bootstrap draws through map_product: the two
    parallel axes flatten onto one replicate axis, chunked and
    downgraded by the scheduler like any other replicate program."""
    from repro.inference.bootstrap import replicate_keys

    # non-resampling methods (jackknife) have no per-cell replicate
    # program; they substitute the pairs bootstrap, and the column's
    # events carry a "ci:<scheme>" tag so the substitution is visible
    method = cfg.inference if cfg.inference in _BOOT_SCHEMES else "bootstrap"
    scheme = resolve_scheme(method)
    ci_key = jax.random.fold_in(jax.random.fold_in(key, col_index), 0x0B00)
    bkeys = replicate_keys(ci_key, cfg.n_bootstrap)
    rep_cell = _make_replicate_cell(cell, scheme)
    draws = rt.map_product(rep_cell, xs, bkeys, data, label="sweep:ci")
    a = cfg.alpha
    return dict(
        ci_lo=jnp.quantile(draws["ate"], a / 2.0, axis=1),
        ci_hi=jnp.quantile(draws["ate"], 1.0 - a / 2.0, axis=1),
        replicates=draws["theta"],
        ci_scheme=scheme,
    )


def _count_column(path: str, cfg: CausalConfig, n_segments: int,
                  mm_steps: int = 0) -> None:
    """Counters on the process registry, per column run: the path it
    took (``sweep.path[segmented|cells|shared]``), its (segment, fold)
    nuisance cells (``sweep.cells``, E·K) and its MM logistic steps
    (``sweep.mm_steps``, segmented columns with a binary treatment)."""
    reg = default_registry()
    reg.counter(f"sweep.path[{path}]").inc()
    reg.counter("sweep.cells").inc(n_segments * cfg.n_folds)
    if mm_steps:
        reg.counter("sweep.mm_steps").inc(mm_steps)


def _events(rt, start_total: int = 0) -> Tuple[str, ...]:
    # EventLog.since is drop-safe: start_total is an events.total
    # checkpoint, valid even if the ring dropped older entries
    return tuple(f"{e.action}:{e.backend}" for e in rt.events.since(start_total))


def _want_ci(cfg: CausalConfig, with_ci: Optional[bool]) -> bool:
    if with_ci is not None:
        return bool(with_ci) and cfg.n_bootstrap > 0
    return cfg.inference not in ("none", "") and cfg.n_bootstrap > 0


# -- elastic per-column checkpoints (repro.checkpoint) ----------------------

_CKPT_SCHEMA = "sweep-column-v1"
_CKPT_ARRAYS = ("thetas", "ates", "ses", "ci_lo", "ci_hi", "replicates")


def _column_signature(name: str, cfg: CausalConfig, n_segments: int) -> str:
    """Provenance key a resumed column must match: same estimator, same
    frozen config (repr is stable for the dataclass), same grid height."""
    import hashlib

    return hashlib.sha1(
        f"{name}|{cfg!r}|{n_segments}".encode()
    ).hexdigest()[:16]


def _save_column(mgr, idx: int, col: ColumnResult, n_segments: int) -> None:
    """One checkpoint step per column (step = column index): the present
    result arrays + provenance meta.  Failed columns save too (the
    attempt is on record) but never restore — a resume recomputes them,
    which is the whole point: a lost shard costs ONE column."""
    state = {
        k: getattr(col, k)
        for k in _CKPT_ARRAYS
        if getattr(col, k) is not None
    }
    extra = {
        "schema": _CKPT_SCHEMA,
        "signature": _column_signature(col.estimator, col.cfg, n_segments),
        "estimator": col.estimator,
        "key_index": int(col.key_index),
        "shared_nuisance": bool(col.shared_nuisance),
        "events": list(col.events),
        "error": col.error,
        "aligned": col.aligned,
    }
    mgr.save(idx, state, extra=extra)


def _restore_column(
    mgr, idx: int, name: str, cfg: CausalConfig, n_segments: int
) -> Optional[ColumnResult]:
    """The saved ColumnResult for step ``idx``, or None when it is
    missing, provenance-mismatched (spec changed under the checkpoint
    dir), or errored (failed columns recompute on resume)."""
    if not mgr.has_step(idx):
        return None
    arrays, meta = mgr.load(step=idx)
    extra = meta.get("extra") or {}
    if extra.get("schema") != _CKPT_SCHEMA:
        return None
    if extra.get("signature") != _column_signature(name, cfg, n_segments):
        return None
    if extra.get("error"):
        return None
    kw = {k: jnp.asarray(arrays[k]) for k in _CKPT_ARRAYS if k in arrays}
    return ColumnResult(
        estimator=name,
        cfg=cfg,
        key_index=int(extra.get("key_index", idx)),
        shared_nuisance=bool(extra.get("shared_nuisance", False)),
        events=tuple(extra.get("events") or ()) + ("restored",),
        aligned=extra.get("aligned"),
        **kw,
    )


def _run_column(
    rspec: EstimatorSpec,
    cfg: CausalConfig,
    col_index: int,
    base_data,
    n_segments: int,
    key,
    executor,
    with_ci: Optional[bool],
    tracer=None,
    data_mesh=None,
) -> ColumnResult:
    """One column as E masked single-fit cells through the runtime."""
    cell = rspec.weighted_fit(cfg)
    data = _column_data(base_data, cfg)
    xs = {
        "key": column_keys(key, col_index, n_segments),
        "sid": jnp.arange(n_segments, dtype=jnp.int32),
    }
    rt = _runtime(cfg, executor, tracer, data_mesh)
    _count_column("cells", cfg, n_segments)
    with layer_span(
        rt.tracer, f"sweep.column[{col_index}]", cat="sweep",
        estimator=rspec.name, segments=n_segments,
    ):
        out = rt.map(_make_masked_cell(cell), xs, data, label=f"sweep:{rspec.name}")
        extra: Dict[str, Any] = {}
        if _want_ci(cfg, with_ci):
            extra = _column_ci(cell, cfg, rt, xs, data, key, col_index)
    ci_tag = ()
    if "ci_scheme" in extra:
        ci_tag = (f"ci:{extra['ci_scheme']}",)
    return ColumnResult(
        estimator=rspec.name,
        cfg=cfg,
        thetas=out["theta"],
        ates=out["ate"],
        ses=out.get("se"),
        ci_lo=extra.get("ci_lo"),
        ci_hi=extra.get("ci_hi"),
        replicates=extra.get("replicates"),
        key_index=col_index,
        events=_events(rt) + ci_tag,
    )


def _run_shared_group(
    rspec: EstimatorSpec,
    members: List[Tuple[int, CausalConfig]],
    base_data,
    n_segments: int,
    key,
    executor,
    with_ci: Optional[bool],
    tracer=None,
    data_mesh=None,
) -> List[Tuple[int, ColumnResult]]:
    """Columns differing only in final stage: ONE residual pass per
    segment (keyed on the first member's lineage), then a cheap
    final-stage map per column."""
    first_idx, cfg0 = members[0]
    resid_fn = rspec.residual_fit(cfg0)
    keys = column_keys(key, first_idx, n_segments)
    sid = jnp.arange(n_segments, dtype=jnp.int32)
    rt = _runtime(cfg0, executor, tracer, data_mesh)
    # the shared residual pass is group-fatal by design (every member
    # consumes it); everything after is isolated per member
    with layer_span(
        rt.tracer, f"sweep.group:{rspec.name}", cat="sweep",
        members=len(members), segments=n_segments,
    ):
        resids = rt.map(
            _make_masked_resid(resid_fn),
            {"key": keys, "sid": sid},
            dict(base_data),
            label=f"sweep:{rspec.name}:resid",
        )
    results = []
    for col_index, cfg in members:
        ev_start = rt.events.total
        try:
            col = _shared_member_column(
                rspec, cfg, first_idx, col_index, base_data, resids,
                keys, sid, rt, key, with_ci, ev_start
            )
        except Exception as err:  # noqa: BLE001 — one member must not
            # discard its siblings' already-computed columns
            col = ColumnResult(
                estimator=rspec.name, cfg=cfg, key_index=first_idx,
                shared_nuisance=col_index != first_idx, error=str(err)
            )
        results.append((col_index, col))
    return results


def _shared_member_column(
    rspec: EstimatorSpec,
    cfg: CausalConfig,
    first_idx: int,
    col_index: int,
    base_data,
    resids,
    keys,
    sid,
    rt,
    key,
    with_ci: Optional[bool],
    ev_start: int,
) -> ColumnResult:
    data = _column_data(base_data, cfg)
    _count_column("shared", cfg, sid.shape[0])
    with layer_span(
        rt.tracer, f"sweep.column[{col_index}]", cat="sweep",
        estimator=rspec.name, shared_nuisance=col_index != first_idx,
    ):
        out = rt.map(
            _make_masked_final(rspec.final_fit(cfg)),
            {"sid": sid, "resid": resids},
            data,
            label=f"sweep:{rspec.name}:final",
        )
        extra: Dict[str, Any] = {}
        if _want_ci(cfg, with_ci):
            # replicate refits reweight the nuisances, so CIs cannot
            # reuse the shared residuals — they run the full cell
            cell = rspec.weighted_fit(cfg)
            xs = {"key": keys, "sid": sid}
            extra = _column_ci(cell, cfg, rt, xs, data, key, first_idx)
    ci_tag = ()
    if "ci_scheme" in extra:
        ci_tag = (f"ci:{extra['ci_scheme']}",)
    return ColumnResult(
        estimator=rspec.name,
        cfg=cfg,
        thetas=out["theta"],
        ates=out["ate"],
        ses=out.get("se"),
        ci_lo=extra.get("ci_lo"),
        ci_hi=extra.get("ci_hi"),
        replicates=extra.get("replicates"),
        key_index=first_idx,
        shared_nuisance=col_index != first_idx,
        events=_events(rt, ev_start) + ci_tag,
    )


def _segmented_or_cells(
    rspec: EstimatorSpec,
    cfg: CausalConfig,
    col_index: int,
    base_data,
    n_segments: int,
    key,
    executor,
    with_ci: Optional[bool],
    tracer=None,
    data_mesh=None,
) -> ColumnResult:
    """mode="segmented" dispatch: the one-pass kernels where they apply,
    the plain cell path otherwise.  The segmented fast path stays
    single-host (its module-level jits would cache a mesh trace across
    unrelated sweeps); data_mesh applies to the cells fallback only."""
    from repro.sweep.segmented import segmented_column, segmented_supported

    if not segmented_supported(rspec, cfg):
        return _run_column(
            rspec, cfg, col_index, base_data, n_segments, key, executor,
            with_ci, tracer, data_mesh,
        )
    mm_steps = 2 * cfg.newton_iters if cfg.discrete_treatment else 0
    _count_column("segmented", cfg, n_segments, mm_steps)
    with layer_span(
        tracer, f"sweep.column[{col_index}]", cat="sweep",
        estimator=rspec.name, segmented=True,
    ):
        with layer_span(tracer, "sweep.segmented", cat="sweep",
                        segments=n_segments, folds=cfg.n_folds,
                        mm_steps=mm_steps):
            out = segmented_column(
                cfg, base_data, n_segments, jax.random.fold_in(key, col_index)
            )
        if tracer is not None:
            tracer.sync(out)
        # the (E, K) row counts come back to the host: the column
        # returns once its device work is done
        empty = int(jnp.sum(out["cell_rows"] == 0))
    default_registry().counter("sweep.empty_cells").inc(empty)
    return ColumnResult(
        estimator=rspec.name,
        cfg=cfg,
        thetas=out["theta"],
        ates=out["ate"],
        ses=out.get("se"),
        beta_y=out["beta_y"],
        beta_t=out["beta_t"],
        cell_rows=out["cell_rows"],
        key_index=col_index,
        events=("segmented",),
    )


def sweep(
    spec: SweepSpec,
    *,
    X: jax.Array,
    y: jax.Array,
    t: jax.Array,
    segment_ids: jax.Array,
    z: Optional[jax.Array] = None,
    key: Optional[jax.Array] = None,
    executor=None,
    mode: str = "cells",
    reuse: bool = True,
    with_ci: Optional[bool] = None,
    tracer=None,
    data_mesh=None,
    checkpoint=None,
    resume: bool = True,
    column_callback=None,
) -> EffectPanel:
    """Run the (segments × estimator-configs) grid as batched programs.

    mode="cells"      every cell is a masked weighted single fit —
                      bitwise identical to ``serial_loop`` at the
                      canonical row-blocked shapes (the default, and
                      the contract tests certify).
    mode="segmented"  DML-family columns collapse onto the one-pass
                      segment×fold Gram kernels (repro.sweep.segmented,
                      ~10x at E=64); unsupported columns fall back to
                      cells.
    reuse=True        columns sharing a nuisance signature share one
                      residual pass (cells mode).
    with_ci           None = per column from cfg.inference; True/False
                      forces replicate CIs on/off.  CIs are resampling
                      draws: a non-resampling cfg.inference (jackknife)
                      substitutes the pairs bootstrap, tagged
                      "ci:pairs" in the column's events.
    tracer            optional repro.obs.Tracer: every column (and
                      shared-nuisance group) opens a labelled span, and
                      the runtimes under it inherit the tracer — chunk
                      spans, metrics, and the cost audit nest inside.
                      None (the default) changes nothing.
    data_mesh         optional runtime.distributed.DataMesh: column
                      cells row-shard across ("hosts", "devices"), with
                      the shard_map → single-host ladder rung catching
                      lost shards — bitwise the single-host panel in
                      "ordered" mode (cells path; the segmented fast
                      path stays single-host).
    checkpoint        optional repro.checkpoint.CheckpointManager: each
                      column saves as checkpoint step = column index the
                      moment it settles (success OR error), so a killed
                      job — or a shard loss that exhausted the ladder —
                      costs at most the in-flight column on the next
                      run.  ``keep_latest`` is raised to cover the grid.
    resume            with ``checkpoint``: restore provenance-matching
                      completed columns (tagged "restored" in their
                      events) and recompute only missing/failed ones.
    column_callback   ``f(index, ColumnResult)`` called as each column
                      settles (including restored ones) — the event
                      stream hook of runtime.jobs.
    """
    if mode not in ("cells", "segmented"):
        raise ValueError(f"unknown sweep mode {mode!r} (cells | segmented)")
    key = key if key is not None else jax.random.PRNGKey(0)
    sids = segment_ids.astype(jnp.int32)
    n_seg = spec.n_segments
    base_data: Dict[str, Any] = {"X": X, "y": y, "t": t, "sids": sids}
    if z is not None:
        base_data["z"] = z
    counts = segment_counts(sids, n_seg)

    results: Dict[int, ColumnResult] = {}

    if checkpoint is not None:
        # retention must cover one step per column or early columns
        # would be pruned before the sweep finishes
        checkpoint.keep_latest = max(
            checkpoint.keep_latest, len(spec.columns) + 1
        )

    def record(idx: int, col: ColumnResult, *, save: bool = True) -> None:
        results[idx] = col
        if save and checkpoint is not None:
            _save_column(checkpoint, idx, col, n_seg)
        if column_callback is not None:
            column_callback(idx, col)

    restored: set = set()
    if checkpoint is not None and resume:
        for idx, (name, cfg) in enumerate(spec.columns):
            col = _restore_column(checkpoint, idx, name, cfg, n_seg)
            if col is not None:
                restored.add(idx)
                record(idx, col, save=False)

    # -- group columns: (estimator, nuisance signature) -----------------
    groups: Dict[Any, List[Tuple[int, CausalConfig]]] = {}
    order: List[Any] = []
    for idx, (name, cfg) in enumerate(spec.columns):
        if idx in restored:
            continue
        gk = (name, nuisance_signature(cfg))
        if gk not in groups:
            groups[gk] = []
            order.append(gk)
        groups[gk].append((idx, cfg))

    for gk in order:
        name = gk[0]
        members = groups[gk]
        try:
            rspec = get_spec(name)
            if rspec.weighted_fit is None:
                raise ValueError(f"estimator {name!r} has no weighted fit")
            if rspec.needs_instrument and z is None:
                raise ValueError(f"estimator {name!r} needs an instrument z")
        except Exception as err:  # noqa: BLE001 — isolated per column
            for idx, cfg in members:
                record(idx, ColumnResult(
                    estimator=name, cfg=cfg, key_index=idx, error=str(err)
                ))
            continue

        if mode == "segmented":
            for idx, cfg in members:
                try:
                    record(idx, _segmented_or_cells(
                        rspec, cfg, idx, base_data, n_seg, key, executor,
                        with_ci, tracer, data_mesh,
                    ))
                except Exception as err:  # noqa: BLE001
                    record(idx, ColumnResult(
                        estimator=name, cfg=cfg, key_index=idx, error=str(err)
                    ))
            continue

        shareable = (
            reuse
            and len(members) > 1
            and rspec.residual_fit is not None
            and rspec.final_fit is not None
        )
        try:
            if shareable:
                for idx, col in _run_shared_group(
                    rspec, members, base_data, n_seg, key, executor,
                    with_ci, tracer, data_mesh,
                ):
                    record(idx, col)
            else:
                for idx, cfg in members:
                    record(idx, _run_column(
                        rspec, cfg, idx, base_data, n_seg, key, executor,
                        with_ci, tracer, data_mesh,
                    ))
        except Exception as err:  # noqa: BLE001 — one column/group must
            # not poison the panel; the runtime ladder already retried
            for idx, cfg in members:
                if idx not in results:
                    record(idx, ColumnResult(
                        estimator=name, cfg=cfg, key_index=idx, error=str(err)
                    ))

    columns = tuple(results[i] for i in range(len(spec.columns)))
    return EffectPanel(
        columns=columns,
        counts=counts,
        n_segments=n_seg,
        segment_key=spec.segment_key,
    )


def serial_loop(
    estimator: str,
    cfg: CausalConfig,
    *,
    X: jax.Array,
    y: jax.Array,
    t: jax.Array,
    segment_ids: jax.Array,
    n_segments: int,
    z: Optional[jax.Array] = None,
    key: Optional[jax.Array] = None,
    col_index: int = 0,
) -> Dict[str, jax.Array]:
    """The reference baseline: a Python loop of masked single-estimator
    fits — one compiled program dispatched per cell, no cross-cell
    batching — with exactly the key lineage ``sweep()`` gives column
    ``col_index``.  The panel's cells mode is certified bitwise
    identical to this loop at the canonical row-blocked shapes; it is
    also the serial side of benchmarks/bench_sweep.py."""
    from repro.inference.executor import make_executor

    key = key if key is not None else jax.random.PRNGKey(0)
    rspec = get_spec(estimator)
    cell = rspec.weighted_fit(cfg)
    base_data: Dict[str, Any] = {
        "X": X,
        "y": y,
        "t": t,
        "sids": segment_ids.astype(jnp.int32),
    }
    if z is not None:
        base_data["z"] = z
    data = _column_data(base_data, cfg)
    xs = {
        "key": column_keys(key, col_index, n_segments),
        "sid": jnp.arange(n_segments, dtype=jnp.int32),
    }
    return make_executor("serial").map(_make_masked_cell(cell), xs, data)

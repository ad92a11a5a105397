"""repro.store — the persistent, incrementally-updatable effect store.

Every estimator in this repo bottoms out in Gram-additive sufficient
statistics; this package makes that additivity operational for the
daily-refresh workload.  A ``MomentStore`` keeps per-(segment, fold)
nuisance and final-stage moment accumulators for every column of a
``SweepSpec``; ``ingest`` folds each newly arrived row block into them
with one fused/blocked pass over only the new rows (history is never
re-read), and ``refresh`` re-solves thetas/SEs in O(p³) per cell and
emits a fresh ``EffectPanel``.  At canonical row-blocked shapes the
incremental path is *bitwise identical* to a full refit on the
concatenated data (the fixed-order block-fold contract), and versioned
snapshots ride through ``repro.checkpoint`` for hot-swap/rollback.
Coverage is gated by ``store_supported`` (all-ridge DML and OrthoIV
families; with a ``Window``, also DML with a logistic treatment model,
refreshed from the window's rows kept on the device); unsupported
columns fault-isolate as failed panel columns.  A ``Window`` of days
turns the store into a trailing-window daily refresh: each ingest is
one day and retires the oldest once the window is full.
"""

from repro.store.stats import ColumnLayout
from repro.store.store import MomentStore, Window, store_supported

__all__ = ["ColumnLayout", "MomentStore", "Window", "store_supported"]

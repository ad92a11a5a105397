"""Elastic sweeps: resume a segment sweep after a lost shard or a killed
process — the SPMD answer to Ray's "recover tasks from a failed machine"
(DESIGN.md §7).

The per-column checkpoint format is mesh-free, so a restart may run on
a different mesh than the one that saved.

Straggler note: within a compiled step there are no stragglers (lock-step
SPMD); a persistently slow pod is handled by dropping it through this
path.
"""
from __future__ import annotations

from repro.checkpoint.manager import CheckpointManager


def sweep_checkpoint_manager(directory: str, spec,
                             *, keep_best: int = 1) -> CheckpointManager:
    """CheckpointManager sized for a per-column sweep checkpoint
    (step = column index): retention must cover every column plus one
    in-flight save, or early columns get pruned before the sweep ends.
    ``sweep()`` applies the same floor defensively; creating the
    manager here makes the elastic entry point one call."""
    return CheckpointManager(directory,
                             keep_latest=len(spec.columns) + 1,
                             keep_best=keep_best)


def elastic_sweep(spec, *, directory: str, data_mesh=None, **sweep_kwargs):
    """Run (or resume) a sweep with per-column checkpointing.  A lost
    shard or a killed process costs at most the in-flight column:
    re-invoking with the same ``directory`` restores every completed
    column from disk and recomputes only the missing ones
    (sweep.engine's resume path, signature-checked per column).  ``data_mesh`` passes through to
    row-shard each column's moment passes."""
    from repro.sweep import sweep

    manager = sweep_checkpoint_manager(directory, spec)
    return sweep(spec, data_mesh=data_mesh, checkpoint=manager,
                 resume=True, **sweep_kwargs)

"""repro.kernels — Pallas TPU kernels (interpret-mode certified on CPU).

Each kernel directory ships ``kernel.py`` (the ``pl.pallas_call`` +
BlockSpec), ``ops.py`` (a jit'd dispatch wrapper with backend
selection), and ``ref.py`` (a pure-jnp oracle for tests).  The causal
workload's hot spot is the fused segment-Gram family (``seg_gram``),
reached from estimation code via
``CausalConfig.row_block_strategy="pallas"``: one kernel streams
``(block_n, p)`` tiles HBM→VMEM, runs the per-row builder in
registers, and accumulates per-segment augmented Grams — with a
``pallas → chunked → whole`` fallback ladder (counter-instrumented on
``repro.obs.metrics.default_registry``) for forms without a fused
builder.  ``residual_gram`` is the ``row_block=0`` final stage.
"""
# Each kernel directory ships kernel.py (pl.pallas_call + BlockSpec),
# ops.py (jit'd dispatch wrapper) and ref.py (pure-jnp oracle).
#
# Hot spots covered (see DESIGN.md §6):
#   residual_gram/    fused residualize->Gram for the DML final stage
#   seg_gram/         fused segment-Gram family (moments, sweep, MM step)

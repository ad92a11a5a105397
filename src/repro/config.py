"""Configuration dataclasses: the causal estimators' ``CausalConfig``
and the MLP nuisance's optimizer settings (``TrainConfig``).

Configs are frozen (hashable) so they can be closed over by jitted
functions and used as cache keys.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1_000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class CausalConfig:
    """DML estimator configuration (the paper's §5 case study)."""

    n_folds: int = 5
    nuisance_y: str = "ridge"  # ridge | logistic | mlp
    nuisance_t: str = "logistic"  # ridge | logistic | mlp
    final_stage: str = "linear"  # linear CATE: theta(x) = <beta, phi(x)>
    cate_features: int = 1  # phi(x) dims (1 => ATE-only / constant effect)
    ridge_lambda: float = 1e-3
    newton_iters: int = 16
    # --- streaming sufficient statistics (repro.core.moments) ---
    # 0 = whole-array moments (legacy einsum forms, one allocation);
    # R > 0 = lax.scan over row blocks of R — peak activation memory
    # drops from O(n·p) to O(R·p), so n can exceed a single allocation.
    # Chunked and whole evaluation of the SAME row_block are
    # bit-identical (see core/moments.py); different settings agree to
    # float reassociation only.
    row_block: int = 0
    # Blocked-evaluation strategy at row_block > 0: "chunked" streams
    # one lax.scan-sliced block at a time (bounded memory), "whole"
    # materializes every block partial at once.  The two are
    # bit-identical for equal row_block (the moments contract); the
    # knob exists so the conformance harness can assert that equality
    # at the ESTIMATOR level, and so perf work can trade memory for
    # fusion freedom without touching call sites.  "pallas" routes the
    # Gram-shaped forms through the fused mask→weight→residualize→
    # accumulate kernel family (repro.kernels.seg_gram: compiled
    # mosaic on TPU, a fused XLA scatter lowering on CPU, interpret
    # mode for certification); forms without a fused builder ladder
    # back to "chunked".  Parity with "chunked" is tolerance-certified
    # (≤1e-6 estimator-wide), not bitwise.
    row_block_strategy: str = "chunked"  # chunked | whole | pallas
    mlp_hidden: Tuple[int, ...] = (256, 256)
    mlp_steps: int = 200
    mlp_lr: float = 1e-3
    discrete_treatment: bool = True
    engine: str = "parallel"  # parallel (paper, C1) | sequential (EconML baseline)
    # --- instrumental variables (repro.core.iv: OrthoIV / DRIV) ---
    nuisance_z: str = "logistic"  # instrument model E[Z|X] (logistic | ridge | mlp)
    discrete_instrument: bool = True
    # DRIV clips the compliance denominator E[rt·rz|X] away from zero
    # (EconML's cov_clip); magnitude floor, sign-preserving.
    iv_cov_clip: float = 0.1
    # --- uncertainty quantification (repro.inference subsystem) ---
    inference: str = "bootstrap"  # bootstrap (pairs) | multiplier | jackknife | none
    n_bootstrap: int = 200        # B replicates (EconML BootstrapInference)
    alpha: float = 0.05           # CI level: 1 - alpha
    inference_executor: str = "vmap"  # serial | vmap | shard_map
    # --- task-graph runtime (repro.runtime) ---
    # Per-device peak-memory budget (bytes) for replicate batching: the
    # scheduler probes the lowered closure (launch.hlo_cost peak temps)
    # and streams the replicate axis in chunks that fit.  0 = unbounded
    # (one batched program, the legacy behavior).
    runtime_memory_budget: int = 0
    runtime_chunk: int = 0        # explicit chunk size; 0 = auto from budget
    runtime_max_retries: int = 2  # per-chunk backend-downgrade attempts
    # --- segment-parallel sweeps (repro.sweep) ---
    # Name of the cohort/segment column in the caller's frame — pure
    # provenance carried into EffectPanel summaries ("" = unsegmented);
    # the sweep engine itself takes the integer segment-id array.
    segment_key: str = ""
    # Max sweep cells batched per compiled program (the segment × config
    # axis); 0 defers to runtime_chunk / the memory model.  Bounds the
    # (cells, n) live mask/weight activations at industrial n.
    sweep_chunk: int = 0


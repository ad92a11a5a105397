"""Memory-aware replicate batching: how many replicates fit one device.

Ray sizes task placement by declared resources; XLA has no such
declaration, but the compiled program *is* inspectable: lowering the
vmapped replicate closure at a probe batch size yields the temporary
bytes the program needs: the compiler's own buffer assignment
(``memory_analysis().temp_size_in_bytes``, which counts the device's
tile padding — an (n, 1) f32 column takes n x 512 bytes on a TPU), and
never less than the largest temporary in the post-optimization HLO
(``launch.hlo_cost.peak_temp_bytes``).  Two probes (batch 1 and batch
``PROBE_CHUNK``) fit the affine model

    peak(c) ≈ base + slope · c

— ``base`` is the replicate-independent footprint (the shared data
tensors every replicate reads), ``slope`` the per-replicate increment
(the (c, k, n) weight tensors and fold-batched Gram stacks that grow
with the batch).  The scheduler then solves for the largest chunk whose
predicted peak stays under ``CausalConfig.runtime_memory_budget``, so
``n_bootstrap=2000`` at industrial n streams in chunks instead of
OOMing the one-big-vmap path.

The probe batches stay small: a probe that does not fit the device
fails to compile (and then the map runs unsized), so the model is
fitted where the program fits and extrapolated from there.  Probes
are compile-only (no execution) and cached per (closure, input
signature), so repeated ``map`` calls with the same closure lower at
most twice.  The caller owns the closure and decides how long it and
its probes live: the caches here hold it weakly.  ``DML`` keeps its
bootstrap closures for the estimator's life, so only its first fit
probes; a closure built per call is probed on every call.
"""

from __future__ import annotations

import dataclasses
import warnings
import weakref
from typing import Any, Optional, Tuple

import jax

from repro.launch.hlo_cost import cost_summary, peak_temp_bytes
from repro.obs.metrics import default_registry

PROBE_CHUNK = 2


@dataclasses.dataclass(frozen=True)
class MemoryModel:
    """Affine peak-memory model of one replicate chunk."""

    base: float  # replicate-independent bytes (shared data passes)
    slope: float  # incremental bytes per replicate in the batch

    def peak(self, chunk: int) -> float:
        return self.base + self.slope * max(chunk, 0)

    def max_chunk(self, budget_bytes: int, b: int) -> int:
        """Largest chunk (≤ b) whose predicted peak fits the budget.
        Never returns less than 1 — a single replicate must run even if
        it alone exceeds the budget (the serial floor)."""
        if budget_bytes <= 0 or self.peak(b) <= budget_bytes:
            return b
        if self.slope <= 0:
            return b
        c = int((budget_bytes - self.base) // self.slope)
        return max(1, min(c, b))


def _signature(xs: Any, args: Tuple[Any, ...]) -> Tuple:
    leaves = jax.tree_util.tree_leaves((xs, args))
    return tuple(
        (tuple(getattr(leaf, "shape", ())), str(getattr(leaf, "dtype", type(leaf))))
        for leaf in leaves
    )


def _element_spec(xs: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), xs
    )


def _spec(tree: Any) -> Any:
    # scalar / non-array pass-through args stay concrete: executors
    # accept them (jit bakes them in), so lowering must too
    return jax.tree_util.tree_map(
        lambda x: (
            jax.ShapeDtypeStruct(x.shape, x.dtype) if hasattr(x, "shape") else x
        ),
        tree,
    )


# Closure -> {(element signature, chunk) -> (compiled, HLO text, peak)}:
# each probed program is compiled once, and the scheduler runs a chunk
# of exactly that size through it rather than compiling it again.
_PROBE_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _compile(fn, xs: Any, args: Tuple[Any, ...], chunk: int) -> Tuple[Any, str, int]:
    """The compiled ``chunk``-replicate vmapped program, its
    post-optimization HLO and its temporary bytes (compile-only, no
    execution; cached per closure).  Each probe compiled counts as
    ``runtime.probe_compiles`` on the process registry."""
    elem = _element_spec(xs)
    key = (_signature(elem, args), int(chunk))
    per_fn = _PROBE_CACHE.setdefault(fn, {})
    if key in per_fn:
        return per_fn[key]
    xs_spec = jax.tree_util.tree_map(
        lambda e: jax.ShapeDtypeStruct((chunk,) + e.shape, e.dtype), elem
    )

    def batched(xs_, *a):
        return jax.vmap(lambda x_: fn(x_, *a))(xs_)

    compiled = jax.jit(batched).lower(xs_spec, *_spec(args)).compile()
    default_registry().counter("runtime.probe_compiles").inc()
    text = compiled.as_text()
    peak = peak_temp_bytes(text)
    mem = compiled.memory_analysis()
    if mem is not None:
        peak = max(peak, int(mem.temp_size_in_bytes))
    per_fn[key] = (compiled, text, peak)
    return per_fn[key]


def compiled_chunk(fn, xs: Any, args: Tuple[Any, ...]):
    """The program a probe already compiled for exactly this chunk's
    shapes (the vmapped closure over ``xs``'s leading axis), or None."""
    per_fn = _PROBE_CACHE.get(fn)
    if not per_fn:
        return None
    chunk = jax.tree_util.tree_leaves(xs)[0].shape[0]
    hit = per_fn.get((_signature(_element_spec(xs), args), int(chunk)))
    return None if hit is None else hit[0]


def probe_peak_bytes(fn, xs: Any, args: Tuple[Any, ...], chunk: int) -> int:
    """Peak-temp bytes of the ``chunk``-replicate vmapped program, from
    the compiled program (no execution)."""
    return _compile(fn, xs, args, chunk)[2]


# Closure -> {input signature -> MemoryModel}.  Weak keys let dead
# closures drop out, mirroring the executors' _JitCache.
_MODEL_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _probe_failed(what: str, err: Exception) -> None:
    """A compile-only probe could not lower the closure: the caller
    proceeds without it, so say so — a warning carrying the error and
    a ``runtime.probe_failed[<what>]`` counter on the process registry
    (drivers that must not run unsized, such as chip_smoke.py, check
    it)."""
    default_registry().counter(f"runtime.probe_failed[{what}]").inc()
    warnings.warn(f"runtime: {what} probe failed, continuing without it: "
                  f"{type(err).__name__}: {err}", RuntimeWarning,
                  stacklevel=3)


def memory_model(fn, xs: Any, args: Tuple[Any, ...], b: int) -> Optional[MemoryModel]:
    """Fit (and cache) the affine peak model for ``fn`` on these input
    shapes.  Returns None when the closure cannot be lowered from specs
    alone — the scheduler then falls back to unchunked execution, and
    the failure is reported (``_probe_failed``)."""
    sig = _signature(xs, args)
    per_fn = _MODEL_CACHE.setdefault(fn, {})
    if sig in per_fn:
        return per_fn[sig]
    try:
        p1 = probe_peak_bytes(fn, xs, args, 1)
        c2 = min(max(b, 1), PROBE_CHUNK)
        if c2 <= 1:
            model = MemoryModel(base=0.0, slope=float(p1))
        else:
            p2 = probe_peak_bytes(fn, xs, args, c2)
            slope = max((p2 - p1) / (c2 - 1), 0.0)
            model = MemoryModel(base=max(p1 - slope, 0.0), slope=slope)
    except Exception as e:  # noqa: BLE001 — scheduling must go on
        _probe_failed("memory_model", e)
        model = None
    per_fn[sig] = model
    return model


@dataclasses.dataclass(frozen=True)
class ChunkCost:
    """Compile-time cost truth for ONE chunk size of a mapped closure —
    what the cost audit (repro.obs.audit) joins to measured chunk
    durations.  ``peak_temp_bytes`` is the probed temporary bytes at
    this size (vs the affine model's interpolation); flops/hbm_bytes are the
    trip-count-aware roofline totals of one chunk execution."""

    chunk: int
    peak_temp_bytes: float
    flops: float
    hbm_bytes: float


# Closure -> {(input signature, chunk) -> Optional[ChunkCost]}.  Same
# weak-key shape as _MODEL_CACHE: audits of a hot closure lower each
# chunk size at most once.
_COST_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def probe_chunk_cost(
    fn, xs: Any, args: Tuple[Any, ...], chunk: int
) -> Optional[ChunkCost]:
    """Lower the ``chunk``-sized program once and read its exact peak /
    roofline costs off the compiled HLO.  Returns None when the closure
    cannot be lowered from specs alone (the audit then skips the chunk
    rather than guessing, and the failure is reported)."""
    sig = (_signature(xs, args), int(chunk))
    per_fn = _COST_CACHE.setdefault(fn, {})
    if sig in per_fn:
        return per_fn[sig]
    try:
        _, text, peak = _compile(fn, xs, args, chunk)
        cs = cost_summary(text, world=1)
        cost = ChunkCost(
            chunk=int(chunk),
            peak_temp_bytes=float(peak),
            flops=cs["flops"],
            hbm_bytes=cs["bytes"],
        )
    except Exception as e:  # noqa: BLE001 — the audit is best-effort
        _probe_failed("chunk_cost", e)
        cost = None
    per_fn[sig] = cost
    return cost

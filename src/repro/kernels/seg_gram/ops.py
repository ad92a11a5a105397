"""Dispatch wrapper for the fused segment-Gram kernel family.

``repro.core.moments`` routes ``row_block_strategy="pallas"`` here.
Three lowerings of the same builder vocabulary (ref.py):

  "pallas"    the Pallas kernel (kernel.py) compiled with Mosaic — ONE
              fused HBM pass.  TPU only: asking for it on another
              backend raises rather than quietly interpreting.
  "interpret" the same kernel grid in interpret mode — the CPU
              certification target (same block decomposition and
              accumulation order as the compiled kernel).
  "scatter"   pure-XLA fast lowering for hosts without a mosaic
              compiler: one segment is the fused augmented matmul
              ``(w*L)^T R``; many segments scatter per-row outer
              products with ``jax.ops.segment_sum`` — measured ~2x
              over the one-hot einsum at sweep shapes on CPU, because
              the (n, S) mask never materializes.
  "ref"       the one-hot einsum oracle (ref.py).

``default_backend()`` picks "pallas" on TPU and "scatter" elsewhere;
``force_backend("interpret")`` pins the kernel path for parity tests
(the conformance suite certifies chunked = pallas estimator-wide).

Every dispatch is counted on ``obs.metrics.default_registry()`` at
trace time: ``seg_gram.lowering[<name>]`` names the lowering that ran,
and an active data mesh, which replaces the kernel with the sharded
scatter lowering, also counts ``seg_gram.fallback[data_mesh:<builder>]``
so the substitution is never silent.

Contract: all lowerings share the padding rules of the moments engine
(zero data rows, seg = -1 — ``segment_sum`` drops negative ids exactly
as the one-hot maps them to a zero row — and w = 0), so padded rows
are exact no-ops.  Counts/n_eff are computed OUTSIDE the kernels from
the same plain sums in every mode (the ``fold_weighted_gram``
precedent: strategy-independent by construction).
"""

from __future__ import annotations

import contextlib
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.seg_gram import kernel as _kernel
from repro.kernels.seg_gram import ref as _ref
from repro.obs.metrics import default_registry

Array = jax.Array
_F32 = jnp.float32

_FORCED: List[str] = []


def default_backend() -> str:
    if _FORCED:
        return _FORCED[-1]
    return "pallas" if jax.default_backend() == "tpu" else "scatter"


@contextlib.contextmanager
def force_backend(name: str):
    """Pin the lowering for the dynamic extent (tests: "interpret"
    certifies the kernel path on CPU, "ref" the einsum oracle)."""
    _FORCED.append(name)
    try:
        yield
    finally:
        _FORCED.pop()


def _col(x: Array) -> Array:
    x = x.astype(_F32)
    return x[:, None] if x.ndim == 1 else x


def _active_data_mesh():
    """Trace-time DataMesh probe (same sys.modules trick as
    core.moments: no runtime-layer import unless a mesh can exist)."""
    import sys

    rd = sys.modules.get("repro.runtime.distributed")
    return None if rd is None else rd.current_data_mesh()


def _scatter_dist(builder, arrays, seg, w, n_segments, row_block, init, dm):
    """Row-sharded blocked scatter lowering: per-block partials (the
    same ``segment_sum`` / augmented-matmul graphs as ``_scatter``'s
    scan body) evaluate shard-locally over the data mesh, then an
    ordered left fold combines them in global block order
    (runtime.distributed.dist_reduce).  Deterministic; parity with the
    single-host lowerings is tolerance-grade like every pallas-strategy
    path (per-block matmul partials reassociate the row reduction)."""
    from repro.runtime.distributed import dist_reduce

    r = int(row_block)
    sids = None if seg is None else seg[:, 0]
    bcast = {i: a for i, a in enumerate(arrays) if a.shape[0] == 1}
    row_arrays = [a for i, a in enumerate(arrays) if i not in bcast]
    qL, qR = jax.eval_shape(
        builder,
        *[
            jax.ShapeDtypeStruct(
                (a.shape[0] if a.shape[0] == 1 else r,) + a.shape[1:],
                a.dtype,
            )
            for a in arrays
        ],
    )
    qL, qR = qL.shape[1], qR.shape[1]

    def block(*blks):
        it = iter(blks)
        full = [bcast[i] if i in bcast else next(it) for i in range(len(arrays))]
        sb = next(it) if sids is not None else None
        wb = next(it) if w is not None else None
        L, R = builder(*full)
        Lw = L if wb is None else L * wb
        if sb is None:
            return Lw.T @ R
        outer = (Lw[:, :, None] * R[:, None, :]).reshape(L.shape[0], -1)
        return jax.ops.segment_sum(outer, sb, num_segments=n_segments)

    dist_arrays = list(row_arrays)
    pad_values = [0] * len(row_arrays)
    if sids is not None:
        dist_arrays.append(sids)
        pad_values.append(-1)
    if w is not None:
        dist_arrays.append(w)
        pad_values.append(0)
    acc0 = init
    if init is not None and sids is not None:
        acc0 = init.reshape(n_segments, qL * qR)
    G = dist_reduce(block, dist_arrays, row_block=r, dm=dm,
                    pad_values=pad_values, init=acc0)
    return G if sids is None else G.reshape(n_segments, qL, qR)


def _scatter(builder, arrays, seg, w, n_segments, row_block,
             init=None) -> Array:
    n = max(a.shape[0] for a in arrays)
    if n_segments == 1:
        L, R = builder(*arrays)
        Lw = L if w is None else L * w
        G = Lw.T @ R
        return G if init is None else init + G
    sids = seg[:, 0]
    r = int(row_block or 0)
    if r <= 0 or r >= n:
        L, R = builder(*arrays)
        Lw = L if w is None else L * w
        outer = (Lw[:, :, None] * R[:, None, :]).reshape(n, -1)
        G = jax.ops.segment_sum(outer, sids, num_segments=n_segments)
        G = G.reshape(n_segments, L.shape[1], R.shape[1])
        return G if init is None else init + G
    # blocked scan: bounded O(r * qL*qR) temporaries at industrial n
    pad = (-n) % r
    if pad:
        arrays = [
            a if a.shape[0] == 1 else jnp.pad(a, ((0, pad), (0, 0)))
            for a in arrays
        ]
        sids = jnp.pad(sids, (0, pad), constant_values=-1)
        if w is not None:
            w = jnp.pad(w, ((0, pad), (0, 0)))
    nb = (n + pad) // r

    def _slc(a, i):
        if a.shape[0] == 1:
            return a
        return lax.dynamic_slice_in_dim(a, i * r, r, axis=0)

    qL, qR = jax.eval_shape(
        builder,
        *[
            jax.ShapeDtypeStruct(
                (a.shape[0] if a.shape[0] == 1 else r,) + a.shape[1:],
                a.dtype,
            )
            for a in arrays
        ],
    )
    qL, qR = qL.shape[1], qR.shape[1]

    def step(acc, i):
        L, R = builder(*[_slc(a, i) for a in arrays])
        Lw = L if w is None else L * _slc(w, i)
        outer = (Lw[:, :, None] * R[:, None, :]).reshape(r, qL * qR)
        sb = lax.dynamic_slice_in_dim(sids, i * r, r, axis=0)
        return (
            acc + jax.ops.segment_sum(outer, sb, num_segments=n_segments),
            None,
        )

    # init seeds the left fold (repro.store's incremental ingest): the
    # scan replays the same addition sequence a one-shot pass over the
    # concatenated rows would, so within-backend ingest stays bitwise
    # when every prior ingest ended on a row_block boundary.
    acc0 = (jnp.zeros((n_segments, qL * qR), _F32) if init is None
            else init.reshape(n_segments, qL * qR))
    G, _ = lax.scan(step, acc0, jnp.arange(nb, dtype=jnp.int32))
    return G.reshape(n_segments, qL, qR)


def seg_reduce(
    builder,
    arrays: Sequence[Array],
    *,
    seg: Optional[Array] = None,
    w: Optional[Array] = None,
    n_segments: int = 1,
    row_block: int = 0,
    backend: str = "",
    init: Optional[Array] = None,
) -> Array:
    """The one entry point: dispatch ``G[s] = sum w_n L_n (x) R_n`` to
    the selected lowering.  ``row_block`` bounds the scatter lowering's
    temporaries and engages the data mesh; the kernel sizes its own
    tiles from VMEM (kernel.plan).

    ``init`` seeds the accumulator (incremental ingest): the blocked
    scatter lowering threads it as the scan seed — bitwise the one-shot
    pass over concatenated rows at aligned boundaries — while the
    kernel/ref/whole-array lowerings add it to their result (delta-add:
    correct, tolerance-equal to one-shot)."""
    be = backend or default_backend()
    arrays = [a.astype(_F32) for a in arrays]
    if w is not None:
        w = _col(w)
    if seg is not None:
        seg = seg.astype(jnp.int32)
        seg = seg[:, None] if seg.ndim == 1 else seg
    n = max(a.shape[0] for a in arrays)
    reg = default_registry()
    if be != "ref" and 0 < row_block < n:
        dm = _active_data_mesh()
        if dm is not None:
            # an active data mesh overrides the single-host lowerings
            # on the blocked path ("ref" stays the unsharded oracle)
            if be != "scatter":
                reg.counter(
                    f"seg_gram.fallback[data_mesh:{builder.__name__}]").inc()
            reg.counter("seg_gram.lowering[scatter_dist]").inc()
            return _scatter_dist(
                builder, arrays, seg, w, n_segments, row_block, init, dm
            )
    if be not in ("ref", "scatter", "pallas", "interpret"):
        raise ValueError(f"unknown seg_gram backend {be!r}")
    if be == "pallas" and jax.default_backend() != "tpu":
        raise RuntimeError(
            "seg_gram: the 'pallas' lowering compiles with Mosaic and runs "
            f"on TPU only (the backend is {jax.default_backend()!r}); use "
            "'interpret' to run the kernel in interpret mode, or 'scatter'")
    reg.counter(f"seg_gram.lowering[{be}]").inc()
    if be == "ref":
        G = _ref.seg_gram_ref(
            builder, arrays, seg=seg, w=w, n_segments=n_segments
        )
        return G if init is None else init + G
    if be == "scatter":
        return _scatter(
            builder, arrays, seg, w, n_segments, row_block, init=init
        )
    G = _kernel.seg_gram_pallas(
        builder,
        arrays,
        seg=seg,
        w=w,
        n_segments=n_segments,
        interpret=be == "interpret",
    )
    return G if init is None else init + G


def segment_counts(
    seg: Array, n_segments: int, *, w: Optional[Array] = None
) -> Array:
    """Per-segment row counts (or weight sums) — a plain O(n) sum,
    computed identically in every backend so counts stay
    strategy-independent (exact integers match the one-hot column
    sums of the chunked reference bitwise)."""
    ones = jnp.ones((seg.shape[0],), _F32) if w is None else w.astype(_F32)
    return jax.ops.segment_sum(
        ones, seg.astype(jnp.int32), num_segments=n_segments
    )


# ---------------------------------------------------------------------------
# Moment-form API mirroring repro.core.moments (the strategy="pallas"
# targets).  All return fp32; n_eff/counts ride alongside like the
# moments signatures they replace.
# ---------------------------------------------------------------------------


def _parts(D) -> List[Array]:
    """A design given whole or as its column parts ``[X, 1, y]``."""
    return list(D) if isinstance(D, (list, tuple)) else [D]


def design_gram(
    D, *, w: Optional[Array] = None, row_block: int = 0, backend: str = ""
) -> Array:
    """(q, q) weighted Gram over a design (whole or column parts)."""
    return seg_reduce(
        _ref.build_design, _parts(D), w=w, row_block=row_block, backend=backend
    )


def fold_design_gram(
    D,
    folds: Array,
    k: int,
    *,
    row_block: int = 0,
    backend: str = "",
) -> Tuple[Array, Array]:
    """(k, q, q) fold-segmented Gram + per-fold counts, over a design
    whole or as column parts."""
    G = seg_reduce(
        _ref.build_design,
        _parts(D),
        seg=folds,
        n_segments=k,
        row_block=row_block,
        backend=backend,
    )
    return G, segment_counts(folds, k)


def fold_weighted_design_gram(
    D, Wk: Array, *, row_block: int = 0, backend: str = ""
) -> Array:
    """(k, q, q) dense-weight fold Gram ``G[k] = Σ_n Wk[k, n] d_n d_nᵀ``
    — the ``ni,kn,nj->kij`` form fused as one kernel pass (the kron
    builder widens L to k·q columns; n_eff stays outside, computed as a
    plain strategy-independent sum by moments.fold_weighted_gram).  The
    design comes whole or as column parts."""
    parts = _parts(D)
    k, q = Wk.shape[0], sum(a.shape[1] for a in parts)
    G = seg_reduce(
        _ref.build_fold_weighted,
        [Wk.T] + parts,
        row_block=row_block,
        backend=backend,
    )
    return G.reshape(k, q, q)


def gram_and_vec(
    D: Array, wg: Array, v: Array, *, row_block: int = 0, backend: str = ""
) -> Tuple[Array, Array]:
    """((q, q) Gram with weights wg, (q,) cross-moment with weights v)
    in one fused pass — the logistic Newton step's two-weight form,
    read off the augmented L = [wg·d | v]."""
    q = D.shape[1]
    Gaug = seg_reduce(
        _ref.build_gram_and_vec,
        [D, _col(wg), _col(v)],
        row_block=row_block,
        backend=backend,
    )
    return Gaug[:q], Gaug[q]


def residual_gram(
    y: Array,
    t: Array,
    my: Array,
    mt: Array,
    phi: Array,
    *,
    w: Optional[Array] = None,
    row_block: int = 0,
    backend: str = "",
) -> Tuple[Array, Array]:
    """(G (p, p), b (p,)) of the orthogonal moment, read off the fused
    augmented Gram M = [rt*phi | ry]."""
    p = phi.shape[1]
    Gaug = seg_reduce(
        _ref.build_residual,
        [_col(y), _col(t), _col(my), _col(mt), phi],
        w=w,
        row_block=row_block,
        backend=backend,
    )
    return Gaug[:p, :p], Gaug[:p, p]


def residual_weighted_gram(
    ry: Array,
    rt: Array,
    phi: Array,
    w: Array,
    *,
    row_block: int = 0,
    backend: str = "",
) -> Tuple[Array, Array]:
    """Weighted augmented residual Gram (inference.numerics form)."""
    Gaug = seg_reduce(
        _ref.build_residual_direct,
        [_col(ry), _col(rt), phi],
        w=w,
        row_block=row_block,
        backend=backend,
    )
    return Gaug, w.astype(_F32).sum()


def iv_gram(
    ry: Array,
    rt: Array,
    rz: Array,
    phi: Array,
    w: Array,
    *,
    row_block: int = 0,
    backend: str = "",
) -> Tuple[Array, Array]:
    """((2p+1, 2p+1) instrumented augmented Gram, n_eff)."""
    Gaug = seg_reduce(
        _ref.build_iv,
        [_col(ry), _col(rt), _col(rz), phi],
        w=w,
        row_block=row_block,
        backend=backend,
    )
    return Gaug, w.astype(_F32).sum()


def fold_iv_gram(
    ry: Array,
    rt: Array,
    rz: Array,
    phi: Array,
    folds: Array,
    k: int,
    *,
    row_block: int = 0,
    backend: str = "",
) -> Tuple[Array, Array]:
    """((k, 2p+1, 2p+1) fold-segmented instrumented Gram, counts)."""
    G = seg_reduce(
        _ref.build_iv,
        [_col(ry), _col(rt), _col(rz), phi],
        seg=folds,
        n_segments=k,
        row_block=row_block,
        backend=backend,
    )
    return G, segment_counts(folds, k)


def residual_meat(
    y: Array,
    t: Array,
    my: Array,
    mt: Array,
    phi: Array,
    theta: Array,
    *,
    w: Optional[Array] = None,
    row_block: int = 0,
    backend: str = "",
) -> Array:
    """(p, p) HC0 meat at theta; the (w*e)^2 weighting happens inside
    the builder (w scales e BEFORE squaring, matching moments)."""
    arrays = [_col(y), _col(t), _col(my), _col(mt), phi, theta.reshape(1, -1)]
    if w is not None:
        arrays.append(_col(w))
    return seg_reduce(
        _ref.build_residual_meat, arrays, row_block=row_block, backend=backend
    )


def iv_meat(
    ry: Array,
    rt: Array,
    rz: Array,
    phi: Array,
    theta: Array,
    *,
    w: Optional[Array] = None,
    row_block: int = 0,
    backend: str = "",
) -> Array:
    """(p, p) HC0 meat of the instrumented moment at theta."""
    arrays = [_col(ry), _col(rt), _col(rz), phi, theta.reshape(1, -1)]
    if w is not None:
        arrays.append(_col(w))
    return seg_reduce(
        _ref.build_iv_meat, arrays, row_block=row_block, backend=backend
    )


def segment_outer(
    U: Array,
    V: Array,
    seg: Array,
    n_segments: int,
    *,
    w: Optional[Array] = None,
    row_block: int = 0,
    backend: str = "",
    init: Optional[Array] = None,
) -> Array:
    """(S, qU, qV) segmented outer-product sums — the sweep's per-step
    gradient shape (one-hot einsum 'ns,ni,nj->sij', fused).  ``init``
    seeds the accumulator (see ``seg_reduce``)."""
    return seg_reduce(
        _ref.build_pair,
        [_col(U), _col(V)],
        seg=seg,
        w=w,
        n_segments=n_segments,
        row_block=row_block,
        backend=backend,
        init=init,
    )


def lane_gram(builder, arrays: Sequence[Array], *, backend: str = "") -> Array:
    """``G = Σ_n L_n (x) R_n`` (qL, qR) over lane-major operands: the
    row-shaped inputs transposed, (d, n), and resident tables of any
    other width (``kernel.seg_gram_lanes``).  The builder maps (d, r)
    blocks to (Lᵀ, Rᵀ).  "ref" and "scatter" run it whole-array and
    contract once; "pallas" and "interpret" run the kernel."""
    be = backend or default_backend()
    if be not in ("ref", "scatter", "pallas", "interpret"):
        raise ValueError(f"unknown seg_gram backend {be!r}")
    if be == "pallas" and jax.default_backend() != "tpu":
        raise RuntimeError(
            "seg_gram: the 'pallas' lowering compiles with Mosaic and runs "
            f"on TPU only (the backend is {jax.default_backend()!r}); use "
            "'interpret' to run the kernel in interpret mode, or 'scatter'")
    default_registry().counter(f"seg_gram.lowering[{be}]").inc()
    arrays = [a.astype(_F32) for a in arrays]
    if be in ("ref", "scatter"):
        Lt, Rt = builder(*arrays)
        return lax.dot_general(Lt, Rt, (((1,), (1,)), ((), ())),
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=_F32)
    return _kernel.seg_gram_lanes(builder, arrays, interpret=be == "interpret")


def mm_logistic_grad(xa_t: Array, meta_t: Array, coef: Array, *,
                     backend: str = "") -> Array:
    """(E, K, q) complement gradient of the segmented sweep's MM
    logistic step, ``Σ_{cohort e, fold ≠ k} (sigmoid(xa·coef[e, k]) - t)
    xa``, in one pass over the lane-major rows ``xa_t`` = [X | 1]ᵀ
    (q, n) and ``meta_t`` = [t; cohort + 1; fold] (3, n), with the
    (E, K, q) table ``coef`` resident (``ref.build_mm_logistic``)."""
    E, K, q = coef.shape
    table = jnp.transpose(coef, (1, 2, 0)).reshape(K * q, E)
    G = lane_gram(_ref.build_mm_logistic, [xa_t, meta_t, table],
                  backend=backend)
    return G.reshape(E, K, q)

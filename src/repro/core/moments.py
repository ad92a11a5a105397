"""Streaming sufficient-statistics engine — the single estimation
substrate shared by nuisance fits, the orthogonal final stage, and
replicate inference.

Every estimator in this codebase bottoms out in weighted Gram-shaped
moments: ridge/logistic normal equations, the leave-one-out fold Grams
of cross-fitting, the Neyman-orthogonal final stage, and the
reweighted refits of bootstrap/jackknife inference.  Wong's
*Computational Causal Inference* argues that condensing estimation to
such sufficient statistics is the path to industrial scale; More et
al. (2409.02332) stream DML in row chunks.  This module is both ideas
as one API: compute ``Σ_n w_n · d_n d_nᵀ`` (and friends) over a row
design ``d`` with a *fixed block decomposition* and two evaluation
strategies.

Memory model
------------
  row_block = 0   one whole-array block — the legacy einsum forms
                  verbatim (fastest when (n, q) activations fit in a
                  single allocation; the default).
  row_block = R   rows are zero-padded to a multiple of R and reduced
                  block-by-block in FIXED left-to-right order:

      strategy "whole"    every block partial materializes at once
                          (an unbatched per-block lax.map + an ordered
                          fold) — peak memory ~ O(n·q + B·q²);
      strategy "chunked"  ``lax.scan`` streams one dynamic-sliced
                          block at a time — peak memory
                          ~ O(R·q + q²).  n is no longer bounded by a
                          single dense allocation: the actual
                          "industrial scale" claim.
      strategy "pallas"   the fused mask→weight→residualize→accumulate
                          kernel (repro.kernels.seg_gram): one HBM
                          pass per form — compiled mosaic on TPU, a
                          fused XLA scatter/matmul lowering elsewhere,
                          interpret mode for certification.  Every
                          dense-weight form now has a fused builder
                          (``fold_weighted_gram`` via the kron
                          builder, ``weighted_gram_and_vec`` via the
                          augmented two-weight builder); a residual
                          pallas→chunked fallback rung remains for
                          not-yet-fused future forms and is counted per
                          form on obs metrics.  Parity with "chunked"
                          is tolerance-certified (≤1e-6
                          estimator-wide, conformance suite), not
                          bitwise.

Bit-identity contract
---------------------
For equal ``row_block`` the two strategies are bit-identical *by
construction* (tests/test_moments.py asserts exact equality):

  * identical block decomposition and zero-row padding (padded rows
    carry zero weight / zero design entries, which contribute exactly
    0.0 to every accumulator);
  * identical per-block einsum forms — the augmented-Gram vocabulary
    of ``repro.inference.numerics``: cross-moments are read off
    appended design columns, NEVER the thin ``ni,n->i`` shape class,
    whose reduction XLA reassociates under fusion (measured: the thin
    form breaks chunked-vs-whole equality, the augmented form does
    not);
  * identical left-fold reduction order over blocks (a ``lax.scan``
    accumulation in both strategies).

Different ``row_block`` values commute only up to float reassociation;
estimator-level invariance across settings is asserted with tight
tolerances, not bitwise.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array


def resolve_row_block(n: int, row_block: Optional[int]) -> int:
    """0 means "one whole-array block" (legacy forms); any R >= n
    collapses to the same thing."""
    r = int(row_block or 0)
    return 0 if r <= 0 or r >= n else r


def _seg_ops():
    """The fused-kernel dispatch (lazy: kernels are optional at import
    time for forms that never take the pallas strategy)."""
    from repro.kernels.seg_gram import ops as sg_ops
    return sg_ops


def _use_pallas(n: int, row_block: int, strategy: Optional[str]) -> bool:
    """strategy="pallas" engages on the blocked path (row_block > 0),
    mirroring the chunked/whole semantics; row_block=0 keeps the legacy
    whole-array forms byte-for-byte."""
    return strategy == "pallas" and resolve_row_block(n, row_block) > 0


def _active_data_mesh():
    """The trace-time DataMesh, if ``repro.runtime.distributed`` has
    been imported AND a ``use_data_mesh`` context is active.  The
    sys.modules probe keeps core.moments free of any runtime-layer
    import: a mesh can only be active if the module that activates it
    is already loaded."""
    import sys
    rd = sys.modules.get("repro.runtime.distributed")
    return None if rd is None else rd.current_data_mesh()


def design_parts(X: Array, *, intercept: bool = False,
                 append: Optional[Array] = None) -> list:
    """The fp32 column parts ``[X, 1?, append?]`` of the design.  The
    fused kernel takes them apart and assembles each row block in VMEM,
    so it never writes an (n, q) copy of X to HBM."""
    f32 = jnp.float32
    cols = [X.astype(f32)]
    if intercept:
        cols.append(jnp.ones((X.shape[0], 1), f32))
    if append is not None:
        a = append.astype(f32)
        cols.append(a[:, None] if a.ndim == 1 else a)
    return cols


def design(X: Array, *, intercept: bool = False,
           append: Optional[Array] = None) -> Array:
    """Assemble the per-(block-)row design ``[X | 1? | append?]`` in
    fp32.  ``append`` (a target / residual column) is how cross-moments
    ride inside a Gram — the replicate-invariant trick from
    repro.inference.numerics."""
    cols = design_parts(X, intercept=intercept, append=append)
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)


def blocked_reduce(block_fn: Callable[..., Any], arrays: Sequence[Array],
                   *, row_block: int = 0, strategy: Optional[str] = None,
                   pad_values: Optional[Sequence] = None,
                   init: Optional[Any] = None, form: str = "") -> Any:
    """Reduce ``block_fn`` over row blocks of the leading axis.

    ``block_fn(*blocks) -> pytree`` must be row-additive AND must map
    zero-padded rows to exactly-zero contributions (every Gram-shaped
    form here does: padded rows carry zero weights / zero one-hot rows
    / zero design entries).  ``pad_values`` overrides the per-array
    padding constant (e.g. -1 for integer fold ids so their one-hot is
    the zero row).

    row_block == 0 evaluates ``block_fn`` once on the whole arrays —
    the legacy path, byte-for-byte.  Otherwise the same fixed
    decomposition is reduced left-to-right either all-at-once
    ("whole") or streamed ("chunked"); see the module docstring for
    the bit-identity contract.

    ``init`` seeds the left-fold accumulator (same pytree structure as
    ``block_fn``'s output) instead of zeros — the incremental-refresh
    hook of ``repro.store``: folding new rows on top of a standing
    accumulator replays the EXACT addition sequence a one-shot pass
    over the concatenated rows would run, **provided every earlier
    ingest ended on a ``row_block`` boundary** (otherwise the block
    decomposition shifts and identity holds only up to float
    reassociation).  On the ``row_block == 0`` path ``init`` is added
    to the whole-array result — correct, but only tolerance-equal to a
    one-shot pass.

    ``form`` labels the moment form for the fallback-ladder counter:
    when ``strategy="pallas"`` reaches this function (no fused
    seg_gram builder for the form), the downgrade to "chunked" is
    counted on ``obs.metrics.default_registry()`` as
    ``seg_gram.fallback[<form>]`` — a trace-time event (jit-cached
    calls do not re-count).
    """
    arrays = tuple(arrays)
    n = arrays[0].shape[0]
    tmap = jax.tree_util.tree_map
    r = resolve_row_block(n, row_block)
    if r == 0:
        out = block_fn(*arrays)
        return out if init is None else tmap(jnp.add, init, out)
    strategy = strategy or "chunked"
    if strategy == "pallas":
        # the fallback ladder (pallas → chunked → whole): forms without
        # a fused seg_gram builder stream chunked — same bits as the
        # reference the pallas forms are certified against.  Counted so
        # the remaining fusion gap stays observable (ROADMAP item).
        from repro.obs.metrics import default_registry
        default_registry().counter(
            f"seg_gram.fallback[{form or 'unlabeled'}]").inc()
        strategy = "chunked"
    dm = _active_data_mesh()
    if dm is not None:
        # row-sharded reduction over the active data mesh: the block
        # axis splits across ("hosts", "devices") and the ordered mode
        # replays this function's exact left-fold addition sequence —
        # bitwise the chunked/whole result (runtime.distributed)
        from repro.runtime.distributed import dist_reduce
        return dist_reduce(block_fn, arrays, row_block=r, dm=dm,
                           pad_values=pad_values, init=init)
    pad = (-n) % r
    if pad:
        pv = pad_values or (0,) * len(arrays)
        arrays = tuple(
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                    constant_values=v)
            for a, v in zip(arrays, pv))
    nb = (n + pad) // r
    if strategy == "whole":
        blocks = tuple(a.reshape((nb, r) + a.shape[1:]) for a in arrays)
        # lax.map, NOT vmap: each block partial comes from the SAME
        # unbatched per-block graph the chunked strategy traces, so
        # chunked ≡ whole is structural — a vmapped block program's
        # einsums can retile under batching (measured: the p=1 meat
        # with no weight operand), which would break the contract
        # data-dependently.  All partials still materialize at once,
        # which is this strategy's memory signature.
        parts = lax.map(lambda bs: block_fn(*bs), blocks)
        acc0 = (tmap(lambda x: jnp.zeros(x.shape[1:], x.dtype), parts)
                if init is None else init)
        out, _ = lax.scan(lambda acc, g: (tmap(jnp.add, acc, g), None),
                          acc0, parts)
        return out
    if strategy != "chunked":
        raise ValueError(f"unknown strategy {strategy!r} "
                         "(expected whole | chunked | pallas)")

    def step(acc, i):
        blks = tuple(lax.dynamic_slice_in_dim(a, i * r, r, axis=0)
                     for a in arrays)
        return tmap(jnp.add, acc, block_fn(*blks)), None

    if init is None:
        shapes = jax.eval_shape(
            block_fn, *[jax.ShapeDtypeStruct((r,) + a.shape[1:], a.dtype)
                        for a in arrays])
        acc0 = tmap(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    else:
        acc0 = init
    out, _ = lax.scan(step, acc0, jnp.arange(nb, dtype=jnp.int32))
    return out


# ---------------------------------------------------------------------------
# Weighted moments (ridge / logistic normal equations, HC0 meats).
# ---------------------------------------------------------------------------

def weighted_gram(X: Array, w: Array, *, intercept: bool = False,
                  append: Optional[Array] = None, row_block: int = 0,
                  strategy: Optional[str] = None
                  ) -> Tuple[Array, Array]:
    """``G = Σ_n w_n d_n d_nᵀ`` over ``d = [X | 1? | append?]`` plus
    ``n_eff = Σ_n w_n`` from the same blocked reduction.  With
    ``append=y``, the cross-moment ``Σ w·d·y`` is ``G[:, -1]``."""
    if _use_pallas(X.shape[0], row_block, strategy):
        D = design_parts(X, intercept=intercept, append=append)
        G = _seg_ops().design_gram(D, w=w, row_block=row_block)
        return G, w.astype(jnp.float32).sum()
    if append is None:
        def block(Xb, wb):
            D = design(Xb, intercept=intercept)
            ws = wb.astype(jnp.float32)
            return jnp.einsum("ni,n,nj->ij", D, ws, D), ws.sum()
        return blocked_reduce(block, (X, w), row_block=row_block,
                              strategy=strategy, form="weighted_gram")

    def block(Xb, ab, wb):
        D = design(Xb, intercept=intercept, append=ab)
        ws = wb.astype(jnp.float32)
        return jnp.einsum("ni,n,nj->ij", D, ws, D), ws.sum()

    return blocked_reduce(block, (X, append, w), row_block=row_block,
                          strategy=strategy, form="weighted_gram")


def weighted_gram_and_vec(X: Array, wg: Array, v: Array, *,
                          intercept: bool = False, row_block: int = 0,
                          strategy: Optional[str] = None
                          ) -> Tuple[Array, Array, Array]:
    """One blocked pass returning ``(G = Σ wg_n d_n d_nᵀ,
    u = Σ v_n d_n, n_eff = Σ wg_n)`` — Gram and cross-moment with
    *different* row weights sharing a single read of X (the logistic
    Newton step: Hessian weights s, gradient residuals r).

    Two regimes for the cross-moment:

      row_block = 0  the thin ``ni,n->i`` mat-vec — the legacy form,
                     byte-for-byte, and half the FLOPs of a second
                     Gram (this is the benchmarked hot path: 16 Newton
                     iterations per logistic fit);
      row_block > 0  ``Σ v_n da_n`` read off the trailing all-ones
                     column of a SECOND v-weighted Gram over
                     ``da = [d | 1]``.  The thin mat-vec compiles to
                     DIFFERENT reduction tilings inside the chunked
                     scan body vs the whole lax.map body (measured:
                     x_learner's blocked propensity fit), so only the
                     augmented-Gram form keeps chunked ≡ whole exact
                     on the blocked path.

    Neither form is certified batch-invariant under an executor's
    replicate vmap — replicate closures read gradients off augmented
    Grams in inference.numerics instead."""
    if _use_pallas(X.shape[0], row_block, strategy):
        D = design(X, intercept=intercept)
        G, u = _seg_ops().gram_and_vec(D, wg, v, row_block=row_block)
        # n_eff through the same blocked left fold as the chunked path
        # (a whole-array sum reassociates) — bitwise, like fold_gram's
        # counts: plain sums stay strategy-independent.
        n_eff = blocked_reduce(lambda wb: wb.astype(jnp.float32).sum(),
                               (wg,), row_block=row_block)
        return G, u, n_eff
    if resolve_row_block(X.shape[0], row_block) == 0:
        D = design(X, intercept=intercept)
        ws = wg.astype(jnp.float32)
        return (jnp.einsum("ni,n,nj->ij", D, ws, D),
                jnp.einsum("ni,n->i", D, v.astype(jnp.float32)),
                ws.sum())

    def block(Xb, wb, vb):
        D = design(Xb, intercept=intercept)
        Da = D if intercept else design(Xb, intercept=True)
        ws = wb.astype(jnp.float32)
        Gv = jnp.einsum("ni,n,nj->ij", Da, vb.astype(jnp.float32), Da)
        return (jnp.einsum("ni,n,nj->ij", D, ws, D),
                Gv[: D.shape[1], -1],
                ws.sum())

    return blocked_reduce(block, (X, wg, v), row_block=row_block,
                          strategy=strategy, form="weighted_gram_and_vec")


# ---------------------------------------------------------------------------
# Fold-segmented moments (the leave-one-out identity of cross-fitting:
# Xᵀdiag(w_k)X = G_total - G_heldout_k needs one segmented pass).
# ---------------------------------------------------------------------------

def fold_gram(X: Array, folds: Array, k: int, *, intercept: bool = False,
              append: Optional[Array] = None, row_block: int = 0,
              strategy: Optional[str] = None
              ) -> Tuple[Array, Array]:
    """One-pass fold-segmented Gram: ``Gh[k] = Σ_{n in fold k} d_n d_nᵀ``
    (k, q, q) plus per-fold row counts (k,).  Integer fold ids are
    padded with -1 so padded rows one-hot to the zero row."""
    if _use_pallas(X.shape[0], row_block, strategy):
        D = design_parts(X, intercept=intercept, append=append)
        return _seg_ops().fold_design_gram(D, folds, k,
                                           row_block=row_block)

    def block(Xb, fb, *rest):
        D = design(Xb, intercept=intercept,
                   append=rest[0] if rest else None)
        oh = jax.nn.one_hot(fb, k, dtype=jnp.float32)
        return jnp.einsum("nk,ni,nj->kij", oh, D, D), oh.sum(0)

    arrays = (X, folds) + (() if append is None else (append,))
    pad_values = (0, -1) + (() if append is None else (0,))
    return blocked_reduce(block, arrays, row_block=row_block,
                          strategy=strategy,
                          pad_values=pad_values, form="fold_gram")


def fold_weighted_gram(X: Array, Wk: Array, *, intercept: bool = False,
                       append: Optional[Array] = None, row_block: int = 0,
                       strategy: Optional[str] = None
                       ) -> Tuple[Array, Array]:
    """``G[k] = Σ_n Wk[k,n] d_n d_nᵀ`` (k, q, q) plus per-fold
    ``n_eff = Σ_n Wk[k,n]`` — the replicate-invariant
    ``ni,kn,nj->kij`` form of repro.inference.numerics, blocked.  At
    row_block=0 this IS the legacy whole-array einsum, bitwise."""
    f32 = jnp.float32
    r = resolve_row_block(X.shape[0], row_block)
    # n_eff is an O(n·k) plain sum — computed whole-array in EVERY mode
    # so it is strategy-independent by construction (slicing the
    # transposed Wk operand per block reassociates its reduction)
    n_eff = Wk.astype(f32).sum(axis=1)
    if r == 0:
        D = design(X, intercept=intercept, append=append)
        return jnp.einsum("ni,kn,nj->kij", D, Wk.astype(f32), D), n_eff
    if strategy == "pallas":
        D = design_parts(X, intercept=intercept, append=append)
        return _seg_ops().fold_weighted_design_gram(D, Wk, row_block=r), n_eff

    def block(Xb, Wb, *rest):
        D = design(Xb, intercept=intercept,
                   append=rest[0] if rest else None)
        return jnp.einsum("ni,kn,nj->kij", D, Wb.astype(f32).T, D)

    arrays = (X, Wk.T) + (() if append is None else (append,))
    G = blocked_reduce(block, arrays, row_block=r, strategy=strategy,
                       form="fold_weighted_gram")
    return G, n_eff


def fold_sizes(n: int, k: int) -> Tuple[int, ...]:
    """Rows per fold of ``crossfit.fold_ids``, which shuffles
    ``arange(n) % k``: fold j holds ceil((n - j) / k) of them."""
    return tuple((n - j + k - 1) // k for j in range(k))


def grouped_fold_weighted_gram(X: Array, folds: Array, w: Array, k: int, *,
                               intercept: bool = False,
                               append: Optional[Array] = None,
                               row_block: int = 0,
                               strategy: Optional[str] = None
                               ) -> Tuple[Array, Array]:
    """``fold_weighted_gram`` on ``fold_weights(folds, k) * w`` from
    rows grouped by fold: each fold's own weighted Gram
    ``H_j = Σ_{n in fold j} w_n d_n d_nᵀ`` over its rows alone, then
    ``G[c] = Σ_{j ≠ c} H_j`` by additions in a fixed order (no
    subtraction, so no cancellation).  A row costs one Gram's work, not
    the kron form's k.

    ``folds`` must be drawn by ``crossfit.fold_ids``, so every fold has
    its static size (``fold_sizes``).  A stable sort of the ids groups
    the rows; the folds are reduced one after another, each from a
    gathered copy of its rows padded with zero-weight rows to the
    largest fold, so one fold's copy is live at a time.  Each reduction
    is the S = 1 weighted form of the strategy: the explicit-fold-axis
    einsum with one fold (whole-array or blocked), or the pallas design
    Gram with row weights.  ``n_eff`` is the kron form's plain sum,
    bitwise."""
    f32 = jnp.float32
    n = X.shape[0]
    ks = jnp.arange(k, dtype=folds.dtype)
    n_eff = ((folds[None, :] != ks[:, None]).astype(f32)
             * w[None, :]).astype(f32).sum(axis=1)
    sizes = jnp.asarray(fold_sizes(n, k), jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    # each fold's rows (a stable sort: in ascending order), a shorter
    # fold padded with repeats of its last row at weight zero
    order = jnp.argsort(folds, stable=True)
    at = jnp.arange(fold_sizes(n, k)[0], dtype=jnp.int32)
    idx = order[starts[:, None] + jnp.minimum(at[None, :], sizes[:, None] - 1)]
    keep = at[None, :] < sizes[:, None]
    pallas = _use_pallas(n, row_block, strategy)

    def rows(a, ix):
        return a.at[ix].get(mode="promise_in_bounds")

    def own(args):
        ix, ok = args
        Xj = rows(X, ix)
        aj = None if append is None else rows(append, ix)
        wj = jnp.where(ok, rows(w, ix), 0).astype(f32)
        if pallas:
            D = design_parts(Xj, intercept=intercept, append=aj)
            return _seg_ops().design_gram(D, w=wj, row_block=row_block)
        G, _ = fold_weighted_gram(Xj, wj[None, :], intercept=intercept,
                                  append=aj, row_block=row_block,
                                  strategy=strategy)
        return G[0]

    H = lax.map(own, (idx, keep))                                  # (k, q, q)
    zero = jnp.zeros_like(H[0])
    G = [sum((H[j] for j in range(k) if j != c), zero) for c in range(k)]
    return jnp.stack(G), n_eff


# ---------------------------------------------------------------------------
# Residual moments (the DML final stage): Z = (t - mt) ⊙ phi,
# G = ZᵀZ, b = Zᵀ(y - my), meat = Σ e²·z zᵀ.
# ---------------------------------------------------------------------------

def residual_moments(y: Array, t: Array, my: Array, mt: Array, phi: Array,
                     *, row_block: int = 0, strategy: Optional[str] = None,
                     backend: str = ""
                     ) -> Tuple[Array, Array]:
    """(G (p,p), b (p,)) of the orthogonal moment, fp32.  row_block=0
    delegates to the fused ``residual_gram`` kernel dispatch (Pallas on
    TPU, jnp oracle elsewhere) — today's whole-array path, bitwise.
    Blocked evaluation streams row blocks; with a Pallas-capable
    backend each block takes the fused kernel (one HBM pass per block),
    otherwise the augmented ``M = [Z | ry]`` Gram form (the thin
    ``Zᵀry`` mat-vec is not chunked-stable; the augmented column is)."""
    from repro.kernels.residual_gram import ops as rg_ops
    n, p = phi.shape
    r = resolve_row_block(n, row_block)
    if r == 0:
        return rg_ops.residual_gram(y, t, my, mt, phi, backend=backend)
    if strategy == "pallas":
        return _seg_ops().residual_gram(y, t, my, mt, phi, row_block=r)
    if backend in ("pallas", "interpret"):
        def block(yb, tb, myb, mtb, phib):
            return rg_ops.residual_gram(yb, tb, myb, mtb, phib,
                                        backend=backend)
    else:
        def block(yb, tb, myb, mtb, phib):
            ry = (yb - myb).astype(jnp.float32)
            rt = (tb - mtb).astype(jnp.float32)
            z = rt[:, None] * phib.astype(jnp.float32)
            M = jnp.concatenate([z, ry[:, None]], axis=1)
            Gaug = M.T @ M
            return Gaug[:p, :p], Gaug[:p, p]

    return blocked_reduce(block, (y, t, my, mt, phi), row_block=r,
                          strategy=strategy, form="residual_moments")


def residual_weighted_gram(ry: Array, rt: Array, phi: Array, w: Array,
                           *, row_block: int = 0,
                           strategy: Optional[str] = None
                           ) -> Tuple[Array, Array]:
    """Weighted augmented residual Gram ``Σ_n w_n m_n m_nᵀ`` with
    ``m = [rt·phi | ry]`` plus ``n_eff = Σ w`` — the replicate-invariant
    weighted-final-stage moment (inference.numerics.weighted_theta).
    Z is formed per block: on the blocked path the dense (n, p) moment
    matrix never materializes."""
    f32 = jnp.float32
    if _use_pallas(ry.shape[0], row_block, strategy):
        return _seg_ops().residual_weighted_gram(ry, rt, phi, w,
                                                 row_block=row_block)

    def block(ryb, rtb, phib, wb):
        Z = rtb.astype(f32)[:, None] * phib.astype(f32)
        M = jnp.concatenate([Z, ryb.astype(f32)[:, None]], axis=1)
        ws = wb.astype(f32)
        return jnp.einsum("ni,n,nj->ij", M, ws, M), ws.sum()

    return blocked_reduce(block, (ry, rt, phi, w), row_block=row_block,
                          strategy=strategy, form="residual_weighted_gram")


def _meat_gram(score: Array, e: Array, p: int) -> Array:
    """``Σ_n e_n² s_n s_nᵀ`` in the batch-invariant form for this p.

    XLA's tiling of the n-contraction is shape-dependent: with a
    COMPUTED weight (e² is a fused elementwise producer, unlike the
    plain-input weights of the Gram kernels above) folding e into the
    score and contracting ``mᵀm`` keeps its reduction order under an
    added vmap axis at p ≥ 2 (measured on CPU XLA).  At p = 1 every
    contraction is a vector dot, which CPU XLA emits as a fused loop
    without a batch axis and as a dot kernel with one, so a replicate
    run alone (or in a chunk of one) and the same replicate in a batch
    sum in different orders; the meat is then the plain sum Σ (e·s)²,
    elementwise square and sum, as ``iv_meat``'s p = 1 branch.  The
    serial ≡ vmap CONTRACT is certified on the row-blocked path, where
    the scan barrier makes it shape-robust (tests/test_conformance.py
    pins it there)."""
    m = e[:, None] * score
    if p >= 2:
        return jnp.einsum("ni,nj->ij", m, m)
    return jnp.square(m[:, 0]).sum().reshape(1, 1)


def residual_meat(y: Array, t: Array, my: Array, mt: Array, phi: Array,
                  theta: Array, *, w: Optional[Array] = None,
                  row_block: int = 0, strategy: Optional[str] = None) -> Array:
    """HC0 meat ``Σ_n (w_n e_n)² z_n z_nᵀ`` with ``e = ry - <z, theta>``
    streamed per block — the dense (n, p) moment matrix ``z`` and the
    residual vector never materialize on the blocked path.  The inner
    product uses the small-axis ``(z * theta).sum(-1)`` form (replicate-
    and chunk-invariant); the contraction takes the width-dispatched
    batch-invariant form (see ``_meat_gram``)."""
    p = phi.shape[1]
    if _use_pallas(phi.shape[0], row_block, strategy):
        return _seg_ops().residual_meat(y, t, my, mt, phi, theta, w=w,
                                        row_block=row_block)

    def block(yb, tb, myb, mtb, phib, *rest):
        ry = (yb - myb).astype(jnp.float32)
        rt = (tb - mtb).astype(jnp.float32)
        z = rt[:, None] * phib.astype(jnp.float32)
        e = ry - (z * theta[None, :]).sum(axis=1)
        if rest:
            e = rest[0].astype(jnp.float32) * e
        return _meat_gram(z, e, p)

    arrays = (y, t, my, mt, phi) + (() if w is None else (w,))
    return blocked_reduce(block, arrays, row_block=row_block,
                          strategy=strategy, form="residual_meat")


# ---------------------------------------------------------------------------
# Instrumented moments (the orthogonal-IV family, repro.core.iv):
# M = [rz ⊙ phi | rt ⊙ phi | ry], G = Σ w · m mᵀ.  Every 2SLS-shaped
# sufficient statistic is a slice of this ONE augmented Gram:
#   J    = G[:p, p:2p]   Σ w·rz·rt·φφᵀ   (the residual-on-residual
#                                          instrument moment)
#   b    = G[:p, 2p]     Σ w·rz·ry·φ     (instrumented cross-moment)
#   Szz  = G[:p, :p]     Σ w·rz²·φφᵀ     (instrument strength)
#   Stt  = G[p:2p, p:2p] Σ w·rt²·φφᵀ
#   bty  = G[p:2p, 2p]   Σ w·rt·ry·φ     (the OLS cross-moment, free)
# Like every form in this module, cross-moments ride as appended
# columns of the blocked Gram — bit-identical chunked vs whole.
# ---------------------------------------------------------------------------

def iv_gram(ry: Array, rt: Array, rz: Array, phi: Array, w: Array, *,
            row_block: int = 0, strategy: Optional[str] = None
            ) -> Tuple[Array, Array]:
    """Weighted instrumented augmented Gram ``Σ_n w_n m_n m_nᵀ`` with
    ``m = [rz·phi | rt·phi | ry]`` ((2p+1, 2p+1)) plus ``n_eff = Σ w``.
    Point fits pass w = 1; bootstrap replicates their resampling
    weights — both take the same einsum form, so a w=1 replicate is
    bitwise the point fit."""
    f32 = jnp.float32
    if _use_pallas(phi.shape[0], row_block, strategy):
        return _seg_ops().iv_gram(ry, rt, rz, phi, w, row_block=row_block)

    def block(ryb, rtb, rzb, phib, wb):
        ph = phib.astype(f32)
        M = jnp.concatenate(
            [rzb.astype(f32)[:, None] * ph,
             rtb.astype(f32)[:, None] * ph,
             ryb.astype(f32)[:, None]], axis=1)
        ws = wb.astype(f32)
        return jnp.einsum("ni,n,nj->ij", M, ws, M), ws.sum()

    return blocked_reduce(block, (ry, rt, rz, phi, w),
                          row_block=row_block, strategy=strategy,
                          form="iv_gram")


def iv_slices(Gaug: Array, p: int) -> Tuple[Array, Array, Array, Array]:
    """(J, b, Szz, Stt) read off an ``iv_gram`` result (see the section
    comment above for the algebra)."""
    return (Gaug[:p, p:2 * p], Gaug[:p, 2 * p],
            Gaug[:p, :p], Gaug[p:2 * p, p:2 * p])


def iv_meat(ry: Array, rt: Array, rz: Array, phi: Array, theta: Array,
            *, w: Optional[Array] = None, row_block: int = 0,
            strategy: Optional[str] = None) -> Array:
    """HC0 meat of the instrumented moment: ``Σ_n (w_n e_n)² zc_n zc_nᵀ``
    with score ``zc = rz·phi`` and residual ``e = ry - <rt·phi, theta>``,
    streamed per block (neither the (n, p) score matrix nor the residual
    vector materializes on the blocked path).  The inner product uses
    the small-axis ``(z * theta).sum(-1)`` form and the contraction the
    width-dispatched batch-invariant form, matching ``residual_meat``."""
    f32 = jnp.float32
    p = phi.shape[1]
    if _use_pallas(phi.shape[0], row_block, strategy):
        return _seg_ops().iv_meat(ry, rt, rz, phi, theta, w=w,
                                  row_block=row_block)

    def block(ryb, rtb, rzb, phib, *rest):
        ph = phib.astype(f32)
        z = rtb.astype(f32)[:, None] * ph
        e = ryb.astype(f32) - (z * theta[None, :]).sum(axis=1)
        if rest:
            e = rest[0].astype(f32) * e
        if p >= 2:
            m = e[:, None] * (rzb.astype(f32)[:, None] * ph)
            return jnp.einsum("ni,nj->ij", m, m)
        # p = 1: the meat is the plain sum Σ (e·rz·φ)² — elementwise
        # square + sum, the one contraction-free member of the
        # invariant vocabulary.  (The 3-operand einsum that is stable
        # for residual_meat's score here picks up an extra fused
        # producer and loses batch invariance — measured, and pinned by
        # tests/test_conformance.py.)
        m = e * (rzb.astype(f32)[:, None] * ph)[:, 0]
        return jnp.square(m).sum().reshape(1, 1)

    arrays = (ry, rt, rz, phi) + (() if w is None else (w,))
    return blocked_reduce(block, arrays, row_block=row_block,
                          strategy=strategy, form="iv_meat")


def fold_iv_gram(ry: Array, rt: Array, rz: Array, phi: Array,
                 folds: Array, k: int, *, row_block: int = 0,
                 strategy: Optional[str] = None
                 ) -> Tuple[Array, Array]:
    """Fold-segmented instrumented Gram ``Gh[j] = Σ_{n in fold j}
    m_n m_nᵀ`` ((k, 2p+1, 2p+1)) plus per-fold row counts — the
    delete-fold jackknife's one pass (LOO identity:
    ``G_(-j) = Σ_j Gh - Gh[j]``).  Padded fold ids are -1 so they
    one-hot to the zero row."""
    f32 = jnp.float32
    if _use_pallas(phi.shape[0], row_block, strategy):
        return _seg_ops().fold_iv_gram(ry, rt, rz, phi, folds, k,
                                       row_block=row_block)

    def block(ryb, rtb, rzb, phib, fb):
        ph = phib.astype(f32)
        M = jnp.concatenate(
            [rzb.astype(f32)[:, None] * ph,
             rtb.astype(f32)[:, None] * ph,
             ryb.astype(f32)[:, None]], axis=1)
        oh = jax.nn.one_hot(fb, k, dtype=f32)
        return jnp.einsum("nk,ni,nj->kij", oh, M, M), oh.sum(0)

    return blocked_reduce(block, (ry, rt, rz, phi, folds),
                          row_block=row_block, strategy=strategy,
                          pad_values=(0, 0, 0, 0, -1),
                          form="fold_iv_gram")

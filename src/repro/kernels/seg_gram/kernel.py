"""Pallas TPU kernel: ONE fused mask -> weight -> residualize -> Gram
pass for every segment-Gram-shaped moment in the repo.

The estimators bottom out in ``G[s] = sum_{seg_n = s} w_n L_n (x) R_n``
(repro.kernels.seg_gram.ref documents the builder vocabulary).  The
naive paths write residuals, the (n, p) moment matrix, and an (n, S)
one-hot mask back to HBM between elementwise ops and the Gram matmul;
this kernel streams (block_n, d) tiles through VMEM once per input,
runs the builder in registers, applies the segment mask and bootstrap
weight in registers, and accumulates into a VMEM-resident output:

  grid        (segment tiles, n / block_n) — the row axis is innermost
              and sequential, so each segment tile's output block stays
              pinned in VMEM while every row block streams past it.
  S == 1      g (qL, qR):        g += (w * L)^T R      (one MXU matmul)
  S  > 1      one tile covers ``seg_tile`` segments; the weighted
              one-hot of the tile's segments expands L into
              T[n, s*qL + i] = oh[n, s] * L[n, i] and g_tile += T^T R —
              the segmented sum IS the matmul, a 2-D (seg_tile*qL, qR)
              accumulator the MXU wants.  Tiling the segment axis bounds
              the accumulator and T at any S: untiled, the store's
              final-stage Gram (S = E*K = 320, q = 106 at p = 50) needs
              a (35840, 128) accumulator, 40 MB of VMEM with its double
              buffer even at 8-row blocks.  Each tile re-reads the rows,
              so HBM traffic grows with the tile count, not the VMEM
              footprint.

Sizing: ``plan`` picks the segment tile (T about ``TILE_ROWS`` wide) and
then the largest row block whose VMEM working set (``vmem_bytes``)
fits ``VMEM_BUDGET``; the kernel is compiled with ``VMEM_LIMIT`` so
builder temporaries have headroom.  Nothing is taken from the caller's
``row_block``: that is the XLA strategies' streaming unit, not a tile.

Operands: inputs of 128 columns or more (and broadcast (1, d) rows)
stream as their own tiles; every narrower input, the weights and the
segment ids are packed into one (n, m) operand and sliced apart in
VMEM, because HBM tiles pad an array's minor dim to 128 lanes (an
(n, 1) f32 column alone would take n * 512 bytes).

Padding contract: ``plan`` prefers a block height that divides n (no
tail at all); otherwise the row tail is zero-padded to a multiple of
block_n with seg = -1 (matches no lane of the iota compare -> zero mask
row) and w = 0; builders map all-zero rows to all-zero L/R rows, so
padded rows contribute exactly 0.0 to every accumulator.  L/R columns are
zero-padded in registers to the (8, 128) fp32 tile and segments to a
whole number of tiles (sliced off the output).  Interpret mode runs the
same tiling, so CPU certification covers the compiled block structure.

Lane-major form (``seg_gram_lanes``, S = 1): row-shaped inputs come
transposed, (d, n), so narrow operands are lane-dense in HBM; the
builder returns (Lᵀ, Rᵀ) and may read resident tables (the segmented
sweep's MM step reads its (E·K, q) coefficients so, and forms the
cohort one-hot and the residuals in registers).

The Gram matmul runs at ``PRECISION``: the moments are sums over
millions of rows that the estimators solve against, so they need f32
products, not a single bf16 pass (the MXU default for f32 operands).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

VMEM_BUDGET = 24 << 20  # working set ``plan`` sizes tiles for
VMEM_LIMIT = 64 << 20  # scoped VMEM granted to Mosaic (a v5e core has 128 MiB)
MAX_BLOCK_N = 8192
TILE_ROWS = 1024  # target accumulator height seg_tile * qL
LANES = 128  # inputs narrower than this share one packed operand
PRECISION = lax.Precision.HIGHEST


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def vmem_bytes(block_n: int, row_widths: Sequence[int], seg_tile: int,
               qlp: int, qrp: int, segmented: bool) -> int:
    """fp32 VMEM working set of one grid step: double-buffered row
    tiles (each padded to 128 lanes — an (n, 1) column costs a full
    lane row), the builder's L / w*L / R, for S > 1 the one-hot
    expansion ((rows, seg_tile, qL) then T and its transpose), and the
    double-buffered accumulator block."""
    lanes = lambda d: _round_up(d, 128)  # noqa: E731
    rows_in = 2 * block_n * sum(lanes(d) for d in row_widths)
    regs = block_n * (2 * lanes(qlp) + qrp)
    expand = 0
    if segmented:
        expand = block_n * (_round_up(seg_tile, 8) * lanes(qlp)
                            + 2 * lanes(seg_tile * qlp))
    acc = 2 * seg_tile * qlp * qrp
    return 4 * (rows_in + regs + expand + acc)


def plan(n: int, row_widths: Sequence[int], n_segments: int, qL: int,
         qR: int, *, block_n: Optional[int] = None
         ) -> Tuple[int, int, int, int]:
    """(block_n, seg_tile, qlp, qrp) for an (n, ...) problem.  Raises
    when even an 8-row block cannot fit the VMEM budget (no silent
    fallback: the caller learns the shape is out of the kernel's
    reach)."""
    qlp, qrp = _round_up(qL, 8), _round_up(qR, 128)
    S = int(n_segments)
    seg_tile = 1 if S == 1 else max(1, min(S, TILE_ROWS // qlp))
    if block_n is None:
        bn = MAX_BLOCK_N
        while bn > 8 and vmem_bytes(bn, row_widths, seg_tile, qlp, qrp,
                                    S > 1) > VMEM_BUDGET:
            bn //= 2
        need = vmem_bytes(bn, row_widths, seg_tile, qlp, qrp, S > 1)
        if need > VMEM_BUDGET:
            raise ValueError(
                f"seg_gram: (S={S}, qL={qL}, qR={qR}) needs {need} bytes "
                f"of VMEM at block_n=8, over the {VMEM_BUDGET}-byte budget")
    else:
        bn = int(block_n)
    # one block covering every row takes the array's own height; else
    # a block height within a factor 2 of the planned one that divides
    # n needs no padded tail (no padded copy of the inputs), and failing
    # that the blocks are balanced so the tail stays under 8 rows a block
    nb = max(1, -(-n // bn))
    if nb == 1:
        return n, seg_tile, qlp, qrp
    div = next((b for b in range(bn - bn % 8, bn // 2, -8) if n % b == 0), 0)
    bn = div or _round_up(-(-n // nb), 8)
    return bn, seg_tile, qlp, qrp


def _pad_rows(a: Array, pad: int, value) -> Array:
    if pad == 0:
        return a
    return jnp.pad(a, ((0, pad), (0, 0)), constant_values=value)


def _pad_cols(a: Array, pad: int) -> Array:
    if pad == 0:
        return a
    return jnp.concatenate([a, jnp.zeros((a.shape[0], pad), a.dtype)], axis=1)


def kernel_name(builder, n_segments: int) -> str:
    """``seg_gram_<form>``, ``_seg`` appended when S > 1: the name the
    compiled kernel carries, so a profile tells the forms apart."""
    form = builder.__name__.removeprefix("build_")
    return f"seg_gram_{form}" + ("_seg" if n_segments > 1 else "")


def seg_gram_pallas(
    builder,
    arrays: Sequence[Array],
    *,
    seg: Optional[Array] = None,
    w: Optional[Array] = None,
    n_segments: int = 1,
    interpret: bool,
    block_n: Optional[int] = None,
) -> Array:
    """Fused segmented Gram.  ``arrays``: 2-D fp32 inputs, row-shaped
    (n, d) or broadcast (1, d); ``seg``: (n, 1) int32 ids in
    [0, n_segments); ``w``: (n, 1) row weights (default: none, every
    row weighs 1).  Returns (qL, qR) when n_segments == 1, else
    (n_segments, qL, qR), fp32.

    ``interpret`` is explicit: False compiles with Mosaic (TPU only),
    True runs the same grid in interpret mode.  ``block_n`` overrides
    the VMEM-planned row block (tests use small blocks to exercise
    multi-block grids at small n)."""
    S = int(n_segments)
    rows = [a for a in arrays if a.shape[0] != 1]
    n = rows[0].shape[0]
    qL, qR = jax.eval_shape(
        builder,
        *[
            jax.ShapeDtypeStruct((1 if a.shape[0] == 1 else 8,) + a.shape[1:],
                                 a.dtype)
            for a in arrays
        ],
    )
    qL, qR = qL.shape[1], qR.shape[1]

    # Narrow row inputs (fewer than LANES columns), the weights and the
    # segment ids travel as ONE packed (n, m) operand, sliced apart per
    # block in VMEM: HBM tiles pad an array's minor dim to 128 lanes, so
    # every separate (n, 1) column would occupy n * 512 bytes.
    slots, operands, cols = [], [], []  # slot: (operand index) or (offset, width)
    for a in arrays:
        if a.shape[0] == 1 or a.shape[1] >= LANES:
            slots.append((len(operands),))
            operands.append(a)
        else:
            slots.append((sum(c.shape[1] for c in cols), a.shape[1]))
            cols.append(a.astype(jnp.float32))
    m = sum(c.shape[1] for c in cols)
    w_at = seg_at = None
    if w is not None:
        w_at, m = m, m + 1
        cols.append(w.astype(jnp.float32))
    if S > 1:
        seg_at, m = m, m + 1
        cols.append(seg.astype(jnp.float32))  # exact: ids < 2**24

    row_widths = [a.shape[1] for a in operands if a.shape[0] != 1]
    row_widths += [m] if m else []
    bn, st, qlp, qrp = plan(n, row_widths, S, qL, qR, block_n=block_n)
    pad_l, pad_r = qlp - qL, qrp - qR
    n_tiles = -(-S // st)

    pad = (-n) % bn
    operands = [a if a.shape[0] == 1 else _pad_rows(a, pad, 0) for a in operands]
    if m:
        pad_values = [0.0] * len(cols)
        if seg_at is not None:
            pad_values[-1] = -1.0  # matches no segment lane
        operands.append(jnp.concatenate(
            [_pad_rows(c, pad, v) for c, v in zip(cols, pad_values)], axis=1))
    nb = (n + pad) // bn

    def _spec(a: Array) -> pl.BlockSpec:
        if a.shape[0] == 1:
            return pl.BlockSpec((1, a.shape[1]), lambda j, i: (0, 0))
        return pl.BlockSpec((bn, a.shape[1]), lambda j, i: (i, 0))

    def kern(*refs):
        *in_refs, g_ref = refs
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            g_ref[...] = jnp.zeros_like(g_ref)

        P = in_refs[-1][...] if m else None  # (bn, m) packed columns

        def col(at, width=1):
            return P[:, at:at + width]

        L, R = builder(*[in_refs[s[0]][...] if len(s) == 1 else col(*s)
                         for s in slots])
        L = _pad_cols(L, pad_l)
        R = _pad_cols(R, pad_r)
        if S == 1:
            T = L if w_at is None else L * col(w_at)
        else:
            # ids relative to this tile: segments outside it (and the
            # -1 pad id) match no lane and zero their rows
            ids = col(seg_at).astype(jnp.int32) - pl.program_id(0) * st
            hit = ids == lax.broadcasted_iota(jnp.int32, (bn, st), 1)
            oh = (hit.astype(jnp.float32) if w_at is None
                  else jnp.where(hit, col(w_at), 0.0))  # (bn, st)
            T = (oh[:, :, None] * L[:, None, :]).reshape(bn, st * qlp)
        g_ref[...] += lax.dot_general(
            T, R, (((0,), (0,)), ((), ())), precision=PRECISION,
            preferred_element_type=jnp.float32)

    g = pl.pallas_call(
        kern,
        grid=(n_tiles, nb),
        in_specs=[_spec(a) for a in operands],
        out_specs=pl.BlockSpec((st * qlp, qrp), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * st * qlp, qrp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=kernel_name(builder, S),
    )(*operands)
    if S == 1:
        return g[:qL, :qR]
    return g.reshape(n_tiles * st, qlp, qrp)[:S, :qL, :qR]


LANE_BLOCK = 8192  # rows (lanes) per grid step of ``seg_gram_lanes``


def lane_block(n: int, block_n: Optional[int] = None) -> int:
    """Rows per grid step of the lane-major kernel: every row when they
    fit one block, else a multiple of 128 lanes within a factor 2 of
    ``LANE_BLOCK`` that divides n (no padded copy), else ``LANE_BLOCK``."""
    if block_n is not None:
        return int(block_n)
    if n <= LANE_BLOCK:
        return n
    return next((b for b in range(LANE_BLOCK, LANE_BLOCK // 2, -LANES)
                 if n % b == 0), LANE_BLOCK)


def seg_gram_lanes(
    builder,
    arrays: Sequence[Array],
    *,
    interpret: bool,
    block_n: Optional[int] = None,
) -> Array:
    """Fused S = 1 Gram over lane-major operands: row-shaped inputs come
    transposed, (d, n) with the rows on lanes, so an array of a few
    columns is lane-dense in HBM (an (n, d < 128) array pads every row
    to 128 lanes, 512 bytes); any input of another width is resident,
    one whole block at every grid step.  The builder maps (d, r) blocks
    to (Lᵀ (qL, r), Rᵀ (qR, r)) and the kernel accumulates
    ``g += Lᵀ (Rᵀ)ᵀ`` (qL, qR) at ``PRECISION``.  Zero-padded rows must
    give zero Lᵀ or Rᵀ columns, as for ``seg_gram_pallas``."""
    n = max(a.shape[1] for a in arrays)

    def resident(a: Array) -> bool:
        return a.shape[1] != n

    qL, qR = jax.eval_shape(
        builder,
        *[jax.ShapeDtypeStruct(a.shape if resident(a) else (a.shape[0], LANES),
                               a.dtype) for a in arrays],
    )
    qL, qR = qL.shape[0], qR.shape[0]
    bn = lane_block(n, block_n)
    pad = (-n) % bn
    operands = [a if resident(a) else
                jnp.pad(a, ((0, 0), (0, pad))) if pad else a for a in arrays]

    def _spec(a: Array) -> pl.BlockSpec:
        if resident(a):
            return pl.BlockSpec(a.shape, lambda i: (0, 0))
        return pl.BlockSpec((a.shape[0], bn), lambda i: (0, i))

    def kern(*refs):
        *in_refs, g_ref = refs

        @pl.when(pl.program_id(0) == 0)
        def _init():
            g_ref[...] = jnp.zeros_like(g_ref)

        Lt, Rt = builder(*[r[...] for r in in_refs])
        g_ref[...] += lax.dot_general(
            Lt, Rt, (((1,), (1,)), ((), ())), precision=PRECISION,
            preferred_element_type=jnp.float32)

    return pl.pallas_call(
        kern,
        grid=((n + pad) // bn,),
        in_specs=[_spec(a) for a in arrays],
        out_specs=pl.BlockSpec((qL, qR), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((qL, qR), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=kernel_name(builder, 1),
    )(*operands)

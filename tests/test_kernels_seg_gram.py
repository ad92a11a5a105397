"""Certification of the fused segment-Gram kernel family
(repro.kernels.seg_gram) behind ``row_block_strategy="pallas"``.

Two tiers of guarantees:

  tolerance  every moment form the moments engine routes to seg_gram
             agrees with the chunked reference (<= ~1e-4 on raw Grams;
             fp32 reassociation), for ALL lowerings: the one-hot
             oracle, the XLA scatter path, and the Pallas kernel in
             interpret mode (same block decomposition the mosaic
             compiler sees on TPU).
  exact      the structural contracts are bitwise: padded tail rows
             are no-ops, w=0 masks a row exactly like zeroing its
             data, empty segments produce exactly-zero Gram slabs and
             integer-zero counts, and power-of-two weights scale the
             Gram exactly.

Estimator-wide parity (every registry estimator, point estimates)
lives in tests/test_conformance.py::test_pallas_strategy_parity.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import moments
from repro.kernels.residual_gram import ops as rg_ops
from repro.kernels.seg_gram import ops as sg_ops
from repro.kernels.seg_gram import ref as sg_ref

BACKENDS = ("ref", "scatter", "interpret")
_N, _P, _K = 700, 3, 4          # non-divisible into the row block
_RB = 256


@pytest.fixture(scope="module")
def arrs():
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 8)
    return dict(
        y=jax.random.normal(ks[0], (_N,)),
        t=(jax.random.uniform(ks[1], (_N,)) < 0.5).astype(jnp.float32),
        my=0.1 * jax.random.normal(ks[2], (_N,)),
        mt=jnp.full((_N,), 0.5, jnp.float32),
        rz=jax.random.normal(ks[3], (_N,)),
        phi=jax.random.normal(ks[4], (_N, _P)),
        w=jax.random.exponential(ks[5], (_N,)),
        folds=jax.random.randint(ks[6], (_N,), 0, _K),
        theta=jnp.arange(1.0, _P + 1),
        X=jax.random.normal(ks[7], (_N, 5)),
    )


def _close(a, b, msg="", atol=2e-4, rtol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=rtol, atol=atol, err_msg=msg)


# ---------------------------------------------------------------------------
# Tolerance tier: every strategy="pallas" route in the moments engine
# against its chunked reference, per lowering.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_moments_forms_parity(arrs, backend):
    a = arrs
    kw = dict(row_block=_RB)
    with sg_ops.force_backend(backend):
        pairs = [
            ("weighted_gram",
             moments.weighted_gram(a["X"], a["w"], intercept=True,
                                   strategy="chunked", **kw),
             moments.weighted_gram(a["X"], a["w"], intercept=True,
                                   strategy="pallas", **kw)),
            ("fold_gram",
             moments.fold_gram(a["X"], a["folds"], _K, intercept=True,
                               append=a["y"], strategy="chunked", **kw),
             moments.fold_gram(a["X"], a["folds"], _K, intercept=True,
                               append=a["y"], strategy="pallas", **kw)),
            ("residual_moments",
             moments.residual_moments(a["y"], a["t"], a["my"], a["mt"],
                                      a["phi"], strategy="chunked", **kw),
             moments.residual_moments(a["y"], a["t"], a["my"], a["mt"],
                                      a["phi"], strategy="pallas", **kw)),
            ("residual_weighted_gram",
             moments.residual_weighted_gram(a["y"], a["t"], a["phi"],
                                            a["w"], strategy="chunked",
                                            **kw),
             moments.residual_weighted_gram(a["y"], a["t"], a["phi"],
                                            a["w"], strategy="pallas",
                                            **kw)),
            ("residual_meat",
             moments.residual_meat(a["y"], a["t"], a["my"], a["mt"],
                                   a["phi"], a["theta"], w=a["w"],
                                   strategy="chunked", **kw),
             moments.residual_meat(a["y"], a["t"], a["my"], a["mt"],
                                   a["phi"], a["theta"], w=a["w"],
                                   strategy="pallas", **kw)),
            ("iv_gram",
             moments.iv_gram(a["y"], a["t"], a["rz"], a["phi"], a["w"],
                             strategy="chunked", **kw),
             moments.iv_gram(a["y"], a["t"], a["rz"], a["phi"], a["w"],
                             strategy="pallas", **kw)),
            ("iv_meat",
             moments.iv_meat(a["y"], a["t"], a["rz"], a["phi"],
                             a["theta"], w=a["w"], strategy="chunked",
                             **kw),
             moments.iv_meat(a["y"], a["t"], a["rz"], a["phi"],
                             a["theta"], w=a["w"], strategy="pallas",
                             **kw)),
            ("fold_iv_gram",
             moments.fold_iv_gram(a["y"], a["t"], a["rz"], a["phi"],
                                  a["folds"], _K, strategy="chunked",
                                  **kw),
             moments.fold_iv_gram(a["y"], a["t"], a["rz"], a["phi"],
                                  a["folds"], _K, strategy="pallas",
                                  **kw)),
        ]
    for name, ref, got in pairs:
        ref = ref if isinstance(ref, tuple) else (ref,)
        got = got if isinstance(got, tuple) else (got,)
        for i, (r, g) in enumerate(zip(ref, got)):
            _close(g, r, f"{name}[{i}] {backend}")


@pytest.mark.parametrize("backend", BACKENDS)
def test_counts_strategy_independent(arrs, backend):
    """Counts/n_eff are plain sums computed outside the kernels: exact
    integers, bitwise-equal to the chunked one-hot column sums."""
    a = arrs
    _, c_ref = moments.fold_gram(a["X"], a["folds"], _K, row_block=_RB,
                                 strategy="chunked")
    with sg_ops.force_backend(backend):
        _, c = moments.fold_gram(a["X"], a["folds"], _K, row_block=_RB,
                                 strategy="pallas")
    np.testing.assert_array_equal(np.asarray(c_ref), np.asarray(c))


def test_pallas_requires_blocked_path(arrs):
    """row_block=0 keeps the legacy whole-array form byte-for-byte —
    the pallas strategy only engages on the blocked path."""
    a = arrs
    r0 = moments.residual_moments(a["y"], a["t"], a["my"], a["mt"],
                                  a["phi"], row_block=0)
    rp = moments.residual_moments(a["y"], a["t"], a["my"], a["mt"],
                                  a["phi"], row_block=0,
                                  strategy="pallas")
    np.testing.assert_array_equal(np.asarray(r0[0]), np.asarray(rp[0]))
    np.testing.assert_array_equal(np.asarray(r0[1]), np.asarray(rp[1]))


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_forms_take_no_fallback(arrs, backend):
    """``fold_weighted_gram`` and ``weighted_gram_and_vec`` lower
    through fused seg_gram builders: tolerance parity with the chunked
    reference, n_eff bitwise, and — the load-bearing assertion — the
    ``seg_gram.fallback[<form>]`` counters stay at ZERO.  Before the
    fused builders landed, both forms silently laddered pallas→chunked
    on every trace; this pins the fusion so it cannot regress."""
    from repro.obs.metrics import default_registry

    a = arrs
    Wk = jax.random.exponential(jax.random.PRNGKey(9), (_K, _N))
    ref_fw = moments.fold_weighted_gram(a["X"], Wk, intercept=True,
                                        row_block=_RB, strategy="chunked")
    ref_gv = moments.weighted_gram_and_vec(a["X"], a["w"], a["y"],
                                           intercept=True, row_block=_RB,
                                           strategy="chunked")
    with sg_ops.force_backend(backend):
        got_fw = moments.fold_weighted_gram(a["X"], Wk, intercept=True,
                                            row_block=_RB,
                                            strategy="pallas")
        got_gv = moments.weighted_gram_and_vec(a["X"], a["w"], a["y"],
                                               intercept=True,
                                               row_block=_RB,
                                               strategy="pallas")
    _close(got_fw[0], ref_fw[0], f"fold_weighted_gram {backend}")
    np.testing.assert_array_equal(np.asarray(ref_fw[1]),
                                  np.asarray(got_fw[1]))  # n_eff bitwise
    _close(got_gv[0], ref_gv[0], f"gram_and_vec.G {backend}")
    _close(got_gv[1], ref_gv[1], f"gram_and_vec.u {backend}")
    np.testing.assert_array_equal(np.asarray(ref_gv[2]),
                                  np.asarray(got_gv[2]))
    counters = default_registry().snapshot()["counters"]
    fallbacks = {k: v for k, v in counters.items()
                 if k.startswith("seg_gram.fallback[") and v}
    assert not fallbacks, f"fused forms took the fallback rung: {fallbacks}"


def test_fallback_ladder_counts_unfused_form(arrs):
    """Every registry moment form is fused now, but the counted
    pallas→chunked rung in ``blocked_reduce`` stays for future unfused
    forms: a direct call under strategy="pallas" yields the chunked
    bits exactly AND bumps ``seg_gram.fallback[<form>]`` — the
    ladder's observability contract."""
    from repro.core.moments import blocked_reduce
    from repro.obs.metrics import default_registry

    a = arrs

    def block(Xb, wb):
        return (wb[:, None].astype(jnp.float32) * Xb).T @ Xb

    ref = blocked_reduce(block, (a["X"], a["w"]), row_block=_RB,
                         strategy="chunked")
    got = blocked_reduce(block, (a["X"], a["w"]), row_block=_RB,
                         strategy="pallas", form="custom_form")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    counters = default_registry().snapshot()["counters"]
    assert counters.get("seg_gram.fallback[custom_form]", 0) >= 1


# ---------------------------------------------------------------------------
# Exact tier: the structural bitwise contracts.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["scatter", "interpret"])
def test_padded_rows_exact_noop(arrs, backend):
    """Manually appending pad rows (zero data, seg=-1, w=0) changes
    NOTHING, bitwise — the contract the internal tail-padding relies
    on (no n % block_n divisibility requirement).  Scatter and the
    kernel only: the one-hot oracle's einsum retiles with n, so its
    padding invariance is tolerance-level, not bitwise."""
    a = arrs
    pad = 56  # 700 + 56 = 756, still non-divisible by 256
    U = a["phi"]
    V = jnp.concatenate([a["phi"], a["y"][:, None]], axis=1)
    seg = a["folds"]
    w = a["w"]
    Up = jnp.pad(U, ((0, pad), (0, 0)))
    Vp = jnp.pad(V, ((0, pad), (0, 0)))
    segp = jnp.pad(seg, (0, pad), constant_values=-1)
    wp = jnp.pad(w, (0, pad))
    g = sg_ops.segment_outer(U, V, seg, _K, w=w, row_block=_RB,
                             backend=backend)
    gp = sg_ops.segment_outer(Up, Vp, segp, _K, w=wp, row_block=_RB,
                              backend=backend)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(gp),
                                  err_msg=backend)


@pytest.mark.parametrize("backend", BACKENDS)
def test_zero_weight_equals_zero_data(arrs, backend):
    """Masking a row with w=0 is bitwise the same as zeroing its data
    (builders are row-linear and map zero rows to zero L/R rows)."""
    a = arrs
    mask = (jnp.arange(_N) % 3 != 0).astype(jnp.float32)
    g_w = sg_ops.residual_gram(a["y"], a["t"], a["my"], a["mt"],
                               a["phi"], w=mask, row_block=_RB,
                               backend=backend)
    z = mask
    g_z = sg_ops.residual_gram(a["y"] * z, a["t"] * z, a["my"] * z,
                               a["mt"] * z, a["phi"] * z[:, None],
                               row_block=_RB, backend=backend)
    np.testing.assert_array_equal(np.asarray(g_w[0]), np.asarray(g_z[0]),
                                  err_msg=backend)
    np.testing.assert_array_equal(np.asarray(g_w[1]), np.asarray(g_z[1]),
                                  err_msg=backend)


@pytest.mark.parametrize("backend", BACKENDS)
def test_empty_segment_exact_zero(arrs, backend):
    """A segment no row maps to yields an exactly-zero Gram slab and
    an integer-zero count — no NaN, no epsilon."""
    a = arrs
    seg = jnp.where(a["folds"] == 2, 1, a["folds"])  # segment 2 empty
    g = sg_ops.segment_outer(a["phi"], a["phi"], seg, _K,
                             row_block=_RB, backend=backend)
    assert np.all(np.asarray(g[2]) == 0.0), backend
    counts = sg_ops.segment_counts(seg, _K)
    assert float(counts[2]) == 0.0


@pytest.mark.parametrize("backend", BACKENDS)
def test_power_of_two_weights_exact(arrs, backend):
    """w = 2 everywhere scales the Gram EXACTLY by 2 (power-of-two
    scaling is exact in fp32) — pins where the weight is applied."""
    a = arrs
    g1 = sg_ops.segment_outer(a["phi"], a["phi"], a["folds"], _K,
                              row_block=_RB, backend=backend)
    g2 = sg_ops.segment_outer(a["phi"], a["phi"], a["folds"], _K,
                              w=jnp.full((_N,), 2.0), row_block=_RB,
                              backend=backend)
    np.testing.assert_array_equal(2.0 * np.asarray(g1), np.asarray(g2),
                                  err_msg=backend)


def test_blocked_scatter_matches_whole(arrs):
    """The bounded-memory blocked scatter (lax.scan of per-block
    segment_sums) agrees with the one-shot scatter."""
    a = arrs
    whole = sg_ops.segment_outer(a["phi"], a["phi"], a["folds"], _K,
                                 w=a["w"], row_block=0,
                                 backend="scatter")
    blocked = sg_ops.segment_outer(a["phi"], a["phi"], a["folds"], _K,
                                   w=a["w"], row_block=_RB,
                                   backend="scatter")
    _close(blocked, whole, "blocked scatter", atol=1e-4)


# ---------------------------------------------------------------------------
# The historical residual_gram entry point now routes through seg_gram
# (one fused-Gram implementation in the repo).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_residual_gram_entry_point_parity(arrs, backend):
    a = arrs
    G_ref, b_ref = moments.residual_moments(a["y"], a["t"], a["my"],
                                            a["mt"], a["phi"],
                                            row_block=_RB,
                                            strategy="chunked")
    G, b = rg_ops.residual_gram(a["y"], a["t"], a["my"], a["mt"],
                                a["phi"], backend=backend)
    _close(G, G_ref, f"residual_gram G {backend}")
    _close(b, b_ref, f"residual_gram b {backend}")


def test_residual_gram_non_divisible_n():
    """The old hard ``assert n % block_n == 0`` is gone: the wrapper
    zero-pads the row tail (an exact no-op, certified above)."""
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 5)
    n, p = 333, 2  # 333 % 512 != 0 and n < block_n
    y, t, my, mt = (jax.random.normal(k, (n,)) for k in ks[:4])
    phi = jax.random.normal(ks[4], (n, p))
    G, b = rg_ops.residual_gram(y, t, my, mt, phi, backend="interpret")
    G_ref, b_ref = moments.residual_moments(y, t, my, mt, phi)
    _close(G, G_ref, "non-divisible G", atol=1e-4)
    _close(b, b_ref, "non-divisible b", atol=1e-4)


# ---------------------------------------------------------------------------
# End-to-end: the segmented sweep under strategy="pallas".
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["scatter", "interpret"])
def test_segmented_sweep_pallas_parity(backend):
    from repro.config import CausalConfig
    from repro.data.causal_dgp import make_causal_data
    from repro.sweep.segmented import segmented_dml_sweep

    key = jax.random.PRNGKey(0)
    n, E = 400, 5
    data = make_causal_data(jax.random.fold_in(key, 1), n, 4, effect=1.0)
    sids = jax.random.randint(jax.random.fold_in(key, 2), (n,), 0, E)
    cfg_c = CausalConfig(n_folds=3, inference="none", row_block=128,
                         row_block_strategy="chunked")
    cfg_p = dataclasses.replace(cfg_c, row_block_strategy="pallas")
    r_c = segmented_dml_sweep(cfg_c, data.X, data.y, data.t, sids, E, key)
    with sg_ops.force_backend(backend):
        r_p = segmented_dml_sweep(cfg_p, data.X, data.y, data.t, sids,
                                  E, key)
    for k in ("theta", "se", "ate"):
        _close(r_p[k], r_c[k], f"sweep.{k} {backend}", atol=1e-5,
               rtol=1e-5)


def test_builder_zero_rows_are_zero():
    """The builder contract the padding relies on: all-zero input rows
    produce all-zero L and R rows, for every builder."""
    z1 = jnp.zeros((4, 1))
    z3 = jnp.zeros((4, 3))
    theta = jnp.ones((1, 3))
    cases = [
        (sg_ref.build_pair, [z3, z3]),
        (sg_ref.build_design, [z3]),
        (sg_ref.build_residual, [z1, z1, z1, z1, z3]),
        (sg_ref.build_residual_direct, [z1, z1, z3]),
        (sg_ref.build_iv, [z1, z1, z1, z3]),
        (sg_ref.build_residual_meat, [z1, z1, z1, z1, z3, theta]),
        (sg_ref.build_iv_meat, [z1, z1, z1, z3, theta]),
    ]
    for builder, args in cases:
        L, R = builder(*args)
        assert np.all(np.asarray(L) == 0.0), builder.__name__
        assert np.all(np.asarray(R) == 0.0), builder.__name__


# ---------------------------------------------------------------------------
# Tiling: the VMEM-planned kernel grid (row blocks x segment tiles) in
# interpret mode against the one-hot oracle, and the explicit lowering.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,S,q,block_n", [
    (333, 1, 5, 16),      # many row blocks + padded tail, S = 1
    (333, 5, 6, 64),      # fold-sized S, one segment tile
    (500, 200, 8, 96),    # S * qL > TILE_ROWS: two segment tiles
    (257, 130, 3, None),  # planned block, ragged last segment tile
])
def test_kernel_tiling_matches_ref(n, S, q, block_n):
    from repro.kernels.seg_gram import kernel as sg_kernel

    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    U = jax.random.normal(ks[0], (n, q))
    w = jax.random.exponential(ks[1], (n, 1))
    seg = jax.random.randint(ks[2], (n, 1), 0, S)
    got = sg_kernel.seg_gram_pallas(
        sg_ref.build_pair, [U, U], seg=seg if S > 1 else None, w=w,
        n_segments=S, interpret=True, block_n=block_n)
    ref = sg_ref.seg_gram_ref(sg_ref.build_pair, [U, U],
                              seg=seg if S > 1 else None, w=w, n_segments=S)
    _close(got, ref, f"n={n} S={S} q={q}")


def test_pallas_lowering_refuses_cpu(arrs):
    """An explicit "pallas" lowering off the TPU raises; only
    "interpret" interprets."""
    a = arrs
    with pytest.raises(RuntimeError, match="TPU only"):
        sg_ops.design_gram(a["X"], row_block=_RB, backend="pallas")
    with pytest.raises(RuntimeError, match="TPU only"):
        rg_ops.residual_gram(a["y"], a["t"], a["my"], a["mt"], a["phi"],
                             backend="pallas")


@pytest.mark.parametrize("n", [1_000_000, 262_144, 1_000_003])
def test_plan_prefers_a_block_that_divides_n(n):
    """A block height that divides n leaves no padded tail (no padded
    copy of the inputs); otherwise the tail stays under 8 rows a block."""
    from repro.kernels.seg_gram import kernel as sg_kernel

    bn = sg_kernel.plan(n, [7], 1, 8, 128)[0]
    assert bn % 8 == 0 and bn <= sg_kernel.MAX_BLOCK_N
    if n % 8 == 0:
        assert n % bn == 0
    else:
        assert (-n) % bn < 8 * (-(-n // bn))

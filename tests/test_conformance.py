"""The cross-estimator conformance suite: ONE parametrized
certification run over every estimator in the promoted registry
(repro.core.registry: DML, DRLearner, S/T/X metalearners, OrthoIV,
DRIV).

Checks per estimator: serial ≡ vmap bootstrap replicates to float
reassociation (SERIAL_VMAP_RTOL), chunked ≡ whole blocked-evaluation
EXACT equality (non-divisible n), row_block cross-setting invariance,
config round-trip, and loose truth recovery.  Plus the kernel-level
batch-invariance pins for the meat forms whose stability is
shape-dispatched (core/moments._meat_gram and the iv_meat p=1 branch).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import CausalConfig
from repro.core.registry import ROW_BLOCK, SPEC_IDS, SPECS, tree_arrays

_FIT_KEY = jax.random.PRNGKey(0)
_DATA_KEY = jax.random.PRNGKey(42)
_data_cache = {}
# serial vs vmap replicates: an added batch axis may retile an f32
# n-contraction (XLA-build dependent), a reassociation of tens of ulps
SERIAL_VMAP_RTOL = 1e-5


def _data(spec):
    if spec.make_data not in _data_cache:
        _data_cache[spec.make_data] = spec.make_data(_DATA_KEY)
    return _data_cache[spec.make_data]


def _assert_trees_equal(a, b, msg=""):
    la, lb = tree_arrays(a), tree_arrays(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_chunked_equals_whole_bitwise(spec):
    """Blocked evaluation strategy is an execution detail: for the SAME
    row_block (non-divisible into n, so the zero-padding is exercised)
    the streamed and all-at-once evaluations must agree EXACTLY, all
    the way out to the estimator's public result arrays."""
    data = _data(spec)
    cfg_c = dataclasses.replace(spec.base_cfg, row_block=ROW_BLOCK,
                                row_block_strategy="chunked")
    cfg_w = dataclasses.replace(spec.base_cfg, row_block=ROW_BLOCK,
                                row_block_strategy="whole")
    r_c = spec.fit(data, cfg_c, _FIT_KEY)
    r_w = spec.fit(data, cfg_w, _FIT_KEY)
    _assert_trees_equal(r_c, r_w, f"{spec.name}: chunked != whole")


@pytest.mark.parametrize("backend", ["scatter", "interpret"])
@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_pallas_strategy_parity(spec, backend):
    """row_block_strategy="pallas" is tolerance-certified against the
    chunked reference for EVERY registry estimator: the fused seg_gram
    lowerings (XLA scatter on CPU, the Pallas kernel in interpret mode
    — the same kernel logic mosaic compiles on TPU) reassociate the
    Gram sums, so the contract is <= 1e-6 on the point estimate, not
    bitwise.  Non-divisible ROW_BLOCK exercises the padding path."""
    from repro.kernels.seg_gram import ops as sg_ops
    data = _data(spec)
    cfg_c = dataclasses.replace(spec.base_cfg, row_block=ROW_BLOCK,
                                row_block_strategy="chunked")
    cfg_p = dataclasses.replace(spec.base_cfg, row_block=ROW_BLOCK,
                                row_block_strategy="pallas")
    r_c = spec.fit(data, cfg_c, _FIT_KEY)
    with sg_ops.force_backend(backend):
        r_p = spec.fit(data, cfg_p, _FIT_KEY)
    np.testing.assert_allclose(spec.point(r_c), spec.point(r_p),
                               rtol=1e-6, atol=1e-6,
                               err_msg=f"{spec.name}[{backend}]")
    if hasattr(r_c, "theta"):
        np.testing.assert_allclose(np.asarray(r_c.theta),
                                   np.asarray(r_p.theta),
                                   rtol=1e-6, atol=1e-6,
                                   err_msg=f"{spec.name}[{backend}]")


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_row_block_invariance(spec):
    """Different row_block settings commute only up to float
    reassociation — the estimate must be invariant to tolerance."""
    data = _data(spec)
    r0 = spec.fit(data, spec.base_cfg, _FIT_KEY)
    rb = spec.fit(data, dataclasses.replace(spec.base_cfg,
                                            row_block=ROW_BLOCK),
                  _FIT_KEY)
    assert abs(spec.point(r0) - spec.point(rb)) < spec.rb_tol, spec.name
    if hasattr(r0, "theta"):
        np.testing.assert_allclose(np.asarray(r0.theta),
                                   np.asarray(rb.theta),
                                   rtol=2e-3, atol=2e-4,
                                   err_msg=spec.name)


@pytest.mark.parametrize(
    "spec", [s for s in SPECS if s.boot is not None],
    ids=[s.name for s in SPECS if s.boot is not None])
def test_serial_vmap_bit_identity(spec):
    """The executor contract: per-replicate estimates from the loop
    baseline and the batched program agree to float reassociation.
    Bit-identity is not structural: when ``vmap`` adds the replicate
    axis, XLA may retile a row-block Gram's n-contraction, and whether
    it does depends on the XLA build (the installed CPU backend does
    for dml and s_learner).  So the bound is SERIAL_VMAP_RTOL — tens of
    f32 ulps, far below any statistical scale."""
    data = _data(spec)
    r_ser = spec.boot(data, spec.boot_cfg, _FIT_KEY, "serial", 4)
    r_vec = spec.boot(data, spec.boot_cfg, _FIT_KEY, "vmap", 4)
    np.testing.assert_allclose(np.asarray(r_ser.replicates),
                               np.asarray(r_vec.replicates),
                               rtol=SERIAL_VMAP_RTOL, err_msg=spec.name)
    for attr in ("replicate_se", "ate_replicates"):
        a, b = getattr(r_ser, attr), getattr(r_vec, attr)
        assert (a is None) == (b is None), (spec.name, attr)
        if a is not None:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=SERIAL_VMAP_RTOL,
                                       err_msg=f"{spec.name}.{attr}")


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_config_round_trip(spec):
    """asdict -> CausalConfig(**d) is the identity, and the round-
    tripped config drives a bit-identical fit.  The sweep fields
    (segment_key / sweep_chunk) ride along with non-default values so
    the round trip covers them."""
    cfg = dataclasses.replace(spec.base_cfg, segment_key="cohort",
                              sweep_chunk=8)
    cfg2 = CausalConfig(**dataclasses.asdict(cfg))
    assert cfg2 == cfg
    assert (cfg2.segment_key, cfg2.sweep_chunk) == ("cohort", 8)
    data = _data(spec)
    _assert_trees_equal(spec.fit(data, cfg, _FIT_KEY),
                        spec.fit(data, cfg2, _FIT_KEY),
                        f"{spec.name}: config round-trip changed bits")


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_truth_recovery(spec):
    """Loose sanity floor: every estimator lands near its DGP's known
    estimand (tight statistical assertions live in the per-estimator
    modules and tests/test_oracle_properties.py)."""
    data = _data(spec)
    res = spec.fit(data, spec.base_cfg, _FIT_KEY)
    err = abs(spec.point(res) - spec.truth(data))
    assert err < spec.truth_tol, (spec.name, spec.point(res),
                                  spec.truth(data))


_META_IDS = ("s_learner", "t_learner", "x_learner")


@pytest.mark.parametrize("spec",
                         [s for s in SPECS if s.name in _META_IDS],
                         ids=list(_META_IDS))
def test_metalearner_ate_interval(spec):
    """Metalearner fits return EffectResult objects (shared engine
    layer), so they carry replicate ate_intervals like every other
    estimator — B weighted learner refits as one batched program."""
    data = _data(spec)
    cfg = dataclasses.replace(spec.base_cfg, inference="bootstrap",
                              n_bootstrap=8)
    res = spec.fit(data, cfg, _FIT_KEY)
    lo, hi = res.ate_interval()
    assert np.isfinite(lo) and np.isfinite(hi) and lo < hi
    assert lo - 0.3 < spec.truth(data) < hi + 0.3, spec.name
    # the metalearner CATE is not phi-linear: bands must refuse loudly
    with pytest.raises(ValueError):
        res.cate_interval(data.X)


# ---------------------------------------------------------------------------
# Kernel-level pins: the meat contractions whose batch invariance is
# shape-dispatched (XLA retiles computed-weight contractions
# differently per width — core/moments._meat_gram documents the
# measured regimes; this is the regression guard for that dispatch).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p", [1, 2, 3])
@pytest.mark.parametrize("kernel", ["residual", "iv"])
def test_meat_kernels_batch_invariant(kernel, p):
    """serial ≡ vmap for the meat kernels on the ROW-BLOCKED path (the
    canonical bit-identity contract: the scan barrier keeps the
    computed-weight contraction from refusing under batching; the
    whole-array forms are batch-invariant only at specific shapes —
    the p_phi = 1 legacy anchor lives in test_inference.py)."""
    from repro.core import moments
    from repro.inference import make_executor
    key = jax.random.PRNGKey(3)
    n = 1100
    ks = jax.random.split(key, 5)
    ry = jax.random.normal(ks[0], (n,))
    rt = jax.random.normal(ks[1], (n,))
    rz = jax.random.normal(ks[2], (n,))
    phi = jax.random.normal(ks[3], (n, p))
    W = jax.random.exponential(ks[4], (4, n))
    theta = jnp.arange(1.0, p + 1)
    if kernel == "residual":
        def fn(w):
            return moments.residual_meat(
                ry, rt, jnp.zeros_like(ry), jnp.zeros_like(rt), phi,
                theta, w=w, row_block=ROW_BLOCK)
    else:
        def fn(w):
            return moments.iv_meat(ry, rt, rz, phi, theta, w=w,
                                   row_block=ROW_BLOCK)
    ser = make_executor("serial").map(fn, W)
    vec = make_executor("vmap").map(fn, W)
    np.testing.assert_array_equal(np.asarray(ser), np.asarray(vec))
    # and the blocked strategies agree exactly (non-divisible n)
    kw = dict(w=W[0], row_block=ROW_BLOCK)
    if kernel == "residual":
        a = moments.residual_meat(ry, rt, jnp.zeros_like(ry),
                                  jnp.zeros_like(rt), phi, theta,
                                  strategy="chunked", **kw)
        b = moments.residual_meat(ry, rt, jnp.zeros_like(ry),
                                  jnp.zeros_like(rt), phi, theta,
                                  strategy="whole", **kw)
    else:
        a = moments.iv_meat(ry, rt, rz, phi, theta, strategy="chunked",
                            **kw)
        b = moments.iv_meat(ry, rt, rz, phi, theta, strategy="whole",
                            **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_iv_gram_slices_consistent():
    """iv_gram's slice map must reproduce the direct einsum forms.

    J and b are sums of n signed terms whose expectation is zero, so
    their values are O(sqrt(n)) while the f32 rounding of the two
    summation orders scales with sum |terms| = O(n): a relative bound
    alone fails on whichever entry happens to land near zero.  Each
    slice therefore also gets an absolute bound of 16 f32 epsilons of
    its own sum |terms| (both sides sum the same products in f32)."""
    from repro.core import moments
    key = jax.random.PRNGKey(5)
    n, p = 777, 2
    ks = jax.random.split(key, 5)
    ry = jax.random.normal(ks[0], (n,))
    rt = jax.random.normal(ks[1], (n,))
    rz = jax.random.normal(ks[2], (n,))
    phi = jax.random.normal(ks[3], (n, p))
    w = jax.random.exponential(ks[4], (n,))
    Gaug, n_eff = moments.iv_gram(ry, rt, rz, phi, w)
    J, b, Szz, Stt = moments.iv_slices(Gaug, p)
    eps = float(np.finfo(np.float32).eps)
    ph = np.asarray(phi)

    def check(got, u, form):
        u = np.asarray(u)
        ops = (ph, ph) if form == "n,ni,nj->ij" else (ph,)
        want = np.einsum(form, u, *ops)
        scale = np.einsum(form, np.abs(u), *(np.abs(o) for o in ops))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=16 * eps * float(scale.max()))

    check(J, w * rz * rt, "n,ni,nj->ij")
    check(b, w * rz * ry, "n,ni->i")
    check(Szz, w * rz * rz, "n,ni,nj->ij")
    check(Stt, w * rt * rt, "n,ni,nj->ij")
    assert float(n_eff) == pytest.approx(float(w.sum()))
    # chunked ≡ whole, non-divisible n
    a = moments.iv_gram(ry, rt, rz, phi, w, row_block=ROW_BLOCK,
                        strategy="chunked")
    bb = moments.iv_gram(ry, rt, rz, phi, w, row_block=ROW_BLOCK,
                         strategy="whole")
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(bb[0]))

"""Compile rehearsal of the main-path seg_gram kernels for a TPU v5e.

The TPU compiler ships with jaxlib, so a described (not attached)
``v5e:2x2`` topology lets these tests compile the Mosaic kernel at the
widths ``chip_smoke.py`` runs — what interpret mode cannot show (VMEM
limits, tile alignment, device-memory fit) fails here at no chip time.
Nothing executes.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library at a time, and every xdist
worker imports this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.inference.numerics import det_solve
from repro.kernels.seg_gram import kernel as sg_kernel
from repro.kernels.seg_gram import ref as sg_ref

HBM_BYTES = 16e9  # one v5e chip
N_FIT = 1_000_000  # chip_smoke.py's Fit and Sweep row count


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # compiles for a described chip cannot be read back from the
    # persistent cache without one; keep this module off it
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        # a topology that cannot be described fails the module: these
        # tests are the kernels' only compile check before the chip
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", prior)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_plain(fn, *specs):
    """Compile a program for the chip and check it fits the device."""
    compiled = jax.jit(fn).lower(*specs).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, (total, mem)
    return compiled


def _compile(fn, *specs):
    compiled = _compile_plain(fn, *specs)
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _kernel(builder, n_segments=1):
    def fn(*arrays):
        *data, seg = arrays if n_segments > 1 else (*arrays, None)
        return sg_kernel.seg_gram_pallas(builder, list(data), seg=seg,
                                         n_segments=n_segments,
                                         interpret=False)
    return fn


def test_final_stage_residual_s1(one_chip):
    """The DML final stage at the Fit's n (p_phi = 1); the kernel op
    carries the form's name and stays a ``tpu_custom_call``."""
    cols = [_spec((N_FIT, 1), one_chip) for _ in range(4)]
    compiled = _compile(_kernel(sg_ref.build_residual), *cols,
                        _spec((N_FIT, 1), one_chip))
    assert "%seg_gram_residual." in compiled.as_text()


def test_fold_gram_paper_width(one_chip):
    """fold_gram at the paper's Fig. 6 width: S = K = 5, q = 501."""
    compiled = _compile(_kernel(sg_ref.build_design, 5),
                        _spec((N_FIT, 501), one_chip),
                        _spec((N_FIT, 1), one_chip, jnp.int32))
    assert "%seg_gram_design_seg." in compiled.as_text()


def test_crossfit_design_gram_vmapped_over_folds(one_chip):
    """The parallel cross-fit: the S = 1 design Gram (q = 502) vmapped
    over the K = 5 fold-complement weight columns."""
    def fn(D, W):
        return jax.vmap(lambda w: sg_kernel.seg_gram_pallas(
            sg_ref.build_design, [D], w=w, interpret=False))(W)
    _compile(fn, _spec((N_FIT, 502), one_chip),
             _spec((5, N_FIT, 1), one_chip))


def test_bootstrap_fold_weighted_kron(one_chip):
    """The bootstrap replicate's fold-weighted Gram: the kron builder
    widens L to K·q = 2510 columns (q = 502)."""
    _compile(_kernel(sg_ref.build_fold_weighted),
             _spec((N_FIT, 5), one_chip), _spec((N_FIT, 502), one_chip))


def test_bootstrap_shared_nuisance_gram_kron(one_chip):
    """Both ridge nuisances of a replicate from one fold-weighted Gram
    over [X | 1 | y | t]: q = 503, K·q = 2515 columns."""
    compiled = _compile(_kernel(sg_ref.build_fold_weighted),
                        _spec((N_FIT, 5), one_chip),
                        _spec((N_FIT, 503), one_chip))
    assert "%seg_gram_fold_weighted." in compiled.as_text()


def test_bootstrap_replicate_grouped_fold_grams(one_chip, monkeypatch):
    """The ridge bootstrap replicate at the Fit's size (n = 1M, p = 500,
    K = 5, the pairs scheme) as the runtime runs it, two replicates a
    chunk: its fold Grams come from rows grouped by fold, so the program
    holds the S = 1 design kernel and no kron form.  Its temp is 3.07 GB
    a replicate, as the kron form's was, so a 60% budget of a v5e's
    16 GB still takes chunks of 2."""
    from repro.core.nuisance import make_ridge
    from repro.inference.bootstrap import make_dml_replicate_fn
    from repro.kernels.seg_gram import ops as sg_ops

    monkeypatch.setattr(sg_ops.jax, "default_backend", lambda: "tpu")
    p, K, rb, chunk = 500, 5, 8192, 2
    ny, nt = (make_ridge(1e-3, rb, "pallas") for _ in range(2))
    fn = make_dml_replicate_fn(ny, nt, K, row_block=rb, strategy="pallas")

    def batched(kb, X, y, t, phi):
        return jax.vmap(lambda k: fn(k, X, y, t, phi))(kb)

    row = _spec((N_FIT,), one_chip)
    with sg_ops.force_backend("pallas"):
        compiled = _compile(batched, _spec((chunk, 2), one_chip, jnp.uint32),
                            _spec((N_FIT, p), one_chip), row, row,
                            _spec((N_FIT, 1), one_chip))
    text = compiled.as_text()
    assert "%seg_gram_design." in text
    assert "seg_gram_fold_weighted" not in text
    per_replicate = compiled.memory_analysis().temp_size_in_bytes / chunk
    assert per_replicate < 3.2e9, per_replicate


@pytest.mark.parametrize("qU,qV", [(1, 51), (52, 52), (106, 106)])
def test_sweep_segment_outer_s320(one_chip, qU, qV):
    """S = E·K = 320 cells at E = 64, K = 5, p = 50: the sweep's MM
    gradient segment_outer (qU = 1) and fold Gram (q = 52), and the
    store's final-stage Gram phi (x) [X | 1 | t | y] (q = 2·53 = 106).
    Untiled, that last one's (320·112, 128) accumulator needs 40 MB of
    VMEM even at 8-row blocks; the segment tiles keep it in budget."""
    _compile(_kernel(sg_ref.build_pair, 320),
             _spec((N_FIT, qU), one_chip), _spec((N_FIT, qV), one_chip),
             _spec((N_FIT, 1), one_chip, jnp.int32))


def test_iv_gram(one_chip):
    """The instrumented augmented Gram M = [rz·φ | rt·φ | ry]."""
    cols = [_spec((N_FIT, 1), one_chip) for _ in range(3)]
    _compile(_kernel(sg_ref.build_iv), *cols, _spec((N_FIT, 2), one_chip))


def test_bootstrap_det_solve_panelled(one_chip):
    """The replicate chunk's ridge solves at the Fig. 6 width: det_solve
    over 2 replicates x K = 5 folds of p + 1 = 501 unknowns, panelled.
    Plain XLA, no kernel.  Its scratch stays within a few carries: a
    layout that puts the batch axes minor pads (2, 5) to a (2, 128)
    tile, 25 times the carry."""
    compiled = _compile_plain(jax.vmap(jax.vmap(det_solve)),
                              _spec((2, 5, 501, 501), one_chip),
                              _spec((2, 5, 501), one_chip))
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4 * mem.argument_size_in_bytes, mem


def test_bootstrap_det_solve_two_right_hand_sides(one_chip):
    """The shared nuisance Gram's elimination: both ridges' right-hand
    sides over 2 replicates x K = 5 folds of 501 unknowns, panelled,
    in the one-column solve's scratch bound."""
    compiled = _compile_plain(jax.vmap(jax.vmap(det_solve)),
                              _spec((2, 5, 501, 501), one_chip),
                              _spec((2, 5, 501, 2), one_chip))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4 * mem.argument_size_in_bytes, mem


def test_sweep_mm_step_fused(one_chip):
    """One MM logistic step of the binary uplift sweep (n = 4,194,304,
    p = 12, E = 64 cohorts x K = 5 folds): the lane-major kernel reads
    [X | 1]ᵀ and [t; cohort + 1; fold] with the (E, K, q) coefficients
    resident.  Its temp stays under the n·K·q floats of per-row
    coefficients, which the gather ``beta[sids]`` it replaces took
    (16 GB in (8, 128) tiles)."""
    n, E, K, q = 4_194_304, 64, 5, 13

    def step(xa_t, meta_t, coef):
        table = jnp.transpose(coef, (1, 2, 0)).reshape(K * q, E)
        return sg_kernel.seg_gram_lanes(sg_ref.build_mm_logistic,
                                        [xa_t, meta_t, table], interpret=False)

    compiled = _compile(step, _spec((q, n), one_chip), _spec((3, n), one_chip),
                        _spec((E, K, q), one_chip))
    assert "%seg_gram_mm_logistic." in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < n * K * q * 4


def test_store_window_refresh_and_ingest(one_chip, monkeypatch):
    """The windowed store at ``panel_daily``'s size (28 days of 499,712
    rows, p = 12, E = 64 x K = 5): the refresh program, 32 fused MM
    steps and the sweep's final stage over the 14.0M rows of the ring,
    fits well under 12 GB (9.14 GB when written); a day's ingest writes
    its slot and its rows into the donated ring in place."""
    from repro.config import CausalConfig
    from repro.core.final_stage import cate_basis
    from repro.kernels.seg_gram import ops as sg_ops
    from repro.store import stats
    from repro.store.solve import refresh_rows
    from repro.store.store import _row_folds

    # steer the seg_gram dispatch to the Mosaic kernels while JAX runs
    # on the CPU (the programs are compiled for the described chip)
    monkeypatch.setattr(sg_ops.jax, "default_backend", lambda: "tpu")
    days, rows, p, E, K = 28, 499_712, 12, 64, 5
    cfg = CausalConfig(n_folds=K, nuisance_t="logistic",
                       discrete_treatment=True, newton_iters=16,
                       row_block=8192, row_block_strategy="pallas",
                       inference="none")
    lay = stats.ColumnLayout(p=p, pf=1, k=K, iv=False, rows=True)
    state = {"ng": _spec((days, E * K, lay.qd, lay.qd), one_chip),
             "counts": _spec((days, E * K), one_chip),
             "xa": _spec((lay.q, days * rows), one_chip),
             "meta": _spec((4, days * rows), one_chip)}
    slot = _spec((), one_chip, jnp.int32)
    with sg_ops.force_backend("pallas"):
        refresh = _compile(
            lambda st, s: refresh_rows(lay, cfg, st, s, E, rows), state, slot)
        text = refresh.as_text()
        assert "%seg_gram_mm_logistic." in text
        assert "%seg_gram_pair_seg." in text
        mem = refresh.memory_analysis()
        assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                < 12e9), mem

        def day(st, X, t, y, sids, start, key, s):
            folds = _row_folds(key, start, rows, K)
            return stats.ingest_day(lay, st, s, X, t, y, None,
                                    cate_basis(X, 1), sids, folds, E * K,
                                    row_block=8192, strategy="pallas")

        ingest = jax.jit(day, donate_argnums=(0,)).lower(
            state, _spec((rows, p), one_chip), _spec((rows,), one_chip),
            _spec((rows,), one_chip), _spec((rows,), one_chip, jnp.int32),
            _spec((), one_chip, jnp.uint32),
            _spec((2,), one_chip, jnp.uint32), slot).compile()
    mem = ingest.memory_analysis()
    assert mem.alias_size_in_bytes >= days * rows * (lay.q + 4) * 4, mem
    assert mem.temp_size_in_bytes < 1e9, mem

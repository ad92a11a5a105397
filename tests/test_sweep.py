"""repro.sweep certification: the segment-parallel panel against the
loop of single fits it replaces.

Contracts:
  * cells mode equals ``serial_loop`` (a Python loop of masked
    single-estimator fits) to float reassociation (SERIAL_VMAP_RTOL) at
    the canonical row-blocked conformance shapes, for EVERY sweepable
    registry estimator;
  * runtime-chunked scheduling of the cell axis changes nothing — the
    chunked and whole-batch panels are exactly equal;
  * zero-row segments produce flagged (ok=False) finite cells and do
    not perturb any other cell;
  * one failing column does not poison the panel (per-column fault
    isolation), and the surviving columns stay bit-exact;
  * shared-nuisance reuse (columns differing only in final stage) is
    bitwise the per-cell fit with the group's key lineage;
  * the segmented one-pass path equals a gathered per-segment
    LOO-kernel reference to float tolerance (it shares one fold draw
    across cells — a different execution of the same estimator, like
    engine="parallel_loo").
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import CausalConfig
from repro.core.registry import ROW_BLOCK, get_spec
from repro.data.causal_dgp import make_causal_data, make_iv_data
from repro.sweep import SweepSpec, serial_loop, sweep
from repro.sweep.segmented import segmented_dml_sweep

# cells panel vs serial loop: the vmapped cell axis may retile an f32
# n-contraction (XLA-build dependent), a reassociation of tens of ulps
SERIAL_VMAP_RTOL = 1e-5

N, E = 1100, 5
_KEY = jax.random.PRNGKey(3)
_CFG = CausalConfig(n_folds=3, inference="none", row_block=ROW_BLOCK)

SWEEPABLE = ("dml", "drlearner", "s_learner", "t_learner", "x_learner",
             "orthoiv", "driv")


@pytest.fixture(scope="module")
def data():
    return make_causal_data(jax.random.PRNGKey(42), N, 6, effect=1.2)


@pytest.fixture(scope="module")
def iv_data():
    return make_iv_data(jax.random.PRNGKey(42), N, 6, effect=1.2,
                        compliance=0.75)


@pytest.fixture(scope="module")
def sids():
    return jax.random.randint(jax.random.PRNGKey(9), (N,), 0, E)


def _kw(name, data, iv_data, sids):
    d = iv_data if get_spec(name).needs_instrument else data
    kw = dict(X=d.X, y=d.y, t=d.t, segment_ids=sids, key=_KEY)
    if get_spec(name).needs_instrument:
        kw["z"] = d.z
    return kw


@pytest.mark.parametrize("name", SWEEPABLE)
def test_panel_equals_serial_loop_bitwise(name, data, iv_data, sids):
    """The acceptance contract: the batched panel is the loop of single
    fits at the canonical row-blocked shapes, to float reassociation —
    the vmapped cell axis may retile an f32 n-contraction (XLA-build
    dependent), so the bound is SERIAL_VMAP_RTOL, tens of ulps."""
    kw = _kw(name, data, iv_data, sids)
    spec = SweepSpec(n_segments=E, columns=((name, _CFG),))
    panel = sweep(spec, executor="vmap", **kw)
    loop = serial_loop(name, _CFG, n_segments=E, **kw)
    col = panel.columns[0]
    assert not col.failed
    np.testing.assert_allclose(np.asarray(col.thetas),
                               np.asarray(loop["theta"]),
                               rtol=SERIAL_VMAP_RTOL, err_msg=name)
    np.testing.assert_allclose(np.asarray(col.ates),
                               np.asarray(loop["ate"]),
                               rtol=SERIAL_VMAP_RTOL, err_msg=name)
    if col.ses is not None and "se" in loop:
        np.testing.assert_allclose(np.asarray(col.ses),
                                   np.asarray(loop["se"]),
                                   rtol=SERIAL_VMAP_RTOL, err_msg=name)
    assert bool(col.ok(panel.counts).all())


def test_chunked_equals_whole_panel(data, sids):
    """Runtime-chunked scheduling of the cell axis (sweep_chunk) is an
    execution detail: exactly equal to the whole-batch panel."""
    kw = dict(X=data.X, y=data.y, t=data.t, segment_ids=sids, key=_KEY)
    whole = sweep(SweepSpec(n_segments=E, columns=(("dml", _CFG),)),
                  executor="vmap", **kw)
    cfg_c = dataclasses.replace(_CFG, sweep_chunk=2)
    chunked = sweep(SweepSpec(n_segments=E, columns=(("dml", cfg_c),)),
                    executor="vmap", **kw)
    assert any(ev.startswith("chunk") for ev in chunked.columns[0].events)
    np.testing.assert_array_equal(np.asarray(whole.columns[0].thetas),
                                  np.asarray(chunked.columns[0].thetas))
    np.testing.assert_array_equal(np.asarray(whole.columns[0].ses),
                                  np.asarray(chunked.columns[0].ses))


@pytest.mark.parametrize("name", ("dml", "t_learner"))
def test_zero_row_segment(name, data, sids):
    """A segment with no rows yields a flagged finite cell; every
    populated cell keeps its exact estimate."""
    sids0 = jnp.where(sids == 2, 1, sids)       # segment 2 emptied
    kw = dict(X=data.X, y=data.y, t=data.t, segment_ids=sids0, key=_KEY)
    panel = sweep(SweepSpec(n_segments=E, columns=((name, _CFG),)),
                  executor="vmap", **kw)
    col = panel.columns[0]
    ok = np.asarray(col.ok(panel.counts))
    assert int(panel.counts[2]) == 0 and not ok[2]
    assert ok[[0, 1, 3, 4]].all()
    assert np.isfinite(np.asarray(col.thetas)).all()
    loop = serial_loop(name, _CFG, n_segments=E, **kw)
    np.testing.assert_array_equal(np.asarray(col.thetas)[ok],
                                  np.asarray(loop["theta"])[ok])


def test_fault_isolation(data, sids):
    """A column that cannot even build (unknown nuisance) is recorded
    as failed; its neighbors keep bit-exact estimates."""
    bad = dataclasses.replace(_CFG, nuisance_y="nope")
    spec = SweepSpec(n_segments=E,
                     columns=(("dml", bad), ("dml", _CFG)))
    kw = dict(X=data.X, y=data.y, t=data.t, segment_ids=sids, key=_KEY)
    panel = sweep(spec, executor="vmap", **kw)
    assert panel.columns[0].failed
    assert "nope" in panel.columns[0].error
    assert not panel.columns[1].failed
    loop = serial_loop("dml", _CFG, n_segments=E, col_index=1, **kw)
    np.testing.assert_array_equal(np.asarray(panel.columns[1].thetas),
                                  np.asarray(loop["theta"]))
    assert panel.failures() == ((0, panel.columns[0].error),)
    # NaN column in the table, not an exception
    table = np.asarray(panel.ate_table())
    assert np.isnan(table[:, 0]).all() and np.isfinite(table[:, 1]).all()


def test_missing_instrument_isolated(data, sids):
    """An IV column without z fails alone; the DML column survives."""
    spec = SweepSpec(n_segments=E,
                     columns=(("orthoiv", _CFG), ("dml", _CFG)))
    panel = sweep(spec, X=data.X, y=data.y, t=data.t, segment_ids=sids,
                  key=_KEY, executor="vmap")
    assert panel.columns[0].failed and "instrument" in panel.columns[0].error
    assert not panel.columns[1].failed


def test_shared_nuisance_reuse_bitwise(data, sids):
    """Columns differing only in final stage share one residual pass —
    and still equal the per-cell single fits (group key lineage) bit
    for bit."""
    cfg2 = dataclasses.replace(_CFG, cate_features=2)
    spec = SweepSpec(n_segments=E,
                     columns=(("dml", _CFG), ("dml", cfg2)))
    kw = dict(X=data.X, y=data.y, t=data.t, segment_ids=sids, key=_KEY)
    panel = sweep(spec, executor="vmap", reuse=True, **kw)
    assert [c.shared_nuisance for c in panel.columns] == [False, True]
    assert panel.columns[1].key_index == 0
    for col, cfg in zip(panel.columns, (_CFG, cfg2)):
        loop = serial_loop("dml", cfg, n_segments=E, col_index=0, **kw)
        np.testing.assert_array_equal(np.asarray(col.thetas),
                                      np.asarray(loop["theta"]))
    # and reuse=False reproduces the plain per-column panel
    plain = sweep(spec, executor="vmap", reuse=False, **kw)
    assert not any(c.shared_nuisance for c in plain.columns)


def test_shared_group_member_failure_isolated(data, sids, monkeypatch):
    """One member of a shared-nuisance group failing (here: its CI
    dispatch) must not discard its siblings' computed columns — the
    shared residual pass alone is group-fatal."""
    import repro.sweep.engine as eng

    def boom(*args, **kwargs):
        raise RuntimeError("synthetic CI failure")

    monkeypatch.setattr(eng, "_column_ci", boom)
    cfg2 = dataclasses.replace(_CFG, cate_features=2,
                               inference="bootstrap", n_bootstrap=4)
    spec = SweepSpec(n_segments=E, columns=(("dml", _CFG), ("dml", cfg2)))
    panel = sweep(spec, X=data.X, y=data.y, t=data.t, segment_ids=sids,
                  key=_KEY, executor="vmap", reuse=True)
    assert not panel.columns[0].failed
    assert panel.columns[1].failed
    assert "synthetic" in panel.columns[1].error
    loop = serial_loop("dml", _CFG, X=data.X, y=data.y, t=data.t,
                       segment_ids=sids, n_segments=E, key=_KEY,
                       col_index=0)
    np.testing.assert_array_equal(np.asarray(panel.columns[0].thetas),
                                  np.asarray(loop["theta"]))


def test_sweep_bootstrap_ci(data, sids):
    """(cell × replicate) draws through map_product: per-cell CIs with
    ordered finite bounds and the full replicate tensor attached."""
    cfg = dataclasses.replace(_CFG, inference="bootstrap", n_bootstrap=8)
    panel = sweep(SweepSpec(n_segments=E, columns=(("dml", cfg),)),
                  X=data.X, y=data.y, t=data.t, segment_ids=sids,
                  key=_KEY, executor="vmap")
    col = panel.columns[0]
    assert col.replicates.shape == (E, 8, 1)
    assert col.ci_lo.shape == (E,) and col.ci_hi.shape == (E,)
    assert np.isfinite(np.asarray(col.ci_lo)).all()
    assert bool((col.ci_lo < col.ci_hi).all())


def test_segmented_matches_gathered_loo_reference(data):
    """The one-pass segmented path = per-segment gathered fits with the
    SAME shared folds and the SAME LOO/MM kernels, to float tolerance
    (different summation order only)."""
    from repro.core.crossfit import _oof_select, fold_ids
    from repro.core.final_stage import cate_basis
    from repro.core.nuisance import logistic_fit_folds, ridge_fit_folds
    from repro.inference.numerics import det_solve

    e_seg, k = 3, 3
    cfg = CausalConfig(n_folds=k)
    sids3 = jax.random.randint(jax.random.PRNGKey(11), (N,), 0, e_seg)
    key = jax.random.PRNGKey(7)
    out = segmented_dml_sweep(cfg, data.X, data.y, data.t, sids3, e_seg,
                              key)
    folds = fold_ids(key, N, k)
    f32 = jnp.float32

    def aug(x):
        return jnp.concatenate([x, jnp.ones((x.shape[0], 1), x.dtype)],
                               axis=1)

    for s in range(e_seg):
        m = np.asarray(sids3) == s
        xs, ys = data.X[m], data.y[m]
        ts, fs = data.t[m], folds[m]
        sty = ridge_fit_folds(cfg.ridge_lambda, xs, ys, fs, k)
        my = _oof_select(jnp.einsum("kp,np->kn", sty["beta"],
                                    aug(xs.astype(f32))), fs)
        stt = logistic_fit_folds(cfg.ridge_lambda, 2 * cfg.newton_iters,
                                 xs, ts.astype(f32), fs, k)
        mt = _oof_select(jax.nn.sigmoid(
            jnp.einsum("kp,np->kn", stt["beta"], aug(xs.astype(f32)))),
            fs)
        ry, rt = ys.astype(f32) - my, ts.astype(f32) - mt
        phi = cate_basis(xs, cfg.cate_features)
        z = rt[:, None] * phi
        mm = jnp.concatenate([z, ry[:, None]], axis=1)
        g = mm.T @ mm
        p = phi.shape[1]
        a = g[:p, :p] + 1e-8 * xs.shape[0] * jnp.eye(p)
        ref = det_solve(a, g[:p, p])
        np.testing.assert_allclose(np.asarray(out["theta"][s]),
                                   np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_segmented_mode_through_engine(data, sids):
    """mode='segmented' routes DML columns onto the one-pass kernels
    (tagged in events) and recovers the effect on every segment."""
    panel = sweep(SweepSpec(n_segments=E, columns=(("dml", _CFG),)),
                  X=data.X, y=data.y, t=data.t, segment_ids=sids,
                  key=_KEY, mode="segmented")
    col = panel.columns[0]
    assert col.events == ("segmented",)
    assert np.isfinite(np.asarray(col.thetas)).all()
    assert np.abs(np.asarray(col.ates) - 1.2).max() < 0.6  # ~220 rows/seg
    # unsupported configs fall back to cells (still bit-exact vs loop)
    mlp_cfg = dataclasses.replace(_CFG, nuisance_y="mlp", mlp_steps=5,
                                  mlp_hidden=(8,))
    panel2 = sweep(SweepSpec(n_segments=E, columns=(("dml", mlp_cfg),)),
                   X=data.X, y=data.y, t=data.t, segment_ids=sids,
                   key=_KEY, mode="segmented", executor="vmap")
    assert panel2.columns[0].events != ("segmented",)
    assert np.isfinite(np.asarray(panel2.columns[0].thetas)).all()


def test_spec_validation():
    with pytest.raises(ValueError):
        SweepSpec(n_segments=0, columns=(("dml", _CFG),))
    with pytest.raises(ValueError):
        SweepSpec(n_segments=4, columns=())
    spec = SweepSpec.grid(4, estimators=("dml", "drlearner"),
                          configs=(_CFG,))
    assert spec.n_cells == 8 and len(spec.columns) == 2


def test_unknown_estimator_is_isolated(data, sids):
    panel = sweep(SweepSpec(n_segments=E, columns=(("nope", _CFG),)),
                  X=data.X, y=data.y, t=data.t, segment_ids=sids,
                  key=_KEY)
    assert panel.columns[0].failed
    assert "nope" in panel.columns[0].error


def test_panel_summary(data, sids):
    cfg = dataclasses.replace(_CFG, segment_key="cohort")
    spec = SweepSpec.grid(E, estimators=("dml",), configs=(cfg,))
    panel = sweep(spec, X=data.X, y=data.y, t=data.t, segment_ids=sids,
                  key=_KEY, executor="vmap")
    s = panel.summary()
    assert "cohort" in s and f"{E} segments" in s
    assert panel.ate_table().shape == (E, 1)
    assert panel.ok().shape == (E, 1)

"""Certification of the row-sharded data-mesh path
(repro.runtime.distributed) against the single-process chunked
baseline it accelerates.

Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
CI tier1-dist leg) for a real 8-shard mesh; on a plain 1-device host
the mesh degrades to (1, 1) and every contract still holds on the
same code path.

Contracts:
  * ``reduction="ordered"`` is BITWISE: every registry estimator's
    full fit under ``use_data_mesh`` equals the single-process chunked
    fit at the canonical conformance shapes, and the blocked moments
    entry points match at several row_blocks including non-divisible
    row counts (the padded-block path);
  * ``init``-seeded reductions replay the same left fold —
    ``MomentStore.ingest`` sharded ≡ serial bitwise on aligned blocks;
  * ``reduction="psum"`` is tolerance-grade (documented, not bitwise);
  * a lost shard downgrades through the runtime ladder to the
    single-host rung with the SAME bits (default retry budget), and
    with a zero retry budget costs exactly one sweep column — resume
    through the checkpoint recomputes only that column;
  * the job API (submit / poll / subscribe) streams one event per
    column and returns the same panel ``sweep`` would.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.config import CausalConfig
from repro.core import moments
from repro.core.registry import ROW_BLOCK, SPEC_IDS, SPECS, tree_arrays
from repro.runtime import (
    JobManager,
    dist_reduce,
    inject_shard_failure,
    make_data_mesh,
    use_data_mesh,
)
from repro.store import MomentStore
from repro.sweep import SweepSpec, sweep

N = 1100  # the conformance row count: non-divisible into ROW_BLOCK
_FIT_KEY = jax.random.PRNGKey(0)
_DATA_KEY = jax.random.PRNGKey(42)
_data_cache = {}


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


@pytest.fixture(scope="module")
def dm():
    return make_data_mesh()


@pytest.fixture(scope="module")
def arrs():
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    return dict(
        X=jax.random.normal(ks[0], (N, 5)),
        w=jax.random.exponential(ks[1], (N,)).astype(jnp.float32),
        folds=jax.random.randint(ks[2], (N,), 0, 4),
        ry=jax.random.normal(ks[3], (N,)),
        rt=jax.random.normal(ks[4], (N,)),
        rz=jax.random.normal(ks[5], (N,)),
    )


def test_mesh_shape_adapts_to_devices(dm):
    """The default mesh spans every visible device — 8 under the
    forced-8 CI leg, (1, 1) on a plain host — and says so in its
    label."""
    assert dm.n_shards == jax.device_count()
    assert dm.label.endswith(":ordered")
    with pytest.raises(ValueError):
        make_data_mesh(reduction="median")
    with pytest.raises(ValueError):
        dist_reduce(lambda x: x.sum(0), [jnp.ones((8, 2))], row_block=4)


# ---------------------------------------------------------------------------
# The tentpole certificate: registry-wide bitwise identity.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_registry_fit_sharded_bitwise(spec, dm):
    """EVERY registry estimator: the full fit with the data mesh active
    is bit-for-bit the single-process chunked fit at the canonical
    row-blocked shapes."""
    data = _data(spec)
    cfg = dataclasses.replace(spec.base_cfg, row_block=ROW_BLOCK,
                              row_block_strategy="chunked")
    r_single = spec.fit(data, cfg, _FIT_KEY)
    with use_data_mesh(dm):
        r_dist = spec.fit(data, cfg, _FIT_KEY)
    _assert_trees_equal(r_single, r_dist, spec.name)


@pytest.mark.parametrize("rb", [256, 128, 64])
def test_moments_ordered_bitwise(arrs, dm, rb):
    """The blocked moments entry points at several row_blocks (N=1100
    never divides evenly — the padded-tail-and-extra-blocks path):
    sharded ordered reduction ≡ chunked, bitwise."""
    a = arrs
    ref_wg = moments.weighted_gram(a["X"], a["w"], intercept=True,
                                   row_block=rb, strategy="chunked")
    ref_fg = moments.fold_gram(a["X"], a["folds"], 4, intercept=True,
                               row_block=rb, strategy="chunked")
    ref_iv = moments.iv_gram(a["ry"], a["rt"], a["rz"], a["X"], a["w"],
                             row_block=rb, strategy="chunked")
    with use_data_mesh(dm):
        got_wg = moments.weighted_gram(a["X"], a["w"], intercept=True,
                                       row_block=rb, strategy="chunked")
        got_fg = moments.fold_gram(a["X"], a["folds"], 4, intercept=True,
                                   row_block=rb, strategy="chunked")
        got_iv = moments.iv_gram(a["ry"], a["rt"], a["rz"], a["X"],
                                 a["w"], row_block=rb, strategy="chunked")
    _assert_trees_equal(ref_wg, got_wg, f"weighted_gram rb={rb}")
    _assert_trees_equal(ref_fg, got_fg, f"fold_gram rb={rb}")
    _assert_trees_equal(ref_iv, got_iv, f"iv_gram rb={rb}")


def test_dist_reduce_init_seeded_bitwise(arrs, dm):
    """``init`` seeds the ordered fold exactly like blocked_reduce —
    the store-ingest hook."""
    a = arrs

    def block(Xb, wb):
        return (wb[:, None].astype(jnp.float32) * Xb).T @ Xb

    seed = jnp.full((5, 5), 0.25, jnp.float32)
    ref = moments.blocked_reduce(block, (a["X"], a["w"]), row_block=128,
                                 strategy="chunked", init=seed)
    got = dist_reduce(block, (a["X"], a["w"]), row_block=128, dm=dm,
                      init=seed)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_psum_mode_tolerance(arrs, dm):
    """The wire-efficient psum mode reassociates — tolerance-grade
    against chunked, by design."""
    a = arrs

    def block(Xb, wb):
        return (wb[:, None].astype(jnp.float32) * Xb).T @ Xb

    ref = moments.blocked_reduce(block, (a["X"], a["w"]), row_block=128,
                                 strategy="chunked")
    got = dist_reduce(block, (a["X"], a["w"]), row_block=128, dm=dm,
                      reduction="psum")
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=2e-5, atol=2e-4)


# ---------------------------------------------------------------------------
# Fault tolerance: lost shards.
# ---------------------------------------------------------------------------

def _sweep_kw(n_segments=3):
    d = _data(SPECS[0])
    sids = jax.random.randint(jax.random.PRNGKey(9), (N,), 0, n_segments)
    return dict(X=d.X, y=d.y, t=d.t, segment_ids=sids, key=_FIT_KEY)


_CFG = CausalConfig(n_folds=3, inference="none", row_block=ROW_BLOCK)


def test_lost_shard_downgrades_to_single_host_bitwise(dm):
    """Default retry budget: a shard lost at trace time drops the chunk
    to the plain single-host rung — SAME bits as the no-mesh run, with
    the downgrade recorded on the column's events."""
    kw = _sweep_kw()
    spec = SweepSpec(n_segments=3, columns=(("dml", _CFG),))
    plain = sweep(spec, **kw).columns[0]
    inject_shard_failure(1)
    try:
        col = sweep(spec, data_mesh=dm, **kw).columns[0]
    finally:
        inject_shard_failure(0)
    assert not col.failed
    assert any(ev.startswith("downgrade:") for ev in col.events), col.events
    np.testing.assert_array_equal(np.asarray(plain.thetas),
                                  np.asarray(col.thetas))
    np.testing.assert_array_equal(np.asarray(plain.ates),
                                  np.asarray(col.ates))


def test_lost_shard_costs_one_column_and_resumes(tmp_path, dm):
    """Zero retry budget on the struck column: the loss is isolated to
    that column (its group neighbor lands bitwise), and re-running the
    sweep against the same checkpoint directory recomputes ONLY the
    lost column."""
    kw = _sweep_kw()
    cfg_fragile = dataclasses.replace(_CFG, runtime_max_retries=0)
    spec = SweepSpec(n_segments=3, columns=(("dml", cfg_fragile),
                                            ("drlearner", _CFG)))
    plain = sweep(spec, **kw)
    mgr = CheckpointManager(str(tmp_path / "ckpt"))

    inject_shard_failure(1)
    try:
        struck = sweep(spec, data_mesh=dm, checkpoint=mgr, **kw)
    finally:
        inject_shard_failure(0)
    assert struck.columns[0].failed
    assert "injected shard failure" in struck.columns[0].error
    assert not struck.columns[1].failed  # at most ONE column lost
    np.testing.assert_array_equal(np.asarray(plain.columns[1].thetas),
                                  np.asarray(struck.columns[1].thetas))

    # resume: the surviving column restores from disk, the lost one
    # recomputes (errored checkpoints never restore) and now succeeds
    recovered = sweep(spec, data_mesh=dm, checkpoint=mgr, **kw)
    assert not recovered.columns[0].failed
    assert "restored" not in recovered.columns[0].events
    assert "restored" in recovered.columns[1].events
    np.testing.assert_array_equal(np.asarray(plain.columns[0].thetas),
                                  np.asarray(recovered.columns[0].thetas))
    np.testing.assert_array_equal(np.asarray(plain.columns[1].thetas),
                                  np.asarray(recovered.columns[1].thetas))


def test_elastic_sweep_helper(tmp_path, dm):
    """launch.elastic.elastic_sweep: one call = checkpointed sweep; the
    second call restores every column bitwise without recomputing."""
    from repro.launch.elastic import elastic_sweep, sweep_checkpoint_manager

    kw = _sweep_kw()
    spec = SweepSpec(n_segments=3, columns=(("dml", _CFG),))
    mgr = sweep_checkpoint_manager(str(tmp_path / "ck"), spec)
    assert mgr.keep_latest >= len(spec.columns) + 1

    first = elastic_sweep(spec, directory=str(tmp_path / "es"),
                          data_mesh=dm, **kw)
    second = elastic_sweep(spec, directory=str(tmp_path / "es"),
                           data_mesh=dm, **kw)
    assert "restored" in second.columns[0].events
    np.testing.assert_array_equal(np.asarray(first.columns[0].thetas),
                                  np.asarray(second.columns[0].thetas))


# ---------------------------------------------------------------------------
# The sharded store.
# ---------------------------------------------------------------------------

def test_store_ingest_sharded_bitwise(dm):
    """``MomentStore.ingest`` with a data mesh: accumulators AND the
    refreshed panel are bitwise the serial store's after the same
    aligned ingests (the init-seeded ordered fold)."""
    n_blk = 2 * ROW_BLOCK
    d = _data(SPECS[0])
    sids = jax.random.randint(jax.random.PRNGKey(9), (N,), 0, 3)
    cfg = dataclasses.replace(_CFG, nuisance_t="ridge",
                              discrete_treatment=False, cate_features=1)
    spec = SweepSpec(n_segments=3, columns=(("dml", cfg),))
    serial = MomentStore(spec, n_features=d.X.shape[1], key=_FIT_KEY)
    shard = MomentStore(spec, n_features=d.X.shape[1], key=_FIT_KEY,
                        data_mesh=dm)
    for lo in (0, n_blk):  # two ingests, both on row_block boundaries
        blk = dict(X=d.X[lo:lo + n_blk], y=d.y[lo:lo + n_blk],
                   t=d.t[lo:lo + n_blk],
                   segment_ids=sids[lo:lo + n_blk])
        serial.ingest(**blk)
        shard.ingest(**blk)
    for c1, c2 in zip(serial._cols, shard._cols):
        _assert_trees_equal(c1.state, c2.state, "accumulators")
    p1, p2 = serial.refresh(), shard.refresh()
    for c1, c2 in zip(p1.columns, p2.columns):
        assert not (c1.failed or c2.failed)
        np.testing.assert_array_equal(np.asarray(c1.thetas),
                                      np.asarray(c2.thetas))


# ---------------------------------------------------------------------------
# The job API.
# ---------------------------------------------------------------------------

def test_job_submit_blocking_matches_sweep(dm):
    """``block=True``: deterministic inline run — same panel bits as a
    direct ``sweep`` call, one "column" event per column, bracketed by
    submitted/done."""
    kw = _sweep_kw()
    spec = SweepSpec(n_segments=3, columns=(("dml", _CFG),))
    direct = sweep(spec, data_mesh=dm, **kw)
    jm = JobManager()
    job = jm.submit(spec, block=True, data_mesh=dm, **kw)
    st = job.status()
    assert st["status"] == "done"
    assert st["columns_done"] == 1 and st["columns_failed"] == 0
    actions = [e.action for e in job.events_since(0)]
    assert actions == ["submitted", "column", "done"]
    panel = job.result()
    np.testing.assert_array_equal(np.asarray(direct.columns[0].thetas),
                                  np.asarray(panel.columns[0].thetas))


def test_job_background_subscribe(dm):
    """A threaded job: ``subscribe`` yields every event in order and
    terminates when the job settles; ``wait`` unblocks."""
    kw = _sweep_kw()
    spec = SweepSpec(n_segments=2, columns=(("dml", _CFG),))
    jm = JobManager()
    job = jm.submit(spec, data_mesh=dm, **kw)
    events = list(job.subscribe())
    assert job.wait(timeout=60)
    assert [e.action for e in events] == ["submitted", "column", "done"]
    assert job.result(timeout=5) is not None
    assert jm.status(job.job_id)["status"] == "done"


@pytest.mark.slow
def test_two_process_smoke_best_effort():
    """The real ``jax.distributed`` two-process launcher, workers
    pinned to the CPU: the installed jax runs multi-process CPU
    collectives, so anything but a matching result fails."""
    from repro.launch.dist_smoke import run_smoke

    verdict = run_smoke(timeout=300)
    assert verdict == "OK", verdict


def test_job_failure_surfaces():
    """A sweep that cannot even start marks the job failed; ``result``
    re-raises."""
    kw = _sweep_kw()
    spec = SweepSpec(n_segments=3, columns=(("dml", _CFG),))
    jm = JobManager()
    job = jm.submit(spec, block=True, mode="no_such_mode", **kw)
    assert job.status()["status"] == "failed"
    with pytest.raises(Exception):
        job.result()

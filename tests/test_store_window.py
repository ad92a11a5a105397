"""The windowed moment store: a trailing window of days, and the
logistic-treatment DML column it refreshes from the rows it keeps.

Contracts:
  * the windowed refresh of a DML column with a ridge visit model and a
    fixed-majorizer MM logistic treatment model equals a plain float64
    reference of the estimand over the window's rows (the last 3 of 5
    days, each row in the fold its global index gives it), under the
    default strategy and under ``pallas`` (interpret mode);
  * a short day, padded to ``day_rows``, counts its real rows only;
  * the same days held at different ring offsets give equal bits;
  * the gate rejects a logistic column without a window, with a reason;
  * ridge-only columns of a store without a window keep the bits they
    had before windows existed (hashes recorded from that store);
  * the segmented sweep's programs lower byte-equal to the ones
    recorded before its stages were shared with the store;
  * the store's counters count once per ingest or refresh;
  * a windowed store's snapshot restores its window: the restored
    store's next refresh equals the original's in bits.
"""
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.config import CausalConfig
from repro.core.registry import get_spec
from repro.kernels.seg_gram.ops import force_backend
from repro.obs.trace import Tracer
from repro.store import MomentStore, Window, store_supported
from repro.sweep import segmented
from repro.sweep.spec import SweepSpec

E, K, P = 4, 5, 5
ROWS = 768          # a day: 3 row blocks of 256
DAYS = 3            # the window
N_DAYS = 5          # days ingested: 2 retire
_KEY = jax.random.PRNGKey(17)


def _cfg(strategy=None, **kw):
    return CausalConfig(n_folds=K, inference="none", row_block=256,
                        nuisance_y="ridge", nuisance_t="logistic",
                        discrete_treatment=True, newton_iters=4,
                        row_block_strategy=strategy, **kw)


def _spec(cfg):
    return SweepSpec(n_segments=E, columns=(("dml", cfg),))


def _day(d, n=ROWS):
    """Day ``d``: a randomised binary treatment, a binary visit with a
    per-cohort uplift, cohorts of unequal sizes."""
    rng = np.random.default_rng(1000 + d)
    X = rng.normal(size=(n, P)).astype(np.float32)
    sids = rng.choice(E, size=n, p=[0.4, 0.3, 0.2, 0.1]).astype(np.int32)
    t = (rng.random(n) < 0.7).astype(np.float32)
    base = 0.2 + 0.1 * np.tanh(X[:, 0])
    y = (rng.random(n) < base + 0.05 * (1 + sids) * t).astype(np.float32)
    return {"X": X, "y": y, "t": t, "segment_ids": sids}


def _store(cfg, days, tracer=None):
    store = MomentStore(_spec(cfg), n_features=P, key=_KEY, tracer=tracer,
                        window=Window(days=DAYS, day_rows=ROWS))
    for day in days:
        store.ingest(**day)
    return store


def _folds(start, n):
    """The documented fold rule, re-derived:
    randint(fold_in(column_key, global_row_index), K)."""
    ck = jax.random.fold_in(_KEY, 0)
    idx = jnp.arange(start, start + n, dtype=jnp.uint32)
    return np.asarray(jax.vmap(lambda i: jax.random.randint(
        jax.random.fold_in(ck, i), (), 0, K))(idx))


def _reference(days, starts, cfg):
    """Float64 per-cohort DML over the rows of ``days`` (row i of a day
    at global index start + i): ridge visit and fixed-majorizer MM
    logistic treatment per (cohort, fold complement), 2·newton_iters
    steps from zero; the final stage and its HC0 SE.  Returns (theta
    (E,), se (E,), beta_y (E, K, q), beta_t (E, K, q))."""
    X = np.concatenate([d["X"] for d in days]).astype(np.float64)
    y = np.concatenate([d["y"] for d in days]).astype(np.float64)
    t = np.concatenate([d["t"] for d in days]).astype(np.float64)
    s = np.concatenate([d["segment_ids"] for d in days])
    f = np.concatenate([_folds(a, len(d["X"])) for d, a in zip(days, starts)])
    n, q, lam = len(X), P + 1, cfg.ridge_lambda
    xa = np.concatenate([X, np.ones((n, 1))], axis=1)
    beta_y, beta_t = np.zeros((E, K, q)), np.zeros((E, K, q))
    my, mt = np.zeros(n), np.zeros(n)
    for e in range(E):
        for j in range(K):
            comp = (s == e) & (f != j)
            own = (s == e) & (f == j)
            nc = max(comp.sum(), 1)
            G = xa[comp].T @ xa[comp] / nc
            beta_y[e, j] = np.linalg.solve(G + lam * np.eye(q),
                                           xa[comp].T @ y[comp] / nc)
            H0 = G / 4.0 + lam * np.eye(q)
            b = np.zeros(q)
            for _ in range(2 * cfg.newton_iters):
                mu = 1.0 / (1.0 + np.exp(-xa[comp] @ b))
                g = xa[comp].T @ (mu - t[comp]) / nc + lam * b
                b = b - np.linalg.solve(H0, g)
            beta_t[e, j] = b
            my[own] = xa[own] @ beta_y[e, j]
            mt[own] = 1.0 / (1.0 + np.exp(-xa[own] @ b))
    ry, rt = y - my, t - mt
    theta, se = np.zeros(E), np.zeros(E)
    for e in range(E):
        m = s == e
        a = rt[m] @ rt[m] + 1e-8 * max(m.sum(), 1)
        theta[e] = rt[m] @ ry[m] / a
        se[e] = np.sqrt(np.sum(((ry[m] - theta[e] * rt[m]) * rt[m]) ** 2)) / a
    return theta, se, beta_y, beta_t


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def _assert_matches(col, ref):
    theta, se, beta_y, beta_t = ref
    assert col.events[-1] == "rows"
    # float32 sums over ~2,300 rows against float64: the fold models to
    # 1e-5 relative, the effects to a thousandth of their SE
    assert _rel(col.beta_y, beta_y) < 1e-5
    assert _rel(col.beta_t, beta_t) < 1e-5
    got = np.asarray(col.thetas)[:, 0]
    assert np.max(np.abs(got - theta) / se) < 1e-3
    np.testing.assert_allclose(np.asarray(col.ses)[:, 0], se, rtol=1e-4)


DAYS_DATA = [_day(d) for d in range(N_DAYS)]


@pytest.mark.parametrize("strategy", [None, "pallas"])
def test_window_refresh_matches_reference(strategy):
    with force_backend("interpret"):
        store = _store(_cfg(strategy), DAYS_DATA)
        panel = store.refresh()
    assert store.days_held == DAYS and store.resident_rows == DAYS * ROWS
    held = DAYS_DATA[-DAYS:]
    starts = [d * ROWS for d in range(N_DAYS - DAYS, N_DAYS)]
    _assert_matches(panel.columns[0], _reference(held, starts, _cfg()))
    counts = np.bincount(np.concatenate([d["segment_ids"] for d in held]),
                         minlength=E)
    np.testing.assert_array_equal(np.asarray(panel.counts), counts)


def test_short_day_counts_real_rows_only():
    short = {k: v[:500] for k, v in _day(9).items()}
    days = DAYS_DATA[:3] + [short]
    store = _store(_cfg(), days)
    assert store.resident_rows == 2 * ROWS + 500
    panel = store.refresh()
    # the short day's rows take global indices 3·ROWS .. 3·ROWS + 499
    ref = _reference(days[1:], [ROWS, 2 * ROWS, 3 * ROWS], _cfg())
    _assert_matches(panel.columns[0], ref)
    # the padded tail adds nothing: its cells count real rows only
    assert float(np.asarray(panel.columns[0].cell_rows).sum()) == 2 * ROWS + 500


def test_ring_offset_does_not_change_bits():
    """The same three days at ring offsets 0 and 2 give equal bits.
    Global row indices must agree for the folds to, so the store at
    offset 0 took two half days first: both stores have seen 2·ROWS
    rows before the days they then hold."""
    half = [{k: v[:ROWS // 2] for k, v in _day(20 + i).items()}
            for i in range(2)]
    same = DAYS_DATA[2:5]
    a = _store(_cfg(), half + [DAYS_DATA[1]] + same)  # 6 days: offset 0
    b = _store(_cfg(), DAYS_DATA[:2] + same)          # 5 days: offset 2
    assert a.n_days % DAYS == 0 and b.n_days % DAYS == 2
    pa, pb = a.refresh(), b.refresh()
    for field in ("thetas", "ses", "beta_y", "beta_t", "cell_rows"):
        np.testing.assert_array_equal(np.asarray(getattr(pa.columns[0], field)),
                                      np.asarray(getattr(pb.columns[0], field)))
    np.testing.assert_array_equal(np.asarray(pa.counts), np.asarray(pb.counts))


def test_gate_needs_a_window_for_a_logistic_treatment():
    ok, reason = store_supported(get_spec("dml"), _cfg())
    assert not ok and "window" in reason
    assert store_supported(get_spec("dml"), _cfg(), windowed=True) == (True, "")
    # instrumented and pseudo-outcome families stay out, window or not
    assert not store_supported(get_spec("orthoiv"), _cfg(), windowed=True)[0]
    assert not store_supported(get_spec("drlearner"), _cfg(), windowed=True)[0]
    store = MomentStore(_spec(_cfg()), n_features=P, key=_KEY)
    store.ingest(**DAYS_DATA[0])
    col = store.refresh().columns[0]
    assert col.failed and "window" in col.error


def _ridge_panel_digest():
    """sha256 of an unwindowed store's ridge-only panel: two aligned
    ingests, then one misaligned, each refreshed."""
    from repro.data.causal_dgp import make_causal_data
    cfg = CausalConfig(n_folds=3, inference="none", row_block=128,
                       nuisance_t="ridge", nuisance_z="ridge",
                       discrete_treatment=False, cate_features=2)
    spec = SweepSpec(n_segments=E, columns=(("dml", cfg), ("orthoiv", cfg)))
    d = make_causal_data(jax.random.PRNGKey(4), 700, P, effect=1.2,
                         discrete_treatment=False)
    sids = jax.random.randint(jax.random.PRNGKey(5), (700,), 0, E)
    z = d.t + 0.1 * d.X[:, 0]
    store = MomentStore(spec, n_features=P, key=_KEY)
    h = hashlib.sha256()
    for lo, hi in ((0, 256), (256, 512), (512, 700)):
        store.ingest(X=d.X[lo:hi], y=d.y[lo:hi], t=d.t[lo:hi],
                     segment_ids=sids[lo:hi], z=z[lo:hi])
        panel = store.refresh()
        for col in panel.columns:
            assert col.error is None, col.error
            for a in (col.thetas, col.ses, col.ates):
                h.update(np.asarray(a).tobytes())
        h.update(np.asarray(panel.counts).tobytes())
    return h.hexdigest()


def test_unwindowed_ridge_columns_keep_their_bits():
    assert _ridge_panel_digest() == (
        "b80bf472be6b5c59d94ae3460fbd1be03dec75b621bbe8ae60b3c8ff25cceea7")


# sha256 of ``segmented_dml_sweep``'s lowering (n = 2048, p = 5, E = 4,
# K = 5, newton_iters 3, row_block 512), recorded before its stages
# were shared with the store
SWEEP_LOWERINGS = {
    (True, None, "scatter"):
        "1e4b54899255f276cc58e825dcda7e061876f26ed685608245bf2eacb1484558",
    (True, "pallas", "scatter"):
        "cd0d63c1c6ad9d685efb724c082cfbcb3e2f8a893812240ca8e125411605af6f",
    (True, "pallas", "interpret"):
        "dc4eca81c15b18308631f633d8f727fc7f488b6aa38b32df49ff0d1dd2554bc8",
    (False, None, "scatter"):
        "45bf3e6e5eaab71ece63d007df86eab4cfbf0b5b275cea8ea9d62b18c2d40f8d",
    (False, "pallas", "scatter"):
        "0f37e2258e5b9494887f44530f95ac5872e669d57fa28facde2096ef484d2311",
    (False, "pallas", "interpret"):
        "ceb441a6150acf85d0f4dbed0cf008e2e9a1c2a893cb68e1209364bbc498e816",
}


@pytest.mark.parametrize("discrete,strategy,backend", sorted(
    SWEEP_LOWERINGS, key=str))
def test_sweep_lowering_unchanged(discrete, strategy, backend):
    n = 2048
    cfg = CausalConfig(n_folds=5, discrete_treatment=discrete,
                       nuisance_t="logistic" if discrete else "ridge",
                       newton_iters=3, row_block=512,
                       row_block_strategy=strategy, inference="none")
    fn = jax.jit(lambda X, y, t, s, k: segmented.segmented_dml_sweep(
        cfg, X, y, t, s, E, k))
    row = jax.ShapeDtypeStruct((n,), jnp.float32)
    with force_backend(backend):
        text = fn.lower(jax.ShapeDtypeStruct((n, P), jnp.float32), row, row,
                        jax.ShapeDtypeStruct((n,), jnp.int32),
                        jax.random.PRNGKey(0)).as_text(debug_info=False)
    digest = hashlib.sha256(text.encode()).hexdigest()
    assert digest == SWEEP_LOWERINGS[(discrete, strategy, backend)]


def test_counters_count_once_per_ingest_and_refresh():
    tracer = Tracer()
    store = _store(_cfg(), DAYS_DATA, tracer=tracer)
    store.refresh()
    store.refresh()
    snap = tracer.metrics.snapshot()
    c, g = snap["counters"], snap["gauges"]
    assert c["store.ingests"] == N_DAYS
    assert c["store.days_retired"] == N_DAYS - DAYS
    assert c["store.refreshes"] == 2
    assert c["store.refresh.path[rows]"] == 2
    assert "store.refresh.path[moments]" not in c
    assert c["store.refresh.mm_steps"] == 2 * 2 * _cfg().newton_iters
    assert g["store.resident_rows"] == DAYS * ROWS
    spans = [s for s in tracer.spans if s.name.startswith("store.")]
    assert [s.name for s in spans].count("store.ingest") == N_DAYS
    last = [s for s in spans if s.name == "store.refresh"][-1]
    assert last.attrs["path"] == "rows" and last.attrs["days_held"] == DAYS
    assert last.attrs["resident_rows"] == DAYS * ROWS


def test_window_snapshot_round_trip(tmp_path):
    manager = CheckpointManager(str(tmp_path), keep_latest=4)
    store = _store(_cfg(), DAYS_DATA[:4])
    store.save(manager)
    restored = MomentStore(_spec(_cfg()), n_features=P, key=_KEY,
                           window=Window(days=DAYS, day_rows=ROWS))
    restored.restore(manager)
    assert (restored.n_days, restored.n_total, restored.day_held) == (
        store.n_days, store.n_total, store.day_held)
    for s in (store, restored):  # the next day retires the same slot
        s.ingest(**DAYS_DATA[4])
    pa, pb = store.refresh(), restored.refresh()
    for field in ("thetas", "ses", "beta_y", "beta_t"):
        np.testing.assert_array_equal(np.asarray(getattr(pa.columns[0], field)),
                                      np.asarray(getattr(pb.columns[0], field)))
    # a store with another window, or none, refuses the snapshot
    with pytest.raises(ValueError, match="window"):
        MomentStore(_spec(_cfg()), n_features=P, key=_KEY,
                    window=Window(days=DAYS + 1, day_rows=ROWS)).restore(manager)

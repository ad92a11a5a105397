"""repro.obs: span tracer (Chrome-trace export schema, nesting,
rollups), metrics registry, predicted-vs-measured cost audit, the
EventLog ring buffer, and the two integration contracts — the traced
span tree covers runtime chunks / sweep columns / crossfit targets, and
``tracer=None`` changes nothing (bit-identity, no recompiles)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import CausalConfig
from repro.core.crossfit import crossfit
from repro.core.nuisance import make_ridge
from repro.data.causal_dgp import make_causal_data
from repro.inference.executor import jit_miss_hook
from repro.launch.roofline import peaks_for
from repro.obs import (ChunkAudit, CostAudit, Histogram, MetricsRegistry,
                       Tracer, maybe_span)
from repro.runtime import EventLog, RuntimeEvent, TaskRuntime, memory_model
from repro.sweep import SweepSpec, sweep

_XS = jnp.arange(14, dtype=jnp.float32).reshape(7, 2)
_C = jnp.float32(1.0)


def _double(x, c):
    return {"y": x * 2.0 + c, "s": x.sum()}


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("a").inc()
    reg.counter("a").inc(4)
    reg.gauge("g").set(2.5)
    for v in [1.0, 2.0, 3.0, 4.0]:
        reg.histogram("h").observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 5
    assert snap["gauges"]["g"] == 2.5
    h = snap["histograms"]["h"]
    assert h["count"] == 4 and h["sum"] == 10.0
    assert h["min"] == 1.0 and h["max"] == 4.0
    assert h["mean"] == pytest.approx(2.5)


def test_histogram_percentiles_and_reservoir_cap():
    h = Histogram(cap=10)
    for v in range(100):
        h.observe(float(v))
    # exact stats survive past the reservoir cap
    assert h.count == 100 and h.hi == 99.0 and h.lo == 0.0
    assert len(h._values) == 10  # bounded
    # the reservoir is a sample of the stream, not a warm-up prefix
    assert all(0.0 <= v <= 99.0 for v in h._values)
    assert h.percentile(0.0) <= h.percentile(0.5) <= h.percentile(1.0)
    assert Histogram().summary() == {"count": 0, "sum": 0.0}


def test_histogram_reservoir_tracks_shifted_distribution():
    # the long-running-server regression: latencies shift AFTER the
    # reservoir fills; percentiles must follow the live distribution
    # instead of freezing on the first `cap` (warm-up) observations
    cap = 64
    h = Histogram(cap=cap)
    for _ in range(cap):
        h.observe(1.0)           # warm-up regime fills the reservoir
    assert h.percentile(0.5) == 1.0
    for _ in range(20 * cap):
        h.observe(10.0)          # steady-state regime, post-cap
    assert h.percentile(0.5) == 10.0   # p50 follows the shift
    assert h.percentile(0.99) == 10.0
    # exact aggregates never degrade to the sample
    assert h.count == 21 * cap
    assert h.total == cap * 1.0 + 20 * cap * 10.0
    assert h.lo == 1.0 and h.hi == 10.0
    assert len(h._values) == cap


def test_histogram_reservoir_deterministic_seed():
    def fill(seed):
        h = Histogram(cap=8, seed=seed)
        for v in range(1000):
            h.observe(float(v))
        return list(h._values)

    assert fill(0) == fill(0)        # seeded Algorithm R replays
    assert fill(0) != fill(1)


def test_reset_default_registry_decouples_tests():
    from repro.obs.metrics import default_registry, reset_default_registry

    default_registry().counter("coupling.probe").inc(3)
    assert default_registry().snapshot()["counters"]["coupling.probe"] == 3
    reset_default_registry()
    fresh = default_registry()
    assert "coupling.probe" not in fresh.snapshot()["counters"]
    assert default_registry() is fresh  # stable until the next reset


def test_registry_get_or_create_is_stable():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    assert reg.gauge("x") is reg.gauge("x")
    assert reg.histogram("x") is reg.histogram("x")


# ---------------------------------------------------------------------------
# Tracer: spans, nesting, export
# ---------------------------------------------------------------------------

def test_span_nesting_and_rollup():
    tr = Tracer()
    with tr.span("outer", cat="test", tag="a") as so:
        with tr.span("inner"):
            tr.instant("mark", detail="x")
        with tr.span("inner"):
            pass
    assert so.depth == 0 and not so.open
    inners = [s for s in tr.spans if s.name == "inner"]
    assert all(s.parent_id == so.span_id and s.depth == 1 for s in inners)
    mark = next(s for s in tr.spans if s.name == "mark")
    assert mark.instant and mark.depth == 2 and mark.duration_s == 0.0
    roll = tr.rollup()
    assert roll["inner"]["count"] == 2
    assert "mark" not in roll  # instants don't roll up
    assert roll["outer"]["total_s"] >= roll["inner"]["total_s"]
    text = tr.render()
    assert "outer" in text and "  inner" in text and "! mark" in text


def test_chrome_trace_schema(tmp_path):
    tr = Tracer()
    with tr.span("work", cat="runtime", label="L", size=jnp.int32(3)):
        tr.instant("event")
    path = tr.write_chrome_trace(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())  # round-trips as strict JSON
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert len(evs) == 2
    for e in evs:
        assert {"name", "cat", "ts", "pid", "tid", "ph", "args"} <= set(e)
        assert e["ph"] in ("X", "i")
        assert e["ts"] >= 0.0
        # args must be JSON scalars (jax values are stringified)
        for v in e["args"].values():
            assert isinstance(v, (str, int, float, bool, type(None)))
    x = next(e for e in evs if e["ph"] == "X")
    assert x["dur"] >= 0.0 and x["name"] == "work"
    i = next(e for e in evs if e["ph"] == "i")
    assert i["s"] == "t" and "dur" not in i


def test_maybe_span_none_is_noop():
    with maybe_span(None, "anything") as s:
        assert s is None
    tr = Tracer()
    with maybe_span(tr, "real", cat="c", k=1) as s:
        assert s is not None and s.name == "real"
    assert tr.span_names() == ["real"]


# ---------------------------------------------------------------------------
# Cost audit
# ---------------------------------------------------------------------------

_V5E = peaks_for("TPU v5 lite")


def test_audit_ratios_finite_even_on_zero_inputs():
    row = ChunkAudit(label="z", chunk_index=0, chunk_size=1,
                     predicted_peak_bytes=0.0, probed_peak_bytes=0.0,
                     flops=0.0, hbm_bytes=0.0, measured_s=0.0)
    assert np.isfinite(row.peak_ratio)
    assert np.isfinite(row.time_ratio(_V5E))


def test_peaks_unknown_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")
    # no peaks: the audit keeps its memory side and leaves time out
    audit = CostAudit()
    audit.record(ChunkAudit(label="cpu", chunk_index=0, chunk_size=1,
                            predicted_peak_bytes=1.0, probed_peak_bytes=1.0,
                            flops=1.0, hbm_bytes=1.0, measured_s=1.0))
    assert "time_ratio" not in audit.as_dicts()[0]
    assert "time_ratio_min" not in audit.summary()
    assert "not measured" in audit.table()


def test_audit_summary_and_table():
    audit = CostAudit(peaks=_V5E)
    assert audit.summary() == {"n_chunks": 0}
    audit.record(ChunkAudit(label="boot", chunk_index=0, chunk_size=4,
                            predicted_peak_bytes=1000.0,
                            probed_peak_bytes=800.0, flops=1e9,
                            hbm_bytes=1e6, measured_s=0.01))
    s = audit.summary()
    assert s["n_chunks"] == 1 and s["labels"] == ["boot"]
    assert s["peak_ratio_min"] == pytest.approx(1.25)
    assert np.isfinite(s["time_ratio_min"])
    assert "boot" in audit.table()
    d = audit.as_dicts()[0]
    assert np.isfinite(d["peak_ratio"]) and np.isfinite(d["time_ratio"])


# ---------------------------------------------------------------------------
# EventLog ring buffer (satellite: bounded events growth)
# ---------------------------------------------------------------------------

def _ev(i):
    return RuntimeEvent("chunk", f"e{i}", i)


def test_eventlog_ring_bounds_growth():
    log = EventLog(maxlen=4)
    for i in range(10):
        log.append(_ev(i))
    assert len(log) == 4 and log.total == 10 and log.dropped == 6
    assert [e.label for e in log] == ["e6", "e7", "e8", "e9"]
    assert log[0].label == "e6" and log[-1].label == "e9"
    assert [e.label for e in log[1:3]] == ["e7", "e8"]


def test_eventlog_since_is_drop_safe():
    log = EventLog(maxlen=4)
    for i in range(3):
        log.append(_ev(i))
    start = log.total  # checkpoint at 3
    for i in range(3, 10):
        log.append(_ev(i))  # events 0..5 dropped by now
    # the suffix since the checkpoint that is STILL buffered
    assert [e.label for e in log.since(start)] == ["e6", "e7", "e8", "e9"]
    assert log.since(log.total) == ()
    log.clear()
    assert len(log) == 0 and log.total == 0


def test_runtime_events_are_bounded():
    rt = TaskRuntime("vmap", chunk=1, events_maxlen=3)
    rt.map(_double, _XS, _C)  # 7 chunks -> 1 "chunk" event per map + ...
    for _ in range(5):
        rt.map(_double, _XS, _C)
    assert len(rt.events) <= 3
    assert rt.events.total == 6  # one "chunk" decision per chunked map


# ---------------------------------------------------------------------------
# Traced runtime: span tree, audit join, metrics
# ---------------------------------------------------------------------------

def _outer(v, base):
    return jnp.tanh(v[:, None] * v[None, :] + base).sum()


@pytest.fixture(scope="module")
def traced_budget_run():
    m = 64
    xs = jnp.ones((16, m), jnp.float32)
    base = jnp.zeros((m, m), jnp.float32)
    model = memory_model(_outer, xs, (base,), 16)
    assert model is not None
    tr = Tracer(peaks=_V5E)
    rt = TaskRuntime("vmap", memory_budget=int(model.base + 4 * model.slope),
                     tracer=tr)
    out = rt.map(_outer, xs, base, label="probe")
    ref = TaskRuntime("vmap").map(_outer, xs, base)
    return tr, out, ref


def test_traced_map_is_bitwise_identical(traced_budget_run):
    _, out, ref = traced_budget_run
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_traced_map_span_tree(traced_budget_run):
    tr, _, _ = traced_budget_run
    names = tr.span_names()
    assert "runtime.map" in names
    chunks = [s for s in tr.spans if s.name == "runtime.chunk"]
    assert len(chunks) >= 2  # the budget forced chunking
    mp = next(s for s in tr.spans if s.name == "runtime.map")
    assert all(s.parent_id == mp.span_id for s in chunks)
    assert all(s.attrs["label"] == "probe" for s in chunks)
    sizes = sum(s.attrs["chunk_size"] for s in chunks)
    assert sizes == 16  # chunks cover the replicate axis exactly


def test_traced_map_audit_rows_finite(traced_budget_run):
    tr, _, _ = traced_budget_run
    assert len(tr.audit) >= 2  # every budget-sized chunk audited
    for d in tr.audit.as_dicts():
        assert np.isfinite(d["peak_ratio"]) and d["peak_ratio"] > 0
        assert np.isfinite(d["time_ratio"]) and d["time_ratio"] > 0
        assert d["probed_peak_bytes"] > 0
    # the affine model interpolates the HLO peak well where it was used
    s = tr.audit.summary()
    assert 0.5 <= s["peak_ratio_min"] and s["peak_ratio_max"] <= 2.0


def test_traced_map_metrics(traced_budget_run):
    tr, _, _ = traced_budget_run
    snap = tr.metrics.snapshot()
    n_chunks = len([s for s in tr.spans if s.name == "runtime.chunk"])
    assert snap["counters"]["runtime.chunks"] == n_chunks
    assert snap["counters"]["runtime.events.chunk"] == 1
    assert snap["histograms"]["runtime.chunk_seconds"]["count"] == n_chunks
    assert snap["gauges"]["runtime.chunk_size[probe]"] >= 1
    assert snap["gauges"]["runtime.predicted_peak_bytes[probe]"] > 0


def test_traced_chrome_trace_serializes(traced_budget_run):
    tr, _, _ = traced_budget_run
    doc = json.loads(json.dumps(tr.chrome_trace()))
    assert all(e["ph"] in ("X", "i") for e in doc["traceEvents"])


def test_untraced_runtime_reuses_compiled_programs():
    """tracer=None must add no jit recompiles: a fresh untraced runtime
    mapping a closure the executor already compiled (by a TRACED run at
    the same shapes) hits the cache — zero misses."""
    def fn(x, c):
        return x * 3.0 + c

    TaskRuntime("vmap", chunk=3, tracer=Tracer()).map(fn, _XS, _C)
    misses = []
    with jit_miss_hook(misses.append):
        out = TaskRuntime("vmap", chunk=3).map(fn, _XS, _C)
    assert misses == []
    ref = TaskRuntime("vmap", chunk=3).map(fn, _XS, _C)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_dag_gather_spans():
    tr = Tracer()
    rt = TaskRuntime("vmap", tracer=tr)
    a = rt.submit(_double, _XS, _C, label="stage_a")
    b = rt.submit(_double, rt.call(lambda o: o["y"][:3], a), _C, label="stage_b")
    rt.gather(b)
    dag = [s for s in tr.spans if s.name == "dag.task"]
    assert {s.attrs["label"] for s in dag} == {"stage_a", "stage_b"}
    # each dag.task span wraps its runtime.map span
    for s in tr.spans:
        if s.name == "runtime.map":
            assert tr.spans[s.parent_id].name == "dag.task"


# ---------------------------------------------------------------------------
# Integration: sweep columns + crossfit targets in ONE span tree
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sweep_and_crossfit_span_coverage():
    key = jax.random.PRNGKey(0)
    d = make_causal_data(key, 400, 4, effect=1.0)
    tr = Tracer()

    crossfit(make_ridge(), make_ridge(), jax.random.PRNGKey(1),
             d.X, d.y, d.t, 3, engine=TaskRuntime("vmap", tracer=tr))

    sids = jax.random.randint(key, (400,), 0, 2)
    cfg = CausalConfig(n_folds=2, inference="none")
    spec = SweepSpec(n_segments=2, columns=(("dml", cfg),))
    sweep(spec, X=d.X, y=d.y, t=d.t, segment_ids=sids,
          key=jax.random.PRNGKey(2), executor="vmap", tracer=tr)

    names = tr.span_names()
    assert any(n.startswith("crossfit:") for n in names)
    assert any(n.startswith("sweep.column[") for n in names)
    assert "runtime.map" in names
    cf = next(s for s in tr.spans if s.name.startswith("crossfit:"))
    kids = [s for s in tr.spans if s.parent_id == cf.span_id]
    assert any(s.name == "runtime.map" for s in kids)  # nesting holds
    # the whole tree exports as valid Chrome-trace JSON
    doc = json.loads(json.dumps(tr.chrome_trace()))
    assert len(doc["traceEvents"]) == len(tr.spans)

"""repro.obs: span tracer (Chrome-trace export schema, nesting,
rollups), metrics registry, predicted-vs-measured cost audit, the
EventLog ring buffer, and the two integration contracts — the traced
span tree covers runtime chunks / sweep columns / crossfit targets, and
``tracer=None`` changes nothing (bit-identity, no recompiles)."""
import contextlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import CausalConfig
from repro.core.crossfit import crossfit
from repro.core.dml import DML
from repro.core.nuisance import make_ridge
from repro.data.causal_dgp import make_causal_data
from repro.inference.bootstrap import make_dml_replicate_fn
from repro.inference.executor import jit_miss_hook
from repro.inference.numerics import det_inv, det_solve
from repro.launch.roofline import peaks_for
from repro.obs import (ChunkAudit, CostAudit, Histogram, MetricsRegistry,
                       Span, Tracer, default_registry, layer_span, maybe_span,
                       process_tracer, reset_process_tracer)
from repro.obs.trace import PROCESS_MAX_SPANS
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


# ---------------------------------------------------------------------------
# The process tracer: layer spans of the fit path, the ring, compile
# accounting, the profiler mirror, and that none of it changes a result
# or adds a compile
# ---------------------------------------------------------------------------

FIT_SPANS = {"dml.fit", "crossfit:ridge", "dml.final_stage",
             "inference.bootstrap", "runtime.map", "runtime.plan",
             "runtime.chunk"}


@pytest.fixture
def fresh_process_tracer():
    reset_process_tracer()
    yield process_tracer()
    reset_process_tracer()


@pytest.fixture(scope="module")
def tiny_fit_data():
    return make_causal_data(jax.random.PRNGKey(0), 2048, 8, effect=1.0)


def _tiny_dml(row_block=512, **kw):
    return DML(CausalConfig(n_folds=3, nuisance_y="ridge",
                            nuisance_t="ridge", inference="bootstrap",
                            n_bootstrap=4, row_block=row_block,
                            runtime_memory_budget=64 << 20), **kw)


def _fit_and_boot(est, d, seed=0):
    res = est.fit(d.y, d.t, d.X, key=jax.random.PRNGKey(seed))
    inf = res.inference()
    return res, inf


class _OffTracer(Tracer):
    """The process tracer stubbed out: records nothing, marks nothing."""

    def span(self, name, cat="runtime", **attrs):
        return contextlib.nullcontext(Span(-1, name, cat, 0, attrs={}))

    def add_span(self, name, start_ns, end_ns, cat="runtime", **attrs):
        return Span(-1, name, cat, start_ns, end_ns)


def test_process_tracer_records_the_fit_span_tree(fresh_process_tracer,
                                                   tiny_fit_data):
    pt = fresh_process_tracer
    assert not pt.sync_enabled and pt.spans.maxlen == PROCESS_MAX_SPANS
    _fit_and_boot(_tiny_dml(), tiny_fit_data)
    spans = [s for s in pt.spans if not s.name.startswith("compile.")]
    by_id = {s.span_id: s for s in pt.spans}
    parent = {s.span_id: by_id[s.parent_id].name if s.parent_id >= 0
              else None for s in spans}
    assert FIT_SPANS <= {s.name for s in spans}
    for s in spans:
        want = {"dml.fit": None, "inference.bootstrap": None,
                "crossfit:ridge": "dml.fit", "dml.final_stage": "dml.fit",
                "runtime.plan": "runtime.map",
                "runtime.chunk": "runtime.map"}.get(s.name)
        if s.name in ("dml.fit", "inference.bootstrap"):
            assert parent[s.span_id] is None, s
        elif want is not None:
            assert parent[s.span_id] == want, s
    maps = [s for s in spans if s.name == "runtime.map"]
    assert sorted(parent[s.span_id] for s in maps) == [
        "crossfit:ridge", "crossfit:ridge", "inference.bootstrap"]
    boot_plan = next(s for s in spans if s.name == "runtime.plan"
                     and s.attrs["label"] == "dml_bootstrap")
    assert boot_plan.attrs["probes_compiled"] == 2  # the memory model's
    assert boot_plan.attrs["chunk"] >= 1
    assert all(not s.open and s.end_ns >= s.start_ns for s in pt.spans)


@pytest.mark.parametrize("max_spans,dropped", [(3, 2), (None, 0)])
def test_tracer_ring_drops_oldest_and_counts(max_spans, dropped):
    tr = Tracer(max_spans=max_spans)
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    assert tr.recorded == 5 and tr.dropped == dropped
    assert tr.span_names() == [f"s{i}" for i in range(dropped, 5)]
    assert [s.span_id for s in tr.spans] == list(range(dropped, 5))


@pytest.mark.parametrize("explicit", [False, True])
def test_compile_inside_a_span_is_accounted(fresh_process_tracer, explicit):
    tr = Tracer() if explicit else fresh_process_tracer
    x = jnp.arange(37, dtype=jnp.float32)

    @jax.jit
    def inner(v):
        return jnp.sin(v) * 3.0

    @jax.jit
    def outer(v):
        return inner(v).sum() + 1.0

    with layer_span(tr if explicit else None, "first") as first:
        jax.block_until_ready(outer(x))
    with layer_span(tr if explicit else None, "again"):
        jax.block_until_ready(outer(x))
    comp = [s for s in tr.spans if s.name.startswith("compile.")
            and s.start_ns >= first.start_ns]  # not the input's
    backend = [s for s in comp if s.name == "compile.backend"]
    assert backend and all(s.parent_id == first.span_id for s in backend)
    assert all(s.cat == "compile" and not s.open for s in comp)
    # the inner function's trace nests inside the outer one's
    traces = {s.attrs["fun_name"]: s for s in comp if s.name == "compile.trace"}
    assert traces["inner"].parent_id == traces["outer"].span_id
    counters = default_registry().snapshot()["counters"]
    assert counters["compiles[first]"] == len(backend)
    top = [s for s in comp if s.parent_id == first.span_id]
    assert counters["compile_s[first]"] == pytest.approx(
        sum(s.duration_s for s in top), rel=1e-6)
    assert "compiles[again]" not in counters
    assert "compile_s[again]" not in counters
    assert (fresh_process_tracer.recorded == 0) == explicit


def test_profiler_trace_holds_the_program_spans(fresh_process_tracer,
                                                tiny_fit_data, tmp_path):
    from jax.profiler import ProfileData
    est = _tiny_dml()
    _fit_and_boot(est, tiny_fit_data)  # compiles outside the profile
    jax.profiler.start_trace(str(tmp_path))
    try:
        _fit_and_boot(est, tiny_fit_data, seed=1)
    finally:
        jax.profiler.stop_trace()
    files = sorted(tmp_path.rglob("*.xplane.pb"))
    assert files
    names = {e.name for plane in ProfileData.from_file(str(files[-1])).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert FIT_SPANS <= names


@pytest.mark.parametrize("mode", ["process", "explicit"])
def test_fit_is_bitwise_identical_across_tracers(tiny_fit_data, monkeypatch,
                                                 mode):
    """theta, SE and the bootstrap replicates are the same bits with the
    process tracer stubbed out, recording, or an explicit Tracer()."""
    from repro.obs import trace
    d = tiny_fit_data
    est = _tiny_dml()
    with monkeypatch.context() as m:
        m.setattr(trace, "process_tracer", _OffTracer)
        ref, ref_inf = _fit_and_boot(est, d)
    tracer = Tracer() if mode == "explicit" else None
    res, inf = _fit_and_boot(
        _tiny_dml(nuisance_y=est.nuis_y, nuisance_t=est.nuis_t,
                  tracer=tracer), d)
    for got, want in ((res.theta, ref.theta), (res.stderr, ref.stderr),
                      (inf.replicates, ref_inf.replicates),
                      (inf.se, ref_inf.se)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if tracer is not None:  # the explicit tracer saw the whole fit
        assert FIT_SPANS <= set(tracer.span_names())


@pytest.mark.parametrize("row_block", [0, 512])
def test_tracing_adds_no_compile_to_a_warm_fit(tiny_fit_data, monkeypatch,
                                               row_block):
    """A warm point fit run with the process tracer stubbed out, with it
    recording, and with an explicit Tracer(sync=False) compiles the same
    programs: tracing adds none.  At row_block=0 a warm fit compiles
    nothing at all."""
    from repro.obs import trace
    d = tiny_fit_data
    est = _tiny_dml(row_block=row_block)

    def compiles_of(tracer=None, off=False):
        fit = est if tracer is None else _tiny_dml(
            row_block=row_block, nuisance_y=est.nuis_y,
            nuisance_t=est.nuis_t, tracer=tracer)
        before = default_registry().snapshot()["counters"]
        with monkeypatch.context() as m:
            if off:
                m.setattr(trace, "process_tracer", _OffTracer)
            jax.block_until_ready(fit.fit(d.y, d.t, d.X,
                                          key=jax.random.PRNGKey(5)).theta)
        after = default_registry().snapshot()["counters"]
        return {k: v - before.get(k, 0) for k, v in after.items()
                if k.startswith("compiles[") and v != before.get(k, 0)}

    compiles_of()  # warm-up
    off = sum(compiles_of(off=True).values())  # no span open: "(root)"
    recorded = compiles_of()
    assert compiles_of(Tracer(sync=False)) == recorded
    assert sum(recorded.values()) == off
    if row_block == 0:
        assert off == 0


@pytest.mark.parametrize("scope,build", [
    ("det_solve", lambda: (jax.vmap(det_solve),
                           (jnp.eye(4)[None].repeat(3, 0) * 2.0,
                            jnp.ones((3, 4))))),
    ("det_inv", lambda: (jax.vmap(det_inv), (jnp.eye(4)[None].repeat(3, 0),))),
    ("inference.replicate", lambda: _replicate_case()),
    ("dml.final_stage", lambda: _replicate_case()),
])
def test_scopes_reach_the_hlo_op_metadata(scope, build):
    import re
    fn, args = build()
    text = jax.jit(lambda *a: fn(*a)).lower(*args).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    # a scope under vmap reads "vmap(<scope>)"
    pat = re.compile(r"(^|/|\()" + re.escape(scope) + r"(\)|/|$)")
    assert any(pat.search(n) for n in names), names[:5]


def _replicate_case():
    d = make_causal_data(jax.random.PRNGKey(0), 256, 4, effect=1.0)
    rep = make_dml_replicate_fn(make_ridge(), make_ridge(), 3)
    phi = jnp.ones((256, 1), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    return (jax.vmap(lambda k, X, y, t, p: rep(k, X, y, t, p),
                     in_axes=(0, None, None, None, None)),
            (keys, d.X, d.y, d.t, phi))


@pytest.mark.parametrize("n_segments,name", [
    (1, "seg_gram_fold_weighted"), (320, "seg_gram_fold_weighted_seg")])
def test_seg_gram_kernel_is_named_by_form(n_segments, name):
    from repro.kernels.seg_gram import ref
    from repro.kernels.seg_gram.kernel import kernel_name
    assert kernel_name(ref.build_fold_weighted, n_segments) == name


def test_tracer_threads_nest_on_their_own_stacks():
    """More threads than cores on one tracer, switching every few µs: no
    span id is lost or shared, and every span nests under its own
    thread's parent."""
    import sys
    import threading
    tr, n_threads, reps = Tracer(max_spans=None), 16, 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(i):
            for _ in range(reps):
                with tr.span(f"outer{i}"):
                    with tr.span(f"inner{i}"):
                        pass

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert tr.recorded == len(tr.spans) == 2 * n_threads * reps
    assert sorted(s.span_id for s in tr.spans) == list(range(tr.recorded))
    by_id = {s.span_id: s for s in tr.spans}
    for s in tr.spans:
        if s.name.startswith("inner"):
            assert by_id[s.parent_id].name == "outer" + s.name[5:]
        else:
            assert s.parent_id == -1 and s.depth == 0

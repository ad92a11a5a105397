"""Observability bench: ONE traced smoke run across the three execution
families — runtime-chunked bootstrap replicates, cross-fitting, and a
segment sweep — through a single ``repro.obs.Tracer``.

Deliverables (the paper's measurement story, made durable):

  * a Chrome trace-event JSON (``--trace``/``out_trace``; load it in
    Perfetto) whose span tree covers runtime chunks, sweep columns, and
    crossfit targets;
  * the predicted-vs-measured cost audit: every budget-scheduled chunk
    joined to its affine-memory-model prediction and its exact compiled
    HLO peak/roofline costs (the memory model that sizes chunks,
    validated by data);
  * an ``obs`` payload (span rollups + audit summary + metrics
    snapshot) that ``benchmarks/run.py`` embeds into
    ``BENCH_results.json``;
  * with ``--span-cost``, the host cost of the process tracer: µs per
    layer span and per compile event through the compile accounting.

Entries are prefixed ``obs_`` — informational, not under the >20%
bench-regression gate (tracing is instrumentation, not a hot path).
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.config import CausalConfig
from repro.core.crossfit import crossfit
from repro.core.dml import DML
from repro.core.nuisance import make_ridge
from repro.data.causal_dgp import make_causal_data
from repro.inference.bootstrap import make_dml_replicate_fn, replicate_keys
from repro.obs import Tracer
from repro.runtime import TaskRuntime, memory_model
from repro.sweep import SweepSpec, sweep

# the canonical contract shapes (see bench_runtime): auto-chunks <= ~8
# stay inside the verified serial == vmap bit-identity envelope
N, P, K = 2000, 8, 4


def run(B: int = 64, n: int = N, p: int = P, k: int = K,
        n_segments: int = 4, out_trace: str = "BENCH_trace.json",
        csv=print):
    tracer = Tracer()
    key = jax.random.PRNGKey(42)
    d = make_causal_data(key, n, p, effect=1.5)

    # -- 1. budget-chunked bootstrap through a traced runtime ----------
    est = DML(CausalConfig(n_folds=k))
    ctx = est.fit(d.y, d.t, d.X, key=jax.random.PRNGKey(0)).fit_ctx
    fn = make_dml_replicate_fn(ctx.nuis_y, ctx.nuis_t, k, with_se=False)
    args = (ctx.XW, ctx.y, ctx.t, ctx.phi)
    keys = replicate_keys(jax.random.PRNGKey(0x0B00), B)
    model = memory_model(fn, keys, args, B)
    assert model is not None and model.slope > 0
    # budget for ~6 replicates -> several chunks, several audit rows
    budget = int(model.base + 6.5 * model.slope)
    rt = TaskRuntime("vmap", memory_budget=budget, tracer=tracer)
    t0 = time.perf_counter()
    jax.block_until_ready(rt.map(fn, keys, *args, label="bootstrap")["theta"])
    t_boot = time.perf_counter() - t0

    # -- 2. crossfit through a traced runtime --------------------------
    folds_key, fit_key = jax.random.split(jax.random.PRNGKey(7))
    t0 = time.perf_counter()
    crossfit(make_ridge(), make_ridge(), fit_key, d.X, d.y, d.t,
             k, engine=TaskRuntime("vmap", tracer=tracer))
    t_cf = time.perf_counter() - t0

    # -- 3. segment sweep with labelled column spans -------------------
    sids = jax.random.randint(folds_key, (n,), 0, n_segments)
    cfg = CausalConfig(n_folds=k, inference="none")
    spec = SweepSpec(n_segments=n_segments, columns=(("dml", cfg),))
    t0 = time.perf_counter()
    panel = sweep(spec, X=d.X, y=d.y, t=d.t, segment_ids=sids,
                  key=jax.random.PRNGKey(3), executor="vmap", tracer=tracer)
    jax.block_until_ready(panel.columns[0].thetas)
    t_sweep = time.perf_counter() - t0

    if out_trace:
        tracer.write_chrome_trace(out_trace)
        csv(f"# obs: wrote Chrome trace ({len(tracer.spans)} spans) "
            f"-> {out_trace}")
    csv("# obs: cost audit (predicted vs measured per chunk)")
    for line in tracer.audit.table().splitlines():
        csv(f"# {line}")

    csv(f"obs_traced_bootstrap_n{n}_B{B},{t_boot*1e6:.0f},"
        f"audit_chunks={len(tracer.audit)}")
    csv(f"obs_traced_crossfit_n{n}_k{k},{t_cf*1e6:.0f},traced")
    csv(f"obs_traced_sweep_n{n}_E{n_segments},{t_sweep*1e6:.0f},traced")

    return {
        "trace_file": out_trace or None,
        "n_spans": len(tracer.spans),
        "spans": tracer.rollup(),
        "audit": {
            "summary": tracer.audit.summary(),
            "rows": tracer.audit.as_dicts(),
        },
        "metrics": tracer.metrics.snapshot(),
    }


def span_cost(reps: int = 20_000, csv=print):
    """µs per ``layer_span`` on the process tracer (no profiler session
    active) and per compile event through ``jax.monitoring`` into the
    compile accounting (a closed span and two counters)."""
    from repro.obs import layer_span, process_tracer
    process_tracer()
    t0 = time.perf_counter()
    for _ in range(reps):
        with layer_span(None, "obs.bench_span", label="x", chunk=2):
            pass
    span_us = (time.perf_counter() - t0) / reps * 1e6
    with layer_span(None, "obs.bench_compiles"):
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.monitoring.record_event_duration_secs(
                "/jax/core/compile/backend_compile_duration", 1e-3,
                fun_name="f")
        event_us = (time.perf_counter() - t0) / reps * 1e6
    csv(f"obs_span_cost_us,{span_us:.3f},reps={reps}")
    csv(f"obs_compile_event_cost_us,{event_us:.3f},reps={reps}")
    return span_us, event_us


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--B", type=int, default=64)
    ap.add_argument("--trace", default="BENCH_trace.json",
                    help="Chrome trace output path ('' disables)")
    ap.add_argument("--span-cost", action="store_true",
                    help="measure the process tracer's host cost only")
    args = ap.parse_args(argv)
    if args.span_cost:
        span_cost()
        return
    payload = run(B=args.B, out_trace=args.trace)
    print(f"# obs rollup: {payload['spans']}")


if __name__ == "__main__":
    main()

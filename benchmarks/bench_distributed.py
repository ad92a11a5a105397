"""Row-sharded execution benchmark: the data-mesh path vs the
single-process chunked baseline, same process, 8 forced CPU devices
(a CPU rehearsal of the mesh; the chip mesh is ``chip_smoke.py
--chips 4``).

Two workloads, the tentpole's acceptance rows:

  dist_sweep_*         a small estimator sweep end-to-end (trace +
                       compile + run — the per-column latency a job
                       submission pays), ``data_mesh=None`` vs the
                       ("hosts", "devices") mesh;
  dist_store_ingest_*  one incremental ``MomentStore.ingest`` block on
                       a warm store (jit-cached — steady-state
                       streaming cost), serial vs sharded.

Every row's derived column carries ``identity=PASS|FAIL`` — the
sharded panel/accumulators must be BITWISE the single-process result
("ordered" reduction); a FAIL here is a correctness regression, not a
perf one.

Run via ``run_subprocess`` from benchmarks/run.py: the forced
``--xla_force_host_platform_device_count=8`` must live in a CHILD
process, because jax pins the device count at first backend init and
every other bench section measures the 1-device baseline the >20%
gate was recorded against.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import subprocess
import sys
import time

N_DEVICES = 8


def _time(fn, reps=3):
    fn()  # warm-up (and compile, where the callee caches)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(n=8192, p=8, n_segments=4, row_block=256, csv=print, reps=2):
    import jax
    import jax.numpy as jnp

    from repro.config import CausalConfig
    from repro.data.causal_dgp import make_causal_data
    from repro.runtime import make_data_mesh
    from repro.store import MomentStore
    from repro.sweep import SweepSpec, sweep

    dm = make_data_mesh()
    d = make_causal_data(jax.random.PRNGKey(42), n, p, effect=1.2)
    sids = jax.random.randint(jax.random.PRNGKey(9), (n,), 0, n_segments)
    key = jax.random.PRNGKey(0)
    cfg = CausalConfig(n_folds=3, inference="none", row_block=row_block)
    # Two dml columns (different fold counts): dml's weighted cell is
    # blocked END-TO-END, so the bitwise identity check holds at bench
    # scale, not just the canonical conformance shapes.  Estimators
    # with unblocked whole-array functionals (drlearner's ATE mean,
    # the metalearner cores) can drift 1-2 ulp at some data shapes
    # when XLA retiles those ambient reductions around shard_map — the
    # registry-wide certificate at canonical shapes lives in
    # tests/test_distributed_runtime.py.
    cfg5 = CausalConfig(n_folds=5, inference="none", row_block=row_block)
    spec = SweepSpec(n_segments=n_segments,
                     columns=(("dml", cfg), ("dml", cfg5)))
    kw = dict(X=d.X, y=d.y, t=d.t, segment_ids=sids, key=key)
    tag = f"n{n}_p{p}_E{n_segments}_{dm.label}"

    # -- sweep: end-to-end column latency (includes trace + compile) ----
    p_single = sweep(spec, **kw)
    p_dist = sweep(spec, data_mesh=dm, **kw)
    sweep_ok = all(
        bool(jnp.array_equal(c1.thetas, c2.thetas))
        and bool(jnp.array_equal(c1.ates, c2.ates))
        for c1, c2 in zip(p_single.columns, p_dist.columns))
    t_single = _time(lambda: sweep(spec, **kw), reps)
    t_dist = _time(lambda: sweep(spec, data_mesh=dm, **kw), reps)
    csv(f"dist_sweep_single_{tag},{t_single*1e6:.0f},baseline")
    csv(f"dist_sweep_sharded_{tag},{t_dist*1e6:.0f},"
        f"speedup={t_single/max(t_dist, 1e-12):.2f}x "
        f"identity={'PASS' if sweep_ok else 'FAIL'}")

    # -- store: steady-state incremental ingest (jit warm) --------------
    scfg = CausalConfig(n_folds=3, inference="none", row_block=row_block,
                        nuisance_t="ridge", discrete_treatment=False,
                        cate_features=1)
    sspec = SweepSpec(n_segments=n_segments, columns=(("dml", scfg),))
    blk = dict(X=d.X, y=d.y, t=d.t, segment_ids=sids)  # aligned: n % rb == 0
    ms_serial = MomentStore(sspec, n_features=p, key=key)
    ms_shard = MomentStore(sspec, n_features=p, key=key, data_mesh=dm)
    ms_serial.ingest(**blk)
    ms_shard.ingest(**blk)
    r1, r2 = ms_serial.refresh(), ms_shard.refresh()
    store_ok = all(
        bool(jnp.array_equal(c1.thetas, c2.thetas))
        for c1, c2 in zip(r1.columns, r2.columns))
    t_ser = _time(lambda: ms_serial.ingest(**blk), reps)
    t_shd = _time(lambda: ms_shard.ingest(**blk), reps)
    csv(f"dist_store_ingest_serial_{tag},{t_ser*1e6:.0f},baseline")
    csv(f"dist_store_ingest_sharded_{tag},{t_shd*1e6:.0f},"
        f"speedup={t_ser/max(t_shd, 1e-12):.2f}x "
        f"identity={'PASS' if store_ok else 'FAIL'}")
    return {"sweep": t_dist, "store": t_shd,
            "identity": sweep_ok and store_ok}


def run_subprocess(csv=print, smoke=True, timeout=1800):
    """Spawn this module with the forced 8-device CPU flag and feed its
    CSV stdout lines into ``csv`` (benchmarks/run.py's Recorder)."""
    root = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    # a CPU rehearsal of the mesh: the child must not reach for an
    # accelerator the parent process already holds
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={N_DEVICES}")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(root), str(root / "src"),
                    os.environ.get("PYTHONPATH", "")) if p)
    cmd = [sys.executable, str(pathlib.Path(__file__).resolve())]
    if not smoke:
        cmd.append("--full")
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=timeout)
    if proc.returncode != 0:
        tail = (proc.stdout + proc.stderr).strip().splitlines()[-3:]
        raise RuntimeError("bench_distributed subprocess failed: "
                           + " | ".join(tail))
    for line in proc.stdout.splitlines():
        if line.startswith("dist_"):
            csv(line)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="larger rows (n=32768)")
    args = ap.parse_args(argv)
    if args.full:
        run(n=32_768)
    else:
        run()


if __name__ == "__main__":
    main()

"""Benchmark aggregator: one section per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines AND writes a
standardized ``BENCH_results.json`` (override with --json) so the
bench trajectory is machine-readable across PRs:

    {"meta": {...}, "entries": [
        {"name": ..., "us_per_call": ..., "derived": ...}, ...]}
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import platform
import sys
import time

# make `python benchmarks/run.py` work from any cwd: the repo root
# provides the `benchmarks` package, src/ provides `repro` when the
# package isn't pip-installed
_ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class Recorder:
    """print-compatible sink that also parses the CSV lines into
    standardized JSON entries."""

    def __init__(self):
        self.entries = []

    def __call__(self, line: str):
        print(line)
        if not line or line.startswith("#"):
            return
        parts = line.split(",", 2)
        if len(parts) < 2:
            return
        try:
            us = float(parts[1])
        except ValueError:
            return
        self.entries.append({
            "name": parts[0],
            "us_per_call": us,
            "derived": parts[2] if len(parts) > 2 else "",
        })


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-exact scales (1M x 500; slow on CPU)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced fixed-size subset for the CI "
                         "bench-gate: crossfit/inference/final_stage/"
                         "runtime/obs only, minutes not tens of minutes")
    ap.add_argument("--json", default="BENCH_results.json",
                    help="output path for the standardized bench JSON "
                         "('' disables)")
    ap.add_argument("--outdir", default="bench_out",
                    help="directory for bench side artifacts (the "
                         "Chrome trace) — keeps the repo root clean")
    args = ap.parse_args(argv)
    pathlib.Path(args.outdir).mkdir(parents=True, exist_ok=True)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # a fixed in-checkout path (the path is part of the cache key);
        # JAX reads JAX_COMPILATION_CACHE_DIR itself when it is set
        jax.config.update("jax_compilation_cache_dir",
                          str(_ROOT / ".jax_cache"))

    rec = Recorder()
    t0 = time.time()
    print("name,us_per_call,derived")

    print("# --- paper Fig. 6: DML vs DML_Ray crossfit runtime ---")
    from benchmarks import bench_crossfit
    if args.full:
        bench_crossfit.run(sizes=(10_000, 100_000, 1_000_000), p=500,
                           csv=rec)
    elif args.smoke:
        bench_crossfit.run(sizes=(5_000, 10_000), p=20, csv=rec)
    else:
        bench_crossfit.run(sizes=(10_000, 30_000, 100_000), p=50, csv=rec)

    if not args.smoke:
        print("# --- paper Fig. 5 / 5.2: distributed tuning ---")
        from benchmarks import bench_tuning
        bench_tuning.run(n=20_000, p=50, n_trials=8, n_folds=5, csv=rec)

    print("# --- bootstrap inference: serial vs batched executor ---")
    from benchmarks import bench_inference
    if args.full:
        bench_inference.run(sizes=(10_000, 100_000), p=500, B=200, csv=rec)
    elif args.smoke:
        bench_inference.run(sizes=(5_000,), p=20, B=16, csv=rec)
    else:
        bench_inference.run(sizes=(5_000, 10_000), p=20, B=32, csv=rec)

    print("# --- orthogonal-IV family: OrthoIV/DRIV fits + bootstrap ---")
    from benchmarks import bench_iv
    if args.full:
        bench_iv.run(sizes=(10_000, 100_000), p=500, B=200, csv=rec)
    elif args.smoke:
        bench_iv.run(sizes=(5_000,), p=20, B=16, csv=rec)
    else:
        bench_iv.run(sizes=(5_000, 10_000), p=20, B=32, csv=rec)

    print("# --- streaming moments: chunked vs whole final stage ---")
    from benchmarks import bench_final_stage
    if args.full:
        bench_final_stage.run(n=1_000_000, p=50, p_phi=4, row_block=8192,
                              csv=rec)
    else:
        bench_final_stage.run(csv=rec)

    print("# --- task runtime: memory-budgeted chunked scheduling ---")
    from benchmarks import bench_runtime
    if args.smoke:
        bench_runtime.run(B=200, csv=rec)
    else:
        bench_runtime.run(B=2000, csv=rec)

    print("# --- segment sweep: serial loop vs batched panel (E=64) ---")
    from benchmarks import bench_sweep
    if args.full:
        bench_sweep.run(n=65_536, p=50, n_folds=5, csv=rec)
    elif args.smoke:
        bench_sweep.run(n=8192, csv=rec)
    else:
        bench_sweep.run(csv=rec)

    print("# --- fused segment-Gram kernel vs one-hot einsum ---")
    from benchmarks import bench_seg_gram
    if args.full:
        bench_seg_gram.run(n=65_536, csv=rec)
    elif args.smoke:
        bench_seg_gram.run(n=8192, csv=rec)
    else:
        bench_seg_gram.run(csv=rec)

    print("# --- effect store: incremental ingest vs full refit ---")
    from benchmarks import bench_store
    if args.full:
        bench_store.run(n_day=16_384, days=5, p=20, csv=rec)
    elif args.smoke:
        bench_store.run(n_day=2048, days=3, csv=rec)
    else:
        bench_store.run(csv=rec)

    print("# --- effect serving: wave-batched scoring latency/QPS ---")
    from benchmarks import bench_serve
    if args.full:
        bench_serve.run(n_requests=4096, wave=256, n_day=16_384, p=20,
                        n_segments=64, csv=rec)
    elif args.smoke:
        bench_serve.run(n_requests=256, wave=64, n_day=2048, csv=rec)
    else:
        bench_serve.run(csv=rec)

    print("# --- observability: traced smoke run + cost audit ---")
    from benchmarks import bench_obs
    trace_path = str(pathlib.Path(args.outdir) / "BENCH_trace.json")
    if args.smoke:
        obs_payload = bench_obs.run(B=32, csv=rec, out_trace=trace_path)
    else:
        obs_payload = bench_obs.run(csv=rec, out_trace=trace_path)

    print("# --- distributed: row-sharded sweep + store over 8 devices ---")
    # Runs in a SUBPROCESS: the forced host-platform device count must
    # not leak into this process (jax pins the device count at first
    # init, and every other section benches the 1-device baseline the
    # >20% gate was recorded against).
    from benchmarks import bench_distributed
    bench_distributed.run_subprocess(
        csv=rec, smoke=bool(args.smoke or not args.full))

    if not args.smoke:
        print("# --- kernel micro-benchmarks ---")
        from benchmarks import bench_kernels
        bench_kernels.main(csv=rec)

        print("# --- multi-pod dry-run roofline (deliverable e/g) ---")
        from benchmarks import bench_dryrun
        bench_dryrun.main([], csv=rec)

    if args.json:
        payload = {
            "meta": {
                "schema": "bench-v1",
                "unix_time": int(t0),
                "wall_seconds": round(time.time() - t0, 1),
                "full": bool(args.full),
                "smoke": bool(args.smoke),
                "backend": jax.default_backend(),
                "platform": platform.platform(),
            },
            "entries": rec.entries,
            # span rollups + predicted-vs-measured audit + metrics from
            # the traced smoke run (benchmarks/bench_obs; informational,
            # not under the bench gate)
            "obs": obs_payload,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {len(rec.entries)} entries -> {args.json}")


if __name__ == "__main__":
    main()

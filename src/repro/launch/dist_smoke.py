"""Two-process ``jax.distributed`` smoke test for the data mesh.

Launches N worker processes (default 2) on localhost, each pinned to
the CPU (``JAX_PLATFORMS=cpu``) with its own forced device count,
initializes ``jax.distributed`` against a local coordinator, builds a
``("hosts", "devices")`` data mesh spanning every process, and runs one
``dist_reduce`` weighted-Gram pass in "psum" mode, checking the result
against a local numpy reference.

A CPU multi-process rehearsal: the workers never touch an accelerator,
so the script is safe on a TPU host (where a child reaching for the
chip would fight its holder).  Anything but a matching result — a
wrong answer, a worker error, a timeout — is a FAIL with a non-zero
exit.  The bitwise "ordered" certificate is carried by the
single-process forced-8-device suite (tests/test_distributed_runtime.py);
the real multi-chip path is ``chip_smoke.py --chips 4``.

Usage:  python -m repro.launch.dist_smoke [--nprocs 2]
"""
from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys

OK_MARKER = "DIST_SMOKE_OK"
FAIL_MARKER = "DIST_SMOKE_FAIL"


def _worker(proc: int, nprocs: int, port: int) -> int:
    import jax

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nprocs,
        process_id=proc,
    )
    import jax.numpy as jnp
    import numpy as np

    from repro.runtime.distributed import dist_reduce, make_data_mesh

    dm = make_data_mesh(n_hosts=nprocs, reduction="psum")
    rng = np.random.default_rng(0)
    n, p = 512, 8
    X = rng.standard_normal((n, p)).astype(np.float32)
    w = rng.random(n).astype(np.float32)

    def block(xb, wb):
        return (wb[:, None] * xb).T @ xb

    got = dist_reduce(block, [jnp.asarray(X), jnp.asarray(w)],
                      row_block=64, dm=dm)
    ref = (w[:, None] * X).T @ X
    ok = bool(np.allclose(np.asarray(got), ref, rtol=1e-4, atol=1e-3))
    if proc == 0:
        print(OK_MARKER if ok else FAIL_MARKER, flush=True)
    return 0 if ok else 1


def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_smoke(nprocs: int = 2, devices_per_proc: int = 2,
              timeout: float = 120.0) -> str:
    """Spawn the workers; returns "OK" or "FAIL: <why>"."""
    port = _free_port()
    src = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices_per_proc}")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, os.environ.get("PYTHONPATH", "")) if p)
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "repro.launch.dist_smoke",
             "--proc", str(i), "--nprocs", str(nprocs),
             "--port", str(port)],
            env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        for i in range(nprocs)
    ]
    outs = []
    try:
        for pr in procs:
            out, _ = pr.communicate(timeout=timeout)
            outs.append(out or "")
    except subprocess.TimeoutExpired:
        for pr in procs:
            pr.kill()
            pr.communicate()
        return f"FAIL: workers timed out after {timeout:.0f}s"
    combined = "\n".join(outs)
    if FAIL_MARKER in combined:
        return "FAIL: result diverged from the numpy reference"
    if OK_MARKER in combined and all(pr.returncode == 0 for pr in procs):
        return "OK"
    tail = combined.strip().splitlines()[-1] if combined.strip() else "no output"
    return f"FAIL: workers did not complete ({tail[:200]})"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nprocs", type=int, default=2)
    ap.add_argument("--devices-per-proc", type=int, default=2)
    ap.add_argument("--timeout", type=float, default=120.0)
    ap.add_argument("--proc", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--port", type=int, default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.proc is not None:
        return _worker(args.proc, args.nprocs, args.port)
    verdict = run_smoke(nprocs=args.nprocs,
                        devices_per_proc=args.devices_per_proc,
                        timeout=args.timeout)
    print(f"dist_smoke: {verdict}")
    return 0 if verdict == "OK" else 1


if __name__ == "__main__":
    sys.exit(main())

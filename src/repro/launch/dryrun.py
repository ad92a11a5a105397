import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST stay the first statements in this module
# (jax locks the platform device count at first init), which is also why
# there is no `from __future__ import annotations` here.

"""Multi-pod dry-run (deliverable e).

Lowers + compiles every (architecture × input-shape) cell against the
production mesh — single-pod (16,16)=256 chips and multi-pod
(2,16,16)=512 chips — and reports memory_analysis / cost_analysis /
collective stats per cell.  This is how the distribution config is
proven coherent without hardware: sharding mismatches, unsupported
collectives and compile-time OOMs all surface here as hard failures.

The two lines above MUST stay the first statements in this module: jax
locks the platform device count at first init, and only the dry-run is
allowed to see 512 placeholder devices (tests/benches see 1).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --json out.jsonl
"""
import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax

from repro.config import SHAPES, TrainConfig
from repro.configs import ARCH_IDS
from repro.launch.cells import Cell, cell_input_shardings, make_cell
from repro.launch.mesh import make_production_mesh
from repro.launch import hlo_cost
from repro.launch.roofline import Roofline, model_flops_for, peaks_for
from repro.launch.train import make_train_step
from repro.optim.adamw import AdamWState, adamw_init


# the chip the production meshes (launch/mesh.py) are laid out for
TARGET_DEVICE_KIND = "TPU v5 lite"


def _abstract_opt(model, params_abs) -> AdamWState:
    return jax.eval_shape(
        lambda p: adamw_init(p, model.parallel.adam_moment_dtype), params_abs)


def _opt_shardings(param_sh, mesh) -> AdamWState:
    from jax.sharding import NamedSharding, PartitionSpec as P
    return AdamWState(step=NamedSharding(mesh, P()), m=param_sh, v=param_sh)


def lower_cell(cell: Cell, mesh, tcfg: Optional[TrainConfig] = None):
    """Returns (lowered, example shapes) for the cell's entry point."""
    model = cell.model()
    params_abs = model.abstract_params()
    param_sh = model.param_shardings(cell.rules, mesh)
    inputs, input_sh = cell_input_shardings(cell, mesh)

    if cell.shape.kind == "train":
        tcfg = tcfg or TrainConfig()
        opt_abs = _abstract_opt(model, params_abs)
        opt_sh = _opt_shardings(param_sh, mesh)
        step = make_train_step(model, tcfg)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step,
                in_shardings=(param_sh, opt_sh, input_sh),
                out_shardings=(param_sh, opt_sh, None),
            ).lower(params_abs, opt_abs, inputs)
        return lowered

    if cell.shape.kind == "prefill":
        def prefill(params, batch):
            return model.prefill(params, batch)

        with jax.set_mesh(mesh):
            lowered = jax.jit(
                prefill, in_shardings=(param_sh, input_sh),
            ).lower(params_abs, inputs)
        return lowered

    # decode: keep the cache sharding stable across steps
    def serve_step(params, tokens, cache, pos):
        return model.decode_step(params, tokens, cache, pos)

    with jax.set_mesh(mesh):
        lowered = jax.jit(
            serve_step,
            in_shardings=(param_sh, input_sh["tokens"], input_sh["cache"],
                          input_sh["pos"]),
            out_shardings=(None, input_sh["cache"]),
        ).lower(params_abs, inputs["tokens"], inputs["cache"], inputs["pos"])
    return lowered


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             verbose: bool = True) -> Dict[str, Any]:
    t0 = time.time()
    cell = make_cell(arch, shape_name, multi_pod=multi_pod)
    model = cell.model()
    ok, why = model.supports_shape(cell.shape)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": 512 if multi_pod else 256,
    }
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    lowered = lower_cell(cell, mesh)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    # trip-count-aware cost (XLA's cost_analysis counts while bodies once;
    # see launch/hlo_cost.py) — raw XLA numbers kept alongside for audit
    hc = hlo_cost.analyze(hlo, world=rec["chips"])
    rl = Roofline(
        flops=hc.flops,
        hbm_bytes=hc.bytes,
        wire_bytes=hc.wire_bytes,
        model_flops=model_flops_for(cell.cfg, cell.shape),
        chips=rec["chips"],
        peaks=peaks_for(TARGET_DEVICE_KIND),
    )
    mem = {}
    if ma is not None:
        mem = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
        mem["peak_bytes"] = (mem["argument_bytes"] + mem["output_bytes"]
                             + mem["temp_bytes"] - mem["alias_bytes"])
    rec.update(
        status="ok",
        flops_per_chip=rl.flops,
        hbm_bytes_per_chip=rl.hbm_bytes,
        xla_flops_single_trip=float(ca.get("flops", 0.0)),
        xla_bytes_single_trip=float(ca.get("bytes accessed", 0.0)),
        unknown_trip_counts=hc.unknown_trip_counts,
        wire_bytes_per_chip=rl.wire_bytes,
        collective_count=hc.coll_count,
        collective_by_op={k: float(v) for k, v in hc.coll_by_op.items()},
        model_flops=rl.model_flops,
        t_compute=rl.t_compute, t_memory=rl.t_memory,
        t_collective=rl.t_collective,
        bottleneck=rl.bottleneck, step_time=rl.step_time,
        useful_frac=rl.useful_flops_frac, mfu_bound=rl.mfu_bound,
        memory=mem, lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
    )
    if verbose:
        print(f"[{rec['mesh']}] {arch}/{shape_name}: "
              f"bottleneck={rl.bottleneck} step>={rl.step_time*1e3:.1f}ms "
              f"mfu_bound={rl.mfu_bound:.2%} "
              f"peak_mem={mem.get('peak_bytes', 0)/2**30:.2f}GiB "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
        print("  memory_analysis:", ma)
    return rec


def run_dml_cell(*, multi_pod: bool, verbose: bool = True,
                 n: int = 0, p: int = 0,
                 engine: str = "parallel") -> Dict[str, Any]:
    """The paper's own 1M x 500 fold-parallel DML fit on the mesh."""
    from repro.launch import dml_cell
    t0 = time.time()
    rec: Dict[str, Any] = {
        "arch": f"dml-crossfit-{engine}",
        "shape": f"{n or dml_cell.N_ROWS}rows",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": 512 if multi_pod else 256,
    }
    mesh = make_production_mesh(multi_pod=multi_pod)
    lowered = dml_cell.lower_dml_cell(
        mesh, n=n or dml_cell.N_ROWS, p=p or dml_cell.N_COVARIATES,
        engine=engine)
    compiled = lowered.compile()
    hc = hlo_cost.analyze(compiled.as_text(), world=rec["chips"])
    ma = compiled.memory_analysis()
    nn, pp = n or dml_cell.N_ROWS, p or dml_cell.N_COVARIATES
    # useful model flops: 2 nuisance Gram/Newton passes + final stage
    model_fl = 2.0 * 5 * nn * pp * pp * (1 + 16) / 4  # rough; see roofline
    rl = Roofline(flops=hc.flops, hbm_bytes=hc.bytes,
                  wire_bytes=hc.wire_bytes, model_flops=model_fl,
                  chips=rec["chips"], peaks=peaks_for(TARGET_DEVICE_KIND))
    mem = {}
    if ma is not None:
        mem = {"argument_bytes": int(ma.argument_size_in_bytes),
               "temp_bytes": int(ma.temp_size_in_bytes)}
        mem["peak_bytes"] = (mem["argument_bytes"] + mem["temp_bytes"]
                             + int(ma.output_size_in_bytes))
    rec.update(status="ok", flops_per_chip=rl.flops,
               hbm_bytes_per_chip=rl.hbm_bytes,
               wire_bytes_per_chip=rl.wire_bytes,
               collective_by_op={k: float(v)
                                 for k, v in hc.coll_by_op.items()},
               collective_count=hc.coll_count,
               model_flops=model_fl, t_compute=rl.t_compute,
               t_memory=rl.t_memory, t_collective=rl.t_collective,
               bottleneck=rl.bottleneck, step_time=rl.step_time,
               useful_frac=rl.useful_flops_frac, mfu_bound=rl.mfu_bound,
               memory=mem, compile_s=round(time.time() - t0, 1))
    if verbose:
        print(f"[{rec['mesh']}] dml-crossfit/{rec['shape']}: "
              f"bottleneck={rl.bottleneck} step>={rl.step_time*1e3:.1f}ms "
              f"peak_mem={mem.get('peak_bytes', 0)/2**30:.2f}GiB")
        print("  memory_analysis:", ma)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="")
    ap.add_argument("--shape", default="")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--paper-cell", action="store_true",
                    help="lower the paper's 1Mx500 DML fit instead")
    ap.add_argument("--json", default="")
    args = ap.parse_args(argv)

    if args.paper_cell:
        out = open(args.json, "a") if args.json else None
        for mp in {"single": [False], "multi": [True],
                   "both": [False, True]}[args.mesh]:
            for engine in ("parallel", "parallel_loo"):
                rec = run_dml_cell(multi_pod=mp, engine=engine)
                if out:
                    out.write(json.dumps(rec) + "\n")
                    out.flush()
        if out:
            out.close()
        return 0

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = ([s.name for s in SHAPES] if (args.all or not args.shape)
              else [args.shape])
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    out = open(args.json, "a") if args.json else None
    failed = 0
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                try:
                    rec = run_cell(arch, shape, multi_pod=mp)
                except Exception as e:  # a sharding bug — report, keep going
                    failed += 1
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "error", "error": repr(e),
                           "trace": traceback.format_exc()[-2000:]}
                    print(f"[FAIL] {arch}/{shape}: {e}", file=sys.stderr)
                if out:
                    out.write(json.dumps(rec) + "\n")
                    out.flush()
    if out:
        out.close()
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Readings for the correctness limits of one cell, on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>
    python3 chipbench/control.py --workload <cell> --seeds 4,5,6 --seconds <s> \\
        --control-seeds 0 --fault <name>

Runs the cell once per seed, in one process, and reads each number the
check compares: from the program (what the benchmark's runs compare),
and on the first ``--control-seeds`` seeds also from the control, the
plain reference computed in bfloat16 put in the program's place.  With
``--fault`` a fault of ``chipbench.faults`` is planted in the program
first.  Prints, per number, the largest and the smallest program
reading and the smallest control reading, and writes every reading to
``chiprun_out/control/<cell>[.<fault>].json``.  The benchmark's own runs
never run the control or plant a fault.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench.harness import run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also read the control")
    ap.add_argument("--fault", default="",
                    help="a fault of chipbench.faults to plant first")
    args = ap.parse_args(argv)
    if args.fault:
        sys.path.insert(0, str(ROOT / "src"))
        from chipbench.faults import FAULTS
        FAULTS[args.fault](setattr)
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    for i, seed in enumerate(seeds):
        r = run_cell(args.workload, seed, args.seconds, False,
                     control=i < args.control_seeds)
        rows.append({"seed": seed, "fault": args.fault, "correct": r["correct"],
                     "program": {k: v["value"] for k, v in r["compared"].items()},
                     "limit": {k: v["limit"] for k, v in r["compared"].items()},
                     "control": r.get("control", {}),
                     "metrics": r["metrics"]})
        print(json.dumps(rows[-1]), flush=True)
    out = ROOT / "chiprun_out" / "control"
    out.mkdir(parents=True, exist_ok=True)
    name = args.workload + (f".{args.fault}" if args.fault else "")
    (out / f"{name}.json").write_text(json.dumps(rows, indent=1))
    for k in rows[0]["program"]:
        got = [r["program"][k] for r in rows]
        ctl = [r["control"][k] for r in rows if k in r["control"]]
        print(f"reading {k}: program max {max(got)!r} min {min(got)!r} "
              f"control min {min(ctl) if ctl else float('nan')!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

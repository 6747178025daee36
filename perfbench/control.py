"""Readings that set a cell's limits: the program's and its control's.

    python3 perfbench/control.py --workload <cell> --seeds 11,12,13 --seconds 5

For each seed this runs the cell's window once, as ``run.py`` does, and
prints one JSON line with every number compared for the program and for
the control: the plain reference computed in the next lower precision
(float32 for the dispatch estimates, bfloat16 payloads for validation),
put in the program's place. The limits in the traffic files lie between
the two. Runs on the chip only; the benchmark's own runs never run the
control.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from perfbench.run import prepare

    try:
        cell, devices = prepare(args.workload)
    except (RuntimeError, KeyError) as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    driver = importlib.import_module(f"perfbench.drivers.{cell.traffic['driver']}")
    trace_dir = str(ROOT / "perfbench" / ".cache" / "trace")
    for seed in (int(s) for s in args.seeds.split(",")):
        run = driver.run(cell, seed, args.seconds, False, devices, time.perf_counter(),
                         trace_dir)
        t0 = time.perf_counter()
        ctrl = driver.control(cell, run)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "attempted": run.attempted,
            "program": {k: v for k, (v, _) in run.checks.items()},
            "control": {k: v for k, (v, _) in ctrl.items()},
            "limits": {k: lim for k, (_, lim) in run.checks.items()},
            "control_s": time.perf_counter() - t0,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Chip benchmark of the BOINC reproduction: one run of one cell.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic and metrics are found by name in
``BENCHMARK.json``. With ``--trace 0`` the result line carries the cell's
end-to-end metrics; with ``--trace 1`` the same window runs under the
profiler and the line carries its per-layer metrics, the device's busy
time and a breakdown of the trace. Every run checks what its timed path
produced against a plain reference and prints each number compared with
its limit. With no TPU, or fewer chips than the cell asks for, it exits
with code 2 before anything is built.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE = ROOT / "perfbench" / ".cache"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare(workload: str):
    """Everything a run needs before the driver: the import paths, the
    compile cache at its fixed place in the checkout, the cell found by
    name and its chips. Raises ``NoAccelerator`` (or ``KeyError`` for a
    chip with no published peaks) before anything is built."""
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    from perfbench.harness import device
    from perfbench.harness.cell import find_cell

    cell = find_cell(workload, ROOT)
    devices = device.require_chips(cell.chips)
    device.peaks(devices[0].device_kind)
    from repro.core import jax_backend

    jax_backend.configure_compile_cache()
    return cell, devices


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell, devices = prepare(args.workload)
    except (RuntimeError, KeyError) as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    from perfbench.harness.counters import earlier_line
    from perfbench.harness.report import emit, result

    driver = importlib.import_module(f"perfbench.drivers.{cell.traffic['driver']}")
    trace_dir = str(CACHE / "trace")
    try:
        run = driver.run(cell, args.seed, args.seconds, bool(args.trace), devices,
                         T_START, trace_dir)
        run.device_planes = [f"/device:TPU:{d.id}" for d in devices]
        if run.trace is not None:
            earlier_line("trace", {"lines": run.trace.lines,
                                   "spans": len(run.trace.spans),
                                   "ops": {p: len(v) for p, v in run.trace.ops.items()}})
        line = result(cell, run, bool(args.trace))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The result line: metrics by name, the device, the trace's breakdown and
every number compared beside its limit."""
from __future__ import annotations

import json
import sys
from typing import Dict

from . import trace as tracing
from .cell import Cell, Run, load_module


def device_fields(run: Run) -> Dict:
    """``busy_s`` (mean over the chips used) and ``window_s`` of a traced run."""
    lo, hi = run.window_ns()
    busy = [tracing.busy(run.trace.ops.get(p, []), lo, hi) for p in run.device_planes]
    return {"busy_s": sum(busy) / len(busy) / 1e9, "window_s": (hi - lo) / 1e9}


def breakdown(run: Run) -> Dict:
    lo, hi = run.window_ns()
    ops = run.trace.ops.get(run.device_planes[0], [])
    top = tracing.time_by_name(ops, lo, hi)[:10]
    gaps = tracing.longest_gaps(ops, run.trace.spans, lo, hi, k=10,
                                exclude=(tracing.SPAN_PREFIX + "clock",))
    return {"device_ops": [[n, t / 1e9] for n, t in top],
            "idle_gaps": [[n, t / 1e9] for n, t in gaps]}


def result(cell: Cell, run: Run, trace: bool) -> Dict:
    metrics: Dict[str, Dict] = {}
    if trace:
        for name, unit in cell.per_layer.items():
            value = load_module("metrics", name).read(run)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": unit}
    else:
        for name, unit in cell.end_to_end.items():
            metrics[name] = {"value": float(run.end_to_end[name]), "unit": unit}
    dev = dict(run.device)
    out: Dict = {
        "correct": all(v <= lim for v, lim in run.checks.values()),
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": metrics,
        "device": dev,
    }
    if trace:
        dev.update(device_fields(run))
        out["breakdown"] = breakdown(run)
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in run.checks.items()}
    return out


def emit(line: Dict) -> None:
    """The checks as the last lines of standard error, then the result as
    the last line of standard output."""
    for k, c in line["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {k} = {c['value']!r} limit {c['limit']!r} {ok}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)

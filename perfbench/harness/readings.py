"""Helpers the per-layer metric readers share: the trace cut to the
measured window, to the benchmark's spans, and the device's idle share."""
from __future__ import annotations

from typing import List, Optional

from . import trace as tracing
from .cell import Run


def ops(run: Run) -> List[tracing.Interval]:
    """Device operations of the first chip used."""
    return run.trace.ops.get(run.device_planes[0], [])


def modules(run: Run) -> List[tracing.Interval]:
    """Device programs (XLA modules) of the first chip used."""
    return run.trace.modules.get(run.device_planes[0], [])


def window_spans(run: Run, name: str) -> List[tracing.Interval]:
    """The ``pb.<name>`` spans that start inside the measured window."""
    lo, hi = run.window_ns()
    return [s for s in tracing.spans_named(run.trace.spans, name) if lo <= s[1] < hi]


def idle_percent(run: Run) -> Optional[float]:
    if run.trace is None or not run.device_planes:
        return None
    lo, hi = run.window_ns()
    busy = sum(tracing.busy(run.trace.ops.get(p, []), lo, hi) for p in run.device_planes)
    return 100.0 * (1.0 - busy / len(run.device_planes) / (hi - lo))

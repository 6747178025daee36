"""Profiler trace of the measured window and its reduction to metrics.

The run with ``--trace 1`` records one ``jax.profiler`` trace around its
window. The reduction reads the trace's XPlane file with
``jax.profiler.ProfileData`` and turns it into plain interval lists:

* device operations: events of the ``XLA Ops`` line of each TPU plane
  (``XLA Modules`` where a plane has no op line), as ``(name, t0, t1)``;
* device programs: events of the ``XLA Modules`` line;
* host spans: the benchmark's ``pb.*`` annotations from the host plane.

All times are in nanoseconds on the trace's own clock. The arithmetic
(busy time as the union of op intervals, device time by op name, idle gaps
and what the host was doing in them) works on those lists alone, so it is
tested on hand-made and CPU-recorded intervals.
"""
from __future__ import annotations

import glob
import os
import shutil
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[str, float, float]

SPAN_PREFIX = "pb."


@dataclass
class TraceEvents:
    ops: Dict[str, List[Interval]] = field(default_factory=dict)  # per device plane
    modules: Dict[str, List[Interval]] = field(default_factory=dict)
    spans: List[Interval] = field(default_factory=list)
    lines: List[str] = field(default_factory=list)  # "plane/line" seen, for diagnosis


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------


def start(log_dir: str) -> None:
    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    os.makedirs(log_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # spans come from TraceAnnotation, not a Python tracer
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop(log_dir: str) -> str:
    """Stop tracing; returns the path of the XPlane file written."""
    import jax

    jax.profiler.stop_trace()
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise RuntimeError(f"profiler wrote no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str) -> TraceEvents:
    """Interval lists from an XPlane file: every ``/device:TPU:<n>`` plane is
    read as a device, every ``/host:`` plane for the benchmark's spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = TraceEvents()
    for plane in pd.planes:
        name = plane.name
        lines = {ln.name: ln for ln in plane.lines}
        out.lines.extend(f"{name}/{ln}" for ln in lines)
        if name.startswith("/device:TPU:"):
            op_line = lines.get("XLA Ops") or lines.get("XLA Modules")
            mod_line = lines.get("XLA Modules")
            out.ops[name] = _intervals(op_line) if op_line is not None else []
            out.modules[name] = _intervals(mod_line) if mod_line is not None else []
        if name.startswith("/host:"):
            for ln in plane.lines:
                out.spans.extend(
                    iv for iv in _intervals(ln) if iv[0].startswith(SPAN_PREFIX)
                )
    return out


def _intervals(line) -> List[Interval]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)) for e in line.events]


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------


def merge(intervals: Sequence[Interval], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Union of intervals clipped to [lo, hi], as sorted disjoint pairs."""
    clipped = sorted(
        (max(a, lo), min(b, hi)) for _, a, b in intervals if b > lo and a < hi
    )
    out: List[List[float]] = []
    for a, b in clipped:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of the union of intervals inside [lo, hi]."""
    return sum(b - a for a, b in merge(intervals, lo, hi))


def time_by_name(intervals: Sequence[Interval], lo: float, hi: float) -> List[Tuple[str, float]]:
    """Summed duration per op name inside [lo, hi], longest first."""
    acc: Dict[str, float] = {}
    for name, a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            acc[name] = acc.get(name, 0.0) + (b - a)
    return sorted(acc.items(), key=lambda kv: -kv[1])


def idle_gaps(intervals: Sequence[Interval], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Stretches of [lo, hi] in which no interval runs."""
    gaps = []
    t = lo
    for a, b in merge(intervals, lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def enclosing(spans: Sequence[Interval], t: float, exclude: Sequence[str] = ()) -> str:
    """Name of the innermost (shortest) span that covers time ``t``."""
    best, best_len = "(no span)", float("inf")
    for name, a, b in spans:
        if a <= t <= b and name not in exclude and b - a < best_len:
            best, best_len = name, b - a
    return best


def longest_gaps(intervals: Sequence[Interval], spans: Sequence[Interval], lo: float,
                 hi: float, k: int = 10, exclude: Sequence[str] = ()) -> List[Tuple[str, float]]:
    """The ``k`` longest idle gaps, each named by the host span that
    encloses its midpoint, with its length in nanoseconds."""
    gaps = sorted(idle_gaps(intervals, lo, hi), key=lambda g: g[0] - g[1])[:k]
    return [(enclosing(spans, (a + b) / 2, exclude), b - a) for a, b in gaps]


def inside(intervals: Sequence[Interval], spans: Sequence[Interval]) -> List[Interval]:
    """The intervals that start inside one of ``spans``."""
    import bisect

    windows = merge(spans, float("-inf"), float("inf"))
    starts = [lo for lo, _ in windows]
    out = []
    for iv in intervals:
        k = bisect.bisect_right(starts, iv[1]) - 1
        if k >= 0 and iv[1] <= windows[k][1]:
            out.append(iv)
    return out


def spans_named(spans: Sequence[Interval], name: str) -> List[Interval]:
    return [s for s in spans if s[0] == SPAN_PREFIX + name]


def clock_offset(events: TraceEvents, records: Sequence[Tuple[str, float, float]]) -> float:
    """Trace ns minus ``perf_counter`` ns, read from the ``clock`` span that
    a driver opens as the trace starts (``records`` are its host spans as
    ``(name, t0, t1)`` in ``perf_counter`` seconds)."""
    mark = spans_named(events.spans, "clock")
    mine = [a for n, a, _ in records if n == "clock"]
    if not mark or not mine:
        raise RuntimeError("the trace holds no pb.clock span to align clocks")
    return mark[0][1] - mine[0] * 1e9

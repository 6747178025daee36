"""The program's own spans (``boinc.*``) in a traced run, and the window
arithmetic the readers of the program's per-layer metrics share.

``trace.load`` keeps only the benchmark's ``pb.*`` spans. The program's
``TraceAnnotation`` spans are read here, from the same XPlane file: the one
``perfbench/run.py`` has the driver write under ``perfbench/.cache/trace``
and deletes only after the result line is made. They are on the trace's
clock, like the device planes. On a program that opens no such span the
list is empty and every reader returns None.
"""
from __future__ import annotations

import glob
from typing import List, Optional, Sequence, Tuple

from . import trace as tracing
from .cell import BENCH_DIR, Run

PREFIX = "boinc."
TRACE_DIR = BENCH_DIR / ".cache" / "trace"


def load(path: str) -> List[tracing.Interval]:
    """``(name, t0, t1)`` of every ``boinc.*`` event on a host plane, in
    nanoseconds. Metadata is kept as the event's stats, so the name is
    the bare span name; a ``#k=v#`` suffix, where a profiler writes one,
    is cut off."""
    from jax.profiler import ProfileData

    out: List[tracing.Interval] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.name.split("#", 1)[0], float(e.start_ns),
                                float(e.start_ns + e.duration_ns)))
    return out


def spans(run: Run) -> List[tracing.Interval]:
    """All ``boinc.*`` spans of a traced run, read once and kept in
    ``run.data["program_spans"]``; empty for an untraced run."""
    if run.trace is None:
        return []
    if "program_spans" not in run.data:
        files = sorted(glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"), recursive=True))
        run.data["program_spans"] = load(files[-1]) if files else []
    return run.data["program_spans"]


def window(run: Run, name: str) -> List[tracing.Interval]:
    """The ``boinc.<name>`` spans that start inside the measured window."""
    lo, hi = run.window_ns()
    return [s for s in spans(run) if s[0] == PREFIX + name and lo <= s[1] < hi]


def overlap(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    ``(t0, t1)`` pairs, such as ``trace.merge`` and ``trace.idle_gaps``
    give."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def total_ms(intervals: Sequence[tracing.Interval]) -> float:
    return sum(b - a for _, a, b in intervals) / 1e6


def mean_ms(intervals: Sequence[tracing.Interval]) -> Optional[float]:
    return total_ms(intervals) / len(intervals) if intervals else None


def window_requests(run: Run) -> Optional[int]:
    """Requests the service dispatched in the window, from its own counters
    read at the window's edges."""
    marks = run.data.get("marks", {})
    if "w0" not in marks or "w1" not in marks:
        return None
    return marks["w1"][1]["requests"] - marks["w0"][1]["requests"] or None


def window_jobs(run: Run) -> Optional[int]:
    """Jobs of the validation passes that start inside the window."""
    lo, hi = run.window
    return sum(n for t0, _, n, _ in run.data.get("passes", []) if lo <= t0 < hi) or None

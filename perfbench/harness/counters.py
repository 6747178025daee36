"""Compile counting, host spans and small statistics shared by the drivers."""
from __future__ import annotations

import gc
import math
import time
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np


class CompileCounter:
    """Counts compile requests (a jit's first call for a shape, compiled or
    loaded from the persistent cache) and persistent-cache hits through
    ``jax.monitoring``. Listeners cannot be removed, so one instance is
    registered per process and callers read deltas of ``snapshot()``."""

    _instance: Optional["CompileCounter"] = None

    def __init__(self) -> None:
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> Tuple[int, int]:
        return self.compiles, self.cache_hits


class Spans:
    """Host spans around the benchmark's calls into each layer.

    Each span is written into the profiler's trace as a
    ``jax.profiler.TraceAnnotation`` named ``pb.<layer>`` and kept in memory
    as ``(name, t0, t1)`` on the ``time.perf_counter`` clock."""

    PREFIX = "pb."

    def __init__(self) -> None:
        self.records: List[Tuple[str, float, float]] = []

    @contextmanager
    def span(self, name: str) -> Iterator[None]:
        import jax

        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(self.PREFIX + name):
                yield
        finally:
            self.records.append((name, t0, time.perf_counter()))


class GcLog:
    """This process's garbage collections, recorded through ``gc.callbacks``
    while the log is entered: ``(generation, t0, t1)`` on the
    ``time.perf_counter`` clock, each also a ``pb.gc`` span in the trace."""

    def __init__(self) -> None:
        self.records: List[Tuple[int, float, float]] = []
        self._open: Optional[Tuple[float, object]] = None

    def _callback(self, phase: str, info: Dict) -> None:
        import jax

        if phase == "start":
            ann = jax.profiler.TraceAnnotation(Spans.PREFIX + "gc")
            ann.__enter__()
            self._open = (time.perf_counter(), ann)
        elif self._open is not None:
            t0, ann = self._open
            ann.__exit__(None, None, None)
            self.records.append((int(info["generation"]), t0, time.perf_counter()))
            self._open = None

    def __enter__(self) -> "GcLog":
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._callback)

    def summary(self, lo: float, hi: float) -> Dict[str, List[float]]:
        """Per generation, the collections that began in [lo, hi]:
        [count, total ms, longest ms]."""
        out: Dict[str, List[float]] = {}
        for g, t0, t1 in self.records:
            if lo <= t0 < hi:
                s = out.setdefault(str(g), [0, 0.0, 0.0])
                s[0] += 1
                s[1] += (t1 - t0) * 1e3
                s[2] = max(s[2], (t1 - t0) * 1e3)
        return out


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-quantile (0..1) by the nearest-rank rule: the smallest value
    with at least ``q`` of the samples at or below it. Infinite samples
    (requests never answered) sort last."""
    if not values:
        return math.nan
    s = sorted(values)
    k = max(0, math.ceil(q * len(s)) - 1)
    return s[k]


def float_diff(a: Sequence[float], b: Sequence[float]) -> Tuple[int, float]:
    """(elements that differ, largest relative difference) of two float
    arrays; equal infinities and NaNs count as equal."""
    a = np.asarray(a, dtype=np.float64).ravel()
    b = np.asarray(b, dtype=np.float64).ravel()
    diff = ~((a == b) | (np.isnan(a) & np.isnan(b)))
    if not diff.any():
        return 0, 0.0
    da, db = a[diff], b[diff]
    scale = np.maximum(np.abs(da), np.abs(db))
    rel = np.where(scale > 0, np.abs(da - db) / np.where(scale > 0, scale, 1.0), np.inf)
    return int(diff.sum()), float(rel.max())


def earlier_line(tag: str, payload: Dict) -> None:
    """A diagnostic line on standard output, before the result line."""
    import json

    print(f"[{tag}] {json.dumps(payload, default=float)}", flush=True)

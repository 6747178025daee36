"""The program's own spans and compile counter, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation`` named
``boinc.<layer>.<what>``. While a profiler runs (``jax.profiler.start_trace``
around a live process) each one lands in the trace's host plane, on the
same clock as the device planes, with its keyword metadata as the event's
stats; with none running it costs about a microsecond. Without JAX a span
is a no-op. A span opens and closes on one thread without yielding: never
hold one across an ``await``.

Spans of the hot paths (the per-layer metrics of ``perfbench`` read them):

* wire front: ``boinc.svc.decode`` (``seq``), ``boinc.svc.encode``;
* server: ``boinc.server.rpc_batch`` (``requests``),
  ``boinc.server.shard_pass`` (``shard``, ``requests``),
  ``boinc.feeder.fill``;
* device path: ``boinc.sched.snapshot_build`` (``shard``),
  ``boinc.dispatch.device`` (``kernel``);
* validation: ``boinc.validate.stack`` and its child
  ``boinc.validate.put`` (one a result), ``boinc.validate.group``
  (``rows``), ``boinc.validate.pair`` and its child
  ``boinc.validate.upload``.
"""
from __future__ import annotations

from typing import Optional, Tuple

# a module import, not names from it: jax_backend imports this module too
from . import jax_backend


class _NoSpan:
    """The span without JAX: enters, exits and takes metadata, doing nothing."""

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **meta) -> None:
        pass


_NO_SPAN = _NoSpan()


def span(name: str, **meta):
    """A context manager that records ``name`` in the profiler's trace for
    the time it is entered; the value it enters as takes more metadata
    through ``set_metadata(**meta)``."""
    if jax_backend.HAVE_JAX:
        return jax_backend.jax.profiler.TraceAnnotation(name, **meta)
    return _NO_SPAN


class CompileCounter:
    """Counts compile requests (a jit's first call for a shape, compiled or
    loaded from the persistent cache) and persistent-cache hits through
    ``jax.monitoring``. Listeners cannot be removed, so they are registered
    once per process, by the first ``get()``, and callers read deltas of
    ``snapshot()``. Without JAX both counts stay 0."""

    _instance: Optional["CompileCounter"] = None

    def __init__(self) -> None:
        self.compiles = 0
        self.cache_hits = 0
        if jax_backend.HAVE_JAX:
            monitoring = jax_backend.jax.monitoring
            monitoring.register_event_duration_secs_listener(self._on_duration)
            monitoring.register_event_listener(self._on_event)

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

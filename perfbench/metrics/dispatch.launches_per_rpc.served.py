"""Device programs run per request: XLA modules on the chip that start
inside the window's ``rpc_batch`` spans, over the requests those waves
carried."""
from perfbench.harness import readings, trace


def read(run):
    if run.trace is None:
        return None
    spans = readings.window_spans(run, "rpc_batch")
    lo, hi = run.window
    requests = sum(n for t0, _, n in run.data["waves"] if lo <= t0 < hi)
    if not spans or not requests:
        return None
    return len(trace.inside(readings.modules(run), spans)) / requests

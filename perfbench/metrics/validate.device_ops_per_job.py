"""Device operations per validated job: XLA ops on the chip that start
inside the window's ``validate_pass`` spans, over the jobs of those
passes."""
from perfbench.harness import readings, trace


def read(run):
    if run.trace is None:
        return None
    spans = readings.window_spans(run, "validate_pass")
    lo, hi = run.window
    jobs = sum(n for t0, _, n, _ in run.data["passes"] if lo <= t0 < hi)
    if not spans or not jobs:
        return None
    return len(trace.inside(readings.ops(run), spans)) / jobs

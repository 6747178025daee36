"""Share of the chip's idle time in which the program was working on the
host, %: of the window's gaps between the first chip's operations, the
part covered by the union of the program's ``boinc.*`` spans. The rest is
time the service spent waiting for requests or outside the program."""
from perfbench.harness import program_spans as ps
from perfbench.harness import readings, trace


def read(run):
    spans = ps.spans(run)
    if not spans:
        return None
    lo, hi = run.window_ns()
    gaps = trace.idle_gaps(readings.ops(run), lo, hi)
    idle = sum(b - a for a, b in gaps)
    if idle <= 0:
        return None
    return 100.0 * ps.overlap(gaps, trace.merge(spans, lo, hi)) / idle

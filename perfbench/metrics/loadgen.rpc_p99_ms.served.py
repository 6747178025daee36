"""p99 of the window's requests, each timed from its due time to its reply
(a request never answered counts as waiting until the grace period
closed), in ms. The tail is reported on the traced run and not held to a
bound: a few calls into the project per run stall for 50-150 ms, about
half of it off the CPU, so whether their delayed requests pass 1% of the
window decides the p99 from run to run (see PERF.md)."""
from perfbench.harness.counters import percentile


def read(run):
    lat = run.data.get("latency_s")
    return percentile(lat, 0.99) * 1e3 if lat else None

"""How late the load generator sent the window's requests: p99 of send
time minus due time, in ms. A high value means the generator, not the
service, held requests back."""
from perfbench.harness.counters import percentile


def read(run):
    late = run.data.get("late_s")
    return percentile(late, 0.99) * 1e3 if late else None

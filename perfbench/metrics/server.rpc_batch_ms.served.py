"""Mean time of one call from the service into the project
(``ProjectServer.rpc_batch`` for a wave, ``rpc`` for a single request),
from the benchmark's host spans around those calls in the window, in ms."""


def read(run):
    lo, hi = run.window
    d = [t1 - t0 for t0, t1, _ in run.data.get("waves", []) if lo <= t0 < hi]
    return 1e3 * sum(d) / len(d) if d else None

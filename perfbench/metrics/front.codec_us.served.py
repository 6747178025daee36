"""Wire codec time per request, in us: the program's ``boinc.svc.decode``
spans (one per frame) and ``boinc.svc.encode`` spans (one per wave's
replies) that start in the window, over the requests the service counted
in it."""
from perfbench.harness import program_spans as ps


def read(run):
    spans = ps.window(run, "svc.decode") + ps.window(run, "svc.encode")
    requests = ps.window_requests(run)
    if not spans or not requests:
        return None
    return 1e3 * ps.total_ms(spans) / requests

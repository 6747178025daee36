"""Host time spent in the dispatch jits per request, in ms: the program's
``boinc.dispatch.device`` spans (padding, the jitted calls and the copy of
their results back to the host) that start in the window, over the
requests the service counted in it."""
from perfbench.harness import program_spans as ps


def read(run):
    spans = ps.window(run, "dispatch.device")
    requests = ps.window_requests(run)
    if not spans or not requests:
        return None
    return ps.total_ms(spans) / requests

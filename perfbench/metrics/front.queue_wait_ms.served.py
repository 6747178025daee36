"""Mean time a window's request waited in the service's queue, from its
frame being queued to the start of its dispatch wave, in ms: the service's
``queue_wait_s`` counter over its ``requests`` counter, both read at the
window's edges (``SchedulerService.stats()``)."""


def read(run):
    marks = run.data.get("marks", {})
    if "w0" not in marks or "w1" not in marks:
        return None
    s0, s1 = marks["w0"][1], marks["w1"][1]
    if "queue_wait_s" not in s1:
        return None
    requests = s1["requests"] - s0["requests"]
    return 1e3 * (s1["queue_wait_s"] - s0["queue_wait_s"]) / requests if requests else None

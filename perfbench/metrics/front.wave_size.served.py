"""Requests per dispatch wave of the service's coalescing front, from its
own counters (``SchedulerService.stats()``) read at the window's edges."""


def read(run):
    marks = run.data.get("marks", {})
    if "w0" not in marks or "w1" not in marks:
        return None
    s0, s1 = marks["w0"][1], marks["w1"][1]
    waves = s1["waves"] - s0["waves"]
    return (s1["requests"] - s0["requests"]) / waves if waves else None

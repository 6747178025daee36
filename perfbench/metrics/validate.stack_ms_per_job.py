"""Host time spent stacking a job's results into one matrix and scanning
it for NaN, per job, in ms: the program's ``boinc.validate.stack`` spans
that start in the window, over the jobs of the passes that start in it."""
from perfbench.harness import program_spans as ps


def read(run):
    spans = ps.window(run, "validate.stack")
    jobs = ps.window_jobs(run)
    if not spans or not jobs:
        return None
    return ps.total_ms(spans) / jobs

"""Mean time to rebuild a shard's dispatch snapshot after the cache
changed (a feeder fill or a work migration), in ms: the program's
``boinc.sched.snapshot_build`` spans that start in the window."""
from perfbench.harness import program_spans as ps


def read(run):
    return ps.mean_ms(ps.window(run, "sched.snapshot_build"))

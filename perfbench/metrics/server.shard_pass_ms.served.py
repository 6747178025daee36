"""Mean time of one shard's turn in a call into the project, in ms: the
program's ``boinc.server.shard_pass`` spans (work migration, then the
shard's dispatch pass) that start in the window."""
from perfbench.harness import program_spans as ps


def read(run):
    return ps.mean_ms(ps.window(run, "server.shard_pass"))

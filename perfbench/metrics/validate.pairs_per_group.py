"""Comparisons a job's grouping makes, in pairs a job: the program's
``boinc.validate.pair`` spans over its ``boinc.validate.group`` spans (one
around each job's grouping), both starting in the window. A program that
groups no job on its own opens no group span, and reads nothing."""
from perfbench.harness import program_spans as ps


def read(run):
    groups = ps.window(run, "validate.group")
    if not groups:
        return None
    return len(ps.window(run, "validate.pair")) / len(groups)

"""Transfers to the chip per reported result in the validation pass, in
uploads a result: the program's ``boinc.validate.put`` spans (one around
each result's put) that start in the window, over the results of the
passes that start in it. A program that opens no put span reads nothing."""
from perfbench.harness import program_spans as ps


def read(run):
    puts = ps.window(run, "validate.put")
    lo, hi = run.window
    results = sum(r for t0, _, _, r in run.data.get("passes", []) if lo <= t0 < hi)
    if not puts or not results:
        return None
    return len(puts) / results

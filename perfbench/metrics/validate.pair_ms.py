"""Mean time of one comparison of two results in the validation pass, in
ms: the program's ``boinc.validate.pair`` spans (upload of both rows, the
comparison kernel and the read of its verdict) that start in the window."""
from perfbench.harness import program_spans as ps


def read(run):
    return ps.mean_ms(ps.window(run, "validate.pair"))

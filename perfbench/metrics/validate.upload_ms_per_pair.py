"""Mean host time of one comparison's call, in ms: the program's
``boinc.validate.upload`` spans that start in the window. Each covers the
host side of the jitted call, staging both rows for their upload to the
chip and the launch; the transfer itself and the kernel are waited for
afterwards, in the rest of the ``boinc.validate.pair`` span."""
from perfbench.harness import program_spans as ps


def read(run):
    return ps.mean_ms(ps.window(run, "validate.upload"))

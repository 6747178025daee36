"""Share of the validation window in which no operation ran on the chip, %."""
from perfbench.harness import readings


def read(run):
    return readings.idle_percent(run)

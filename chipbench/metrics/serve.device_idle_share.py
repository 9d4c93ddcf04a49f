"""Share of the traced window in which no operation ran on the chip."""
from chipbench import readers


def read(outcome, run):
    return readers.idle_share(outcome)

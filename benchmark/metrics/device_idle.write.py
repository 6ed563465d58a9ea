"""device_idle.write: share of the traced window in which no operation
ran on the chip (1 - union of the XLA Ops events / window), in %."""

from benchmark import readers


def read(run):
    return readers.device_idle_pct(run)

"""read_MBps: object bytes of the reads that completed inside the
window and equalled what was written, over the window's length."""

from benchmark import readers


def read(run):
    return readers.mb_per_s(run, ("read",))

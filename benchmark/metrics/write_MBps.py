"""write_MBps: object bytes of the write_full ops acknowledged inside
the window, over the window's length."""

from benchmark import readers


def read(run):
    return readers.mb_per_s(run, ("write_full",))

"""dispatches_per_op.read: device programs dispatched per codec op
submitted (window deltas of l_tpu_dispatches over l_tpu_ops of
osd/tpu_dispatch.py, summed over the OSDs). Below 1 where ops share a
dispatch."""

from benchmark import readers


def read(run):
    return readers.dispatches_per_op(run)

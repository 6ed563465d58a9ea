"""osd_queue_ms.read: mean time a client op waited in the OSD op queue
(the `op_queue` span of osd/osd_daemon.py, from every OSD's span
collector) over the ops of the window."""

from benchmark import readers


def read(run):
    return readers.span_mean_ms(run, "op_queue")

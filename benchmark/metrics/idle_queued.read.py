"""idle_queued.read: as idle_queued.write, in the read cells: share of
the device-idle time in which an op sat in `tpu_queue` or `h2d`, in %
(benchmark/spans.py)."""

from benchmark import spans


def read(run):
    return spans.idle_queued_pct(run)

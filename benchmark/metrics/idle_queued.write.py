"""idle_queued.write: share of the traced window's device-idle time
(no `XLA Ops` event on the chip) in which at least one op sat in the
dispatcher's queue or host-to-device leg (`tpu_queue` or `h2d` spans,
mapped onto the trace's clock), in %. High: the dispatcher's host legs
starve the device; low: nothing was submitted, and the op path
upstream sets the pace (benchmark/spans.py)."""

from benchmark import spans


def read(run):
    return spans.idle_queued_pct(run)

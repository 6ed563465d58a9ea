"""op_p95_ms.write: 95th percentile (nearest rank) latency of the
client ops the window started. A failed op counts as beyond any limit,
and an op still running when the window closed counts at its age then.

The cells run a closed loop at 16 ops in flight, at the system's
capacity: there the tail swings with the smallest stall (a run of the
same seed read 1.7 s or 3.7 s), so it is a per-layer reading beside the
throughput the cell is held to, not an end-to-end metric."""

from benchmark import readers


def read(run):
    return readers.p95_ms(run, ("write_full",))

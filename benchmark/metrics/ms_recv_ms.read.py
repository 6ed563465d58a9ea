"""ms_recv_ms.read: mean ms per client read of the `ms_recv` spans on
its critical path: the primary's receipt of the op and the critical
shard's receipt of its sub-read (benchmark/spans.py)."""

from benchmark import spans


def read(run):
    return spans.stage_ms(run, "read", ("ms_recv",))

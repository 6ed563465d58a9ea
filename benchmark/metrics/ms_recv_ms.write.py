"""ms_recv_ms.write: mean ms per client write of the `ms_recv` spans
on its critical path: the primary's receipt of the op, and the
critical shard's receipt of its sub-write (first bytes of the frame
read -> the daemon's dispatch entered: read, decode, dispatch
throttle), from every OSD's span collector (benchmark/spans.py)."""

from benchmark import spans


def read(run):
    return spans.stage_ms(run, "write", ("ms_recv",))

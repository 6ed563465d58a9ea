"""ec_rmw_read_ms.rbd: mean ms per client write of the `ec_rmw_read`
span on its critical path (osd/ec_backend.py: the primary's read-back
of the stripes a partial overwrite re-encodes, from the first sub-read
launched to the last one done), over the window's writes
(benchmark/spans.py). None on a program that records no such span."""

from benchmark import spans


def read(run):
    return spans.stage_ms(run, "write", ("ec_rmw_read",))

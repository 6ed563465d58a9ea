"""fused_write_roofline: share of the chip's roofline reached by the
fused write program (osd/fused_transform.py: digests and EC encode of
one whole object in one program) in the window, in %.

Work per execution (one object, never coalesced), from the shapes:

- bytes: the object's data read once, its parity written, and its
  digests written: one crc32 per shard, and one crc32c and one xxh32
  per data chunk of each stripe;
- operations: the encode as a GF(2^8) matrix, m x k, over each data
  row, and the CRCs as GF(2) bit-matrix products: crc32 over every
  shard, crc32c over the data. xxh32 (about 1.5 integer operations a
  byte, against 512 for a CRC) is left out.

The least time for it (its bytes at the HBM peak or its operations at
the int8 peak, whichever is longer: here the operations), over the
summed device time of the program's executions, is the share.
"""

from benchmark import readers, trace

#: XLA module names of the program in today's trace
PROGRAMS = [r"^jit_program$"]


def work_bytes(object_size: int, k: int, m: int, stripe_unit: int) -> int:
    stripes = object_size // (k * stripe_unit)
    parity = object_size // k * m
    digests = 4 * (k + m) + 2 * 4 * stripes * k
    return object_size + parity + digests


def work_ops(object_size: int, k: int, m: int) -> int:
    return (readers.gf_ops(m, k, object_size // k)
            + readers.crc_ops(object_size * (k + m) // k)
            + readers.crc_ops(object_size))


def read(run):
    if run.trace is None:
        return None
    ex = trace.executions(run.trace, PROGRAMS)
    if not ex:
        return None
    size, k, m = int(run.traffic["object_size"]), run.code.k, \
        run.code.n - run.code.k
    per = work_bytes(size, k, m, int(run.config["pool"]["stripe_unit"]))
    return readers.roofline_pct(len(ex) * per,
                                len(ex) * work_ops(size, k, m),
                                readers.device_seconds(ex), run)

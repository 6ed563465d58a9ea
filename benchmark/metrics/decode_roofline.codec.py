"""decode_roofline.codec: share of the chip's roofline reached by the
decode programs (the codec's decode_batch, ops/xor_mm.py
matrix_encode with the decode matrix) the EC benchmark tool's decode
calls ran in the window, in %.

Work per call: one object's k surviving chunks read and its erased
chunks written (bytes), and an erasures x k GF(2^8) matrix applied to
the survivors (operations, `readers.gf_ops`); the bytes bind. The calls are the benchmark's own `codec.decode` host
spans inside the window; the time is the device time of the programs
that ran inside them.
"""

from benchmark import readers, trace

PROGRAMS = [r"^jit_matrix_encode$"]


def work_bytes(k: int, erasures: int, chunk: int) -> int:
    return (k + erasures) * chunk


def work_ops(k: int, erasures: int, chunk: int) -> int:
    return readers.gf_ops(erasures, k, chunk)


def read(run):
    if run.trace is None:
        return None
    calls = trace.host_spans(run.trace, "codec.decode")
    ex = trace.inside(trace.executions(run.trace, PROGRAMS), calls)
    if not calls or not ex:
        return None
    k = run.code.k
    shape = (k, int(run.traffic["erasures"]),
             int(run.traffic["object_size"]) // k)
    return readers.roofline_pct(len(calls) * work_bytes(*shape),
                                len(calls) * work_ops(*shape),
                                readers.device_seconds(ex), run)

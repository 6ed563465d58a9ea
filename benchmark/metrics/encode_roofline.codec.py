"""encode_roofline.codec: share of the chip's roofline reached by the
encode programs (ops/xor_mm.py matrix_encode) the EC benchmark tool's
encode calls ran in the window, in %.

Work per call: a batch of objects, each with its k data chunks read
and its m parity chunks written (bytes), and an m x k GF(2^8) matrix
applied to each object's chunks (operations, `readers.gf_ops`); the
bytes bind. The calls are the benchmark's own
`codec.encode` host spans inside the window; the time is the device
time of the programs that ran inside them.
"""

from benchmark import readers, trace

PROGRAMS = [r"^jit_matrix_encode$"]


def work_bytes(batch: int, k: int, m: int, chunk: int) -> int:
    return batch * (k + m) * chunk


def work_ops(batch: int, k: int, m: int, chunk: int) -> int:
    return batch * readers.gf_ops(m, k, chunk)


def read(run):
    if run.trace is None:
        return None
    calls = trace.host_spans(run.trace, "codec.encode")
    ex = trace.inside(trace.executions(run.trace, PROGRAMS), calls)
    if not calls or not ex:
        return None
    k = run.code.k
    shape = (int(run.traffic["batch"]), k, run.code.n - k,
             int(run.traffic["object_size"]) // k)
    return readers.roofline_pct(len(calls) * work_bytes(*shape),
                                len(calls) * work_ops(*shape),
                                readers.device_seconds(ex), run)

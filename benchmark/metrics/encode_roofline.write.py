"""encode_roofline.write: share of the chip's roofline reached by the
EC encode programs (ops/xor_mm.py matrix_encode, which the LRC
codec runs once per dispatch with its layers precomposed) in the
window, in %.

Work: every data byte read once and every parity byte written once,
so data bytes x n / k (bytes), and the (n - k) x k GF(2^8) matrix of
the precomposed layers applied to each data row (operations,
`readers.gf_ops`); the bytes bind. The data bytes are the window's delta of the
dispatchers' l_tpu_enc_bytes (ops coalesce, so one execution may hold
several objects); the time is the summed device time of the encode
programs' executions in the traced window.
"""

from benchmark import readers, trace

PROGRAMS = [r"^jit_matrix_encode$"]


def work_bytes(data_bytes: int, k: int, n: int) -> int:
    return data_bytes * n // k


def work_ops(data_bytes: int, k: int, n: int) -> int:
    return readers.gf_ops(n - k, k, data_bytes // k)


def read(run):
    if run.trace is None:
        return None
    ex = trace.executions(run.trace, PROGRAMS)
    data = readers.counter_total(run, "l_tpu_enc_bytes")
    if not ex or not data:
        return None
    shape = (data, run.code.k, run.code.n)
    return readers.roofline_pct(work_bytes(*shape), work_ops(*shape),
                                readers.device_seconds(ex), run)

"""codec_MBps: object bytes encoded plus object bytes decoded by the
codec calls that completed inside the window (results on the host, as
the EC benchmark tool has them), over the window's length."""

from benchmark import readers


def read(run):
    return readers.mb_per_s(run, ("encode", "decode"))

"""ec_encode_ms.write: mean `ec_encode` span (osd/ec_backend.py: the
primary's encode of one write, the device dispatch and its wait
included) over the writes of the window."""

from benchmark import readers


def read(run):
    return readers.span_mean_ms(run, "ec_encode")

"""rmw_read_bytes_per_write.rbd: chunk bytes the EC primaries read back
per read-modify-write (window deltas of l_osd_ec_rmw_read_bytes over
l_osd_ec_rmw_ops of osd/ec_backend.py, summed over the OSDs). A 4 KiB
overwrite of a k=4 stripe of 4 KiB chunks reads k chunks: 16384."""

from benchmark import readers


def read(run):
    ops = readers.counter_total(run, "l_osd_ec_rmw_ops")
    if not ops:
        return None
    return readers.counter_total(run, "l_osd_ec_rmw_read_bytes") / ops

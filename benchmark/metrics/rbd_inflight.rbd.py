"""rbd_inflight.rbd: mean IOs in flight on the image handle over the
window: the window delta of l_librbd_inflight_s (client/rbd.py, the
time integral of the handle's IOs in flight) over the window's length.
Near the traffic's queue depth when one handle keeps it; near 1 for
writes where the handle serializes them."""


def read(run):
    lib = run.counters.get("librbd")
    if not isinstance(lib, dict) or not lib.get("l_librbd_inflight_s"):
        return None
    return lib["l_librbd_inflight_s"] / (run.t1 - run.t0)

"""Device placement registry: one OSD per chip (ROADMAP direction D).

The multichip kernels (`mesh.py`) are proven, but until now every
daemon funnelled through jax's implicit default device — N OSDs in one
process (MiniCluster) or N processes on one host all serialized on
device 0.  `DevicePlacement` makes the mesh a cluster resource: each
OSD resolves a *home device* at startup (`osd_device_index` option;
round-robin over `jax.local_devices()` by default), the dispatcher
pins its h2d/compute/d2h pipeline there with explicit `device_put`,
and the HBM tier accounts residency under a per-device ledger
category.  The registry itself is process-global so `mesh status`
can render the whole placement table of a shared-process cluster.

A process that sees no device cannot place an OSD: `resolve()` raises
rather than leave the daemon on an implicit default device.
"""

from __future__ import annotations

import threading

__all__ = ["DevicePlacement", "PLACEMENT", "device_label", "local_device_count"]


def _local_devices():
    import jax
    return list(jax.local_devices())


def device_label(device) -> str:
    """Stable short label for a jax Device ("cpu:3", "tpu:0"), or
    "default" when unpinned (None)."""
    if device is None:
        return "default"
    try:
        return "%s:%d" % (device.platform, device.id)
    except Exception:
        return str(device)


def local_device_count() -> int:
    return len(_local_devices())


class DevicePlacement:
    """Process-global OSD -> home-device table.

    `resolve(osd_id, device_index)` is the single policy point:

      - device_index >= 0: explicit pin (modulo the local device count,
        so an 8-way conf survives a 1-device dev box);
      - device_index < 0 (the `osd_device_index` default): round-robin
        by osd_id over `jax.local_devices()` — deterministic, so two
        processes hosting the same OSD id agree without coordination;
      - no local device: RuntimeError.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._table: dict = {}      # osd_id -> (index, device-or-None)

    def resolve(self, osd_id: int, device_index: int = -1):
        devices = _local_devices()
        if not devices:
            raise RuntimeError("osd.%d: no local jax device to place on"
                               % osd_id)
        if device_index is None or device_index < 0:
            index = int(osd_id) % len(devices)
        else:
            index = int(device_index) % len(devices)
        device = devices[index]
        with self._lock:
            self._table[int(osd_id)] = (index, device)
        return device

    def lookup(self, osd_id: int):
        """Previously resolved home device for osd_id (None if unknown
        or unpinned)."""
        with self._lock:
            row = self._table.get(int(osd_id))
        return row[1] if row else None

    def forget(self, osd_id: int) -> None:
        with self._lock:
            self._table.pop(int(osd_id), None)

    def assignments(self) -> dict:
        """`mesh status` payload: osd id -> {index, device} plus the
        visible device inventory."""
        devices = _local_devices()
        with self._lock:
            table = {str(osd): {"index": idx, "device": device_label(dev)}
                     for osd, (idx, dev) in sorted(self._table.items())}
        return {"local_devices": [device_label(d) for d in devices],
                "num_devices": len(devices),
                "osds": table}


PLACEMENT = DevicePlacement()

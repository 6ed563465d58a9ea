"""The chip under the benchmark: the devices a cell needs, their peaks,
peak device memory and compile events.

Without a TPU, or with fewer chips than the cell asks for, or with a
``device_kind`` that ``peaks.json`` does not list, the run fails: a
measurement never falls back to the CPU and never guesses a peak.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class NoChip(RuntimeError):
    """The run cannot measure on this machine."""


def require_tpu(count: int) -> list:
    """The first `count` JAX devices, all TPUs."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip("no TPU found: JAX's default backend is %r"
                     % devices[0].platform)
    if len(devices) < count:
        raise NoChip("%d TPU chips wanted, %d found" % (count, len(devices)))
    return devices[:count]


def peaks_for(kind: str, path: str = os.path.join(HERE, "peaks.json")) -> dict:
    """The published peaks of one chip of `kind` (its ``device_kind``)."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise NoChip("device kind %r has no entry in %s" % (kind, path))
    return table[kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileEvents:
    """Persistent compile cache hits and misses (each one a program
    compiled or loaded), counted from JAX's monitoring events."""

    NAMES = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        import jax
        self.counts = {"hits": 0, "misses": 0}

        def listener(event, **kw):
            key = self.NAMES.get(event)
            if key is not None:
                self.counts[key] += 1
        jax.monitoring.register_event_listener(listener)

    def snapshot(self) -> dict:
        return dict(self.counts)

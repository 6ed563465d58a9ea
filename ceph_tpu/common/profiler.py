"""Device-runtime profiler: JIT-compile and device-memory accounting.

The observability groundwork for ROADMAP direction E (the unified
DeviceProgram runtime wants "built-in trace spans + telemetry
counters"): every hand-rolled `jax.jit` site in the tree — the
dispatcher's donation path, the CRUSH batch kernels, the HBM tier's
digest, the ops/ GF kernels, the mesh collectives — registers with ONE
process-wide registry, so "why did streaming stall" decomposes into
per-(kernel, shape-signature) compile counts, compile wall time and
trace-cache hits instead of guesswork.

Two failure classes this makes visible:

* **Recompile storms**: a kernel re-traced for every call because its
  input shapes churn (the classic jax footgun: a new batch size or a
  new erasure signature per op).  The detector keeps a bounded ring of
  compile events and flags any kernel whose compiles-within-window
  cross the threshold; the OSD ships the verdict with its MPGStats
  report and the monitor raises DEVICE_RECOMPILE_STORM cluster-wide.

* **Device-memory creep**: HBM is small and nothing owned the ledger.
  Categories (hbm_tier residency, the dispatcher's staging ring,
  donated buffers, cached decode tables) account live bytes plus a
  high watermark each; the OSD derives DEVICE_MEM_NEARFULL from the
  tier's occupancy against osd_hbm_nearfull_ratio.

The registry is process-global (module-level jit sites have no daemon
context) and config-gated: `osd_profiler` off reduces every wrapped
call to one attribute check (tests/test_profiler.py checks that the
disabled profiler records nothing).
"""

from __future__ import annotations

import threading
import time
from collections import deque

__all__ = ["DeviceProfiler", "PROFILER", "profiled_jit"]

# device-memory ledger categories (mem_* accept any string; these are
# the ones the OSD path populates)
MEM_CATEGORIES = ("hbm_tier", "staging_ring", "donated_buffers",
                  "decode_tables")


def _placement_token(a):
    """Device/sharding component of an array's signature.  jax keys its
    trace cache on committed placement and sharding as well as shape:
    the same (shape, dtype) on a second device is a fresh compile, and
    folding it into one signature would report false cache hits on one
    side and phantom recompile storms on the other.  Host arrays (no
    `.sharding`) contribute nothing, keeping their signatures stable."""
    sh = getattr(a, "sharding", None)
    if sh is None:
        return None
    try:
        devs = sorted("%s:%d" % (d.platform, d.id) for d in a.devices())
        token = ",".join(devs) if len(devs) <= 8 else "%dxdev" % len(devs)
        return (type(sh).__name__, str(getattr(sh, "spec", "")), token)
    except Exception:
        return type(sh).__name__


def _shape_sig(args, kwargs):
    """Cheap shape signature: (shape, dtype[, placement]) per
    array-like argument, repr-type for scalars/statics.  Two calls with
    the same signature hit the same jit trace-cache entry; a fresh
    signature is (to first order) a fresh trace/compile — which is
    exactly the event the storm detector wants, without hooking XLA
    internals."""
    def one(a):
        shape = getattr(a, "shape", None)
        if shape is not None:
            sig = (tuple(shape), str(getattr(a, "dtype", "")))
            placement = _placement_token(a)
            return sig if placement is None else sig + (placement,)
        if isinstance(a, (int, float, bool, str, bytes, type(None))):
            return a
        return type(a).__name__
    sig = tuple(one(a) for a in args)
    if kwargs:
        sig += tuple((k, one(v)) for k, v in sorted(kwargs.items()))
    return sig


class _Kernel:
    __slots__ = ("sigs", "compiles", "compile_wall", "cache_hits")

    def __init__(self):
        self.sigs: dict = {}          # sig -> [compiles, wall, hits]
        self.compiles = 0
        self.compile_wall = 0.0
        self.cache_hits = 0


class DeviceProfiler:
    """Process-wide jit registry + device-memory ledger (one instance,
    `PROFILER`, shared by every daemon in the process — module-level
    kernels have no per-daemon home)."""

    def __init__(self, recompile_window: float = 60.0,
                 recompile_threshold: int = 24):
        self.enabled = True
        self.recompile_window = recompile_window
        self.recompile_threshold = recompile_threshold
        self._lock = threading.Lock()
        self._kernels: dict[str, _Kernel] = {}
        # bounded compile-event ring: (monotonic stamp, kernel name)
        self._compile_events: deque = deque(maxlen=4096)
        # category -> [live_bytes, high_watermark]
        self._mem: dict[str, list] = {}

    def configure(self, conf) -> None:
        """Adopt the daemon's osd_profiler* knobs (idempotent: every
        OSD in a shared-process cluster applies the same conf)."""
        try:
            self.enabled = bool(conf.get_val("osd_profiler"))
            self.recompile_window = float(
                conf.get_val("osd_profiler_recompile_window"))
            self.recompile_threshold = int(
                conf.get_val("osd_profiler_recompile_threshold"))
        except Exception:
            pass

    # -- jit accounting -------------------------------------------------

    def record_compile(self, kernel: str, sig, wall: float) -> None:
        with self._lock:
            k = self._kernels.setdefault(kernel, _Kernel())
            row = k.sigs.setdefault(sig, [0, 0.0, 0])
            row[0] += 1
            row[1] += wall
            k.compiles += 1
            k.compile_wall += wall
            self._compile_events.append((time.monotonic(), kernel))

    def record_hit(self, kernel: str, sig) -> None:
        with self._lock:
            k = self._kernels.setdefault(kernel, _Kernel())
            row = k.sigs.setdefault(sig, [0, 0.0, 0])
            row[2] += 1
            k.cache_hits += 1

    def note_call(self, kernel: str, args=(), kwargs=None) -> bool:
        """Classify one call of `kernel`: True when its signature is
        new (caller should time the call and record_compile), False on
        a trace-cache hit (recorded here)."""
        sig = _shape_sig(args, kwargs or {})
        with self._lock:
            k = self._kernels.setdefault(kernel, _Kernel())
            if sig in k.sigs:
                k.sigs[sig][2] += 1
                k.cache_hits += 1
                return False
        return True

    def wrap_jit(self, kernel: str, fn):
        """Wrap an already-jitted callable: per-(kernel, shape-sig)
        compile/hit accounting with a single attribute check when the
        profiler is off.  First call with a fresh signature is counted
        as the compile and its wall time as the compile wall (jit
        trace-cache semantics, observed from outside)."""
        def wrapped(*args, **kwargs):
            if not self.enabled:
                return fn(*args, **kwargs)
            sig = _shape_sig(args, kwargs)
            with self._lock:
                k = self._kernels.setdefault(kernel, _Kernel())
                fresh = sig not in k.sigs
                if not fresh:
                    k.sigs[sig][2] += 1
                    k.cache_hits += 1
            if not fresh:
                return fn(*args, **kwargs)
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            self.record_compile(kernel, sig, time.perf_counter() - t0)
            return out
        wrapped.__wrapped__ = fn
        wrapped.__name__ = getattr(fn, "__name__", kernel)
        return wrapped

    # -- recompile-storm detection --------------------------------------

    def storm_report(self, now: float | None = None) -> dict:
        """Worst kernel by compiles-within-window.  {kernel, count,
        window_s, threshold, storming}."""
        now = time.monotonic() if now is None else now
        cutoff = now - self.recompile_window
        with self._lock:
            counts: dict[str, int] = {}
            for t, kernel in self._compile_events:
                if t >= cutoff:
                    counts[kernel] = counts.get(kernel, 0) + 1
        worst, count = None, 0
        for kernel, n in counts.items():
            if n > count:
                worst, count = kernel, n
        return {"kernel": worst, "count": count,
                "window_s": self.recompile_window,
                "threshold": self.recompile_threshold,
                "storming": count >= self.recompile_threshold}

    def storm_count(self) -> int:
        """The MPGStats feed: the worst kernel's in-window compile
        count when it crosses the threshold, else 0 (cheap; rides the
        heartbeat path)."""
        rep = self.storm_report()
        return rep["count"] if rep["storming"] else 0

    # -- device-memory ledger -------------------------------------------

    def mem_add(self, category: str, nbytes: int) -> None:
        if not self.enabled:
            return
        with self._lock:
            row = self._mem.setdefault(category, [0, 0])
            row[0] += int(nbytes)
            if row[0] > row[1]:
                row[1] = row[0]

    def mem_sub(self, category: str, nbytes: int) -> None:
        if not self.enabled:
            return
        with self._lock:
            row = self._mem.setdefault(category, [0, 0])
            row[0] = max(0, row[0] - int(nbytes))

    def mem_set(self, category: str, nbytes: int) -> None:
        if not self.enabled:
            return
        with self._lock:
            row = self._mem.setdefault(category, [0, 0])
            row[0] = int(nbytes)
            if row[0] > row[1]:
                row[1] = row[0]

    def mem_dump(self) -> dict:
        with self._lock:
            out = {cat: {"bytes": row[0], "high_watermark": row[1]}
                   for cat, row in sorted(self._mem.items())}
        out["total_bytes"] = sum(r["bytes"] for r in out.values())
        return out

    # -- introspection (asok `profile dump` payload) --------------------

    def dump(self) -> dict:
        with self._lock:
            kernels = {}
            for name, k in sorted(self._kernels.items()):
                sigs = sorted(k.sigs.items(),
                              key=lambda kv: kv[1][0], reverse=True)
                kernels[name] = {
                    "compiles": k.compiles,
                    "compile_wall_s": round(k.compile_wall, 6),
                    "cache_hits": k.cache_hits,
                    "signatures": [
                        {"sig": repr(sig), "compiles": row[0],
                         "compile_wall_s": round(row[1], 6),
                         "cache_hits": row[2]}
                        for sig, row in sigs[:16]],
                    "num_signatures": len(k.sigs)}
        return {"enabled": self.enabled,
                "kernels": kernels,
                "recompile_storm": self.storm_report(),
                "memory": self.mem_dump()}

    def reset(self) -> None:
        """Zero the jit registry, the compile-event ring, and the
        memory high watermarks (live bytes stay — they are gauges of
        real residency, not statistics)."""
        with self._lock:
            self._kernels.clear()
            self._compile_events.clear()
            for row in self._mem.values():
                row[1] = row[0]


PROFILER = DeviceProfiler()


def profiled_jit(kernel: str, fn=None, **jit_kwargs):
    """`jax.jit` with registry accounting: profiled_jit("name", fn)
    or @profiled_jit("name", static_argnames=...)."""
    def apply(f):
        import jax
        return PROFILER.wrap_jit(kernel, jax.jit(f, **jit_kwargs))
    if fn is None:
        return apply
    return apply(fn)

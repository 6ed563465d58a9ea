"""From a JAX profiler trace to device busy and idle time, program
time and the `breakdown` of a traced run.

The traced run records the profiler over the whole measured window.
The benchmark's own host spans (``jax.profiler.TraceAnnotation`` named
``bench:<what>``) land in the same trace, on the profiler's clock:
``bench:window`` spans the window, and client ops, codec calls and the
like span the work they name. The reduction reads:

- device planes ``/device:TPU:<n>``: the line ``XLA Modules`` (one
  event per program execution) and the line ``XLA Ops`` (one event per
  operation; their union is the time the device was busy);
- host planes: every ``bench:`` event.

All times in a `Trace` are nanoseconds on the profiler's clock.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

PREFIX = "bench:"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def annotate(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(PREFIX + name)


class Recording:
    """The profiler over the measured window of a traced run."""

    def __init__(self, directory: str):
        self.directory = directory

    def start(self) -> None:
        import jax
        # no Python function tracing: it records every call of every
        # daemon thread, and slows the served path far past what it
        # measures
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)

    def stop(self) -> str:
        import jax
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError("the profiler wrote no trace under %s"
                               % self.directory)
        return max(files, key=os.path.getmtime)


@dataclass
class Trace:
    lo: float                       # window start
    hi: float                       # window end
    modules: dict = field(default_factory=dict)   # chip -> [(name, s, e)]
    ops: dict = field(default_factory=dict)       # chip -> [(name, s, e)]
    host: list = field(default_factory=list)      # [(name, s, e)]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9


def load(path: str, window=None) -> Trace:
    from jax.profiler import ProfileData
    return from_planes(ProfileData.from_file(path).planes, window)


def from_planes(planes, window=None) -> Trace:
    """The trace's device and host events; the window is the
    bench:window span unless given as (start, end)."""
    modules, ops, host = {}, {}, []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                if line.name == "XLA Modules":
                    modules[int(m.group(1))] = _events(line)
                elif line.name == "XLA Ops":
                    ops[int(m.group(1))] = _events(line)
            elif plane.name.startswith("/host:"):
                host.extend((e.name[len(PREFIX):], e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith(PREFIX))
    windows = [(s, e) for n, s, e in host if n == "window"]
    if window is None and not windows:
        raise RuntimeError("the trace holds no bench:window span")
    lo, hi = window if window is not None else windows[0]
    return Trace(lo=lo, hi=hi, modules=modules, ops=ops,
                 host=sorted(h for h in host if h[0] != "window"))


def _events(line) -> list:
    return sorted((e.name, e.start_ns, e.end_ns) for e in line.events)


# -- interval arithmetic -------------------------------------------------

def merge(intervals, lo: float, hi: float) -> list:
    """Disjoint sorted (start, end) covering the intervals, clipped to
    [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def union_length(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merge(intervals, lo, hi))


def gaps(intervals, lo: float, hi: float) -> list:
    """The stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in merge(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


# -- reductions ------------------------------------------------------------

def busy_s(trace: Trace) -> float:
    """Seconds of the window in which an operation ran on the device,
    averaged over the chips in the trace."""
    chips = sorted(set(trace.ops) | set(trace.modules))
    if not chips:
        return 0.0
    total = 0.0
    for c in chips:
        events = trace.ops.get(c) or trace.modules.get(c, [])
        total += union_length([(s, e) for _, s, e in events],
                              trace.lo, trace.hi)
    return total / len(chips) / 1e9


def program_name(name: str) -> str:
    """A module event's program name without its trailing id."""
    return re.sub(r"\(\d+\)$", "", name)


def executions(trace: Trace, patterns) -> list:
    """Program executions (name, start, end) that start inside the
    window, on any chip, whose name matches one of the regexes."""
    pats = [re.compile(p) for p in patterns]
    return [ev for events in trace.modules.values() for ev in events
            if trace.lo <= ev[1] < trace.hi
            and any(p.search(program_name(ev[0])) for p in pats)]


def inside(events, spans) -> list:
    """The events whose midpoint lies inside one of the host spans."""
    spans = sorted(spans)
    out = []
    for ev in events:
        mid = (ev[1] + ev[2]) / 2
        if any(s <= mid <= e for s, e in spans):
            out.append(ev)
    return out


def host_spans(trace: Trace, name: str) -> list:
    """(start, end) of the host spans called `name`, wholly inside the
    window."""
    return [(s, e) for n, s, e in trace.host
            if n == name and s >= trace.lo and e <= trace.hi]


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device programs that took most time, and the longest idle
    gaps, each named by the host spans that overlap it."""
    per = {}
    for events in trace.modules.values():
        for name, s, e in events:
            if trace.lo <= s < trace.hi:
                key = program_name(name)
                per[key] = per.get(key, 0.0) + (min(e, trace.hi) - s) / 1e9
    device_ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    busy = [(s, e) for c in set(trace.ops) | set(trace.modules)
            for _, s, e in (trace.ops.get(c) or trace.modules.get(c, []))]
    idle = sorted(gaps(busy, trace.lo, trace.hi),
                  key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, v] for n, v in device_ops],
            "idle_gaps": [[gap_label(trace, s, e), (e - s) / 1e9]
                          for s, e in idle]}


def gap_label(trace: Trace, s: float, e: float) -> str:
    """What the host was doing in [s, e]: the names of the benchmark's
    host spans that overlap it, most frequent first, with counts."""
    seen = {}
    for name, hs, he in trace.host:
        if hs < e and he > s:
            seen[name] = seen.get(name, 0) + 1
    if not seen:
        return "no benchmark span"
    ranked = sorted(seen.items(), key=lambda kv: (-kv[1], kv[0]))[:3]
    return " ".join("%s x%d" % kv for kv in ranked)

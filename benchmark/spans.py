"""The OSDs' op spans of a traced run, read per layer: grouped into op
trees, folded to each op's critical path, and placed on the profiler
trace's clock.

``run.spans`` holds the span dicts every OSD's collector dumped
(``ceph_tpu/common/tracer.py``: ``name``, ``trace_id``, ``span_id``,
``parent_id``, ``start`` and ``duration`` in seconds of the host's
monotonic clock), those that start inside the window. An op's tree
is rooted at its primary's ``osd_op`` span; the tree of a write holds
``commit_wait``, of a read ``read_gather``.

The critical path is the benchmark's own copy of the rule the mgr's
trace store serves (``ceph_tpu/mgr/trace_store.py``), so that the
yardstick does not move with the code it measures: per span, the
heaviest set of children that do not overlap, recursively; what the
chosen children leave of a span is its own stage's time.

The window's start is stamped on both clocks: ``run.t0`` (monotonic)
and ``trace.lo`` (the ``bench:window`` host span, profiler ns), so a
monotonic stamp t lies at ``trace.lo + (t - run.t0) * 1e9`` on the
trace. `clock_check` tests that anchor against the device.
"""

from __future__ import annotations

import bisect

from . import trace as trace_mod

ROOT = "osd_op"
#: children closer than this abut: stamps pass through a float sum
ABUT = 1e-6


def stage(name: str) -> str:
    """'sub_write(shard=3)' and 'sub_write(shard=5)' are one stage."""
    return name.split("(", 1)[0]


def trees(spans) -> dict:
    """{trace_id: [spans]} of the traces whose spans hold exactly one
    root ``osd_op`` (its parent not among them)."""
    by_trace = {}
    for s in spans:
        by_trace.setdefault(s["trace_id"], []).append(s)
    out = {}
    for tid, group in by_trace.items():
        ids = {s["span_id"] for s in group}
        roots = [s for s in group
                 if s["name"] == ROOT and s["parent_id"] not in ids]
        if len(roots) == 1:
            out[tid] = group
    return out


def ops(run, kind: str) -> list:
    """The span lists of the window's ops of `kind` ("write" or "read")
    whose whole tree lies in the window: the root ends by ``run.t1``."""
    marker = {"write": "commit_wait", "read": "read_gather"}[kind]
    out = []
    for group in trees(run.spans).values():
        root = next(s for s in group if s["name"] == ROOT)
        if root["start"] + root["duration"] <= run.t1 and \
                any(s["name"] == marker for s in group):
            out.append(group)
    return out


def critical_path(spans) -> dict:
    """{stage: seconds on the critical path} of one op's tree. The
    values add up to the root's duration."""
    ids = {s["span_id"] for s in spans}
    kids = {}
    for s in spans:
        if s["parent_id"] in ids:
            kids.setdefault(s["parent_id"], []).append(s)
    path = {}

    def walk(s):
        chosen = chain(kids.get(s["span_id"], []))
        own = s["duration"] - sum(k["duration"] for k in chosen)
        key = stage(s["name"])
        path[key] = path.get(key, 0.0) + max(0.0, own)
        for k in chosen:
            walk(k)

    for root in spans:
        if root["parent_id"] not in ids:
            walk(root)
    return path


def chain(kids) -> list:
    """The heaviest set of spans no two of which overlap (weighted
    interval scheduling), in time order."""
    kids = sorted(kids, key=lambda s: s["start"] + s["duration"])
    ends = [s["start"] + s["duration"] for s in kids]
    best = [0.0]           # best[i]: heaviest set among the first i
    prev = []              # prev[i]: spans that end before kids[i]
    for i, s in enumerate(kids):
        j = i
        while j > 0 and ends[j - 1] > s["start"] + ABUT:
            j -= 1
        prev.append(j)
        best.append(max(best[i], s["duration"] + best[j]))
    chosen, i = [], len(kids)
    while i > 0:
        if best[i] == best[i - 1]:
            i -= 1
        else:
            chosen.append(kids[i - 1])
            i = prev[i - 1]
    return chosen[::-1]


def stage_ms(run, kind: str, stages, keep=None) -> float | None:
    """Mean ms per op of `kind` that the named stages take on its
    critical path, over the window's ops (those `keep` accepts, if
    given). None where no span of the run bears one of the names, as
    on a program that does not record them."""
    stages = set(stages)
    if not any(s["name"] in stages for s in run.spans):
        return None
    per_op = [sum(v for k, v in critical_path(group).items()
                  if k in stages)
              for group in ops(run, kind)
              if keep is None or keep(group)]
    return sum(per_op) / len(per_op) * 1e3 if per_op else None


def reached_dispatcher(group) -> bool:
    return any(s["name"] == "tpu_queue" for s in group)


# -- the trace's clock -----------------------------------------------------

def on_trace(run, t: float) -> float:
    """A monotonic stamp of the run in ns on the profiler trace."""
    return run.trace.lo + (t - run.t0) * 1e9


def intervals(run, names) -> list:
    """(start, end) on the trace's clock of the spans of these names,
    each interval once (coalesced ops share their dispatch's legs)."""
    names = set(names)
    return sorted({(on_trace(run, s["start"]),
                    on_trace(run, s["start"] + s["duration"]))
                   for s in run.spans if s["name"] in names})


def device_busy(tr) -> list:
    """(start, end) of every operation on any chip of the trace."""
    return [(s, e) for c in set(tr.ops) | set(tr.modules)
            for _, s, e in (tr.ops.get(c) or tr.modules.get(c, []))]


def overlap(a, b) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_queued_pct(run) -> float | None:
    """Share of the window's device-idle time in which at least one op
    sat in the dispatcher's queue or in its host-to-device leg
    (``tpu_queue`` or ``h2d`` spans), in %."""
    tr = run.trace
    if tr is None or not run.spans:
        return None
    idle = trace_mod.gaps(device_busy(tr), tr.lo, tr.hi)
    queued = trace_mod.merge(intervals(run, ("tpu_queue", "h2d")),
                             tr.lo, tr.hi)
    idle_ns = sum(e - s for s, e in idle)
    if not queued or idle_ns <= 0:
        return None
    return 100.0 * overlap(idle, queued) / idle_ns


def clock_check(run, slack_ns: float = 1e6) -> dict | None:
    """The anchor against the device: the share of the window's program
    executions (``XLA Modules``) that lie, within `slack_ns`, inside a
    leg of the dispatcher in which programs run: ``compute`` (the codec
    program) or ``tpu_finish`` (the HBM tier's adoption of the staged
    rows); and the range of shifts of the spans, in ms, that keeps the
    most executions strictly inside a leg (a sound anchor holds 0)."""
    tr = run.trace
    legs = intervals(run, ("compute", "tpu_finish"))
    execs = [(s, e) for events in tr.modules.values()
             for _, s, e in events if tr.lo <= s < tr.hi]
    if not legs or not execs:
        return None
    starts = [a for a, _ in legs]
    longest = max(b - a for a, b in legs)
    inside, marks = 0, []
    for s, e in execs:
        near = legs[bisect.bisect_left(starts, s - slack_ns - longest):
                    bisect.bisect_right(starts, s + slack_ns)]
        near = [(a, b) for a, b in near
                if a - slack_ns <= s and e <= b + slack_ns]
        inside += bool(near)
        # the execution fits a leg for the shifts d with a + d <= s and
        # e <= b + d: sweep the shifts within the slack for most fits
        fits = trace_mod.merge([(e - b, s - a) for a, b in near],
                               -slack_ns, slack_ns)
        marks += [(lo, 1) for lo, _ in fits] + [(hi, -1) for _, hi in fits]
    n = best = 0
    lo = hi = 0.0
    marks.sort(key=lambda m: (m[0], -m[1]))
    for i, (d, step) in enumerate(marks):
        n += step
        if step > 0 and n > best:
            best, lo = n, d
            hi = next(x for x, st in marks[i + 1:] if st < 0)
    return {"executions": len(execs), "inside_share": inside / len(execs),
            "fits": best, "shift_ms": (lo / 1e6, hi / 1e6)}

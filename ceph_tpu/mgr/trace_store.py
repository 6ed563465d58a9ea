"""mgr trace store: tail-sampled cross-daemon trace forensics.

The receiving half of the TailSampler pipeline (common/tracer.py): OSDs
judge traces at op completion and ship kept span fragments here as
MTraceFragment messages.  This module

  * ingests fragments OFF the dispatch path (one worker lane, the
    ISSUE-18 sharded-ingest discipline — a flood costs the dispatch
    thread only an append),
  * stitches fragments from different daemons into one tree per
    trace_id, aligning each sender's monotonic span stamps onto a
    shared wall axis via the fragment's (anchor_wall, anchor_mono)
    pair,
  * retains trees in a bounded, byte-accounted store — over budget the
    coldest/fastest traces evict first while the per-pool slowest-N
    and errored traces are protected (the flight-recorder slowest_ops
    discipline, cluster-wide),
  * computes each tree's CRITICAL PATH (the longest chain of
    non-overlapping child intervals, recursively, with parent
    self-time attributed to the parent's stage) and aggregates
    per-pool cross-trace profiles: "pool rbd p99: 41% tpu_queue,
    22% sub_write, 18% h2d",
  * serves `trace slowest` / `trace show <id>` / `trace profile
    <pool>` cluster-wide (no per-daemon asok hop) and feeds the
    POOL_SLO_VIOLATION detail its top critical-path stage.
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque

from ..common.tracer import render_tree, wire_span
from .mgr_module import MgrModule

__all__ = ["TraceModule", "critical_path"]


def _stage(name: str) -> str:
    """Aggregation key for a span name: 'rep_op(osd=2)' and
    'rep_op(osd=5)' are one stage."""
    return name.split("(", 1)[0]


def _approx_span_bytes(span: dict) -> int:
    """Cheap deterministic byte estimate for the store accounting."""
    return (120 + len(str(span.get("name", "")))
            + len(str(span.get("endpoint", "")))
            + 48 * len(span.get("keyvals") or ())
            + 48 * len(span.get("events") or ()))


#: siblings that overlap by less than this abut: a wall stamp near the
#: epoch's magnitude rounds to about 0.2 us, so the end of one span and
#: the start of the next it hands over to rarely compare equal
_ABUT = 1e-6


def critical_path(spans: list[dict]) -> list[tuple[str, float]]:
    """The trace's critical path as [(stage, seconds), ...].

    Per span: pick the maximum-total-duration set of NON-overlapping
    children (weighted interval scheduling on the wall axis), recurse
    into each chosen child, and attribute the remainder — the parent's
    self time — to the parent's own stage.  Children the chain skips
    (they overlapped a longer sibling) don't contribute: their time
    was concurrent with the path, not on it.
    """
    if not spans:
        return []
    by_id = {s["span_id"]: s for s in spans}
    children: dict = {}
    roots = []
    for s in spans:
        parent = s.get("parent_id")
        if parent and parent in by_id:
            children.setdefault(parent, []).append(s)
        else:
            roots.append(s)

    def span_wall(s):
        return s.get("wall", s.get("start_wall", 0.0))

    def chain(kids: list) -> list:
        """Max-duration non-overlapping subset (sorted by end)."""
        kids = sorted(kids, key=lambda s: span_wall(s)
                      + s.get("duration", 0.0))
        n = len(kids)
        if not n:
            return []
        starts = [span_wall(k) for k in kids]
        ends = [span_wall(k) + k.get("duration", 0.0) for k in kids]
        durs = [max(0.0, k.get("duration", 0.0)) for k in kids]
        # p[i]: rightmost j < i with ends[j] <= starts[i] (else -1)
        p = []
        for i in range(n):
            j = i - 1
            while j >= 0 and ends[j] > starts[i] + _ABUT:
                j -= 1
            p.append(j)
        best = [0.0] * (n + 1)
        take = [False] * n
        for i in range(n):
            skip = best[i]
            with_i = durs[i] + best[p[i] + 1]
            take[i] = with_i >= skip
            best[i + 1] = max(skip, with_i)
        chosen = []
        i = n - 1
        while i >= 0:
            if take[i] and best[i + 1] == durs[i] + best[p[i] + 1]:
                chosen.append(kids[i])
                i = p[i]
            else:
                i -= 1
        chosen.reverse()
        return chosen

    out: list[tuple[str, float]] = []

    def walk(s: dict) -> None:
        kids = chain(children.get(s["span_id"], []))
        dur = max(0.0, s.get("duration", 0.0))
        on_path = sum(max(0.0, k.get("duration", 0.0)) for k in kids)
        self_t = max(0.0, dur - on_path)
        if self_t > 0.0:
            out.append((_stage(str(s.get("name", "?"))), self_t))
        for k in kids:
            walk(k)

    # a stitched trace has one logical root (the osd_op span); partial
    # gathers may leave several — walk each, the profile still reads
    for root in sorted(roots, key=span_wall):
        walk(root)
    # fold repeated stages (parent self-time + two rep_op legs)
    folded: dict[str, float] = {}
    order: list[str] = []
    for stage, sec in out:
        if stage not in folded:
            order.append(stage)
        folded[stage] = folded.get(stage, 0.0) + sec
    return [(stage, folded[stage]) for stage in order]


class TraceModule(MgrModule):
    COMMANDS = [
        {"cmd": "trace slowest",
         "desc": "slowest retained traces, cluster-wide"},
        {"cmd": "trace show",
         "desc": "one stitched cross-daemon trace tree + its "
                 "critical path"},
        {"cmd": "trace profile",
         "desc": "cross-trace critical-path profile for a pool"},
    ]

    def __init__(self, mgr):
        super().__init__(mgr)
        self.name = "trace"
        conf = mgr.ctx.conf
        self.store_budget = self._conf(conf, "mgr_trace_store_bytes",
                                       4 << 20, int)
        self.protect_slowest = self._conf(
            conf, "mgr_trace_protect_slowest", 16, int)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue: deque = deque()
        self._traces: dict[int, dict] = {}
        self._tracked_bytes = 0
        self._ingested_bytes = 0       # lifetime demand, pre-eviction
        self._evicted = 0
        self._stopping = False
        # one ingest lane off the dispatch thread (the ISSUE-18
        # discipline; trace volume never needs more than one)
        self._worker = threading.Thread(target=self._run,
                                        name="mgr-trace-ingest",
                                        daemon=True)
        self._worker.start()

    @staticmethod
    def _conf(conf, name, default, cast):
        try:
            return cast(conf.get_val(name))
        except Exception:
            return default

    def shutdown(self) -> None:
        with self._cond:
            self._stopping = True
            self._cond.notify()

    # -- ingest (dispatch thread -> worker lane) ------------------------

    def enqueue(self, msg) -> None:
        """Called by MgrDaemon.ms_dispatch for every MTraceFragment:
        one append, the worker does the stitching."""
        with self._cond:
            self._queue.append(msg)
            self._cond.notify()

    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._queue and not self._stopping:
                    self._cond.wait(0.5)
                if self._stopping and not self._queue:
                    return
                batch = list(self._queue)
                self._queue.clear()
            for msg in batch:
                try:
                    self._ingest(msg)
                except Exception:
                    pass     # one bad fragment must not kill the lane

    def flush(self, timeout: float = 5.0) -> bool:
        """Wait until the ingest lane drained (tests/bench barrier)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if not self._queue:
                    return True
            time.sleep(0.005)
        return False

    def _ingest(self, msg) -> None:
        perf = getattr(self.mgr, "perf", None)
        raw = msg.spans
        if isinstance(raw, (bytes, bytearray)):
            # senders pack span records into one json blob (see
            # _ship_trace_fragments) — one C-speed parse here
            raw = json.loads(raw.decode("utf-8"))
        spans = []
        nbytes = 0
        for rec in raw or ():
            # fragments carry compact dump_wire records; expand and
            # put the sender's monotonic stamps onto the shared wall
            # axis
            s = wire_span(rec, msg.trace_id) \
                if isinstance(rec, (list, tuple)) else dict(rec)
            s["wall"] = msg.anchor_wall + (s.get("start", 0.0)
                                           - msg.anchor_mono)
            spans.append(s)
            nbytes += _approx_span_bytes(s)
        with self._lock:
            entry = self._traces.get(msg.trace_id)
            if entry is None:
                entry = self._traces[msg.trace_id] = {
                    "trace_id": msg.trace_id,
                    "pool": msg.pool, "op_type": msg.op_type,
                    "reason": msg.reason, "duration": msg.duration,
                    "stored_mono": time.monotonic(),
                    "daemons": set(), "spans": [], "bytes": 0,
                    "cp": None,
                }
            # the root's verdict metadata wins over a replica's echo
            if msg.reason:
                entry["reason"] = msg.reason
            if msg.duration > entry["duration"]:
                entry["duration"] = msg.duration
            if msg.pool and not entry["pool"]:
                entry["pool"] = msg.pool
            if msg.op_type and not entry["op_type"]:
                entry["op_type"] = msg.op_type
            if msg.daemon_name:
                entry["daemons"].add(msg.daemon_name)
            entry["spans"].extend(spans)
            entry["bytes"] += nbytes
            entry["cp"] = None         # restitch on next read
            self._tracked_bytes += nbytes
            self._ingested_bytes += nbytes
            if perf is not None:
                perf.inc("l_mgr_trace_fragments")
                perf.inc("l_mgr_trace_spans", len(spans))
            self._evict_locked()
            if perf is not None:
                perf.set("l_mgr_trace_bytes", self._tracked_bytes)
                perf.set("l_mgr_trace_stored", len(self._traces))
                perf.set("l_mgr_trace_evicted", self._evicted)

    # -- bounded retention ---------------------------------------------

    def _evict_locked(self) -> None:
        """Coldest/fastest first; per-pool slowest-N and errored
        traces protected — but the byte budget is HARD: if the
        protected set alone overflows it, protected traces go too."""
        if self.store_budget <= 0 or \
                self._tracked_bytes <= self.store_budget:
            return
        by_pool: dict[str, list] = {}
        for e in self._traces.values():
            by_pool.setdefault(e["pool"], []).append(e)
        protected = set()
        for entries in by_pool.values():
            entries.sort(key=lambda e: -e["duration"])
            for e in entries[:max(0, self.protect_slowest)]:
                protected.add(e["trace_id"])
        for e in self._traces.values():
            if e["reason"] == "error":
                protected.add(e["trace_id"])
        victims = sorted(
            (e for e in self._traces.values()
             if e["trace_id"] not in protected),
            key=lambda e: (e["duration"], e["stored_mono"]))
        # hard-budget fallback: protected traces, fastest first
        victims += sorted(
            (e for e in self._traces.values()
             if e["trace_id"] in protected),
            key=lambda e: (e["duration"], e["stored_mono"]))
        for e in victims:
            if self._tracked_bytes <= self.store_budget:
                break
            del self._traces[e["trace_id"]]
            self._tracked_bytes -= e["bytes"]
            self._evicted += 1

    # -- read surfaces --------------------------------------------------

    def _cp_locked(self, entry: dict) -> list[tuple[str, float]]:
        if entry["cp"] is None:
            entry["cp"] = critical_path(entry["spans"])
        return entry["cp"]

    def status(self) -> dict:
        with self._lock:
            return {"retained": len(self._traces),
                    "tracked_bytes": self._tracked_bytes,
                    "ingested_bytes": self._ingested_bytes,
                    "budget_bytes": self.store_budget,
                    "evicted": self._evicted,
                    "queue_depth": len(self._queue)}

    def slowest(self, pool: str | None = None,
                count: int = 10) -> dict:
        with self._lock:
            entries = [e for e in self._traces.values()
                       if pool is None or e["pool"] == pool]
            entries.sort(key=lambda e: -e["duration"])
            rows = []
            for e in entries[:max(1, int(count))]:
                cp = self._cp_locked(e)
                top = max(cp, key=lambda kv: kv[1]) if cp else None
                rows.append({
                    "trace_id": "0x%x" % e["trace_id"],
                    "pool": e["pool"], "op_type": e["op_type"],
                    "duration_ms": round(e["duration"] * 1e3, 3),
                    "reason": e["reason"],
                    "daemons": sorted(e["daemons"]),
                    "spans": len(e["spans"]),
                    "top_stage": top[0] if top else "",
                })
        doc = {"slowest": rows}
        doc.update(self.status())
        return doc

    def show(self, trace_id) -> dict:
        tid = int(trace_id, 0) if isinstance(trace_id, str) \
            else int(trace_id)
        with self._lock:
            entry = self._traces.get(tid)
            if entry is None:
                return {"error": "trace 0x%x not retained" % tid}
            spans = [dict(s) for s in entry["spans"]]
            cp = list(self._cp_locked(entry))
            meta = {"trace_id": "0x%x" % tid, "pool": entry["pool"],
                    "op_type": entry["op_type"],
                    "reason": entry["reason"],
                    "duration_ms": round(entry["duration"] * 1e3, 3),
                    "daemons": sorted(entry["daemons"])}
        total = sum(sec for _, sec in cp) or 1.0
        meta["tree"] = render_tree(spans, trace_id=tid)
        meta["critical_path"] = [
            {"stage": stage, "seconds": round(sec, 6),
             "fraction": round(sec / total, 4)} for stage, sec in cp]
        return meta

    def profile(self, pool: str) -> dict:
        """Cross-trace critical-path profile: where the pool's
        retained latency actually lives."""
        stages: dict[str, float] = {}
        n = 0
        with self._lock:
            for e in self._traces.values():
                if pool and e["pool"] != pool:
                    continue
                n += 1
                for stage, sec in self._cp_locked(e):
                    stages[stage] = stages.get(stage, 0.0) + sec
        total = sum(stages.values())
        rows = [{"stage": stage, "seconds": round(sec, 6),
                 "fraction": round(sec / total, 4) if total else 0.0}
                for stage, sec in
                sorted(stages.items(), key=lambda kv: -kv[1])]
        return {"pool": pool, "traces": n,
                "critical_path_seconds": round(total, 6),
                "stages": rows}

    def top_stage(self, pool: str) -> tuple[str, float] | None:
        """(stage, fraction) dominating the pool's critical paths —
        what POOL_SLO_VIOLATION detail stamps."""
        prof = self.profile(pool)
        if not prof["stages"]:
            return None
        top = prof["stages"][0]
        return top["stage"], top["fraction"]

    def prom_stats(self) -> dict:
        """What the prometheus module exports: per-(pool, stage)
        critical-path seconds, the per-pool slowest trace as a bounded
        exemplar series, and the store gauges."""
        per_pool: dict[str, dict] = {}
        slowest: dict[str, tuple[str, float]] = {}
        with self._lock:
            for e in self._traces.values():
                pool = e["pool"] or "_none"
                agg = per_pool.setdefault(pool, {})
                for stage, sec in self._cp_locked(e):
                    agg[stage] = agg.get(stage, 0.0) + sec
                cur = slowest.get(pool)
                if cur is None or e["duration"] > cur[1]:
                    slowest[pool] = ("0x%x" % e["trace_id"],
                                     e["duration"])
        return {"critical_path": per_pool, "slowest": slowest,
                **self.status()}

    # -- CLI ------------------------------------------------------------

    def handle_command(self, cmd: dict):
        prefix = cmd.get("prefix", "")
        if prefix == "trace slowest":
            return 0, json.dumps(self.slowest(
                pool=cmd.get("pool"),
                count=int(cmd.get("count") or 10)), indent=2), ""
        if prefix == "trace show":
            doc = self.show(cmd.get("trace_id") or "0")
            if "error" in doc:
                return -2, "", doc["error"]
            return 0, json.dumps(doc, indent=2), ""
        if prefix == "trace profile":
            return 0, json.dumps(self.profile(
                cmd.get("pool") or ""), indent=2), ""
        return -22, "", "unknown trace command %r" % prefix

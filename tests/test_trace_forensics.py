"""SLO forensics tests (common/tracer.py TailSampler +
mgr/trace_store.py): tail-based keep/drop verdicts, the replica
pending-fragment buffer, the wire span round-trip, critical-path
attribution, wall-anchored tree rendering, and — on a MiniCluster —
a replica-side stall surfacing as a stitched cross-daemon tree whose
critical path names the remote sub-op leg, plus the Prometheus
exposition lint over the trace lanes with hostile pool names.
"""

import random
import time
import types

import pytest

from ceph_tpu.common.tracer import (SpanCollector, TailSampler,
                                    parse_slo_targets, render_tree,
                                    wire_span)
from ceph_tpu.mgr.trace_store import critical_path

from .cluster_util import (MiniCluster, lint_exposition, wait_until)

# -- tail sampler: the keep/drop call ----------------------------------


class TestTailVerdict:
    def _sampler(self, rate=0.0, targets=None, rng=None):
        ts = TailSampler(rng=rng)
        ts.rate = rate
        ts.slo_targets = targets or {}
        return ts

    def test_slo_keep(self):
        ts = self._sampler(targets={"rbd": (0.025, 0.99)})
        assert ts.verdict("rbd", 0.050, 0) == (True, "slo")
        assert ts.stats["kept_slo"] == 1

    def test_slo_threshold_is_per_pool(self):
        ts = self._sampler(targets={"rbd": (0.025, 0.99)})
        # same latency, untargeted pool: drop
        assert ts.verdict("other", 0.050, 0) == (False, "")
        assert ts.stats["dropped"] == 1

    def test_error_result_keeps(self):
        ts = self._sampler()
        assert ts.verdict("rbd", 0.001, -5) == (True, "error")
        assert ts.stats["kept_error"] == 1

    def test_error_event_keeps_wire_form(self):
        # spans arrive in the compact dump_wire list form: events at
        # index 7
        ts = self._sampler()
        spans = [[1, None, "sub_write", "osd.1", 0.0, 0.001, {},
                  [(0.0, "error: eio")]]]
        assert ts.verdict("rbd", 0.001, 0, spans) == (True, "error")

    def test_error_event_keeps_dict_form(self):
        ts = self._sampler()
        spans = [{"events": [(0.0, "error")]}]
        assert ts.verdict("rbd", 0.001, 0, spans) == (True, "error")

    def test_clean_fast_op_drops(self):
        ts = self._sampler()
        spans = [[1, None, "osd_op", "osd.0", 0.0, 0.001, {},
                  [(0.0, "queued")]]]
        assert ts.verdict("rbd", 0.001, 0, spans) == (False, "")

    def test_slo_wins_over_error(self):
        # a slow AND errored op is accounted as "slo" — one reason
        # per trace, SLO breach is the stronger signal
        ts = self._sampler(targets={"rbd": (0.025, 0.99)})
        assert ts.verdict("rbd", 0.050, -5) == (True, "slo")

    def test_reservoir_statistics_seeded(self):
        ts = self._sampler(rate=0.25, rng=random.Random(42))
        kept = sum(1 for _ in range(2000)
                   if ts.verdict("rbd", 0.001, 0)[0])
        # binomial(2000, 0.25): +-5 sigma is ~±97
        assert 400 <= kept <= 600
        assert ts.stats["kept_reservoir"] == kept
        assert ts.pool_stats["rbd"] == {"seen": 2000, "kept": kept}

    def test_zero_rate_never_reservoir_keeps(self):
        ts = self._sampler(rate=0.0, rng=random.Random(42))
        assert all(not ts.verdict("rbd", 0.001, 0)[0]
                   for _ in range(500))


class TestParseSloTargets:
    def test_parses_and_skips_malformed(self):
        got = parse_slo_targets(
            "rbd:25:0.99, cephfs:100:0.95,bad,also:bad,neg:-5:0.9")
        assert got == {"rbd": (0.025, 0.99), "cephfs": (0.1, 0.95)}

    def test_empty(self):
        assert parse_slo_targets("") == {}
        assert parse_slo_targets(None) == {}


# -- replica side: the pending-fragment buffer -------------------------


def _traced_collector(tail):
    col = SpanCollector(capacity=64, endpoint="osd.1")
    col.enabled = True
    col.tail = tail
    return col


class TestPendingBuffer:
    def test_observe_take_round_trip(self):
        ts = TailSampler()
        col = _traced_collector(ts)
        span = col.start_trace("osd_op")
        span.child("sub_write").finish()
        span.finish()
        got = ts.take(span.trace_id)
        assert got is not None and len(got) == 2
        # buffered in wire form, ready to ship without conversion
        assert all(isinstance(r, list) for r in got)
        assert {r[2] for r in got} == {"osd_op", "sub_write"}
        # take pops: a second verdict for the same trace finds nothing
        assert ts.take(span.trace_id) is None

    def test_untraced_spans_not_buffered(self):
        ts = TailSampler()
        ts.observe(types.SimpleNamespace(trace_id=0))
        assert ts.pending_traces() == 0

    def test_ttl_reaps_unjudged_fragments(self):
        ts = TailSampler()
        ts.pending_ttl = 0.01
        col = _traced_collector(ts)
        span = col.start_trace("osd_op")
        span.finish()
        assert ts.pending_traces() == 1
        assert ts.sweep(time.monotonic() + 1.0) == 1
        assert ts.pending_traces() == 0
        assert ts.stats["pending_expired"] == 1
        assert ts.take(span.trace_id) is None

    def test_bounded_pending_drops_oldest(self):
        ts = TailSampler(max_pending=2)
        col = _traced_collector(ts)
        spans = []
        for _ in range(3):
            s = col.start_trace("osd_op")
            s.finish()
            spans.append(s)
        assert ts.pending_traces() == 2
        assert ts.stats["pending_overflow"] == 1
        assert ts.take(spans[0].trace_id) is None     # oldest evicted
        assert ts.take(spans[2].trace_id) is not None


class TestWireRoundTrip:
    def test_dump_wire_expands_to_dump(self):
        col = SpanCollector(capacity=8, endpoint="osd.3")
        col.enabled = True
        span = col.start_trace("osd_op")
        span.keyval("pool", "rbd")
        span.event("queued")
        span.finish()
        full = span.dump()
        back = wire_span(span.dump_wire(), span.trace_id)
        # everything but start_wall survives the compact form (the
        # fragment envelope's anchor pair replaces it)
        full.pop("start_wall")
        assert back == full


# -- critical-path attribution -----------------------------------------


def _span(sid, parent, name, wall, dur):
    return {"trace_id": 9, "span_id": sid, "parent_id": parent,
            "name": name, "endpoint": "osd.0", "start": wall,
            "duration": dur, "wall": wall}


class TestCriticalPath:
    def test_overlapping_sibling_excluded(self):
        # queue [0, 30ms) overlaps rep_op [20, 90ms): the chain keeps
        # the longer leg, the concurrent one contributes nothing
        spans = [_span(1, None, "osd_op", 0.0, 0.100),
                 _span(2, 1, "queue", 0.0, 0.030),
                 _span(3, 1, "rep_op(osd=1)", 0.020, 0.070)]
        got = dict(critical_path(spans))
        assert "queue" not in got
        assert got["rep_op"] == pytest.approx(0.070)
        assert got["osd_op"] == pytest.approx(0.030)   # parent self

    def test_non_overlapping_siblings_both_on_path(self):
        spans = [_span(1, None, "osd_op", 0.0, 0.100),
                 _span(2, 1, "queue", 0.0, 0.030),
                 _span(3, 1, "rep_op(osd=1)", 0.040, 0.050)]
        got = dict(critical_path(spans))
        assert got["queue"] == pytest.approx(0.030)
        assert got["rep_op"] == pytest.approx(0.050)
        assert got["osd_op"] == pytest.approx(0.020)

    def test_stage_key_folds_per_target_legs(self):
        # rep_op(osd=1) + rep_op(osd=2) are ONE stage
        spans = [_span(1, None, "osd_op", 0.0, 0.100),
                 _span(2, 1, "rep_op(osd=1)", 0.000, 0.040),
                 _span(3, 1, "rep_op(osd=2)", 0.050, 0.040)]
        got = dict(critical_path(spans))
        assert got["rep_op"] == pytest.approx(0.080)

    def test_recurses_into_chosen_children(self):
        spans = [_span(1, None, "osd_op", 0.0, 0.100),
                 _span(2, 1, "rep_op(osd=1)", 0.000, 0.090),
                 _span(3, 2, "rep_apply", 0.010, 0.070)]
        got = dict(critical_path(spans))
        assert got["rep_apply"] == pytest.approx(0.070)
        assert got["rep_op"] == pytest.approx(0.020)
        assert got["osd_op"] == pytest.approx(0.010)

    def test_empty(self):
        assert critical_path([]) == []


class TestTraceStoreBudget:
    def test_flood_of_ten_budgets_stays_within_budget(self):
        """The mgr store's byte budget is hard: ten budgets' worth of
        kept traces never leave more than the budget tracked, and the
        slowest traces are the ones retained."""
        from ceph_tpu.mgr.trace_store import TraceModule
        conf = {"mgr_trace_store_bytes": 2000,
                "mgr_trace_protect_slowest": 4}
        mgr = types.SimpleNamespace(ctx=types.SimpleNamespace(
            conf=types.SimpleNamespace(get_val=conf.__getitem__)))
        store = TraceModule(mgr)
        try:
            n = 200
            for i in range(1, n + 1):
                span = {"span_id": i, "parent_id": 0, "name": "osd_op",
                        "endpoint": "osd.0", "start": 0.0,
                        "duration": i * 1e-3, "keyvals": {},
                        "events": []}
                store._ingest(types.SimpleNamespace(
                    trace_id=i, spans=[span], anchor_wall=0.0,
                    anchor_mono=0.0, pool="flood", op_type="write",
                    reason="slo", duration=i * 1e-3,
                    daemon_name="osd.0"))
                assert store.status()["tracked_bytes"] <= 2000
            st = store.status()
            assert st["ingested_bytes"] >= 10 * 2000
            assert st["evicted"] == n - st["retained"]
            kept = {int(r["trace_id"], 16)
                    for r in store.slowest("flood", count=4)["slowest"]}
            assert kept == set(range(n - 3, n + 1))
        finally:
            store.shutdown()


class TestRenderTreeWallOrder:
    def test_siblings_order_by_wall_not_monotonic(self):
        # cross-process siblings: the replica's monotonic start (5.0)
        # is far below the root daemon's (100.01) yet its wall anchor
        # puts it LATER — wall must win
        spans = [_span(1, None, "osd_op", 50.00, 0.100),
                 dict(_span(2, 1, "late_remote", 50.08, 0.010),
                      start=5.0, endpoint="osd.1"),
                 dict(_span(3, 1, "early_local", 50.01, 0.010),
                      start=100.01)]
        text = render_tree(spans, trace_id=9)
        assert text.index("early_local") < text.index("late_remote")


# -- live cluster: stall -> stitched tree -> attribution ----------------


@pytest.fixture(scope="module")
def trace_cluster():
    from ceph_tpu.mgr import TraceModule
    from ceph_tpu.mgr.modules import PrometheusModule
    cluster = MiniCluster(num_mons=1, num_osds=3, conf_overrides={
        "osd_tracing": True,
        "osd_profiler": False,
        "mgr_stats_period": 0.25,
        # reservoir off: only the injected stall keeps traces
        "osd_trace_tail_sample_rate": 0.0,
        "mgr_slo_pool_targets": "tracepool:25:0.99",
    }).start()
    mgr = cluster.start_mgr(modules=(PrometheusModule, TraceModule))
    client = cluster.client()
    pool_id = cluster.create_replicated_pool(client, "tracepool",
                                             size=2, pg_num=8)
    assert cluster.wait_clean(pool_id)
    assert wait_until(lambda: all(o.mgr_addr is not None
                                  for o in cluster.osds.values()),
                      timeout=20)
    yield cluster, mgr, client, pool_id
    cluster.stop()


def _pool_entries(tm, pool):
    with tm._lock:
        return [dict(e, daemons=set(e["daemons"]),
                     spans=list(e["spans"]))
                for e in tm._traces.values() if e["pool"] == pool]


class TestStitchedForensics:
    N = 4

    def test_replica_stall_lands_in_stitched_tree(self, trace_cluster):
        from ceph_tpu.osd.replicated_backend import ReplicatedBackend
        cluster, mgr, client, pool_id = trace_cluster
        tm = mgr.modules["trace"]
        orig = ReplicatedBackend.handle_rep_op

        def sleepy(self, msg, local=False):
            # replica-side apply stall only: the primary stays fast,
            # the bottleneck is REMOTE
            if not local and self.pg.pgid.pool == pool_id:
                time.sleep(0.04)
            return orig(self, msg, local)

        ReplicatedBackend.handle_rep_op = sleepy
        try:
            io = client.open_ioctx("tracepool")
            for i in range(self.N):
                io.write_full("stall-%d" % i, b"s" * 1024)
        finally:
            ReplicatedBackend.handle_rep_op = orig

        # replicas ship only after the root's verdict round-trips
        def settled():
            tm.flush(0.5)
            entries = _pool_entries(tm, "tracepool")
            return (len(entries) >= self.N
                    and all(len(e["daemons"]) >= 2 for e in entries))
        assert wait_until(settled, timeout=30, interval=0.25), \
            _pool_entries(tm, "tracepool")

        entries = _pool_entries(tm, "tracepool")
        assert all(e["reason"] == "slo" for e in entries)
        # every tree carries the replica's rep_apply span, stitched
        # from a DIFFERENT daemon than the root's osd_op (the primary
        # records its own local-apply rep_apply too — at least one
        # must be remote)
        for e in entries:
            names = {s["name"] for s in e["spans"]}
            assert "rep_apply" in names, sorted(names)
            root = next(s for s in e["spans"]
                        if s["name"] == "osd_op")
            assert any(s["name"] == "rep_apply"
                       and s["endpoint"] != root["endpoint"]
                       for s in e["spans"])

        # the cross-trace profile and the per-trace critical path
        # both name the remote sub-op leg
        top = tm.top_stage("tracepool")
        assert top is not None and top[0] == "rep_op", top
        shown = tm.show(entries[0]["trace_id"])
        assert "rep_apply" in shown["tree"]
        cp_top = max(shown["critical_path"],
                     key=lambda r: r["seconds"])
        assert cp_top["stage"] == "rep_op", shown["critical_path"]

        # the CLI surface answers without any per-daemon asok hop
        code, out, err = tm.handle_command({"prefix": "trace slowest"})
        assert code == 0 and "rep_op" in out

    def test_prom_lint_with_hostile_pool_names(self, trace_cluster):
        from ceph_tpu.msg.message import MTraceFragment
        cluster, mgr, client, pool_id = trace_cluster
        tm = mgr.modules["trace"]
        hostile_pool = 'po"ol\\x\n{evil="1"}'
        hostile_stage = 'sta"ge\\y\nz'
        frag = MTraceFragment(
            op="ship", trace_id=0xbadcafe, daemon_name="osd.0",
            pool=hostile_pool, op_type="write", keep=True,
            reason="slo", duration=0.5,
            spans=[[41, None, hostile_stage + "(osd=1)", "osd.0",
                    100.0, 0.5, {}, []]],
            anchor_wall=time.time(), anchor_mono=100.0)
        tm.enqueue(frag)
        assert tm.flush()
        assert wait_until(lambda: _pool_entries(tm, hostile_pool))

        text = mgr.modules["prometheus"].render()
        lint_exposition(text)           # raw newline/quote would fail
        assert "ceph_trace_critical_path_seconds{" in text
        assert "ceph_trace_slowest_seconds{" in text
        assert "ceph_trace_store_bytes" in text
        # the hostile name appears only in escaped form
        assert hostile_pool not in text
        from ceph_tpu.mgr.modules import _escape_label
        assert _escape_label(hostile_pool) in text
        assert 'trace_id="0xbadcafe"' in text

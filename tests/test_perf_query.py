"""Per-client/per-pool perf-query attribution: OSD engine bounds and
filters, attribution integrity across client reconnects, the mgr
module's cluster-wide merge + ageout, counter-reset handling in the
aggregator's derivations (bounced-daemon regression), the iotop /
`osd perf query` CLI against a live cluster, POOL_SLO_VIOLATION
raise/clear through the mon, and the exposition discipline of the new
labeled series (bounded top-N, hostile labels, appear-then-age-out).
"""

from __future__ import annotations

import json
import time
import types

import pytest

from ceph_tpu.mgr import (MetricsAggregator, PerfQueryModule,
                          PrometheusModule, StatusModule)
from ceph_tpu.osd.perf_query import (PQ_LAT_BUCKETS_US,
                                     PerfQueryEngine)

from .cluster_util import MiniCluster, wait_until
from .cluster_util import lint_exposition as _lint_exposition

FAST = {"osd_heartbeat_interval": 0.1, "osd_heartbeat_grace": 0.6,
        "mon_osd_down_out_interval": 1.0,
        "paxos_propose_interval": 0.02,
        "mgr_stats_period": 0.25}


def _msg(client_id=1, session="cafebabe" * 4, oid="obj",
         ops=None):
    """A fake MOSDOp carrying just what the engine keys/accounts by."""
    return types.SimpleNamespace(
        client_id=client_id, session=session, oid=oid,
        ops=ops if ops is not None else [("write_full", b"x" * 64)])


# -- OSD engine: bounds, filters, attribution integrity ----------------

class TestEngine:
    def test_key_table_bounded_under_churn(self):
        """10x max_keys distinct clients churn through one query: the
        table never exceeds the bound, LRU evicts oldest-updated
        first, and every displacement is counted."""
        eng = PerfQueryEngine()
        eng.add_query(1, {"key_by": ["client"], "max_keys": 32})
        for i in range(320):
            eng.account(_msg(client_id=i, session="%032x" % i),
                        "p", "1.0", False, 100, 0, 0.001, now=float(i))
        q = eng._queries[1]
        assert len(q.table) <= 32
        assert q.evictions == 320 - 32
        # the survivors are exactly the most recent 32 clients
        survivors = {k[0] for k in q.table}
        expected = {"client.%d:%s" % (i, ("%032x" % i)[:8])
                    for i in range(288, 320)}
        assert survivors == expected

    def test_add_query_idempotent_redefine_resets(self):
        """Re-adding the same spec (the mgr's osdmap re-broadcast)
        must NOT reset an accumulating table; a changed spec must."""
        eng = PerfQueryEngine()
        eng.add_query(1, {"key_by": ["client", "pool"]})
        eng.account(_msg(), "p", "1.0", False, 10, 0, 0.001)
        assert len(eng._queries[1].table) == 1
        eng.add_query(1, {"key_by": ["client", "pool"]})
        assert len(eng._queries[1].table) == 1    # preserved
        eng.add_query(1, {"key_by": ["pool"]})
        assert len(eng._queries[1].table) == 0    # redefined

    def test_pool_and_prefix_filters(self):
        eng = PerfQueryEngine()
        eng.add_query(1, {"key_by": ["client"], "pool": "gold"})
        eng.add_query(2, {"key_by": ["client"],
                          "object_prefix": "img-"})
        eng.account(_msg(oid="img-7"), "gold", "1.0", False,
                    10, 0, 0.001)
        eng.account(_msg(oid="doc-7"), "silver", "1.0", False,
                    10, 0, 0.001)
        assert len(eng._queries[1].table) == 1    # only the gold op
        assert len(eng._queries[2].table) == 1    # only the img- op

    def test_fresh_session_nonce_is_a_fresh_key(self):
        """Attribution integrity: a reconnect reusing client_id 7 with
        a NEW session nonce must not merge into the dead process's
        key."""
        eng = PerfQueryEngine()
        eng.add_query(1, {"key_by": ["client"]})
        eng.account(_msg(client_id=7, session="a" * 32), "p", "1.0",
                    False, 10, 0, 0.001)
        eng.account(_msg(client_id=7, session="b" * 32), "p", "1.0",
                    False, 20, 0, 0.001)
        keys = sorted(k[0] for k in eng._queries[1].table)
        assert keys == ["client.7:" + "a" * 8,
                        "client.7:" + "b" * 8]
        stats = {k[0]: st for k, st in eng._queries[1].table.items()}
        assert stats["client.7:" + "a" * 8].wr_bytes == 10
        assert stats["client.7:" + "b" * 8].wr_bytes == 20

    def test_read_write_split_and_histogram(self):
        eng = PerfQueryEngine()
        eng.add_query(1, {"key_by": ["client", "pool"]})
        eng.account(_msg(), "p", "1.0", False, 100, 0, 0.001)
        eng.account(_msg(), "p", "1.0", True, 0, 4096, 0.004)
        (key, st), = eng._queries[1].table.items()
        assert key == ("client.1:cafebabe", "p")
        assert (st.ops, st.rd_ops, st.wr_ops) == (2, 1, 1)
        assert st.wr_bytes == 100 and st.rd_bytes == 4096
        assert st.lat_count == 2
        assert sum(st.lat_hist) == 2
        # 1ms = 1000us lands in the bucket whose edge first covers it
        idx = next(i for i, e in enumerate(PQ_LAT_BUCKETS_US)
                   if 1000 <= e)
        assert st.lat_hist[idx] == 1
        row = eng._queries[1].dump()["keys"][0]
        assert row["k"] == ["client.1:cafebabe", "p"]
        assert row["lat_count"] == 2

    def test_idle_keys_pruned_at_dump(self):
        eng = PerfQueryEngine()
        eng.key_age = 5.0
        eng.add_query(1, {"key_by": ["client"]})
        eng.account(_msg(client_id=1), "p", "1.0", False, 1, 0,
                    0.001, now=100.0)
        eng.account(_msg(client_id=2), "p", "1.0", False, 1, 0,
                    0.001, now=104.0)
        dump = eng.dump(now=107.0)   # client 1 idle 7s > 5s
        labels = [r["k"][0] for r in dump["1"]["keys"]]
        assert len(labels) == 1 and labels[0].startswith("client.2:")


# -- aggregator counter-reset regression (simulated OSD bounce) --------

class TestCounterReset:
    def _agg(self):
        return MetricsAggregator(history=32, stale_after=100.0,
                                 window=100.0)

    def test_rate_clamped_and_rederived_after_bounce(self):
        """osd.0 bounces mid-window: its counter restarts from zero.
        The rate must never go negative and must derive from the
        post-reset segment only."""
        m = self._agg()
        for t, v in ((0.0, 1000), (1.0, 2000), (2.0, 100),
                     (3.0, 300)):
            m.record("osd.0", {"osd": {"op_w": v}}, now=t)
        # post-reset segment: (300 - 100) / (3 - 2)
        assert m.rate("osd.0", "osd", "op_w", now=3.0) == 200.0

    def test_rate_zero_when_reset_is_newest_sample(self):
        m = self._agg()
        for t, v in ((0.0, 1000), (1.0, 2000), (2.0, 5)):
            m.record("osd.0", {"osd": {"op_w": v}}, now=t)
        assert m.rate("osd.0", "osd", "op_w", now=2.0) == 0.0

    def test_time_avg_never_negative_across_bounce(self):
        """The bounced daemon restarted with a SMALLER sum but a
        sample count the naive delta reads as positive — the old
        derivation returned a negative latency."""
        m = self._agg()
        m.record("osd.0", {"osd": {"lat": {"avgcount": 100,
                                           "sum": 50.0}}}, now=0.0)
        m.record("osd.0", {"osd": {"lat": {"avgcount": 120,
                                           "sum": 0.6}}}, now=1.0)
        got = m.time_avg("osd.0", "osd", "lat", now=1.0)
        assert got == pytest.approx(0.6 / 120)
        assert got >= 0.0

    def test_percentiles_use_fresh_fills_after_bounce(self):
        m = self._agg()
        m.record("osd.0",
                 {"osd": {"h": {"count": 100, "sum": 1,
                                "buckets": [0, 100, 0, 0]}}}, now=0.0)
        m.record("osd.0",
                 {"osd": {"h": {"count": 8, "sum": 1,
                                "buckets": [0, 0, 0, 8]}}}, now=1.0)
        q = m.percentiles("osd.0", "osd", "h", (0.5,), window=10.0,
                          now=1.0)
        # negative windowed delta -> the newest (post-reset) fills are
        # the distribution: all mass in bucket 3 (bounds 2,4,8,16)
        assert q[0.5] > 8.0


# -- mgr module merge: windowed views, ageout, SLO burn ----------------

class _Conf:
    def get_val(self, name):
        raise KeyError(name)


class _FakeMgr:
    def __init__(self, metrics):
        self.ctx = types.SimpleNamespace(conf=_Conf())
        self.metrics = metrics
        self.modules: dict = {}
        self.health: dict = {}
        self.name = "mgr.t"
        self.mon_client = None
        self.sent: list = []
        self.msgr = types.SimpleNamespace(
            send_message=lambda msg, addr: self.sent.append((msg,
                                                             addr)))

    def get_state(self, name):
        if name == "metrics":
            return self.metrics
        if name == "osd_map":
            return None
        if name == "health":
            return dict(self.health)
        if name == "perf_counters":
            return {}
        raise KeyError(name)

    def set_module_health(self, module, checks):
        if checks:
            self.health[module] = dict(checks)
        else:
            self.health.pop(module, None)


def _payload(qid, key_by, rows):
    """An OSD perf_query dump: rows = [(key tuple, ops, wr_bytes,
    lat_count, hist_bucket_index)]"""
    keys = []
    for key, ops, wr_bytes, lat_count, bucket in rows:
        hist = [0] * (len(PQ_LAT_BUCKETS_US) + 1)
        hist[bucket] = lat_count
        keys.append({"k": list(key), "ops": ops, "rd_ops": 0,
                     "wr_ops": ops, "rd_bytes": 0,
                     "wr_bytes": wr_bytes, "lat_sum": 0.001 * ops,
                     "lat_count": lat_count, "lat_hist": hist})
    return {str(qid): {"key_by": list(key_by),
                       "buckets_us": list(PQ_LAT_BUCKETS_US),
                       "evictions": 0, "keys": keys}}


class TestMgrMerge:
    def _module(self):
        metrics = MetricsAggregator(history=64, stale_after=100.0,
                                    window=10.0)
        mgr = _FakeMgr(metrics)
        mod = PerfQueryModule(mgr)
        mgr.modules["perf_query"] = mod
        return mgr, metrics, mod

    def test_views_sum_rates_across_osds(self):
        mgr, metrics, mod = self._module()
        key = ("client.1:aaaa", "data")
        for osd, (o0, o1) in (("osd.0", (10, 30)),
                              ("osd.1", (5, 15))):
            metrics.record(osd, {}, daemon_type="osd", now=100.0,
                           perf_query=_payload(
                               1, ["client", "pool"],
                               [(key, o0, o0 * 100, o0, 12)]))
            metrics.record(osd, {}, daemon_type="osd", now=102.0,
                           perf_query=_payload(
                               1, ["client", "pool"],
                               [(key, o1, o1 * 100, o1, 12)]))
        rows = mod.views(window=10.0, now=102.0)[1]["rows"]
        # (30-10)/2 + (15-5)/2 = 15 ops/s summed across both OSDs
        assert rows[key]["ops_rate"] == pytest.approx(15.0)
        assert rows[key]["wr_Bps"] == pytest.approx(1500.0)
        top = mod.top_clients(now=102.0)
        assert top[0]["client"] == "client.1:aaaa"
        assert top[0]["pool"] == "data"
        assert top[0]["p99_ms"] > 0

    def test_osd_bounce_counts_as_fresh_window(self):
        """An OSD restart resets its key table: the post-bounce value
        is the fresh delta, never a negative contribution."""
        mgr, metrics, mod = self._module()
        key = ("client.1:aaaa", "data")
        metrics.record("osd.0", {}, daemon_type="osd", now=100.0,
                       perf_query=_payload(1, ["client", "pool"],
                                           [(key, 1000, 10, 10, 5)]))
        metrics.record("osd.0", {}, daemon_type="osd", now=102.0,
                       perf_query=_payload(1, ["client", "pool"],
                                           [(key, 8, 80, 8, 5)]))
        rows = mod.views(window=10.0, now=102.0)[1]["rows"]
        assert rows[key]["ops_rate"] == pytest.approx(8 / 2.0)

    def test_stale_client_ages_out_of_views(self):
        """A client that stops issuing ops leaves the merged views
        after mgr_perf_query_client_age even while its key still rides
        the OSD dumps (unchanged counters)."""
        mgr, metrics, mod = self._module()
        key = ("client.9:dead", "data")
        pay = _payload(1, ["client", "pool"], [(key, 50, 500, 50, 5)])
        metrics.record("osd.0", {}, daemon_type="osd", now=100.0,
                       perf_query=_payload(1, ["client", "pool"],
                                           [(key, 10, 100, 10, 5)]))
        metrics.record("osd.0", {}, daemon_type="osd", now=101.0,
                       perf_query=pay)
        assert key in mod.views(window=10.0, now=101.0)[1]["rows"]
        # the client vanishes: counters freeze, reports keep coming
        for i in range(2, 15):
            metrics.record("osd.0", {}, daemon_type="osd",
                           now=100.0 + i, perf_query=pay)
        rows = mod.views(window=10.0, now=114.0).get(1, {}) \
            .get("rows", {})
        assert key not in rows

    def test_slo_raise_then_clear(self):
        mgr, metrics, mod = self._module()
        mod.slo_targets = {"data": (0.001, 0.9)}   # 1ms, 99.. 90%
        # all latency mass in bucket 12 (lower bound 2^12 us = 4.1ms
        # > 1ms threshold) -> violation fraction 1.0, burn 10x
        metrics.record("osd.0", {}, daemon_type="osd", now=100.0,
                       perf_query=_payload(2, ["pool"],
                                           [(("data",), 10, 100,
                                             10, 12)]))
        metrics.record("osd.0", {}, daemon_type="osd", now=102.0,
                       perf_query=_payload(2, ["pool"],
                                           [(("data",), 40, 400,
                                             40, 12)]))
        state = mod.evaluate_slo(now=102.0)
        assert state["data"]["violation_fraction"] == 1.0
        assert state["data"]["burn_ratio"] == pytest.approx(10.0)
        checks = mgr.health.get("perf_query", {})
        assert "POOL_SLO_VIOLATION" in checks
        assert "pool 'data'" in checks["POOL_SLO_VIOLATION"][
            "detail"][0]
        # burn within budget -> the check clears
        mod.slo_targets = {"data": (10.0, 0.9)}    # 10s threshold
        state = mod.evaluate_slo(now=102.0)
        assert state["data"]["violation_fraction"] == 0.0
        assert "perf_query" not in mgr.health
        status = mod.slo_status()
        assert status["alerting"] is False

    def test_prometheus_exports_bounded_top_n(self):
        """Only prom_top_n client rows reach the page — client labels
        are unbounded-cardinality input."""
        mgr, metrics, mod = self._module()
        mod.prom_top_n = 3
        now = time.monotonic()
        rows0 = [(("client.%d:aaaa" % i, "data"), 10 * (i + 1),
                  100, 10, 5) for i in range(8)]
        rows1 = [(("client.%d:aaaa" % i, "data"), 20 * (i + 1),
                  200, 20, 5) for i in range(8)]
        metrics.record("osd.0", {}, daemon_type="osd", now=now - 2,
                       perf_query=_payload(1, ["client", "pool"],
                                           rows0))
        metrics.record("osd.0", {}, daemon_type="osd", now=now,
                       perf_query=_payload(1, ["client", "pool"],
                                           rows1))
        prom = PrometheusModule(mgr)
        mgr.modules["prometheus"] = prom
        text = prom.render()
        n = text.count("ceph_client_op_rate{")
        assert n == 3, text
        # the top-3 by ops/s are the highest-indexed clients
        for i in (5, 6, 7):
            assert 'client="client.%d:aaaa"' % i in text


# -- live cluster: end-to-end attribution ------------------------------

@pytest.fixture(scope="module")
def pq_cluster():
    cluster = MiniCluster(num_mons=1, num_osds=3,
                          conf_overrides=FAST).start()
    mgr = cluster.start_mgr(modules=(PerfQueryModule, StatusModule,
                                     PrometheusModule))
    client = cluster.client()
    pool_id = cluster.create_replicated_pool(client, "attrpool",
                                             size=2, pg_num=8)
    assert cluster.wait_clean(pool_id)
    assert wait_until(lambda: mgr.osdmap is not None, timeout=10)
    yield cluster, mgr, client, pool_id
    cluster.stop()


def _load(client, n=24, size=4096):
    io = client.open_ioctx("attrpool")
    for i in range(n):
        io.write_full("pq-%d" % i, b"w" * size)
    for i in range(0, n, 3):
        assert io.read("pq-%d" % i) == b"w" * 4096


class TestLiveAttribution:
    def test_default_queries_reach_every_osd(self, pq_cluster):
        cluster, mgr, _, _ = pq_cluster
        assert wait_until(
            lambda: all(o.perf_query.active
                        for o in cluster.osds.values()), timeout=15)
        specs = cluster.osds[0].perf_query.list_queries()
        key_bys = sorted(tuple(s["key_by"]) for s in specs.values())
        assert ("client", "pool") in key_bys
        assert ("pool",) in key_bys

    def test_attributed_bytes_match_op_in_bytes(self, pq_cluster):
        """The (client, pool) tables attribute at least 95% of the
        client bytes the OSDs themselves count in op_in_bytes."""
        cluster, _, client, _ = pq_cluster
        osds = list(cluster.osds.values())
        assert wait_until(lambda: all(o.perf_query.active
                                      for o in osds), timeout=15)

        def totals():
            attributed = 0
            for o in osds:
                for table in o.perf_query.dump().values():
                    if table["key_by"] == ["client", "pool"]:
                        attributed += sum(
                            r["wr_bytes"] for r in table["keys"]
                            if r["k"][1] == "attrpool")
            return attributed, sum(o.perf.get("op_in_bytes")
                                   for o in osds)
        a0, b0 = totals()
        _load(client, n=24)

        def faithful():
            a1, b1 = totals()
            return b1 - b0 >= 24 * 4096 and a1 - a0 >= 0.95 * (b1 - b0)
        assert wait_until(faithful, timeout=10, interval=0.2)

    def test_iotop_attributes_live_load(self, pq_cluster):
        cluster, mgr, client, _ = pq_cluster
        _load(client)
        label = "client.%d:%s" % (client.client_id,
                                  client.session[:8])

        def sees_client():
            _load(client, n=6)
            return any(r["client"] == label and r["pool"] == "attrpool"
                       for r in mgr.modules["perf_query"]
                       .top_clients(window=30.0))
        assert wait_until(sees_client, timeout=20, interval=0.3)
        rc, out, _ = mgr.module_command(
            {"prefix": "iotop", "window": 30.0})
        assert rc == 0
        assert label in out and "CLIENT" in out

    def test_status_top_clients_line(self, pq_cluster):
        cluster, mgr, client, _ = pq_cluster
        _load(client, n=12)

        def status_has_line():
            _load(client, n=6)
            rc, out, _ = mgr.module_command({"prefix": "status"})
            assert rc == 0
            return "top clients:" in out
        assert wait_until(status_has_line, timeout=20, interval=0.3)

    def test_reconnect_fresh_session_not_merged_live(self, pq_cluster):
        """Two incarnations of client_id 77 (fresh session nonce each)
        write through the same cluster: the OSD key tables keep them
        apart."""
        from ceph_tpu.client.rados import RadosClient
        from ceph_tpu.common.context import Context
        cluster, mgr, _, _ = pq_cluster
        sessions = []
        for _ in range(2):
            c = RadosClient(cluster.monmap,
                            Context(cluster.conf_overrides,
                                    name="client.77"), client_id=77)
            c.connect()
            try:
                sessions.append(c.session[:8])
                io = c.open_ioctx("attrpool")
                for i in range(8):
                    io.write_full("re-%d" % i, b"r" * 2048)
            finally:
                c.shutdown()
        assert sessions[0] != sessions[1]

        def both_keys():
            labels = set()
            for osd in cluster.osds.values():
                for dump in osd.perf_query.dump().values():
                    for row in dump["keys"]:
                        if row["k"] and str(row["k"][0]) \
                                .startswith("client.77:"):
                            labels.add(row["k"][0])
            return {"client.77:" + s for s in sessions} <= labels
        assert wait_until(both_keys, timeout=15)

    def test_cli_iotop_and_perf_query(self, pq_cluster, capsys):
        from ceph_tpu.tools import ceph_cli
        cluster, mgr, client, _ = pq_cluster
        _load(client, n=12)
        rc = ceph_cli.main(["--asok", cluster.mgr_asok, "iotop",
                            "--period", "5", "--count", "1"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "CLIENT" in out and "p99_ms" in out
        # add a prefix-filtered query, see it land on the OSDs, rm it
        rc = ceph_cli.main(["--asok", cluster.mgr_asok, "osd", "perf",
                            "query", "add", "client",
                            "--object-prefix", "pq-"])
        out = capsys.readouterr().out
        assert rc == 0
        qid = json.loads(out)["query_id"]
        assert wait_until(
            lambda: all(str(qid) in o.perf_query.list_queries()
                        for o in cluster.osds.values()), timeout=15)
        rc = ceph_cli.main(["--asok", cluster.mgr_asok, "osd", "perf",
                            "query", "ls"])
        out = capsys.readouterr().out
        assert rc == 0 and str(qid) in json.loads(out)["queries"]
        rc = ceph_cli.main(["--asok", cluster.mgr_asok, "osd", "perf",
                            "query", "rm", str(qid)])
        out = capsys.readouterr().out
        assert rc == 0 and json.loads(out)["removed"] is True
        assert wait_until(
            lambda: all(str(qid) not in o.perf_query.list_queries()
                        for o in cluster.osds.values()), timeout=15)

    def test_slo_violation_raises_and_clears_through_mon(
            self, pq_cluster):
        """An unreachable 2us target turns all real ops into
        violations -> POOL_SLO_VIOLATION raises on the mgr AND the
        mon; a sane target clears both."""
        cluster, mgr, client, _ = pq_cluster
        mod = mgr.modules["perf_query"]
        mod.slo_targets = {"attrpool": (2e-6, 0.5)}
        try:
            def raised():
                _load(client, n=6)
                return "POOL_SLO_VIOLATION" in mgr.get_state("health")
            assert wait_until(raised, timeout=20, interval=0.3)

            def mon_raised():
                _, _, data = client.mon_command({"prefix": "health"})
                return "POOL_SLO_VIOLATION" in data["checks"]
            assert wait_until(mon_raised, timeout=15)
            rc, out, _ = mgr.module_command({"prefix": "slo status"})
            assert rc == 0
            assert json.loads(out)["alerting"] is True
            # mon carry-until-first-report: a fresh leader with no mgr
            # report yet keeps the committed verdict
            hm = cluster.leader().healthmon
            hm._slo_report = None
            hm.recompute()

            def still_raised():
                _, _, data = client.mon_command({"prefix": "health"})
                return "POOL_SLO_VIOLATION" in data["checks"]
            assert still_raised()
        finally:
            mod.slo_targets = {"attrpool": (1000.0, 0.5)}

        def cleared():
            _load(client, n=6)
            _, _, data = client.mon_command({"prefix": "health"})
            return "POOL_SLO_VIOLATION" not in data["checks"] and \
                "POOL_SLO_VIOLATION" not in mgr.get_state("health")
        assert wait_until(cleared, timeout=20, interval=0.3)

    def test_prometheus_live_page_has_attribution_series(
            self, pq_cluster):
        cluster, mgr, client, _ = pq_cluster
        prom = mgr.modules["prometheus"]

        def on_page():
            _load(client, n=6)
            return "ceph_client_op_rate{" in prom.render()
        assert wait_until(on_page, timeout=20, interval=0.3)
        text = prom.render()
        assert "ceph_client_byte_rate{" in text
        _lint_exposition(text)

    def test_hostile_labels_roundtrip_then_age_out(self, pq_cluster):
        """Hostile client/pool names (spaces, quotes, backslashes,
        UTF-8, raw newline) injected through the same ingest path the
        OSD reports use must round-trip escaped on the FULL live page
        — and leave it when the OSD-side prune drops the key."""
        cluster, mgr, client, _ = pq_cluster
        prom = mgr.modules["prometheus"]
        mod = mgr.modules["perf_query"]
        hostile_client = 'cli "ent\\ß\n77'
        hostile_pool = 'pøol "q\\'
        key = (hostile_client, hostile_pool)
        now = time.monotonic()
        mgr.metrics.record(
            "osd.96", {"osd": {}}, daemon_type="osd", now=now - 1.0,
            perf_query=_payload(1, ["client", "pool"],
                                [(key, 10, 100, 10, 5)]))
        mgr.metrics.record(
            "osd.96", {"osd": {}}, daemon_type="osd", now=now,
            perf_query=_payload(1, ["client", "pool"],
                                [(key, 9000, 9000, 9000, 5)]))
        # a hostile pool name through the SLO series too
        mod._slo_state = {hostile_pool: {"threshold_ms": 1.0,
                                         "objective": 0.9,
                                         "samples": 1,
                                         "violation_fraction": 0.5,
                                         "burn_ratio": 5.0}}
        try:
            text = prom.render()
            esc_client = (hostile_client.replace("\\", "\\\\")
                          .replace('"', '\\"').replace("\n", "\\n"))
            esc_pool = (hostile_pool.replace("\\", "\\\\")
                        .replace('"', '\\"'))
            assert 'client="%s"' % esc_client in text
            assert 'ceph_pool_slo_burn_ratio{pool="%s"}' % esc_pool \
                in text
            _lint_exposition(text)
            # the OSD prunes the idle key from its dumps (empty key
            # table keeps riding the reports): the series leave the
            # page — appear-then-age-out
            for dt in (0.1, 0.2):
                mgr.metrics.record(
                    "osd.96", {"osd": {}}, daemon_type="osd",
                    now=now + dt,
                    perf_query=_payload(1, ["client", "pool"], []))
        finally:
            mod._slo_state = {}
            mgr.metrics.remove("osd.96")
        text = prom.render()
        assert 'client="%s"' % esc_client not in text

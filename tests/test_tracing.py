"""End-to-end op tracing (ZTracer analog) + OSD_SLOW_OPS health.

Covers the observability spine: span parent/child integrity across a
live mini-cluster EC write (client -> primary -> per-shard sub-ops,
stitched by trace id through the message envelope), per-shard span
count == k+m, TPU device h2d/compute/d2h segments on a batched encode,
the zero-allocation disabled path, the admin-socket dump_tracing /
trace reset surface, the `trace tree` renderer, perf schema/reset, and
the slow-op -> OSD_SLOW_OPS health round trip.
"""

import time

import numpy as np
import pytest

from ceph_tpu.common.admin_socket import AdminSocket
from ceph_tpu.common.config import Config
from ceph_tpu.common.tracer import (NULL_SPAN, SpanCollector,
                                    render_tree, trace_ctx)

FAST = {"osd_heartbeat_interval": 0.1, "osd_heartbeat_grace": 0.6,
        "mon_osd_down_out_interval": 1.0,
        "paxos_propose_interval": 0.02}


class TestSpanCollector:
    def test_disabled_allocates_no_spans(self):
        conf = Config({"osd_tracing": False})
        tracer = SpanCollector(conf=conf)
        span = tracer.start_trace("op")
        assert span is NULL_SPAN
        assert not span.valid()
        with span.child("sub") as sub:
            sub.keyval("k", 1)
            sub.event("e")
            sub.child_interval("i", 0.0, 1.0)
        assert tracer.continue_trace("x", 123, 45) is NULL_SPAN
        assert tracer.dump() == []
        assert trace_ctx(span) == (0, 0)

    def test_config_hot_toggle(self):
        conf = Config({"osd_tracing": False})
        tracer = SpanCollector(conf=conf)
        assert tracer.start_trace("x") is NULL_SPAN
        conf.set_val("osd_tracing", True)
        conf.apply_changes()
        assert tracer.enabled
        tracer.start_trace("y").finish()
        assert len(tracer.dump()) == 1

    def test_sampling_one_in_n(self):
        conf = Config({"osd_tracing": True, "osd_tracing_sample": 4})
        tracer = SpanCollector(conf=conf)
        real = sum(tracer.start_trace("s").valid() for _ in range(16))
        assert real == 4
        # sampled-out roots propagate nullness to the whole subtree
        assert tracer.continue_trace("c", 0, 0) is NULL_SPAN

    def test_parent_child_and_continue(self):
        tracer = SpanCollector()
        tracer.enabled = True
        root = tracer.start_trace("client_op", "client.0")
        child = root.child("messenger")
        # the envelope context stitches a second collector's spans
        remote = SpanCollector(endpoint="osd.1")
        remote.enabled = True
        t_id, p_id = trace_ctx(child)
        osd_span = remote.continue_trace("osd_op", t_id, p_id)
        assert osd_span.trace_id == root.trace_id
        assert osd_span.parent_id == child.span_id
        osd_span.finish()
        child.finish()
        root.finish()
        spans = tracer.dump() + remote.dump()
        by_name = {s["name"]: s for s in spans}
        assert by_name["messenger"]["parent_id"] == root.span_id
        assert len({s["trace_id"] for s in spans}) == 1

    def test_child_interval_backfill(self):
        tracer = SpanCollector()
        tracer.enabled = True
        root = tracer.start_trace("op")
        now = time.monotonic()
        iv = root.child_interval("queued", now - 0.5, now, batch=3)
        assert iv.valid()
        root.finish()
        doc = [s for s in tracer.dump() if s["name"] == "queued"][0]
        assert 0.45 < doc["duration"] < 0.55
        assert doc["keyvals"] == {"batch": 3}

    def test_ring_capacity(self):
        tracer = SpanCollector(capacity=3)
        tracer.enabled = True
        for i in range(6):
            tracer.start_trace("s%d" % i).finish()
        assert [s["name"] for s in tracer.dump()] == ["s3", "s4", "s5"]
        assert tracer.dropped == 3
        tracer.clear()
        assert tracer.dropped == 0

    def test_admin_socket_surface(self, tmp_path):
        asok = AdminSocket(str(tmp_path / "t.asok"))
        tracer = SpanCollector()
        tracer.enabled = True
        tracer.register_admin_commands(asok)
        span = tracer.start_trace("op")
        span.finish()
        doc = asok.execute("dump_tracing")
        assert doc["num_spans"] == 1 and doc["enabled"]
        assert doc["dropped"] == 0
        # filter by trace id (string form accepted, the CLI spelling)
        doc = asok.execute("dump_tracing",
                           {"trace_id": str(span.trace_id)})
        assert doc["num_spans"] == 1
        assert asok.execute("dump_tracing",
                            {"trace_id": span.trace_id + 1}
                            )["num_spans"] == 0
        assert asok.execute("trace reset") == {"reset": True}
        assert asok.execute("dump_tracing")["num_spans"] == 0

    def test_render_tree_self_times(self):
        tracer = SpanCollector()
        tracer.enabled = True
        root = tracer.start_trace("osd_op", "osd.0")
        time.sleep(0.01)
        with root.child("store_commit"):
            time.sleep(0.01)
        root.finish()
        out = render_tree(tracer.dump())
        assert "osd_op" in out and "store_commit" in out
        assert "self" in out
        # rendering a forest with a missing parent must not crash
        orphans = [{"trace_id": 1, "span_id": 2, "parent_id": 99,
                    "name": "x", "endpoint": "osd.1", "start": 0.0,
                    "start_wall": 0.0, "duration": 0.1, "keyvals": {},
                    "events": []}]
        assert "x" in render_tree(orphans)
        assert render_tree([]) == "(no spans)"


class _XorCodec:
    """Tiny stand-in codec: encode_batch works on host or device."""

    def encode_batch(self, batch):
        return batch ^ 0x5A


class TestDispatcherTracing:
    def test_device_segments_on_batched_encode(self):
        from ceph_tpu.osd.tpu_dispatch import TpuDispatcher
        tracer = SpanCollector()
        tracer.enabled = True
        disp = TpuDispatcher(max_batch=4, max_delay=0.001,
                             tracer=tracer)
        try:
            codec = _XorCodec()
            batch = np.arange(32, dtype=np.uint8).reshape(2, 4, 4)
            root = tracer.start_trace("op")
            out = disp.encode(codec, batch, trace=root)
            root.finish()
            assert np.array_equal(out, batch ^ 0x5A)
            names = {s["name"] for s in tracer.dump()}
            assert {"tpu_queue", "tpu_device",
                    "h2d", "compute", "d2h"} <= names
            # h2d/compute/d2h nest under the tpu_device span
            spans = tracer.dump()
            dev = [s for s in spans if s["name"] == "tpu_device"][0]
            for leg in ("h2d", "compute", "d2h"):
                leg_span = [s for s in spans if s["name"] == leg][0]
                assert leg_span["parent_id"] == dev["span_id"]
            assert disp.perf.get("l_tpu_dispatches") >= 1
            assert disp.perf.dump()["l_tpu_compute"]["avgcount"] >= 1
        finally:
            disp.shutdown()

    def test_disabled_tracer_no_spans_no_segments(self):
        """Disabled tracing mints ZERO spans.  The pipeline stages
        time their legs anyway (each blocks per leg), so the segment
        counters advance, but no span object is made."""
        from ceph_tpu.osd.tpu_dispatch import TpuDispatcher
        tracer = SpanCollector()          # disabled
        disp = TpuDispatcher(tracer=tracer)
        try:
            out = disp.encode(_XorCodec(),
                              np.zeros((1, 2, 4), dtype=np.uint8))
            assert out.shape == (1, 2, 4)
            assert tracer.dump() == []
            assert disp.perf.dump()["l_tpu_compute"]["avgcount"] == 1
        finally:
            disp.shutdown()


class TestPerfSchemaReset:
    def test_schema_and_reset_over_asok(self, tmp_path):
        from ceph_tpu.common.context import Context
        ctx = Context(name="t")
        from ceph_tpu.common.perf_counters import PerfCountersBuilder
        pc = (PerfCountersBuilder("osd")
              .add_u64_counter("op")
              .add_time_avg("op_latency")
              .add_histogram("l_osd_op_trace_us")
              .create_perf_counters())
        ctx.perf.add(pc)
        pc.inc("op", 3)
        pc.tinc("op_latency", 0.5)
        pc.hinc("l_osd_op_trace_us", 1000)
        asok = AdminSocket(str(tmp_path / "t.asok"))
        asok.register("perf schema",
                      lambda args: ctx.perf.perf_schema(), "")
        asok.register("perf reset",
                      lambda args: {"reset": ctx.perf.perf_reset(
                          args.get("key"))}, "")
        schema = asok.execute("perf schema")["osd"]
        assert schema["op"]["type"] == "u64_counter"
        assert schema["op_latency"]["type"] == "time_avg"
        assert schema["l_osd_op_trace_us"]["type"] == "histogram"
        assert schema["l_osd_op_trace_us"]["buckets"][0] == 2
        assert asok.execute("perf reset") == {"reset": ["osd"]}
        dumped = pc.dump()
        assert dumped["op"] == 0
        assert dumped["op_latency"]["avgcount"] == 0
        assert sum(dumped["l_osd_op_trace_us"]["buckets"]) == 0


class TestClusterTracing:
    def test_ec_write_stitches_cross_daemon_trace(self):
        """A single client write on a 3-OSD EC pool yields ONE stitched
        trace: client_op -> messenger -> osd_op -> {op_queue, pg_do_op,
        ec_encode (tpu_queue + tpu_device{h2d,compute,d2h}),
        sub_write(shard=i) x (k+m) -> ec_sub_write -> store span}."""
        from .cluster_util import MiniCluster, wait_until
        cluster = MiniCluster(num_mons=1, num_osds=3,
                              conf_overrides=FAST).start()
        try:
            client = cluster.client()
            cluster.create_ec_pool(
                client, "trace-ec",
                {"plugin": "jerasure", "technique": "reed_sol_van",
                 "k": "2", "m": "1", "w": "8"}, pg_num=1)
            assert cluster.wait_clean(client.pool_id("trace-ec"))
            ioctx = client.open_ioctx("trace-ec")
            # one whole stripe (2 x 4 KiB): reading it back asks k shards
            payload = bytes(range(256)) * 32
            ioctx.write_full("tobj", payload)
            assert ioctx.read("tobj") == payload

            def all_spans():
                spans = list(client.tracer.dump())
                for osd in cluster.osds.values():
                    spans.extend(osd.tracer.dump())
                return spans

            def write_trace():
                spans = all_spans()
                roots = [s for s in spans if s["name"] == "client_op"
                         and "writefull" in str(s["keyvals"].get("op"))]
                if not roots:
                    return None
                tid = roots[0]["trace_id"]
                mine = [s for s in spans if s["trace_id"] == tid]
                names = [s["name"] for s in mine]
                subs = [n for n in names
                        if n.startswith("sub_write(shard=")]
                # the full tree lands asynchronously (replica commits)
                if len(subs) < 3 or "ec_sub_write" not in names:
                    return None
                return mine

            assert wait_until(lambda: write_trace() is not None)
            mine = write_trace()
            names = [s["name"] for s in mine]
            # messenger + queue + pg + per-shard + store + device legs
            for want in ("client_op", "messenger", "osd_op",
                         "op_queue", "pg_do_op", "ec_encode",
                         "ec_sub_write", "tpu_queue", "tpu_device",
                         "h2d", "compute", "d2h"):
                assert want in names, (want, sorted(set(names)))
            # per-shard sub-write span count equals k+m
            subs = [n for n in names if n.startswith("sub_write(shard=")]
            assert len(subs) == 3, subs
            # store-phase span present (MemStore: store_apply)
            assert "store_apply" in names
            # parent/child integrity: every non-root parent resolves
            # inside the stitched set
            ids = {s["span_id"] for s in mine}
            roots = [s for s in mine if not s["parent_id"]]
            assert len(roots) == 1 and roots[0]["name"] == "client_op"
            for s in mine:
                if s["parent_id"]:
                    assert s["parent_id"] in ids, s
            # one trace spans multiple daemons
            assert len({s["endpoint"] for s in mine}) >= 3
            # dump_tracing retrieval + the trace tree renderer
            tid = mine[0]["trace_id"]
            primary = next(
                osd for osd in cluster.osds.values()
                if any(s["name"] == "osd_op" for s in osd.tracer.dump()))
            import os
            asok = AdminSocket(os.path.join(
                "/tmp", "trace-test-%d.asok" % os.getpid()))
            primary.tracer.register_admin_commands(asok)
            doc = asok.execute("dump_tracing", {"trace_id": tid})
            assert doc["num_spans"] >= 1
            tree = render_tree(mine, trace_id=tid)
            assert "client_op" in tree and "sub_write" in tree
            assert "self" in tree
            # read path: per-shard sub_read spans + decode
            read_spans = [s for s in all_spans()
                          if s["name"].startswith("sub_read(shard=")]
            assert len(read_spans) >= 2          # k shards read
            assert any(s["name"] == "ec_decode" for s in all_spans())
        finally:
            cluster.stop()

    @pytest.mark.parametrize("case", ["ec_write", "read_stopped_osd"])
    def test_op_tree_adds_up(self, case):
        """An OSD's op tree accounts for the op end to end: the new
        stages are there, children of one span run one after another
        (only the per-shard siblings overlap), osd_op's own time is at
        most a tenth of its critical path, and the critical path keeps
        the backend's stages (ec_encode was once a sibling overlapping
        pg_do_op, and fell off it)."""
        from ceph_tpu.mgr.trace_store import critical_path

        from .cluster_util import MiniCluster, wait_until
        cluster = MiniCluster(num_mons=1, num_osds=5,
                              conf_overrides=FAST).start()
        try:
            client = cluster.client()
            cluster.create_ec_pool(
                client, "tree-ec",
                {"plugin": "jerasure", "technique": "reed_sol_van",
                 "k": "2", "m": "3", "w": "8"}, pg_num=1)
            pool = client.pool_id("tree-ec")
            assert cluster.wait_clean(pool)
            ioctx = client.open_ioctx("tree-ec")
            payload = np.random.default_rng(7).bytes(1 << 20)
            if case == "ec_write":
                # the first write pays the cluster's cold start
                ioctx.write_full("warm", payload)
                want = {"ms_recv", "op_queue", "pg_do_op", "ec_wait",
                        "ec_encode", "ec_assemble", "tpu_queue",
                        "tpu_device", "tpu_finish", "tpu_resume",
                        "ec_txns", "ec_hinfo", "commit_wait",
                        "ec_sub_write"}
                backend = {"ec_wait", "ec_encode", "commit_wait"}
            else:
                ioctx.write_full("tobj", payload)
                # stop the OSDs of data shard 1 and of the XOR parity
                # (shard 2): the read decodes on the device, not by the
                # host's XOR shortcut
                m = client.osdmap
                pgid = m.pools[pool].raw_pg_to_pg(
                    m.object_to_pg(pool, "tobj"))
                for shard in (1, 2):
                    cluster.stop_osd(m.pg_to_up_acting_osds(pgid)[2][shard])
                assert wait_until(
                    lambda: ioctx.read("tobj") == payload, timeout=30)
                want = {"ms_recv", "op_queue", "pg_do_op", "read_gather",
                        "ec_sub_read", "ec_decode", "tpu_finish",
                        "tpu_resume"}
                backend = {"read_gather", "ec_decode"}
            for osd in cluster.osds.values():
                osd.tracer.clear()
            if case == "ec_write":
                ioctx.write_full("tobj", payload)
            else:
                assert ioctx.read("tobj") == payload

            def tree():
                spans = [s for osd in cluster.osds.values()
                         for s in osd.tracer.dump()]
                roots = [s for s in spans if s["name"] == "osd_op"]
                return [s for s in spans
                        if s["trace_id"] == roots[0]["trace_id"]] \
                    if roots else None

            assert wait_until(lambda: tree() is not None)
            mine = tree()
            names = {s["name"] for s in mine}
            assert want <= names, sorted(want - names)
            kids = {}
            for s in mine:
                kids.setdefault(s["parent_id"], []).append(s)
            for group in kids.values():
                seq = sorted((s for s in group
                              if not s["name"].startswith(
                                  ("sub_write(", "sub_read("))),
                             key=lambda s: s["start"])
                for a, b in zip(seq, seq[1:]):
                    assert a["start"] + a["duration"] <= \
                        b["start"] + 1e-6, (a["name"], b["name"])
            path = dict(critical_path(mine))
            root = next(s for s in mine if s["name"] == "osd_op")
            assert path["osd_op"] <= 0.1 * root["duration"], path
            assert backend <= set(path), path
        finally:
            cluster.stop()

    def test_disabled_tracing_cluster_records_nothing(self):
        from .cluster_util import MiniCluster
        conf = dict(FAST)
        conf["osd_tracing"] = False
        cluster = MiniCluster(num_mons=1, num_osds=3,
                              conf_overrides=conf).start()
        try:
            client = cluster.client()
            cluster.create_replicated_pool(client, "quiet", size=2,
                                           pg_num=4)
            ioctx = client.open_ioctx("quiet")
            ioctx.write_full("q", b"silent")
            assert ioctx.read("q") == b"silent"
            assert client.tracer.dump() == []
            for osd in cluster.osds.values():
                assert osd.tracer.dump() == []
        finally:
            cluster.stop()


class TestSlowOpsHealth:
    def test_slow_op_raises_and_clears_osd_slow_ops(self):
        """A wedged op raises OSD_SLOW_OPS in `ceph health` (via the
        MPGStats report into the HealthMonitor) and the check clears
        when the op drains."""
        from .cluster_util import MiniCluster, wait_until
        cluster = MiniCluster(num_mons=1, num_osds=3,
                              conf_overrides=FAST).start()
        try:
            client = cluster.client()
            osd = cluster.osds[0]
            osd.op_tracker.complaint_time = 0.05
            stuck = osd.op_tracker.create_request("wedged write")
            time.sleep(0.1)

            def health_checks():
                res, _, data = client.mon_command({"prefix": "health"})
                assert res == 0
                return data["checks"]

            assert wait_until(
                lambda: "OSD_SLOW_OPS" in health_checks())
            check = health_checks()["OSD_SLOW_OPS"]
            assert "slow" in check["summary"]
            assert any("osd.0" in d for d in check["detail"])
            stuck.mark_done()
            assert wait_until(
                lambda: "OSD_SLOW_OPS" not in health_checks())
        finally:
            cluster.stop()


@pytest.mark.slow
class TestSpanVolume:
    def test_span_volume_stress(self):
        """Span-volume stress: a deep, wide burst stays inside the
        bounded ring and dump/render remain responsive."""
        tracer = SpanCollector(capacity=4096)
        tracer.enabled = True
        for i in range(20000):
            root = tracer.start_trace("op%d" % (i % 7))
            for j in range(4):
                with root.child("leg%d" % j) as leg:
                    leg.keyval("i", i)
            root.finish()
        spans = tracer.dump()
        assert len(spans) == 4096
        out = render_tree(spans[-50:])
        assert out

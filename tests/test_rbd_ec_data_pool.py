"""RBD images whose data blocks live in an erasure-coded pool
(`rbd create --data-pool`, the Luminous EC-overwrites layout).

The image's header, directory and object map stay in a replicated
pool; its 4 KiB overwrites are read-modify-writes of a k=4 m=2
`jax_tpu` stripe through ECBackend and the device dispatcher. Checked
here: where each object lives, concurrent 4 KiB IO on one handle
against a host model (and the stored shards against `ops/gf_ref.py`),
the exclusive lock's handoff quiescing in-flight writes, the RMW span
and counters, and every whole-image op that addresses data objects.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from ceph_tpu.client.rbd import RBD, Image, _data_oid
from ceph_tpu.ops import gf, gf_ref

from .cluster_util import MiniCluster, wait_until

FAST = {"osd_heartbeat_interval": 0.1, "osd_heartbeat_grace": 0.6,
        "mon_osd_down_out_interval": 1.0, "paxos_propose_interval": 0.02}
PROFILE = {"plugin": "jax_tpu", "technique": "reed_sol_van",
           "k": "4", "m": "2", "crush-failure-domain": "osd"}
K, M, SU = 4, 2, 4096
MiB = 1 << 20
BLOCK = 4096
LOCKED = ("exclusive-lock", "object-map")


@pytest.fixture(scope="module")
def ctx():
    cluster = MiniCluster(num_mons=1, num_osds=6,
                          conf_overrides=FAST).start()
    client = cluster.client()
    cluster.create_replicated_pool(client, "rbd", size=3, pg_num=8)
    ec_id = cluster.create_ec_pool(client, "ec42", PROFILE, pg_num=8)
    assert cluster.wait_clean(ec_id, timeout=60)
    yield cluster, client, client.open_ioctx("rbd"), \
        client.open_ioctx("ec42")
    cluster.stop()


def _exists(ioctx, oid) -> bool:
    try:
        ioctx.stat(oid)
        return True
    except OSError:
        return False


def _stored_shards(cluster, client, pool_id, oid) -> list:
    """The object's shard streams, as the acting set's stores hold them."""
    m = client.osdmap
    pgid = m.pools[pool_id].raw_pg_to_pg(m.object_to_pg(pool_id, oid))
    out = []
    for shard, osd_id in enumerate(m.pg_to_up_acting_osds(pgid)[2]):
        pg = cluster.osds[osd_id].pgs[pgid]
        out.append(np.frombuffer(pg.store.read(pg.cid_of_shard(shard), oid),
                                 dtype=np.uint8))
    return out


def _reference_shards(content: bytes) -> np.ndarray:
    """gf_ref's RS k=4 m=2 encode of the object striped in 4 KiB
    chunks: [k + m, object bytes / k]."""
    raw = np.frombuffer(content, dtype=np.uint8)
    data = raw.reshape(-1, K, SU).transpose(1, 0, 2).reshape(K, -1)
    parity = gf_ref.matrix_encode_ref(gf.rs_vandermonde_generator(K, M, 8),
                                      data, 8)
    return np.vstack([data, parity])


def _prefill(img, model: np.ndarray) -> None:
    for off in range(0, model.size, img.block_size):
        img.write(off, model[off:off + img.block_size].tobytes())


class TestDataPool:
    def test_header_and_map_in_metadata_pool_data_in_ec_pool(self, ctx):
        _, client, rbd_io, ec_io = ctx
        RBD.create(rbd_io, "layout", 8 * MiB, features=LOCKED,
                   data_pool="ec42")
        img = Image(rbd_io, "layout")
        try:
            assert img.data_ioctx.pool_id == client.pool_id("ec42")
            assert img.stat()["data_pool"] == client.pool_id("ec42")
            img.write(4 * MiB + 8192, b"d" * BLOCK)
            for oid in ("rbd_header.layout", "rbd_object_map.layout",
                        "rbd_directory"):
                assert _exists(rbd_io, oid) and not _exists(ec_io, oid)
            data = _data_oid("layout", 1)
            assert _exists(ec_io, data) and not _exists(rbd_io, data)
            assert img.read(4 * MiB + 8192, BLOCK) == b"d" * BLOCK
        finally:
            img.close()

    def test_without_data_pool_everything_stays_in_one_pool(self, ctx):
        _, _, rbd_io, ec_io = ctx
        RBD.create(rbd_io, "onepool", 4 * MiB, order=20)
        img = Image(rbd_io, "onepool")
        assert img.data_ioctx is rbd_io
        img.write(0, b"r" * BLOCK)
        assert _exists(rbd_io, _data_oid("onepool", 0))
        assert not _exists(ec_io, _data_oid("onepool", 0))
        img.close()

    def test_unknown_data_pool_is_refused(self, ctx):
        _, _, rbd_io, _ = ctx
        with pytest.raises(OSError):
            RBD.create(rbd_io, "nopool", 4 * MiB, data_pool="no-such")
        assert "nopool" not in RBD.list(rbd_io)


class TestConcurrentIO:
    def test_4k_randrw_on_one_handle_matches_model_and_gf_ref(self, ctx):
        """300 seeded 4 KiB reads (70%) and overwrites (30%) from 32
        threads on one handle, Zipf-skewed blocks over a 16 MiB image;
        an op waits while its block has one in flight. Every read
        equals the host model, and every data object's shards equal
        gf_ref's encode of the model's bytes."""
        cluster, client, rbd_io, _ = ctx
        size = 16 * MiB
        RBD.create(rbd_io, "randrw", size, features=LOCKED,
                   data_pool="ec42")
        img = Image(rbd_io, "randrw")
        rng = np.random.default_rng(26)
        model = rng.integers(0, 256, size, dtype=np.uint8)
        _prefill(img, model)
        nblocks = size // BLOCK
        p = 1.0 / np.arange(1, nblocks + 1) ** 0.99
        blocks = rng.permutation(nblocks)[
            rng.choice(nblocks, size=300, p=p / p.sum())]
        kinds = np.concatenate([rng.permutation([0] * 70 + [1] * 30)
                                for _ in range(3)])
        payloads = rng.integers(0, 256, (300, BLOCK), dtype=np.uint8)
        busy, cond = set(), threading.Condition()
        nxt = iter(range(300))
        bad, errors, peak = [], [], [0]

        def worker():
            while True:
                with cond:
                    i = next(nxt, None)
                    if i is None:
                        return
                    blk = int(blocks[i])
                    while blk in busy:
                        cond.wait()
                    busy.add(blk)
                    peak[0] = max(peak[0], len(busy))
                off = blk * BLOCK
                try:
                    if kinds[i]:
                        img.write(off, payloads[i].tobytes())
                        model[off:off + BLOCK] = payloads[i]
                    elif img.read(off, BLOCK) != \
                            model[off:off + BLOCK].tobytes():
                        bad.append(i)
                except Exception as e:   # counted, not raised in a thread
                    errors.append(repr(e))
                finally:
                    with cond:
                        busy.discard(blk)
                        cond.notify_all()
        before = img.perf_counters()
        threads = [threading.Thread(target=worker) for _ in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        after = img.perf_counters()
        try:
            assert not errors and not bad, (errors[:3], bad[:3])
            assert after["l_librbd_rd"] - before["l_librbd_rd"] == 210
            assert after["l_librbd_wr"] - before["l_librbd_wr"] == 90
            assert after["l_librbd_inflight_s"] > before["l_librbd_inflight_s"]
            assert peak[0] > 1           # IO really overlapped
            assert img.read(0, size) == model.tobytes()
            ec_id = client.pool_id("ec42")
            for blk in range(size // img.block_size):
                obj = model[blk * img.block_size:(blk + 1) * img.block_size]
                want = _reference_shards(obj.tobytes())
                got = _stored_shards(cluster, client, ec_id,
                                     _data_oid("randrw", blk))
                for shard in range(K + M):
                    assert np.array_equal(got[shard], want[shard]), \
                        (blk, shard)
        finally:
            img.close()

    def test_lock_handoff_waits_for_inflight_writes(self, ctx):
        """A contender's request_lock makes the owner block new writes
        and drain the in-flight one before it releases; the contender
        gets the lock only then, and the blocked write lands after."""
        cluster, _, rbd_io, _ = ctx
        RBD.create(rbd_io, "handoff", 8 * MiB, features=LOCKED,
                   data_pool="ec42")
        a = Image(rbd_io, "handoff")
        a.write(0, b"a" * BLOCK)                 # A owns the lock
        assert a.lock_owned()
        gate, entered = threading.Event(), threading.Event()
        real_write = a.data_ioctx.write

        def slow_write(oid, data, offset=0):
            if offset == BLOCK:                  # the write held in flight
                entered.set()
                assert gate.wait(30)
            real_write(oid, data, offset)
        a.data_ioctx.write = slow_write
        held = threading.Thread(target=a.write, args=(BLOCK, b"h" * BLOCK))
        held.start()
        assert entered.wait(30)
        client2 = cluster.client()
        b = Image(client2.open_ioctx("rbd"), "handoff")
        contender = threading.Thread(target=b.write,
                                     args=(2 * BLOCK, b"b" * BLOCK))
        contender.start()
        # the owner is asked and starts its pre-release: new IO blocks
        assert wait_until(lambda: a._quiescer is not None, timeout=10)
        late = threading.Thread(target=a.write, args=(3 * BLOCK, b"l" * BLOCK))
        late.start()
        time.sleep(0.5)
        assert a.lock_owned() and not b.lock_owned()
        assert a._inflight == 1                  # the late write waits
        gate.set()
        held.join(30)
        contender.join(30)
        late.join(60)
        assert not held.is_alive() and not contender.is_alive()
        assert not late.is_alive()
        try:
            assert a.read(0, 4 * BLOCK) == \
                b"a" * BLOCK + b"h" * BLOCK + b"b" * BLOCK + b"l" * BLOCK
        finally:
            a.data_ioctx.write = real_write
            b.close()
            a.close()


class TestReadModifyWrite:
    def test_rmw_span_nests_under_the_op_and_counters_count_reads(self, ctx):
        cluster, client, rbd_io, _ = ctx
        RBD.create(rbd_io, "rmwspan", 4 * MiB, features=LOCKED,
                   data_pool="ec42")
        img = Image(rbd_io, "rmwspan")
        try:
            img.write(0, bytes(4 * MiB))         # the object exists whole

            def totals():
                return [sum(o.perf.get(n) for o in cluster.osds.values())
                        for n in ("l_osd_ec_rmw_ops",
                                  "l_osd_ec_rmw_read_bytes")]
            before = totals()
            for osd in cluster.osds.values():
                osd.tracer.clear()
            img.write(5 * BLOCK, b"o" * BLOCK)   # one chunk of stripe 1
            assert [a - b for a, b in zip(totals(), before)] == \
                [1, K * SU]                      # the stripe's k chunks

            def tree():
                spans = [s for osd in cluster.osds.values()
                         for s in osd.tracer.dump()]
                rmw = [s for s in spans if s["name"] == "ec_rmw_read"]
                return (rmw, spans) if rmw else None
            assert wait_until(lambda: tree() is not None)
            rmw, spans = tree()
            assert len(rmw) == 1
            mine = [s for s in spans if s["trace_id"] == rmw[0]["trace_id"]]
            parent = next(s for s in mine
                          if s["span_id"] == rmw[0]["parent_id"])
            assert parent["name"] == "osd_op"
            kids = sorted((s for s in mine
                           if s["parent_id"] == parent["span_id"]),
                          key=lambda s: s["start"])
            names = [s["name"] for s in kids]
            assert names.index("ec_wait") < names.index("ec_rmw_read") < \
                names.index("ec_encode")
            for a, b in zip(kids, kids[1:]):     # one after another
                assert a["start"] + a["duration"] <= b["start"] + 1e-6, \
                    (a["name"], b["name"])
            assert img.read(5 * BLOCK, BLOCK) == b"o" * BLOCK
        finally:
            img.close()

    def test_whole_object_write_reads_nothing_back(self, ctx):
        cluster, _, rbd_io, _ = ctx
        RBD.create(rbd_io, "norm", 4 * MiB, data_pool="ec42")
        img = Image(rbd_io, "norm")

        def rmw_ops():
            return sum(o.perf.get("l_osd_ec_rmw_ops")
                       for o in cluster.osds.values())
        before = rmw_ops()
        img.write(0, b"w" * (4 * MiB))
        assert rmw_ops() == before
        img.close()


class TestWholeImageOps:
    def test_remove_deletes_data_objects_from_the_data_pool(self, ctx):
        _, _, rbd_io, ec_io = ctx
        RBD.create(rbd_io, "gone", 8 * MiB, data_pool="ec42")
        img = Image(rbd_io, "gone")
        img.write(0, b"x" * BLOCK)
        img.write(4 * MiB, b"y" * BLOCK)
        img.close()
        assert _exists(ec_io, _data_oid("gone", 1))
        RBD.remove(rbd_io, "gone")
        assert not _exists(ec_io, _data_oid("gone", 0))
        assert not _exists(ec_io, _data_oid("gone", 1))
        assert "gone" not in RBD.list(rbd_io)

    def test_du_stats_the_data_pool(self, ctx):
        _, _, rbd_io, _ = ctx
        RBD.create(rbd_io, "usage", 12 * MiB, data_pool="ec42")
        img = Image(rbd_io, "usage")           # no object map: stats
        img.write(4 * MiB + 100, b"u" * BLOCK)
        assert img.du() == 4 * MiB
        img.close()

    def test_discard_removes_and_zeroes_in_the_data_pool(self, ctx):
        _, _, rbd_io, ec_io = ctx
        RBD.create(rbd_io, "disc", 8 * MiB, features=LOCKED,
                   data_pool="ec42")
        img = Image(rbd_io, "disc")
        try:
            img.write(0, b"a" * (4 * MiB))
            img.write(4 * MiB, b"b" * (4 * MiB))
            img.discard(4 * MiB, 4 * MiB)          # a whole block
            img.discard(BLOCK, BLOCK)              # a partial one
            assert not _exists(ec_io, _data_oid("disc", 1))
            assert img.read(0, 3 * BLOCK) == \
                b"a" * BLOCK + bytes(BLOCK) + b"a" * BLOCK
            assert img.read(4 * MiB, BLOCK) == bytes(BLOCK)
            assert img.du() == 4 * MiB
        finally:
            img.close()

    def test_snapshot_rollback_in_the_data_pool(self, ctx):
        _, client, rbd_io, _ = ctx
        RBD.create(rbd_io, "snappy", 4 * MiB, features=LOCKED,
                   data_pool="ec42")
        img = Image(rbd_io, "snappy")
        try:
            img.write(0, b"1" * (2 * BLOCK))
            seq = client.osdmap.pools[client.pool_id("ec42")].snap_seq
            snap_id = img.snap_create("s1")
            assert snap_id > seq                 # the data pool's id
            img.write(BLOCK, b"2" * BLOCK)
            assert img.read(0, 2 * BLOCK) == b"1" * BLOCK + b"2" * BLOCK
            img.snap_rollback("s1")
            assert img.read(0, 2 * BLOCK) == b"1" * (2 * BLOCK)
        finally:
            img.close()

    @staticmethod
    def _golden_and_child(rbd_io, name):
        """A parent with both blocks written and snapshotted, and its
        clone, each with its data in the EC pool."""
        RBD.create(rbd_io, name, 8 * MiB, data_pool="ec42")
        parent = Image(rbd_io, name)
        parent.write(0, b"p" * (4 * MiB))
        parent.write(4 * MiB, b"q" * (4 * MiB))
        parent.snap_create("base")
        parent.close()
        RBD.clone(rbd_io, name, "base", name + "-child", data_pool="ec42")
        return Image(rbd_io, name + "-child")

    def test_clone_copies_up_from_the_parents_data_pool(self, ctx):
        _, _, rbd_io, ec_io = ctx
        child = self._golden_and_child(rbd_io, "golden")
        try:
            # a partial write pulls the parent's block in
            child.write(BLOCK, b"c" * BLOCK)
            assert _exists(ec_io, _data_oid("golden-child", 0))
            assert not _exists(ec_io, _data_oid("golden-child", 1))
            assert child.read(0, 3 * BLOCK) == \
                b"p" * BLOCK + b"c" * BLOCK + b"p" * BLOCK
            assert child.read(4 * MiB, BLOCK) == b"q" * BLOCK
        finally:
            child.close()

    def test_flatten_copies_every_block_into_the_data_pool(self, ctx):
        _, _, rbd_io, ec_io = ctx
        child = self._golden_and_child(rbd_io, "silver")
        try:
            assert not _exists(ec_io, _data_oid("silver-child", 1))
            child.flatten()
            assert _exists(ec_io, _data_oid("silver-child", 0))
            assert _exists(ec_io, _data_oid("silver-child", 1))
            assert child.meta["parent"] is None
            assert child.read(4 * MiB, BLOCK) == b"q" * BLOCK
            assert child.read(0, BLOCK) == b"p" * BLOCK
        finally:
            child.close()

class _MemIoctx:
    """An in-memory IoCtx for one handle's bookkeeping: whole objects
    in a dict, no cluster."""

    pool_id = 1
    client = None

    def __init__(self):
        self.objs: dict = {}
        self.lock = threading.Lock()

    def read(self, oid, length=0, offset=0, snap=None):
        with self.lock:
            if oid not in self.objs:
                raise OSError(2, oid)
            data = bytes(self.objs[oid])
        return data[offset:offset + length] if length else data[offset:]

    def write(self, oid, data, offset=0):
        with self.lock:
            buf = self.objs.setdefault(oid, bytearray())
            if len(buf) < offset + len(data):
                buf.extend(bytes(offset + len(data) - len(buf)))
            buf[offset:offset + len(data)] = data

    def write_full(self, oid, data):
        with self.lock:
            self.objs[oid] = bytearray(data)

    def omap_set(self, oid, kv):
        pass

    def omap_get(self, oid):
        return {}

    def set_snap_context(self, seq, snaps):
        pass


class _Journal:
    """Records the commit positions a handle sets."""
    splay_width, entries_per_object = 4, 1 << 20

    def __init__(self):
        self.tid = 0
        self.commits = []
        self.lock = threading.Lock()

    def append(self, tag, payload):
        with self.lock:
            self.tid += 1
            return self.tid

    def commit(self, client_id, tid):
        self.commits.append(tid)


class TestHandleConcurrency:
    def test_quiesce_sees_no_io_in_flight_under_contention(self):
        """64 IO threads and 4 quiescers on one handle, with a short
        switch interval: while a quiescer holds the handle no IO is in
        flight, every IO completes and is counted, and the in-flight
        count returns to zero."""
        import sys
        io = _MemIoctx()
        RBD.create(io, "mem", 1 * MiB, order=16)
        img = Image(io, "mem")
        seen_busy, stop = [], threading.Event()

        def io_worker(w):
            for i in range(40):
                off = ((w * 40 + i) % 256) * BLOCK
                if i % 3:
                    img.read(off, BLOCK)
                else:
                    img.write(off, bytes([w]) * BLOCK)

        def quiescer():
            while not stop.is_set():
                with img._quiesced():
                    seen_busy.append(img._inflight)
                time.sleep(0.001)
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            qs = [threading.Thread(target=quiescer) for _ in range(4)]
            ws = [threading.Thread(target=io_worker, args=(w,))
                  for w in range(64)]
            for t in qs + ws:
                t.start()
            for t in ws:
                t.join(120)
            stop.set()
            for t in qs:
                t.join(30)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in qs + ws)
        assert seen_busy and not any(seen_busy)
        perf = img.perf_counters()
        assert img._inflight == 0 and img._quiescer is None
        assert perf["l_librbd_wr"] == 64 * 14
        assert perf["l_librbd_rd"] == 64 * 26

    def test_journal_commit_never_passes_an_unapplied_event(self):
        """Events applied out of order commit only the run of applied
        ones: the position stays below the first event still in
        flight."""
        io = _MemIoctx()
        RBD.create(io, "jmem", 1 * MiB, order=16)
        img = Image(io, "jmem")
        img._journal = j = _Journal()
        tids = [img._journal_event({"type": "write"}) for _ in range(5)]
        assert tids == [1, 2, 3, 4, 5]
        img._journal_commit(2)
        img._journal_commit(3)
        assert j.commits == []           # 1 is still in flight
        img._journal_commit(1)
        assert j.commits == [3]
        img._journal_commit(5)
        assert j.commits == [3]
        img._journal_commit(4)
        assert j.commits == [3, 5]

"""EC client reads ask only the data shards their extent covers.

A client read of a range inside one chunk asks that chunk's shard
alone; a range across a stripe boundary asks its two columns' shards;
a stripe or more asks all k. Where a wanted shard is down or fails, the
read asks the codec's decode set for it (`minimum_to_decode`: the XOR
group, LRC's local group, or k shards) and returns the same bytes.
Checked on each plugin family a pool can use, through the primary's
``l_osd_ec_reads`` / ``l_osd_ec_read_shards`` /
``l_osd_ec_partial_reads``. A client resends an op that has not
answered within a second, so a check counts per read planned.
"""

from __future__ import annotations

import numpy as np
import pytest

from ceph_tpu.osd.osd_map import CRUSH_ITEM_NONE

from .cluster_util import MiniCluster, wait_until

CONF = {"osd_heartbeat_interval": 0.5, "osd_heartbeat_grace": 5.0,
        # a stopped OSD is marked down by command, and stays down, not
        # out: its shard is not remapped and recovered while a test
        # reads around it; no OSD of a busy host is marked down falsely
        "mon_osd_down_out_interval": 600.0,
        "paxos_propose_interval": 0.02,
        # whole-object writes of compressible data store a compressed
        # stream (the fused write transform); random data stays raw
        "osd_fused_compression_mode": "bitplane"}

PROFILES = {
    "reed_sol_van": {"plugin": "jax_tpu", "technique": "reed_sol_van",
                     "k": "4", "m": "2"},
    "cauchy_good": {"plugin": "jax_tpu", "technique": "cauchy_good",
                    "k": "4", "m": "2", "packetsize": "512"},
    "lrc": {"plugin": "lrc_tpu", "k": "4", "m": "2", "l": "3"},
    "shec": {"plugin": "shec", "k": "4", "m": "3", "c": "2"},
    "msr": {"plugin": "msr", "technique": "msr", "k": "3", "m": "2"},
}
READS = ("l_osd_ec_reads", "l_osd_ec_read_shards",
         "l_osd_ec_partial_reads")
RMW = ("l_osd_ec_rmw_ops", "l_osd_ec_rmw_read_bytes")


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster(num_mons=1, num_osds=8, conf_overrides=CONF).start()
    yield c
    c.stop()


@pytest.fixture(scope="module")
def pools(cluster):
    """One pool per profile, made on first use."""
    made = {}

    def get(name):
        if name not in made:
            made[name] = Pool(cluster, "partial-" + name, PROFILES[name])
        return made[name]
    return get


class Pool:
    """One EC pool of one PG, and where an object's shards live."""

    def __init__(self, cluster, name, profile):
        self.cluster = cluster
        self.name = name
        self.client = cluster.client()
        self.pool_id = cluster.create_ec_pool(
            self.client, name, dict(profile, **{"crush-failure-domain":
                                                "osd"}), pg_num=1)
        assert cluster.wait_clean(self.pool_id, timeout=60)
        self.ioctx = self.client.open_ioctx(name)

    def place(self, oid):
        """(pgid, acting OSDs by shard, the primary's daemon), once the
        primary's PG is active."""
        m = self.client.osdmap
        pgid = m.pools[self.pool_id].raw_pg_to_pg(
            m.object_to_pg(self.pool_id, oid))
        _, _, acting, primary = m.pg_to_up_acting_osds(pgid)
        osd = self.cluster.osds[primary]
        assert wait_until(lambda: pgid in osd.pgs and
                          osd.pgs[pgid].peer_state == "active", timeout=30)
        return pgid, acting, osd

    def write(self, oid, stripes: int, seed: int) -> tuple:
        """An object of random bytes, `stripes` stripes long:
        (its bytes, the primary's backend, the primary's daemon). Every
        test writes 2 stripes, so that the device codec compiles one
        write program per pool."""
        pgid, _, osd = self.place(oid)
        backend = osd.pgs[pgid].backend
        data = np.random.default_rng(seed).integers(
            0, 256, stripes * backend.sinfo.stripe_width,
            dtype=np.uint8).tobytes()
        self.ioctx.write_full(oid, data)
        return data, backend, osd


@pytest.fixture(scope="module", params=sorted(PROFILES))
def pool(request, pools):
    return pools(request.param)


def deltas(osd, names, fn):
    """(fn's result, the window deltas of the named counters)."""
    before = {n: osd.perf.get(n) for n in names}
    out = fn()
    return out, {n: osd.perf.get(n) - before[n] for n in names}


def column_read(backend, stripe: int, column: int) -> tuple:
    """(offset, length) of up to 4 KiB at the start of one chunk."""
    sinfo = backend.sinfo
    return (stripe * sinfo.stripe_width + column * sinfo.chunk_size,
            min(4096, sinfo.chunk_size))


def remote_column(backend, acting, primary, first: int = 1) -> int:
    """A data column whose shard another OSD than the primary holds."""
    for c in list(range(first, backend.k)) + list(range(first)):
        if acting[backend.codec.chunk_index(c)] != primary.whoami:
            return c
    raise AssertionError("every data shard on the primary")


class TestPartialRead:
    def test_read_inside_one_chunk_asks_its_shard(self, pool):
        data, backend, osd = pool.write("one", 2, seed=1)
        for c in range(backend.k):
            off, n = column_read(backend, 1, c)
            got, d = deltas(osd, READS,
                            lambda: pool.ioctx.read("one", n, off))
            assert got == data[off:off + n], "column %d" % c
            assert d["l_osd_ec_reads"] >= 1
            assert d["l_osd_ec_read_shards"] == d["l_osd_ec_reads"]
            assert d["l_osd_ec_partial_reads"] == d["l_osd_ec_reads"]

    def test_read_across_a_stripe_boundary_asks_two_shards(self, pool):
        data, backend, osd = pool.write("across", 2, seed=2)
        n = min(4096, backend.sinfo.chunk_size)
        off = backend.sinfo.stripe_width - n // 2   # columns k-1 and 0
        got, d = deltas(osd, READS,
                        lambda: pool.ioctx.read("across", n, off))
        assert got == data[off:off + n]
        assert d["l_osd_ec_reads"] >= 1
        assert d["l_osd_ec_read_shards"] == 2 * d["l_osd_ec_reads"]
        assert d["l_osd_ec_partial_reads"] == d["l_osd_ec_reads"]

    @pytest.mark.parametrize("extent", ["stripe", "unaligned_stripe",
                                        "whole"])
    def test_read_of_a_stripe_or_more_asks_all_k(self, pool, extent):
        data, backend, osd = pool.write("wide-" + extent, 2, seed=3)
        sw, cs = backend.sinfo.stripe_width, backend.sinfo.chunk_size
        off, n = {"stripe": (sw, sw), "unaligned_stripe": (cs // 2, sw),
                  "whole": (0, 0)}[extent]
        got, d = deltas(osd, READS, lambda: pool.ioctx.read(
            "wide-" + extent, n, off))
        assert got == (data[off:off + n] if n else data)
        assert d["l_osd_ec_reads"] >= 1
        assert d["l_osd_ec_read_shards"] == backend.k * d["l_osd_ec_reads"]
        assert d["l_osd_ec_partial_reads"] == 0

    def test_wanted_shard_down_reads_its_decode_set(self, pool):
        data, backend, osd = pool.write("down", 2, seed=4)
        pgid, acting, _ = pool.place("down")
        c = remote_column(backend, acting, osd)
        shard = backend.codec.chunk_index(c)
        victim = acting[shard]
        n_shards = backend.codec.get_chunk_count()
        plan = backend.codec.minimum_to_decode(
            {shard}, set(range(n_shards)) - {shard})
        if pool.name.endswith("lrc"):
            assert len(plan) < backend.k     # the local group alone
        cluster = pool.cluster
        store = cluster.stop_osd(victim)
        try:
            pool.client.mon_command({"prefix": "osd down", "id": victim})
            pg = osd.pgs[pgid]
            assert wait_until(lambda: pg.acting_shards().get(shard)
                              == CRUSH_ITEM_NONE, timeout=20)
            off, n = column_read(backend, 1, c)
            got, d = deltas(osd, READS,
                            lambda: pool.ioctx.read("down", n, off))
            assert got == data[off:off + n]
            assert d["l_osd_ec_reads"] >= 1
            assert d["l_osd_ec_read_shards"] == \
                len(plan) * d["l_osd_ec_reads"]
        finally:
            cluster.revive_osd(victim, store=store)
            assert wait_until(cluster.all_osds_up, timeout=20)
            assert wait_until(lambda: osd.pgs[pgid].acting_shards().get(
                shard) == victim, timeout=20)
            assert cluster.wait_clean(pool.pool_id, timeout=60)

    def test_eio_on_the_wanted_shard_replans_and_repairs(self, pool):
        data, backend, osd = pool.write("eio", 2, seed=5)
        pgid, acting, _ = pool.place("eio")
        c = remote_column(backend, acting, osd, first=2)
        shard = backend.codec.chunk_index(c)
        n_shards = backend.codec.get_chunk_count()
        plan = backend.codec.minimum_to_decode(
            {shard}, set(range(n_shards)) - {shard})
        victim = pool.cluster.osds[acting[shard]]
        cid = victim.pgs[pgid].cid_of_shard(shard)
        good = victim.store.read(cid, "eio")
        victim.store.faults.mark_eio(cid, "eio")
        errs, repaired = osd.perf.get("read_err"), osd.perf.get("repaired")
        off, n = column_read(backend, 1, c)
        got, d = deltas(osd, READS,
                        lambda: pool.ioctx.read("eio", n, off))
        assert got == data[off:off + n]
        # the one shard asked failed: the read asked its decode set too
        assert d["l_osd_ec_read_shards"] >= 1 + len(plan)
        assert osd.perf.get("read_err") > errs
        assert wait_until(lambda: osd.perf.get("repaired") > repaired,
                          timeout=20)

        def healed():
            try:
                return victim.store.read(cid, "eio") == good
            except OSError:
                return False
        assert wait_until(healed, timeout=20)

    def test_sub_stripe_overwrite_reads_back_whole_stripes(self, pool):
        data, backend, osd = pool.write("rmw", 2, seed=6)
        off, n = column_read(backend, 1, 1)
        patch = bytes(np.random.default_rng(7).integers(
            0, 256, n, dtype=np.uint8))
        _, d = deltas(osd, RMW, lambda: pool.ioctx.write("rmw", patch, off))
        assert d["l_osd_ec_rmw_ops"] >= 1
        assert d["l_osd_ec_rmw_read_bytes"] == \
            backend.k * backend.sinfo.chunk_size * d["l_osd_ec_rmw_ops"]
        expect = data[:off] + patch + data[off + n:]
        assert pool.ioctx.read("rmw") == expect


def test_compressed_object_reads_its_whole_stream(pools):
    """A fused write that stored a compressed stream: its logical
    offsets map to no stored offset, so a 4 KiB read asks all k."""
    pool = pools("reed_sol_van")
    pgid, _, osd = pool.place("packed")
    backend = osd.pgs[pgid].backend
    data = np.random.default_rng(8).integers(
        0, 4, 2 * backend.sinfo.stripe_width, dtype=np.uint8).tobytes()
    pool.ioctx.write_full("packed", data)
    assert backend.get_hinfo("packed").comp_info is not None
    off, n = column_read(backend, 1, 1)
    got, d = deltas(osd, READS, lambda: pool.ioctx.read("packed", n, off))
    assert got == data[off:off + n]
    assert d["l_osd_ec_reads"] >= 1
    assert d["l_osd_ec_read_shards"] == backend.k * d["l_osd_ec_reads"]
    assert d["l_osd_ec_partial_reads"] == 0


def test_read_counters_in_perf_dump_and_schema(cluster):
    osd = next(iter(cluster.osds.values()))
    dump = osd.ctx.perf.perf_dump()["osd"]
    schema = osd.ctx.perf.perf_schema()["osd"]
    for name in READS:
        assert name in dump and name in schema

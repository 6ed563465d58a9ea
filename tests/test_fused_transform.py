"""Fused device-side write transform (osd/fused_transform.py).

The write path's checksum -> probe/compress -> EC encode as ONE jitted
device program. Ground truth is byte-level: device digests against
independent host oracles, the device compression container against its
host twin, fused shard maps against the separate encode() path, and
the deep-scrub inventory against the write-time hinfo crcs with ZERO
host hashing for device-digested resident objects.
"""

from __future__ import annotations

import zlib

import numpy as np
import pytest

from ceph_tpu import registry
from ceph_tpu.osd import ec_util, fused_transform as ft
from ceph_tpu.osd.tpu_dispatch import TpuDispatcher

from .cluster_util import MiniCluster, wait_until


def make_codec(k=2, m=1):
    return registry.factory("jax_tpu", {"technique": "reed_sol_van",
                                        "k": str(k), "m": str(m)})


def host_dict(out):
    import jax
    return jax.device_get(out)


def compressible(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 4, size=n, dtype=np.uint8)


def incompressible(n, seed=1):
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.uint8)


def shard_streams(rows, parity):
    """Per-shard cumulative streams, physical order (what lands on
    disk and what HashInfo crcs cover)."""
    alln = np.concatenate([np.asarray(rows), np.asarray(parity)], axis=1)
    return [np.ascontiguousarray(alln[:, i, :]).reshape(-1).tobytes()
            for i in range(alln.shape[1])]


class TestHostOracles:
    """The host twins themselves, against published test vectors —
    everything else in this file is measured against them."""

    def test_crc32c_vector(self):
        assert ft.crc32c_host(b"123456789") == 0xE3069283

    def test_xxh32_vectors(self):
        assert ft.xxh32_host(b"") == 0x02CC5D05
        assert ft.xxh32_host(
            b"Nobody inspects the spammish repetition") == 0xE2293B2F

    def test_bitplane_host_roundtrip(self):
        for payload in (b"\0" * 64, bytes(range(64)) * 3,
                        bytes(incompressible(4096)), b"x" * 333):
            buf, padded = ft.bitplane_compress_host(payload)
            out = ft.bitplane_decompress(buf, padded)
            assert out[:len(payload)] == payload


class TestDeviceDigestParity:
    """Device crc32c/xxh32 per-chunk digests equal the host oracles
    across chunk sizes, including non-power-of-two lengths (the CRC
    tree's odd-tail padding) and multi-stripe batches."""

    @pytest.mark.parametrize("chunk", [64, 96, 256])
    @pytest.mark.parametrize("stripes", [1, 3])
    def test_chunk_digests(self, chunk, stripes):
        codec = make_codec()
        k = codec.get_data_chunk_count()
        batch = incompressible(stripes * k * chunk, seed=chunk).reshape(
            stripes, k, chunk)
        host = host_dict(ft.run_fused(codec, batch, mode="store"))
        for s in range(stripes):
            for i in range(k):
                raw = batch[s, i].tobytes()
                assert int(host["chunk_crc32c"][s, i]) == \
                    ft.crc32c_host(raw), (s, i)
                assert int(host["chunk_xxh32"][s, i]) == \
                    ft.xxh32_host(raw), (s, i)

    @pytest.mark.parametrize("mode", ["store", "compress"])
    def test_shard_crcs_match_zlib(self, mode):
        """Device per-shard crcs are exactly zlib.crc32 of the stored
        shard streams — what deep scrub verifies on disk."""
        codec = make_codec()
        batch = compressible(6 * 2 * 128).reshape(6, 2, 128)
        host = host_dict(ft.run_fused(codec, batch, mode=mode))
        r = ft.result_from_host(host, 6, 2, 128, mode)
        rows = r.stored if r.stored is not None else batch
        for i, stream in enumerate(shard_streams(rows, r.parity)):
            assert r.shard_crcs[i] == zlib.crc32(stream) & 0xFFFFFFFF, i


class TestCrcBitMatrixLeaf:
    """The CRC tree's leaf is a GF(2) bit-matrix product: exact against
    the host CRCs at lengths below, at and around the 256-byte segment,
    and it never falls back to a byte-table lookup."""

    @pytest.mark.parametrize("poly", ["zlib", "castagnoli"])
    @pytest.mark.parametrize("fill", ["random", "ones"])
    @pytest.mark.parametrize("length", [1, 63, 255, 256, 257, 4096,
                                        3 * 4096 + 5, 65536])
    def test_crc_matches_host(self, length, fill, poly):
        import jax
        if fill == "random":
            data = incompressible(length, seed=length)
        else:
            data = np.full(length, 0xFF, dtype=np.uint8)
        if poly == "zlib":
            pc, want = ft._poly_consts(ft._POLY_ZLIB), zlib.crc32
        else:
            pc, want = ft._poly_consts(ft._POLY_C), ft.crc32c_host
        init = np.uint32(pc.shift_n(0xFFFFFFFF, length))
        got = jax.jit(lambda x: ft._crc32_full(x[None, :], pc, init))(data)
        assert int(got[0]) == want(data.tobytes()) & 0xFFFFFFFF

    @pytest.mark.parametrize("mode", ["store", "compress"])
    def test_program_has_no_gather(self, mode):
        """A lowered fused program holds no gather: on a TPU a per-byte
        table lookup moves a whole tile of HBM for each byte looked up."""
        import jax
        codec = make_codec()
        z, c = ft._poly_consts(ft._POLY_ZLIB), ft._poly_consts(ft._POLY_C)
        S, k, chunk = 4, 2, 256
        sw = k * chunk

        def spec(a):
            return jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
        cap2 = S * sw if mode == "store" else ft.plan_capacity(S * sw, sw)
        text = ft._build_program(False).lower(
            jax.ShapeDtypeStruct((S, k, chunk), np.uint8),
            spec(codec._bitmat), spec(z.table), spec(z.inv), spec(c.table),
            spec(np.uint32(0)), spec(np.uint32(0)), w=8, mode=mode,
            required_milli=875, entropy_max_milli=7000, cap2=cap2,
            stripe_width=sw).as_text()
        assert "dot_general" in text
        assert "gather" not in text


class TestFusedVsSeparate:
    def test_store_mode_parity_equals_separate_encode(self):
        codec = make_codec()
        batch = incompressible(4 * 2 * 256, seed=7).reshape(4, 2, 256)
        host = host_dict(ft.run_fused(codec, batch, mode="store"))
        assert np.array_equal(host["parity"],
                              np.asarray(codec.encode_batch(batch)))

    def test_compress_mode_container_matches_host_twin(self):
        codec = make_codec()
        batch = compressible(4 * 2 * 256, seed=3).reshape(4, 2, 256)
        host = host_dict(ft.run_fused(codec, batch, mode="compress"))
        assert bool(host["do_compress"])
        comp_len = int(host["comp_len"])
        dev = host["stored"].reshape(-1)[:comp_len].tobytes()
        twin, padded = ft.bitplane_compress_host(batch.tobytes())
        assert dev == twin
        out = ft.bitplane_decompress(dev, padded)
        assert out[:batch.size] == batch.tobytes()
        # the parity on disk is the encode of the STORED stream
        r = ft.result_from_host(host, 4, 2, 256, "compress")
        assert np.array_equal(
            np.asarray(r.parity),
            np.asarray(codec.encode_batch(np.asarray(r.stored))))

    def test_probe_rejects_incompressible(self):
        codec = make_codec()
        batch = incompressible(4 * 2 * 256, seed=9).reshape(4, 2, 256)
        host = host_dict(ft.run_fused(codec, batch, mode="compress"))
        assert not bool(host["probe_ok"])
        assert not bool(host["do_compress"])
        r = ft.result_from_host(host, 4, 2, 256, "compress")
        # the device stored the RAW bytes; nothing was lost to the probe
        assert np.asarray(r.stored).tobytes() == batch.tobytes()
        assert r.used_stripes == 4

    def test_ratio_gate_stores_raw(self):
        """Probe passes (low entropy) but the required ratio is made
        unbeatable -> on-device decision stores raw."""
        codec = make_codec()
        batch = compressible(4 * 2 * 256, seed=5).reshape(4, 2, 256)
        host = host_dict(ft.run_fused(codec, batch, mode="compress",
                                      required_ratio=0.01))
        assert bool(host["probe_ok"])
        assert not bool(host["do_compress"])


class TestEncodeFused:
    def _sinfo(self, codec, chunk=256):
        return ec_util.StripeInfo(codec.get_data_chunk_count(),
                                  codec.get_data_chunk_count() * chunk)

    def test_store_shard_map_equals_encode(self):
        codec = make_codec()
        sinfo = self._sinfo(codec)
        payload = incompressible(3 * sinfo.stripe_width, seed=11).tobytes()
        separate = ec_util.encode(sinfo, codec, payload)
        fused, r = ec_util.encode_fused(sinfo, codec, payload)
        assert set(fused) == set(separate)
        for shard in separate:
            assert np.array_equal(fused[shard], separate[shard]), shard
        assert not r.compressed and r.stored is None

    def test_store_roundtrip_through_decode(self):
        codec = make_codec()
        sinfo = self._sinfo(codec)
        payload = incompressible(2 * sinfo.stripe_width, seed=13).tobytes()
        shards, _ = ec_util.encode_fused(sinfo, codec, payload)
        # lose one shard, reconstruct through the normal read path
        survivors = {s: v for s, v in shards.items() if s != 0}
        out = ec_util.decode_concat(sinfo, codec, survivors)
        assert bytes(out[:len(payload)]) == payload

    def test_compress_roundtrip_and_hinfo(self):
        codec = make_codec()
        sinfo = self._sinfo(codec)
        payload = compressible(3 * sinfo.stripe_width, seed=17).tobytes()
        shards, r = ec_util.encode_fused(sinfo, codec, payload,
                                         mode="compress")
        assert r.compressed
        assert r.used_stripes < 3          # it actually shrank
        # reassemble the stored stream from the DATA shard streams and
        # inflate: byte-identical to the original payload
        k = codec.get_data_chunk_count()
        rows = np.stack(
            [np.asarray(shards[codec.chunk_index(i)]).reshape(
                r.used_stripes, sinfo.chunk_size) for i in range(k)],
            axis=1)                        # back to [S, k, chunk]
        flat = np.ascontiguousarray(
            rows).reshape(-1)[:r.comp_len].tobytes()
        out = ft.bitplane_decompress(flat, r.padded_len)
        assert out[:len(payload)] == payload
        # hinfo accepts the device crcs wholesale and records comp_info
        h = ec_util.HashInfo(codec.get_chunk_count())
        h.set_device_hashes(
            r.shard_crcs, r.used_stripes * sinfo.chunk_size,
            comp_info={"alg": ft.COMP_ALG,
                       "orig_chunk_size":
                           sinfo.aligned_logical_offset_to_chunk_offset(
                               len(payload)),
                       "comp_len": r.comp_len,
                       "padded_len": r.padded_len})
        for i in range(codec.get_chunk_count()):
            idx = codec.chunk_index(i)
            assert h.get_chunk_hash(idx) == \
                zlib.crc32(bytes(shards[idx])) & 0xFFFFFFFF
        assert h.get_total_logical_size(sinfo) == len(payload)
        # the xattr round-trips losslessly
        h2 = ec_util.HashInfo.from_dict(h.to_dict())
        assert h2.comp_info == h.comp_info
        assert h2.get_total_chunk_size() == h.get_total_chunk_size()

    def test_dispatcher_path_matches_direct(self):
        codec = make_codec()
        sinfo = self._sinfo(codec)
        payload = compressible(2 * sinfo.stripe_width, seed=19).tobytes()
        d = TpuDispatcher(max_batch=4, max_delay=0.01)
        try:
            assert d.fused_supported(codec)
            for mode in ("store", "compress"):
                direct, r1 = ec_util.encode_fused(sinfo, codec, payload,
                                                  mode=mode)
                via, r2 = ec_util.encode_fused(sinfo, codec, payload,
                                               mode=mode, dispatcher=d)
                assert r1.compressed == r2.compressed
                assert list(r1.shard_crcs) == list(r2.shard_crcs)
                for shard in direct:
                    assert np.array_equal(direct[shard], via[shard]), \
                        (mode, shard)
            assert d.fused_stats["dispatches"] == 2
            assert d.fused_stats["compressed"] == 1
            assert "fused" in d.dispatch_status()
        finally:
            d.shutdown()

    def test_fused_trace_is_one_h2d_one_program_one_d2h(self):
        """The fused path's whole contract, evidenced by trace spans:
        a traced whole-object fused write shows exactly ONE staged
        h2d, ONE device program, ONE d2h — and zero host compress/
        hash/crc spans, because all of that work happened inside the
        one program."""
        from ceph_tpu.common.tracer import SpanCollector
        codec = make_codec()
        sinfo = self._sinfo(codec)
        payload = compressible(2 * sinfo.stripe_width, seed=23).tobytes()
        tracer = SpanCollector()
        tracer.enabled = True
        d = TpuDispatcher(max_batch=4, max_delay=0.01, tracer=tracer)
        try:
            root = tracer.start_trace("osd_op")
            _, r = ec_util.encode_fused(sinfo, codec, payload,
                                        mode="compress", dispatcher=d,
                                        trace=root)
            root.finish()
            assert r.compressed
        finally:
            d.shutdown()
        names = [s["name"] for s in tracer.dump()]
        assert names.count("tpu_device") == 1
        for leg in ("h2d", "compute", "d2h"):
            assert names.count(leg) == 1, (leg, names)
        banned = ("compress", "crc", "hash", "digest")
        assert not [n for n in names
                    if any(b in n.lower() for b in banned)], names

    def test_resident_adoption_carries_digests(self):
        from ceph_tpu.osd.hbm_tier import HbmChunkTier
        codec = make_codec()
        sinfo = self._sinfo(codec)
        tier = HbmChunkTier(capacity_objects=4)
        payload = compressible(2 * sinfo.stripe_width, seed=23).tobytes()
        shards, r = ec_util.encode_fused(
            sinfo, codec, payload, mode="store",
            resident=(tier, ("1.0", "obj")))
        row = tier.shard_digests(("1.0", "obj"))
        assert row is not None
        assert [int(c) for c in row] == list(r.shard_crcs)
        assert tier.stats()["digested"] == 1
        assert tier.shard_digests(("1.0", "missing")) is None


FAST = {"osd_heartbeat_interval": 0.1, "osd_heartbeat_grace": 0.6,
        "mon_osd_down_out_interval": 1.0, "paxos_propose_interval": 0.02,
        "osd_fused_compression_mode": "bitplane"}

EC_PROFILE = {"plugin": "jax_tpu", "technique": "reed_sol_van",
              "k": "2", "m": "1"}


@pytest.fixture(scope="module")
def ctx():
    cluster = MiniCluster(num_mons=1, num_osds=3,
                          conf_overrides=FAST).start()
    client = cluster.client()
    cluster.create_ec_pool(client, "fusedec", dict(EC_PROFILE), pg_num=4)
    ioctx = client.open_ioctx("fusedec")
    yield cluster, client, ioctx
    cluster.stop()


def primary_pg(cluster, client, pool_name, oid):
    m = client.osdmap
    pool_id = client.pool_id(pool_name)
    pgid = m.pools[pool_id].raw_pg_to_pg(m.object_to_pg(pool_id, oid))
    _, _, _, primary = m.pg_to_up_acting_osds(pgid)
    return cluster.osds[primary].pgs[pgid], pgid


class TestFusedClusterPath:
    """The fused transform wired through the production write path:
    daemon conf -> ec_backend -> ec_transaction -> dispatcher."""

    def test_compressed_write_reads_back(self, ctx):
        cluster, client, ioctx = ctx
        # multiple stripes: the compressed container frees WHOLE
        # stripes (a 1-stripe object can't shrink below its stripe)
        payload = compressible(4 * 8192, seed=29).tobytes()
        ioctx.write_full("cobj", payload)
        assert ioctx.read("cobj") == payload
        # the shards on disk hold the COMPRESSED container (< raw k-th)
        pg, pgid = primary_pg(cluster, client, "fusedec", "cobj")
        cid = pg.cid_of_shard(pg.my_shard())
        st = pg.store.stat(cid, "cobj")
        assert st is not None and 0 < st["size"] < len(payload) // 2
        h = pg.backend.get_hinfo("cobj")
        assert h.comp_info is not None
        assert h.comp_info["alg"] == ft.COMP_ALG

    def test_incompressible_write_stored_raw(self, ctx):
        cluster, client, ioctx = ctx
        payload = incompressible(8192, seed=31).tobytes()
        ioctx.write_full("robj", payload)
        assert ioctx.read("robj") == payload
        pg, _ = primary_pg(cluster, client, "fusedec", "robj")
        st = pg.store.stat(pg.cid_of_shard(pg.my_shard()), "robj")
        assert st is not None and st["size"] == len(payload) // 2
        assert pg.backend.get_hinfo("robj").comp_info is None

    def test_partial_overwrite_of_compressed_object_rmw(self, ctx):
        cluster, client, ioctx = ctx
        payload = bytearray(compressible(8192, seed=37).tobytes())
        ioctx.write_full("mobj", bytes(payload))
        pg, _ = primary_pg(cluster, client, "fusedec", "mobj")
        assert pg.backend.get_hinfo("mobj").comp_info is not None
        patch = incompressible(100, seed=41).tobytes()
        ioctx.write("mobj", patch, offset=1234)   # unaligned overwrite
        payload[1234:1334] = patch
        assert ioctx.read("mobj") == bytes(payload)

    def test_deep_scrub_consumes_device_digest(self, ctx, monkeypatch):
        """The primary's resident fused-written object is inventoried
        from the device digest: ZERO host hash calls, and the digest
        matches the write-time hinfo crc so deep scrub runs clean."""
        from ceph_tpu.osd import pg as pg_mod

        cluster, client, ioctx = ctx
        payload = compressible(8192, seed=43).tobytes()
        ioctx.write_full("sobj", payload)
        pg, pgid = primary_pg(cluster, client, "fusedec", "sobj")
        tier = pg.daemon.hbm_tier
        assert tier is not None
        # pipeline adoption is async: wait for the digests to land
        assert wait_until(
            lambda: tier.shard_digests((str(pgid), "sobj")) is not None,
            10), "fused write never adopted into the HBM tier"
        calls = []
        real = pg_mod.host_crc32
        monkeypatch.setattr(pg_mod, "host_crc32",
                            lambda data: calls.append(1) or real(data))
        inv = pg.__class__._scrub_inventory(pg, pg.my_shard())
        assert "sobj" in inv
        assert not calls, "resident digest path host-hashed anyway"
        h = pg.backend.get_hinfo("sobj")
        assert inv["sobj"][1] == h.get_chunk_hash(pg.my_shard())
        monkeypatch.undo()
        # and the full deep scrub agrees end to end
        osd = cluster.osds[pg.whoami]
        assert osd.scrub_pg(pgid, deep=True)
        assert wait_until(
            lambda: pg.scrub_stats.get("state") in ("clean",
                                                    "inconsistent")
            and pg.scrub_stats.get("deep"), 15), pg.scrub_stats
        assert pg.scrub_stats["state"] == "clean", pg.scrub_stats
        assert pg.scrub_stats["errors"] == 0

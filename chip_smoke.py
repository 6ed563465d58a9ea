#!/usr/bin/env python3
"""Smoke test of the storage path on TPU, through its normal entry points.

The deployment is BASELINE.json's Reed-Solomon Vandermonde k=8 m=3
profile on `plugin=jax_tpu`, as an erasure-coded pool driven with the
defaults of upstream `rados bench` (4 MiB objects, 16 ops in flight:
src/common/obj_bencher.cc).

One chip (no arguments):
  device    jax.devices()[0] must be a TPU; a CPU or unknown device fails
  boot      1 mon, 12 OSDs and the mgr through tools/vstart.py boot();
            the EC profile and a pg_num 64 pool through mon commands
  write     256 objects of 4 MiB (1 GiB), 16 in flight, content from --seed
  read      every object read back and compared byte for byte; the
            shards of 8 objects pulled from the OSD stores and their
            parity checked against ops/gf_ref.py
  degraded  3 OSDs holding a data shard of each of those objects
            stopped, each object read degraded (rebuilt by a device
            decode, or by the codec's host XOR shortcut when it lost one
            data shard only) and compared, OSDs restarted
  crush     a 1000-OSD straw2 map (40 hosts x 25, chooseleaf indep 11
            over host) remapped for 32768 PGs by crush/batched.py and
            512 seeds compared with crush/mapper_ref.py
  ecbench   tools/erasure_code_benchmark.py run as
            ceph_erasure_code_benchmark runs (encode --batch 16 -i 20;
            decode -e 3 -E exhaustive), parity checked with gf_ref

Every phase also proves the device did the work: every OSD's home
device is a TPU, every dispatcher's l_tpu_* counters moved, and the
profiler saw fused_transform.program, xor_mm.matrix_encode and
crush.indep run.

--chips 4 runs the multi-chip path and only it: 12 OSDs round-robin
over 4 chips (each chip's dispatchers busy), the same write / read /
degraded read at 64 objects, mesh_do_rule on a 4-chip mesh against
single-device batched_do_rule, and mesh decode_sharded against
single-device decode.

Wall times printed on the way are smoke timings (cold = first pass,
compiles included; warm = the rest), not benchmark numbers. The last
line of stdout is {"ok": true, "device": {...}}; any failure exits
non-zero without it.

    python3 chip_smoke.py [--chips 4] [--seed N]
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

K, M = 8, 3
PROFILE = {"plugin": "jax_tpu", "technique": "reed_sol_van",
           "k": str(K), "m": str(M), "crush-failure-domain": "osd"}
POOL = "smoke-ec"
MiB = 1 << 20
OSDS, PG_NUM = 12, 64
OBJECT_SIZE, INFLIGHT = 4 * MiB, 16      # rados bench defaults
OBJECTS = {1: 256, 4: 64}                # objects written, by chip count
SAMPLE = 8               # objects whose shards are checked, read degraded
CRUSH_HOSTS, CRUSH_PER_HOST, CRUSH_PGS = 40, 25, 32768
CRUSH_REF_SEEDS = 512
ECBENCH_SIZE = MiB

# a stopped OSD must not be marked out and backfilled around during the
# degraded-read drill (upstream's default down-out interval is 600 s),
# and a busy host must not miss heartbeats into false markdowns
# (upstream osd_heartbeat_grace is 20 s); stopped OSDs are marked down
# explicitly with `osd down`
CLUSTER_CONF = {"mon_osd_down_out_interval": 600.0,
                "osd_heartbeat_interval": 1.0,
                "osd_heartbeat_grace": 20.0}


class SmokeFailure(RuntimeError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print("chip_smoke: %s" % msg, flush=True)


def require_tpu(count: int) -> list:
    """The first `count` JAX devices, which must all be TPUs."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SmokeFailure("no TPU found: JAX's default backend is %r"
                           % platform)
    check(len(devices) >= count,
          "%d TPU chips wanted, %d found" % (count, len(devices)))
    devices = devices[:count]
    check(all(d.platform == "tpu" for d in devices),
          "mixed device platforms: %s" % devices)
    return devices


class CompileEvents:
    """Persistent compile cache hits and misses, from JAX's monitoring
    events (a warm cache shows hits and no new misses)."""

    def __init__(self):
        import jax
        self.counts = {"hits": 0, "misses": 0}
        names = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}

        def listener(event, **kw):
            key = names.get(event)
            if key is not None:
                self.counts[key] += 1
        jax.monitoring.register_event_listener(listener)


def peak_hbm(devices) -> dict:
    out = {}
    for d in devices:
        stats = d.memory_stats() or {}
        out[str(d.id)] = stats.get("peak_bytes_in_use")
    return out


def obj_bytes(seed: int, i: int, size: int) -> bytes:
    return np.random.default_rng([seed, i]).bytes(size)


def oid_of(i: int) -> str:
    return "benchmark_data_smoke_object%d" % i


# -- cluster ------------------------------------------------------------

def boot_cluster(osds: int):
    from ceph_tpu.client import RadosClient
    from ceph_tpu.common import Context
    from ceph_tpu.tools import vstart
    cluster = vstart.boot(mons=1, osds=osds, mgr=True,
                          overrides=dict(CLUSTER_CONF), out=sys.stdout)
    client = RadosClient(cluster.monmap,
                         Context(dict(CLUSTER_CONF), name="client.smoke"))
    client.connect()
    return cluster, client


def create_pool(client, pg_num: int) -> int:
    res, outs, _ = client.mon_command({
        "prefix": "osd erasure-code-profile set", "name": "smoke-rs-k8m3",
        "profile": dict(PROFILE)})
    check(res == 0, "profile set: %s" % outs)
    res, outs, pool_id = client.mon_command({
        "prefix": "osd pool create", "pool": POOL, "pool_type": "erasure",
        "erasure_code_profile": "smoke-rs-k8m3", "pg_num": pg_num})
    check(res == 0, "pool create: %s" % outs)
    deadline = time.monotonic() + 60
    while not (client.osdmap is not None
               and any(p.name == POOL for p in client.osdmap.pools.values())):
        check(time.monotonic() < deadline, "pool never reached the client")
        client.mon_client.renew_subs()
        time.sleep(0.05)
    return client.pool_id(POOL)


def wait_clean(cluster, timeout: float = 180.0) -> None:
    """Every PG peered, nothing missing anywhere."""
    def dirty():
        return [(osd_id, str(pg.pgid), pg.peer_state,
                 sorted(getattr(pg, "_peer_wait", ())))
                for osd_id, osd in cluster.osds.items()
                for pg in list(osd.pgs.values())
                if pg.peer_state not in ("active", "replica")
                or pg.missing or pg.peer_missing]
    deadline = time.monotonic() + timeout
    next_note = time.monotonic() + 15
    while dirty():
        check(time.monotonic() < deadline,
              "cluster never went clean: %s" % dirty()[:5])
        if time.monotonic() > next_note:
            say("waiting for clean: %d PG copies not clean, e.g. %s"
                % (len(dirty()), dirty()[:3]))
            next_note = time.monotonic() + 15
        time.sleep(0.1)


def write_objects(ioctx, seed, ids, size, inflight) -> float:
    def one(i):
        ioctx.write_full(oid_of(i), obj_bytes(seed, i, size), timeout=600)
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=inflight) as pool:
        list(pool.map(one, ids))
    return time.perf_counter() - t0


def read_objects(ioctx, seed, ids, size, inflight) -> float:
    def one(i):
        got = ioctx.read(oid_of(i), timeout=600)
        if got != obj_bytes(seed, i, size):
            raise SmokeFailure("object %d read back wrong (%d bytes)"
                               % (i, len(got)))
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=inflight) as pool:
        list(pool.map(one, ids))
    return time.perf_counter() - t0


def acting_of(client, pool_id: int, oid: str):
    m = client.osdmap
    pgid = m.pools[pool_id].raw_pg_to_pg(m.object_to_pg(pool_id, oid))
    _up, _upp, acting, primary = m.pg_to_up_acting_osds(pgid)
    return pgid, list(acting), primary


def check_shards(cluster, client, pool_id, seed, i, size, coding) -> None:
    """The object's shards as the OSD stores hold them: data shards are
    the object striped chunk by chunk, parity shards equal gf_ref's
    encode of the data shards."""
    from ceph_tpu.ops import gf_ref
    oid = oid_of(i)
    pgid, acting, _ = acting_of(client, pool_id, oid)
    check(len(acting) == K + M, "pg %s acting %s" % (pgid, acting))
    shards = []
    for shard, osd_id in enumerate(acting):
        pg = cluster.osds[osd_id].pgs[pgid]
        shards.append(np.frombuffer(
            pg.store.read(pg.cid_of_shard(shard), oid), dtype=np.uint8))
    lens = {len(s) for s in shards}
    check(len(lens) == 1, "object %d shard lengths differ: %s" % (i, lens))
    check(lens.pop() * K == size, "object %d shard length" % i)
    chunk = client.osdmap.pools[pool_id].stripe_width // K
    data = np.frombuffer(obj_bytes(seed, i, size), dtype=np.uint8)
    striped = data.reshape(-1, K, chunk).transpose(1, 0, 2).reshape(K, -1)
    check(np.array_equal(np.stack(shards[:K]), striped),
          "object %d data shards differ from the object" % i)
    parity = gf_ref.matrix_encode_ref(coding, striped, 8)
    check(np.array_equal(np.stack(shards[K:]), parity),
          "object %d parity differs from gf_ref" % i)


def dispatcher_counters(osd) -> dict:
    perf = osd.tpu_dispatcher.perf
    return {n: perf.get(n) for n in ("l_tpu_ops", "l_tpu_dispatches",
                                     "l_tpu_enc_bytes", "l_tpu_dec_bytes",
                                     "l_tpu_fused_dispatches")}


def profiler_count(kernel: str) -> int:
    from ceph_tpu.common.profiler import PROFILER
    k = PROFILER.dump()["kernels"].get(kernel, {})
    return k.get("compiles", 0) + k.get("cache_hits", 0)


def decoded_bytes(cluster) -> int:
    return sum(dispatcher_counters(o)["l_tpu_dec_bytes"]
               for o in cluster.osds.values())


def xor_shortcuts(cluster) -> int:
    """Single-erasure XOR rebuilds done by the codecs of every PG."""
    codecs = {id(pg.backend.codec): pg.backend.codec
              for osd in cluster.osds.values()
              for pg in list(osd.pgs.values())
              if hasattr(pg.backend, "codec")}
    return sum(c.xor_fast_hits for c in codecs.values())


def pick_victims(client, pool_id, sample, count: int = 3) -> list:
    """`count` OSDs that together hold a data shard of every sampled
    object, so each of their reads has to rebuild. Greedy by objects
    covered; among equals, an OSD that is no sampled object's primary
    (its PGs then need no new primary before the read)."""
    holders, primaries = {}, set()
    for i in sample:
        _, acting, primary = acting_of(client, pool_id, oid_of(i))
        holders[i] = set(acting[:K])
        primaries.add(primary)
    victims, uncovered = [], set(sample)
    candidates = sorted(set().union(*holders.values()))
    for _ in range(count):
        best = max(candidates, key=lambda o: (
            sum(o in holders[i] for i in uncovered), o not in primaries))
        victims.append(best)
        candidates.remove(best)
        uncovered -= {i for i in uncovered if best in holders[i]}
    check(not uncovered, "no %d OSDs hold a data shard of every sampled "
          "object (objects %s left)" % (count, sorted(uncovered)))
    return victims


def stop_osds(cluster, client, victims) -> dict:
    stores = {o: cluster.stop_osd(o) for o in victims}
    for o in victims:
        res, outs, _ = client.mon_command({"prefix": "osd down", "id": o})
        check(res == 0, "osd down %d: %s" % (o, outs))
    deadline = time.monotonic() + 60
    while any(client.osdmap.is_up(o) for o in victims):
        check(time.monotonic() < deadline, "stopped OSDs never went down")
        client.mon_client.renew_subs()
        time.sleep(0.05)
    return stores


def restart_osds(cluster, stores: dict) -> None:
    for o, store in stores.items():
        cluster.start_osd(o, store=store)
    cluster.wait_osds_up(timeout=120)
    say("degraded: osds %s back up" % sorted(stores))
    wait_clean(cluster)


def storage_phases(args, devices, every_osd: bool = True) -> dict:
    """Boot, write, read back, verify shards, degraded read. Returns
    the per-OSD device evidence; with every_osd, each OSD's dispatcher
    must have done device work (the callers on several chips check
    per chip instead)."""
    from ceph_tpu import registry
    from ceph_tpu.parallel.placement import device_label
    coding = registry.factory("jax_tpu", {
        k: v for k, v in PROFILE.items()
        if k != "crush-failure-domain"}).coding
    size = OBJECT_SIZE
    n_objects = OBJECTS[len(devices)]
    t0 = time.perf_counter()
    cluster, client = boot_cluster(OSDS)
    try:
        pool_id = create_pool(client, PG_NUM)
        ioctx = client.open_ioctx(POOL)
        wait_clean(cluster)
        say("boot: %d mon, %d osds, mgr, pool pg_num %d in %.3f s"
            % (len(cluster.mons), len(cluster.osds), PG_NUM,
               time.perf_counter() - t0))
        for osd in cluster.osds.values():
            check(osd.home_device is not None
                  and osd.home_device.platform == "tpu",
                  "osd.%d home device %r" % (osd.whoami, osd.home_device))

        ids = list(range(n_objects))
        cold = write_objects(ioctx, args.seed, ids[:1], size, 1)
        warm = write_objects(ioctx, args.seed, ids[1:], size, INFLIGHT)
        say("write: %d x %d B objects, %d in flight; cold %.3f s, warm "
            "%.3f s (%.1f MB/s smoke timing); bytes written %d; peak HBM %s"
            % (n_objects, size, INFLIGHT, cold, warm,
               (n_objects - 1) * size / warm / 1e6, n_objects * size,
               peak_hbm(devices)))

        # the device evidence is taken before the drill restarts OSDs
        # (a restarted daemon starts its counters from zero)
        evidence = {o.whoami: (device_label(o.home_device),
                               dispatcher_counters(o))
                    for o in cluster.osds.values()}
        for osd_id, (_dev, counters) in sorted(evidence.items()):
            check(not every_osd or counters["l_tpu_dispatches"] > 0
                  and counters["l_tpu_enc_bytes"] > 0,
                  "osd.%d dispatcher idle: %s" % (osd_id, counters))

        cold = read_objects(ioctx, args.seed, ids[:1], size, 1)
        warm = read_objects(ioctx, args.seed, ids[1:], size, INFLIGHT)
        say("read: %d objects byte-exact; cold %.3f s, warm %.3f s "
            "(%.1f MB/s smoke timing)"
            % (n_objects, cold, warm, (n_objects - 1) * size / warm / 1e6))

        sample = ids[::n_objects // SAMPLE]
        for i in sample:
            check_shards(cluster, client, pool_id, args.seed, i, size,
                         coding)
        say("shards: %d objects' data shards striped right, parity equal "
            "to gf_ref" % len(sample))

        victims = pick_victims(client, pool_id, sample)
        stores = stop_osds(cluster, client, victims)
        # one object at a time, so each read's rebuild is seen: a
        # device decode, or (one data shard lost, the XOR parity alive)
        # the codec's single-erasure XOR shortcut on the host
        times, decoded, on_device, by_xor = [], 0, 0, 0
        for i in sample:
            dec0, xor0 = decoded_bytes(cluster), xor_shortcuts(cluster)
            times.append(read_objects(ioctx, args.seed, [i], size, 1))
            grew = decoded_bytes(cluster) - dec0
            xor = xor_shortcuts(cluster) - xor0
            check(grew > 0 or xor > 0,
                  "degraded read of object %d rebuilt nothing" % i)
            decoded += grew
            on_device += grew > 0
            by_xor += grew == 0
        check(on_device > 0, "no degraded read decoded on the device")
        say("degraded: osds %s stopped; each of %d objects lost a data "
            "shard and read back byte-exact, %d decoded on the device "
            "(%d B), %d by the host XOR shortcut; cold %.3f s, warm %.3f s"
            % (victims, len(sample), on_device, decoded, by_xor, times[0],
               sum(times[1:])))
        t1 = time.perf_counter()
        restart_osds(cluster, stores)
        say("degraded: osds %s restarted, cluster clean in %.3f s; peak "
            "HBM %s" % (victims, time.perf_counter() - t1,
                        peak_hbm(devices)))
    finally:
        t1 = time.perf_counter()
        client.shutdown()
        cluster.shutdown()
        say("cluster stopped in %.3f s" % (time.perf_counter() - t1))
    return evidence


# -- CRUSH --------------------------------------------------------------

def crush_map(seed: int, hosts: int, per_host: int):
    """root -> straw2 hosts -> straw2 OSDs with mixed device weights, and
    an EC rule: take root, chooseleaf indep over host, emit."""
    from ceph_tpu.crush import map as cmap_mod
    from ceph_tpu.crush.map import CrushMap, Rule
    rng = np.random.default_rng([seed, 1])
    weights = rng.integers(0x8000, 4 * 0x10000, size=hosts * per_host)
    m = CrushMap()
    m.type_names = {"osd": 0, "host": 1, "root": 2}
    host_ids, host_w = [], []
    for h in range(hosts):
        items = [h * per_host + i for i in range(per_host)]
        w = [int(weights[i]) for i in items]
        host_ids.append(m.add_bucket("straw2", 1, items, w, id=-2 - h))
        host_w.append(sum(w))
    m.add_bucket("straw2", 2, host_ids, host_w, id=-1, name="default")
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSELEAF_INDEP, K + M, 1),
                           (cmap_mod.RULE_EMIT,)]))
    # a few OSDs out and a few reweighted: the is_out path runs too
    reweight = np.full(hosts * per_host, 0x10000, dtype=np.int64)
    picks = rng.choice(hosts * per_host, size=20, replace=False)
    reweight[picks[:10]] = 0
    reweight[picks[10:]] = 0x8000
    return m, reweight


def crush_phase(args) -> None:
    from ceph_tpu.crush.batched import batched_do_rule
    from ceph_tpu.crush.mapper_ref import crush_do_rule
    from ceph_tpu.crush.map import CRUSH_ITEM_NONE
    cmap, reweight = crush_map(args.seed, CRUSH_HOSTS, CRUSH_PER_HOST)
    xs = np.arange(CRUSH_PGS, dtype=np.int64)
    t0 = time.perf_counter()
    out = np.asarray(batched_do_rule(cmap, 0, xs, K + M, reweight))
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = np.asarray(batched_do_rule(cmap, 0, xs, K + M, reweight))
    warm = time.perf_counter() - t0
    check(out.shape == (len(xs), K + M), "crush out shape %s" % (out.shape,))
    check(np.array_equal(out, again), "crush sweep not deterministic")
    rng = np.random.default_rng([args.seed, 2])
    seeds = rng.choice(len(xs), size=min(CRUSH_REF_SEEDS, len(xs)),
                       replace=False)
    for x in seeds:
        ref = crush_do_rule(cmap, 0, int(xs[x]), K + M, reweight)
        ref = list(ref) + [CRUSH_ITEM_NONE] * (K + M - len(ref))
        check(list(out[x]) == ref,
              "crush seed %d: device %s != reference %s"
              % (x, list(out[x]), ref))
    say("crush: %d OSDs (%d hosts x %d), %d PGs, chooseleaf indep %d; "
        "cold %.3f s, warm %.3f s (%.0f PGs/s smoke timing); %d seeds "
        "equal to mapper_ref"
        % (len(reweight), CRUSH_HOSTS, CRUSH_PER_HOST, len(xs),
           K + M, cold, warm, len(xs) / warm, len(seeds)))


# -- EC benchmark tool --------------------------------------------------

def run_tool(argv) -> tuple:
    from ceph_tpu.tools import erasure_code_benchmark
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = erasure_code_benchmark.main(argv)
    return rc, buf.getvalue()


def ecbench_phase(args) -> None:
    from ceph_tpu import registry
    from ceph_tpu.ops import gf_ref
    size = ECBENCH_SIZE
    base = ["-p", "jax_tpu", "-P", "technique=reed_sol_van",
            "-P", "k=%d" % K, "-P", "m=%d" % M, "-s", str(size)]
    rc, out = run_tool(base + ["--batch", "16", "-i", "20"])
    check(rc == 0, "ec benchmark encode rc %d" % rc)
    secs, kib = out.split()[-2:]
    check(int(kib) == 20 * 16 * size // 1024,
          "ec benchmark encode reported %s KiB" % kib)
    say("ecbench encode: %s s for %s KiB (smoke timing)" % (secs, kib))
    rc, out = run_tool(base + ["-w", "decode", "-e", "3", "-E",
                               "exhaustive"])
    check(rc == 0, "ec benchmark exhaustive decode rc %d" % rc)
    secs, kib = out.split()[-2:]
    say("ecbench decode: all C(%d,3) erasure patterns recovered and "
        "verified in %s s (smoke timing)" % (K + M, secs))
    # the same codec and batch shape, checked against the reference
    codec = registry.factory("jax_tpu", {"technique": "reed_sol_van",
                                         "k": str(K), "m": str(M)})
    rng = np.random.default_rng([args.seed, 3])
    chunk = codec.get_chunk_size(size)
    data = rng.integers(0, 256, size=(16, K, chunk), dtype=np.uint8)
    parity = np.asarray(codec.encode_batch(data))
    for b in (0, 15):
        check(np.array_equal(parity[b], gf_ref.matrix_encode_ref(
            codec.coding, data[b], 8)), "encode_batch != gf_ref")
    say("ecbench: device encode of the benchmark's batch shape equal to "
        "gf_ref")


# -- four chips ---------------------------------------------------------

def multichip_phases(args, devices) -> None:
    from ceph_tpu import registry
    from ceph_tpu.crush.batched import (batched_do_rule, make_batch_mesh,
                                        mesh_do_rule)
    from ceph_tpu.parallel import mesh as pmesh
    from ceph_tpu.parallel.placement import PLACEMENT

    evidence = storage_phases(args, devices, every_osd=False)
    placed = PLACEMENT.assignments()
    labels = {row["device"] for row in placed["osds"].values()}
    check(len(labels) == len(devices) and all(
        lbl.startswith("tpu:") for lbl in labels),
        "OSDs placed on %s" % sorted(labels))
    per_dev = {}
    for dev, counters in evidence.values():
        per_dev[dev] = per_dev.get(dev, 0) + counters["l_tpu_dispatches"]
    check(len(per_dev) == len(devices) and all(per_dev.values()),
          "dispatches per device %s" % per_dev)
    say("placement: %d OSDs over %s; dispatches per chip %s"
        % (len(evidence), sorted(labels), per_dev))

    cmap, reweight = crush_map(args.seed, CRUSH_HOSTS, CRUSH_PER_HOST)
    xs = np.arange(CRUSH_PGS, dtype=np.int64)
    single = np.asarray(batched_do_rule(cmap, 0, xs, K + M, reweight))
    mesh = make_batch_mesh(len(devices))
    t0 = time.perf_counter()
    sharded = np.asarray(mesh_do_rule(cmap, 0, xs, K + M, reweight,
                                      mesh=mesh))
    cold = time.perf_counter() - t0
    check(np.array_equal(sharded, single),
          "mesh_do_rule differs from batched_do_rule")
    say("crush mesh: %d PGs over a %d-chip mesh bit-equal to one device; "
        "cold %.3f s (smoke timing)" % (len(xs), len(devices), cold))

    codec = registry.factory("jax_tpu", {"technique": "reed_sol_van",
                                         "k": str(K), "m": str(M)})
    rng = np.random.default_rng([args.seed, 4])
    data = rng.integers(0, 256, size=(16, K, 131072), dtype=np.uint8)
    full = np.concatenate([data, np.asarray(codec.encode_batch(data))], 1)
    avail = (0, 2, 3, 5, 7, 8, 9, 10)        # data shards 1, 4, 6 lost
    chunks = np.ascontiguousarray(full[:, list(avail)])
    one = np.asarray(codec.decode_batch(avail, chunks))
    cmesh = pmesh.make_mesh(len(devices))
    got = np.asarray(pmesh.decode_sharded(codec, avail, chunks, cmesh))
    check(np.array_equal(got, one), "decode_sharded differs from decode")
    check(np.array_equal(one, full), "decode did not restore the stripe")
    say("decode mesh: [16,8,131072] decoded over a %s mesh bit-equal to "
        "one device" % (dict(cmesh.shape),))


# -- driver -------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="chip_smoke", description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4))
    p.add_argument("--seed", type=int, default=0)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from ceph_tpu.common.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    phase = "device"
    t_start = time.perf_counter()
    try:
        devices = require_tpu(args.chips)
        events = CompileEvents()
        say("device: %d x %s (%s); compile cache %s"
            % (len(devices), devices[0].device_kind, devices[0].platform,
               cache_dir))
        if args.chips == 1:
            phase = "storage"
            storage_phases(args, devices)
            phase = "crush"
            crush_phase(args)
            phase = "ecbench"
            ecbench_phase(args)
            phase = "profiler"
            counts = {k: profiler_count(k) for k in (
                "fused_transform.program", "xor_mm.matrix_encode",
                "crush.indep")}
            check(all(counts.values()), "kernels never ran: %s" % counts)
            say("profiler: kernel calls %s" % counts)
        else:
            phase = "multichip"
            multichip_phases(args, devices)
        say("compile cache: %d hits, %d misses; peak HBM %s; total %.3f s"
            % (events.counts["hits"], events.counts["misses"],
               peak_hbm(devices), time.perf_counter() - t_start))
    except Exception as e:       # any phase failing fails the run
        import traceback
        traceback.print_exc()
        print("chip_smoke: FAILED in phase %s: %s" % (phase, e),
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of a stopped cluster must not hold the exit
    os._exit(rc)

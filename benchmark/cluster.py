"""An in-process vstart cluster as a configuration file states it, for
the drivers that send it client traffic (``benchmark/drivers/``).

`Cluster` boots the monitors, OSDs and mgr with the configuration's
option overrides, places the OSDs on the chips as its
``cluster.osd_device`` says, creates its EC profile and pool, waits for
the pool's PGs, stops OSDs, compiles the coalesced codec programs the
traffic meets, reads the dispatchers' counters and the OSDs' spans, and
compares the shards the OSD stores hold with the reference's striping
and parity.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from . import reference

POOL = "bench"

#: dispatcher counters read around the window (osd/tpu_dispatch.py)
COUNTERS = ("l_tpu_ops", "l_tpu_dispatches", "l_tpu_enc_bytes",
            "l_tpu_dec_bytes", "l_tpu_fused_dispatches",
            "l_tpu_fused_bytes_in")


class Cluster:
    def __init__(self, config: dict, log):
        self.config = config
        self.log = log
        self.code = reference.Code(config["profile"])
        self.stripe_unit = int(config["pool"]["stripe_unit"])
        self.pg_num = int(config["pool"]["pg_num"])
        self.cluster = self.client = self.ioctx = None
        self.pool_id = None

    # -- boot ------------------------------------------------------------

    def boot(self) -> None:
        from ceph_tpu.client import RadosClient
        from ceph_tpu.common import Context
        from ceph_tpu.tools import vstart
        cl = self.config["cluster"]
        conf = {k: v["value"] for k, v in self.config["overrides"].items()}
        conf["osd_device_index"] = _device_index(cl["osd_device"])
        t = time.monotonic()
        self.cluster = vstart.boot(mons=cl["mons"], osds=cl["osds"],
                                   mgr=cl["mgr"], overrides=dict(conf),
                                   out=sys.stderr)
        self.client = RadosClient(self.cluster.monmap,
                                  Context(dict(conf), name="client.bench"))
        self.client.connect()
        self._create_pool()
        self.wait_active(clean=True)
        self.log("boot: %d mon, %d osds, pool pg_num %d clean in %.3f s"
                 % (cl["mons"], cl["osds"], self.pg_num,
                    time.monotonic() - t))
        chips = {}
        for o, osd in sorted(self.cluster.osds.items()):
            chips.setdefault(str(osd.home_device), []).append(o)
        self.log("placement: %s" % "; ".join(
            "%s: osds %s" % kv for kv in sorted(chips.items())))

    def _create_pool(self) -> None:
        res, outs, _ = self.client.mon_command({
            "prefix": "osd erasure-code-profile set", "name": POOL,
            "profile": dict(self.config["profile"])})
        if res != 0:
            raise RuntimeError("profile set: %s" % outs)
        res, outs, _ = self.client.mon_command({
            "prefix": "osd pool create", "pool": POOL,
            "pool_type": "erasure", "erasure_code_profile": POOL,
            "pg_num": self.pg_num})
        if res != 0:
            raise RuntimeError("pool create: %s" % outs)
        deadline = time.monotonic() + 60
        while not (self.client.osdmap is not None and any(
                p.name == POOL for p in self.client.osdmap.pools.values())):
            if time.monotonic() > deadline:
                raise RuntimeError("the pool never reached the client")
            self.client.mon_client.renew_subs()
            time.sleep(0.05)
        self.pool_id = self.client.pool_id(POOL)
        width = self.client.osdmap.pools[self.pool_id].stripe_width
        if width != self.code.k * self.stripe_unit:
            raise RuntimeError("pool stripe width %d, the configuration "
                               "states %d x %d" % (width, self.code.k,
                                                   self.stripe_unit))
        self.ioctx = self.client.open_ioctx(POOL)

    # -- state -----------------------------------------------------------

    def pgs(self):
        """(osd, pg) of every PG copy of the pool on a running OSD."""
        return [(o, pg) for o, osd in self.cluster.osds.items()
                for pg in list(osd.pgs.values())
                if pg.pgid.pool == self.pool_id]

    def wait_active(self, clean: bool, timeout: float = 180.0) -> None:
        """Every PG of the pool has an active primary (and, with clean,
        every copy is peered and nothing is missing)."""
        deadline = time.monotonic() + timeout
        while True:
            # seldom: the scan holds the interpreter lock the daemons
            # peer under
            time.sleep(0.5)
            pgs = self.pgs()
            active = {str(pg.pgid) for _, pg in pgs
                      if pg.is_primary() and pg.peer_state == "active"}
            dirty = clean and any(
                pg.peer_state not in ("active", "replica")
                or pg.missing or pg.peer_missing for _, pg in pgs)
            if len(active) == self.pg_num and not dirty:
                return
            if time.monotonic() > deadline:
                raise RuntimeError("%d of %d PGs active after %.0f s"
                                   % (len(active), self.pg_num, timeout))

    def acting(self, oid: str) -> list:
        m = self.client.osdmap
        pgid = m.pools[self.pool_id].raw_pg_to_pg(
            m.object_to_pg(self.pool_id, oid))
        return list(m.pg_to_up_acting_osds(pgid)[2])

    def stop(self, osds) -> None:
        """Stop the OSDs, mark them down (not out) and wait until every
        PG of the pool is active again."""
        for o in osds:
            self.cluster.stop_osd(o)
        for o in osds:
            res, outs, _ = self.client.mon_command({"prefix": "osd down",
                                                    "id": o})
            if res != 0:
                raise RuntimeError("osd down %d: %s" % (o, outs))
        deadline = time.monotonic() + 60
        while any(self.client.osdmap.is_up(o) for o in osds):
            if time.monotonic() > deadline:
                raise RuntimeError("stopped OSDs never went down")
            self.client.mon_client.renew_subs()
            time.sleep(0.05)
        self.wait_active(clean=False)

    # -- warm-up -----------------------------------------------------------

    def warm_coalesced(self, sizes, seen: dict) -> None:
        """Compile (or load from the cache) the codec program for every
        batch an OSD's dispatcher can form by coalescing 1 .. max_batch
        ops of one object each, for each object size in `sizes`, on each
        chip that holds an OSD: ops coalesce as they happen to arrive, so
        the warm-up ops alone need not meet every batch the window meets.
        `seen` is the counters' change over the warm-up ops: encodes that
        did not ride the fused write program ask for the encode programs,
        device decodes for the decode programs."""
        def grew(name):
            return sum(row.get(name, 0) for row in seen.values()
                       if isinstance(row, dict))
        kinds = []
        if grew("l_tpu_enc_bytes") > grew("l_tpu_fused_bytes_in"):
            kinds.append("encode")
        if grew("l_tpu_dec_bytes"):
            kinds.append("decode")
        osds = [o for o in self.cluster.osds.values()
                if o.tpu_dispatcher is not None]
        if not kinds or not osds:
            return
        import jax

        from ceph_tpu import registry
        profile = {k: v for k, v in self.config["profile"].items()
                   if not k.startswith("crush-") and k != "plugin"}
        codec = registry.factory(self.config["profile"]["plugin"], profile)
        batches = max(o.tpu_dispatcher.max_batch for o in osds)
        devices = {str(o.home_device): o.home_device for o in osds}
        k = self.code.k
        t = time.monotonic()
        for dev in devices.values():
            for size in sorted(set(sizes)):
                stripes = -(-size // (k * self.stripe_unit))
                for b in range(1, batches + 1):
                    x = jax.device_put(np.zeros(
                        (stripes * b, k, self.stripe_unit), np.uint8), dev)
                    for kind in kinds:
                        out = codec.encode_batch(x) if kind == "encode" \
                            else codec.decode_batch(tuple(range(1, k + 1)), x)
                        jax.block_until_ready(out)
        self.log("warm-up: %s programs for batches 1..%d on %d chip(s) "
                 "in %.3f s" % ("+".join(kinds), batches, len(devices),
                                time.monotonic() - t))

    # -- readings ------------------------------------------------------------

    def counters(self) -> dict:
        """Every running OSD's device dispatcher counters, and the
        single-erasure XOR rebuilds the codecs did on the host."""
        out = {"osd.%d" % o: {n: osd.tpu_dispatcher.perf.get(n)
                              for n in COUNTERS}
               for o, osd in self.cluster.osds.items()
               if osd.tpu_dispatcher is not None}
        codecs = {id(pg.backend.codec): pg.backend.codec
                  for _, pg in self.pgs() if hasattr(pg.backend, "codec")}
        out["xor_rebuilds"] = sum(getattr(c, "xor_fast_hits", 0)
                                  for c in codecs.values())
        return out

    def spans(self) -> list:
        """The op spans every running OSD collected."""
        out = []
        for osd in self.cluster.osds.values():
            out.extend(osd.tracer.dump())
        return out

    # -- checks ----------------------------------------------------------------

    def shards_match(self, oid: str, content: bytes, acting=None) -> bool:
        """The object's shards on every running OSD of its acting set
        (`acting`, or as the map has it now) equal the reference's
        striping and parity, and at least k of them are held."""
        want = reference.shards(content, self.code, self.stripe_unit)
        m = self.client.osdmap
        pgid = m.pools[self.pool_id].raw_pg_to_pg(
            m.object_to_pg(self.pool_id, oid))
        held = 0
        for shard, osd_id in enumerate(acting or self.acting(oid)):
            osd = self.cluster.osds.get(osd_id)
            if osd is None:
                continue                  # a stopped OSD
            try:
                pg = osd.pgs[pgid]
                got = np.frombuffer(
                    pg.store.read(pg.cid_of_shard(shard), oid), dtype=np.uint8)
            except KeyError:
                return False              # the shard is not there
            if not np.array_equal(got, want[shard]):
                return False
            held += 1
        return held >= self.code.k

    def close(self) -> None:
        if self.client is not None:
            self.client.shutdown()
        if self.cluster is not None:
            self.cluster.shutdown()


def _device_index(osd_device) -> int:
    """``osd_device_index`` for the configuration's ``osd_device``:
    "round_robin" (osd id modulo the chips, the option's default -1) or
    one chip's index for every OSD."""
    if osd_device == "round_robin":
        return -1
    if isinstance(osd_device, int) and osd_device >= 0:
        return osd_device
    raise ValueError("cluster.osd_device %r: \"round_robin\" or a chip index"
                     % (osd_device,))

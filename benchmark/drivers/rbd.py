"""Block IO on one RBD image whose data lives in the configuration's
erasure-coded pool (``rbd create --data-pool``), as fio's
``ioengine=rbd`` sends it (fio ``examples/rbd.fio``): fixed-size IO at
block-aligned offsets, a closed loop of ``in_flight`` ops on one image
handle.

The configuration states the ``image`` (name, ``order``, features),
its ``image_size`` and the replicated ``metadata_pool`` that holds its
header and object map; the EC pool is `benchmark.cluster`'s. The
traffic file states:

- ``io_size``; ``mix``: entries ``{"op": "read" | "write", "share": s}``
  (`benchmark.generator.kinds`);
- ``arrival`` (`benchmark.generator.drive`), ``keys`` (how ops pick
  among the image's ``io_size`` blocks, `benchmark.generator.keys`);
- ``serialize_overlap``: an op whose block has an op in flight waits
  for it (fio's option), so every read has one right answer;
- ``warmup_ops``, ``distinct_payloads``, ``check_objects``,
  ``check_blocks``, ``grace_s``.

Set-up boots the cluster, creates the metadata pool and the image,
writes the whole image in order in whole-object writes at the traffic's
concurrency (fio's precondition pass), runs the warm-up ops and
compiles the coalesced codec programs they show the window will meet.
A host model holds the image's bytes: a read succeeds only if it
returns the model's, and a write updates the model once acknowledged.
The checks read back a sample of the blocks the window wrote and
compare the shards of the most-written data objects with the
reference's striping and parity.
"""

from __future__ import annotations

import inspect
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .. import generator
from ..cluster import POOL, Cluster
from ..generator import Op

KINDS = ("read", "write")
#: OSD counters of the EC read-modify-write (osd/ec_backend.py)
OSD_COUNTERS = ("l_osd_ec_rmw_ops", "l_osd_ec_rmw_read_bytes")
#: the image handle's counters (client/rbd.py)
LIBRBD_COUNTERS = ("l_librbd_rd", "l_librbd_wr", "l_librbd_inflight_s")
#: the tags of the two phases' write payloads
WARM, WINDOW = 2, 3


class Load:
    def __init__(self, config: dict, traffic: dict, seed: int, log):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.log = log
        self.mix = traffic["mix"]
        for e in self.mix:
            if e["op"] not in KINDS:
                raise ValueError("op %r: the rbd driver sends %s"
                                 % (e["op"], ", ".join(KINDS)))
        if not traffic.get("serialize_overlap"):
            raise ValueError("the rbd driver checks every read against "
                             "one answer: serialize_overlap must be true")
        from ceph_tpu.client.rbd import RBD
        if "data_pool" not in inspect.signature(RBD.create).parameters:
            # fail before the cluster boots, not after
            raise TypeError("this program's RBD.create takes no data_pool")
        self.io = int(traffic["io_size"])
        self.size = int(config["image_size"])
        self.object_size = 1 << int(config["image"]["order"])
        self.blocks = self.size // self.io
        self.cl = Cluster(config, log)
        self.code = self.cl.code
        self.img = None
        self.model = None
        self.setup_mismatch = 0
        # blocks with an op in flight (serialize_overlap)
        self._busy: set = set()
        self._cond = threading.Condition()

    def reseed(self, seed: int) -> None:
        """Draw the next window's order, blocks and payloads from
        `seed`, on the image set up already."""
        self.seed = seed
        self._payloads = generator.payloads(
            seed, int(self.traffic["distinct_payloads"]), self.io)

    # -- set-up --------------------------------------------------------

    def setup(self, annotate) -> None:
        from ceph_tpu.client.rbd import RBD, Image
        with annotate("setup.boot"):
            self.cl.boot()
            meta = self._create_metadata_pool()
        image = self.config["image"]
        RBD.create(meta, image["name"], self.size, order=int(image["order"]),
                   features=tuple(image["features"]), data_pool=POOL)
        self.img = Image(meta, image["name"])
        self.reseed(self.seed)
        t = time.monotonic()
        with annotate("setup.prefill"):
            self.model = generator.rng(self.seed, 10).integers(
                0, 256, self.size, dtype=np.uint8)
            objects = self.size // self.object_size
            with ThreadPoolExecutor(self._in_flight()) as ex:
                list(ex.map(self._prefill, range(objects)))
        self.log("prefill: %d objects of %d B in %.3f s"
                 % (objects, self.object_size, time.monotonic() - t))
        t = time.monotonic()
        warm = int(self.traffic["warmup_ops"])
        with annotate("setup.warmup"):
            before = self.cl.counters()
            order = generator.kinds(self.mix, self.seed + 1)
            keys = self._keys(self.seed + 1)
            with ThreadPoolExecutor(self._in_flight()) as ex:
                for ok in ex.map(lambda i: self._op(
                        Op(index=i, start=time.monotonic()), WARM,
                        self.mix[order[i % len(order)]]["op"],
                        int(keys[i % len(keys)])), range(warm)):
                    self.setup_mismatch += not ok
            seen = generator.delta(before, self.cl.counters())
            self.cl.warm_coalesced([self.io], seen)
            self._warm_dispatchers()
        self.log("warm-up: %d ops in %.3f s" % (warm, time.monotonic() - t))

    def _warm_dispatchers(self) -> None:
        """Each OSD dispatcher's own encode of 1 .. max_batch stripes of
        one chunk each. A dispatcher that donates its staged input jits
        the codec's encode once per batch size, and coalescing forms the
        sizes as ops happen to arrive: the warm-up ops need not have met
        each one on each dispatcher."""
        batch = (self.code.k, self.cl.stripe_unit)
        warmed = set()
        for o, pg in self.cl.pgs():
            d = self.cl.cluster.osds[o].tpu_dispatcher
            if d is None or o in warmed:
                continue
            warmed.add(o)
            for b in range(1, d.max_batch + 1):
                d.encode(pg.backend.codec, np.zeros((b,) + batch, np.uint8))

    def _in_flight(self) -> int:
        return generator.concurrency(self.traffic["arrival"])

    def _create_metadata_pool(self):
        """The replicated pool of the image's header and object map,
        every PG of it active and clean."""
        spec = self.config["metadata_pool"]
        client = self.cl.client
        res, outs, _ = client.mon_command({
            "prefix": "osd pool create", "pool": spec["name"],
            "size": int(spec["size"]), "pg_num": int(spec["pg_num"])})
        if res != 0:
            raise RuntimeError("pool create %s: %s" % (spec["name"], outs))
        deadline = time.monotonic() + 180
        while True:
            if client.osdmap is not None and any(
                    p.name == spec["name"]
                    for p in client.osdmap.pools.values()):
                pool_id = client.pool_id(spec["name"])
                pgs = [pg for osd in self.cl.cluster.osds.values()
                       for pg in list(osd.pgs.values())
                       if pg.pgid.pool == pool_id]
                active = {str(pg.pgid) for pg in pgs
                          if pg.is_primary() and pg.peer_state == "active"}
                dirty = any(pg.peer_state not in ("active", "replica")
                            or pg.missing or pg.peer_missing for pg in pgs)
                if len(active) == int(spec["pg_num"]) and not dirty:
                    return client.open_ioctx(spec["name"])
            else:
                client.mon_client.renew_subs()
            if time.monotonic() > deadline:
                raise RuntimeError("pool %s never became active and clean"
                                   % spec["name"])
            time.sleep(0.5)

    def _prefill(self, obj: int) -> None:
        off = obj * self.object_size
        self.img.write(off, self.model[off:off + self.object_size].tobytes())

    def _keys(self, seed: int):
        return generator.keys(self.traffic["keys"], self.blocks, seed)

    # -- ops -----------------------------------------------------------

    def _op(self, op: Op, phase: int, kind: str, blk: int) -> bool:
        """One IO of `kind` on block `blk`; it waits while the block has
        an IO in flight. A read succeeds when it returns the model's
        bytes; a write's payload goes into the model once the image
        acknowledged it."""
        op.kind, op.nbytes, op.obj = kind, self.io, blk
        off = blk * self.io
        with self._cond:
            while blk in self._busy:
                self._cond.wait()
            self._busy.add(blk)
        try:
            if kind == "write":
                data = generator.tagged(
                    self._payloads[op.index % len(self._payloads)],
                    phase, op.index)
                self.img.write(off, data)
                self.model[off:off + self.io] = np.frombuffer(data, np.uint8)
                return True
            if self.img.read(off, self.io) != \
                    self.model[off:off + self.io].tobytes():
                op.error = "mismatch"
                return False
            return True
        finally:
            with self._cond:
                self._busy.discard(blk)
                self._cond.notify_all()

    # -- the window ------------------------------------------------------

    def window(self, seconds: float, annotate) -> tuple:
        order = generator.kinds(self.mix, self.seed)
        keys = self._keys(self.seed)

        def op_fn(op: Op) -> bool:
            kind = self.mix[order[op.index % len(order)]]["op"]
            with annotate("client." + kind):
                return self._op(op, WINDOW, kind,
                                int(keys[op.index % len(keys)]))
        return generator.drive(self.traffic["arrival"], seconds, op_fn,
                               annotate, float(self.traffic["grace_s"]))

    # -- readings ----------------------------------------------------------

    def counters(self) -> dict:
        """`Cluster.counters`, with every OSD's RMW counters and the
        image handle's librbd counters (0 where the program has none)."""
        out = self.cl.counters()
        for o, osd in self.cl.cluster.osds.items():
            dump = osd.perf.dump()
            out.setdefault("osd.%d" % o, {}).update(
                {n: dump.get(n, 0) for n in OSD_COUNTERS})
        if self.img is not None:
            read = getattr(self.img, "perf_counters", dict)
            lib = read()
            out["librbd"] = {n: lib.get(n, 0) for n in LIBRBD_COUNTERS}
        return out

    def spans(self) -> list:
        return self.cl.spans()

    # -- checks ------------------------------------------------------------

    def checks(self, ops: list) -> dict:
        """name -> (value, limit): each must read at most its limit."""
        failed = sum(1 for op in ops if not op.ok and op.error != "mismatch")
        out = {"failed_ops": (failed, 0),
               "read_mismatch": (sum(op.error == "mismatch" for op in ops)
                                 + self.setup_mismatch, 0)}
        writes = [op for op in ops if op.ok and op.kind == "write"]
        written = sorted({op.obj for op in writes})
        r = generator.rng(self.seed, 3)
        n = min(int(self.traffic["check_blocks"]), len(written))
        sample = sorted(r.choice(written, size=n, replace=False)) \
            if n else []

        def readback(blk) -> bool:
            off = int(blk) * self.io
            try:
                return self.img.read(off, self.io) == \
                    self.model[off:off + self.io].tobytes()
            except Exception as e:   # not there: as wrong as it gets
                self.log("read-back of block %d failed: %r" % (blk, e))
                return False
        with ThreadPoolExecutor(self._in_flight()) as ex:
            out["readback_mismatch"] = (
                sum(not ok for ok in ex.map(readback, sample)), 0)
        per_object = {}
        for op in writes:
            obj = op.obj * self.io // self.object_size
            per_object[obj] = per_object.get(obj, 0) + 1
        hot = sorted(per_object, key=lambda o: (-per_object[o], o))[
            :int(self.traffic["check_objects"])]
        prefix = self.img.stat()["block_name_prefix"]
        out["shard_mismatch"] = (sum(
            not self.cl.shards_match(
                "%s.%016x" % (prefix, obj),
                self.model[obj * self.object_size:
                           (obj + 1) * self.object_size].tobytes())
            for obj in hot), 0)
        self.log("checked: %d blocks read back, shards of %d objects"
                 % (len(sample), len(hot)))
        return out

    def close(self) -> None:
        if self.img is not None:
            self.img.close()
        self.cl.close()

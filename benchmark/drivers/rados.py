"""Client traffic through the served path of an in-process vstart
cluster: RadosClient/IoCtx ops against an erasure-coded pool, as
``rados bench`` sends them (src/common/obj_bencher.cc: 4 MiB objects,
a closed loop of concurrent ops), or at a fixed rate.

The traffic file states:

- ``object_size``; ``mix``: entries ``{"op": "write_full" | "read",
  "share": s}`` (a read may give ``length``: that many bytes at an
  offset drawn from the seed, aligned to the length);
- ``arrival``: ``{"kind": "closed", "in_flight": n}`` or
  ``{"kind": "open", "rate_per_s": r, "max_in_flight": n}`` (evenly
  spaced arrivals);
- ``keys``: how reads pick among the prefilled objects
  (`benchmark.generator.keys`);
- ``prefill_objects``, ``down_osds`` (stopped and marked down, not
  out, before the window), ``warmup_ops``, ``distinct_payloads``,
  ``check_objects``, ``op_timeout_s``, ``grace_s``.

Set-up boots the configuration's cluster (`benchmark.cluster`), writes
the objects the reads touch, stops the OSDs, and runs the warm-up ops
and the coalesced codec programs they show the window will meet. Every
write_full goes to a fresh object. The checks read back a sample of
what the window wrote, compare every read with the bytes written, and
compare the shards the OSD stores hold with the reference's striping
and parity.
"""

from __future__ import annotations

import time

from .. import generator
from ..cluster import Cluster
from ..generator import Op

KINDS = ("write_full", "read")


class Load:
    def __init__(self, config: dict, traffic: dict, seed: int, log):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.log = log
        self.size = int(traffic["object_size"])
        self.mix = traffic["mix"]
        for e in self.mix:
            if e["op"] not in KINDS:
                raise ValueError("op %r: the rados driver sends %s"
                                 % (e["op"], ", ".join(KINDS)))
        self.writes = any(e["op"] == "write_full" for e in self.mix)
        self.reads = any(e["op"] == "read" for e in self.mix)
        self.cl = Cluster(config, log)
        self.code = self.cl.code
        self.down: list = []
        self.placement: dict = {}     # prefilled object -> acting OSDs

    # -- names and contents --------------------------------------------

    def name(self, phase: str, i: int) -> str:
        # the objects a traffic reads are the same for every seed (their
        # placement, and so which shards the down OSDs take from them,
        # is the work); a seed draws their contents and the read order
        if phase == "prefill":
            return "bench_prefill_%d" % i
        return "bench_%s_%d_%d" % (phase, self.seed, i)

    def reseed(self, seed: int) -> None:
        """Draw the next window's names and order from `seed`, on the
        cluster, objects and warm state set up already."""
        self.seed = seed

    def content(self, phase_code: int, i: int) -> bytes:
        pool = self._payloads
        return generator.tagged(pool[i % len(pool)], phase_code, i)

    # -- set-up --------------------------------------------------------

    def setup(self, annotate) -> None:
        with annotate("setup.boot"):
            self.cl.boot()
        self._payloads = generator.payloads(
            self.seed, int(self.traffic["distinct_payloads"]), self.size)
        prefill = int(self.traffic.get("prefill_objects", 0))
        if self.reads and not prefill:
            raise ValueError("reads need prefill_objects")
        if prefill:
            t = time.monotonic()
            with annotate("setup.prefill"):
                self._run_ops(prefill, lambda op: self._write(op, "prefill", 1))
            self.placement = {j: self.cl.acting(self.name("prefill", j))
                              for j in range(prefill)}
            self.log("prefill: %d objects of %d B in %.3f s"
                     % (prefill, self.size, time.monotonic() - t))
        self.down = list(self.traffic.get("down_osds", []))
        if self.down:
            t = time.monotonic()
            with annotate("setup.failures"):
                self.cl.stop(self.down)
            self.log("failures: osds %s stopped, marked down, every PG "
                     "active in %.3f s" % (self.down, time.monotonic() - t))
        t = time.monotonic()
        warm = int(self.traffic["warmup_ops"])
        with annotate("setup.warmup"):
            before = self.cl.counters()
            self._run_ops(warm, self._warm_op)
            seen = generator.delta(before, self.cl.counters())
            self.cl.warm_coalesced(
                [e.get("length", self.size) for e in self.mix], seen)
        self.log("warm-up: %d ops in %.3f s" % (warm, time.monotonic() - t))

    def _run_ops(self, count: int, op_fn) -> None:
        """`count` ops at the traffic's concurrency (set-up only; an op
        that raises fails the run)."""
        from concurrent.futures import ThreadPoolExecutor

        def one(i):
            op = Op(index=i, start=time.monotonic())
            if not op_fn(op):
                # wrong bytes are for the checks to count, not a crash
                self.log("set-up op %d read back wrong bytes" % i)
        with ThreadPoolExecutor(
                generator.concurrency(self.traffic["arrival"])) as ex:
            list(ex.map(one, range(count)))

    # -- ops -----------------------------------------------------------

    def _timeout(self) -> float:
        return float(self.traffic["op_timeout_s"])

    def _write(self, op: Op, phase: str, code: int) -> bool:
        op.kind, op.nbytes, op.obj = "write_full", self.size, op.index
        self.cl.ioctx.write_full(self.name(phase, op.index),
                                 self.content(code, op.index),
                                 timeout=self._timeout())
        return True

    def _read(self, op: Op, j: int, length: int = 0, offset: int = 0) -> bool:
        op.kind, op.obj = "read", j
        op.nbytes = length or self.size
        got = self.cl.ioctx.read(self.name("prefill", j), length=length,
                                 offset=offset, timeout=self._timeout())
        want = self.content(1, j)
        if length:
            want = want[offset:offset + length]
        if got != want:
            op.error = "mismatch"
            return False
        return True

    def _op(self, op: Op, entry: dict, phase: str, j: int) -> bool:
        """Op `op` as the mix entry states it; reads touch object j."""
        if entry["op"] == "write_full":
            return self._write(op, phase, 2 if phase == "warm" else 3)
        length = int(entry.get("length", 0))
        offset = 0
        if length:
            slots = self.size // length
            offset = length * int(generator.rng(self.seed, 9, op.index)
                                  .integers(0, slots))
        return self._read(op, j, length, offset)

    def _warm_op(self, op: Op) -> bool:
        entry = self.mix[op.index % len(self.mix)]
        return self._op(op, entry, "warm",
                        op.index % max(1, len(self.placement)))

    # -- the window ------------------------------------------------------

    def window(self, seconds: float, annotate) -> tuple:
        order = generator.kinds(self.mix, self.seed)
        objs = generator.keys(self.traffic.get("keys", {}),
                              len(self.placement), self.seed) \
            if self.reads else None

        def op_fn(op: Op) -> bool:
            entry = self.mix[order[op.index % len(order)]]
            with annotate("client." + entry["op"]):
                return self._op(op, entry, "w",
                                int(objs[op.index % len(objs)])
                                if objs is not None else -1)
        return generator.drive(self.traffic["arrival"], seconds, op_fn,
                               annotate, float(self.traffic["grace_s"]))

    # -- readings ----------------------------------------------------------

    def counters(self) -> dict:
        return self.cl.counters()

    def spans(self) -> list:
        return self.cl.spans()

    # -- checks ------------------------------------------------------------

    def checks(self, ops: list) -> dict:
        """name -> (value, limit): each must read at most its limit."""
        failed = sum(1 for op in ops if not op.ok and op.error != "mismatch")
        out = {"failed_ops": (failed, 0)}
        r = generator.rng(self.seed, 3)
        sample_n = int(self.traffic["check_objects"])
        objs = []
        if self.writes:
            done = [op for op in ops if op.ok and op.kind == "write_full"]
            pick = list(r.choice(len(done), size=min(sample_n, len(done)),
                                 replace=False)) if done else []
            if done:
                longest = max(range(len(done)),
                              key=lambda i: done[i].end - done[i].start)
                pick = sorted(set(pick) | {longest})
            written = [("w", 3, done[i].index) for i in pick]
            readback = 0
            for phase, code, i in written:
                try:
                    got = self.cl.ioctx.read(self.name(phase, i),
                                             timeout=self._timeout())
                except Exception as e:   # not there: as wrong as it gets
                    self.log("read-back of %s failed: %r"
                             % (self.name(phase, i), e))
                    got = None
                readback += got != self.content(code, i)
            out["readback_mismatch"] = (readback, 0)
            objs += written
        if self.reads:
            out["read_mismatch"] = (sum(op.error == "mismatch" for op in ops),
                                    0)
            pick = r.choice(len(self.placement),
                            size=min(sample_n, len(self.placement)),
                            replace=False)
            objs += [("prefill", 1, int(j)) for j in sorted(pick)]
        out["shard_mismatch"] = (sum(
            not self.cl.shards_match(
                self.name(phase, i), self.content(code, i),
                self.placement[i] if phase == "prefill" else None)
            for phase, code, i in objs), 0)
        self.log("checked: %d objects' read-back and shards" % len(objs))
        return out

    def close(self) -> None:
        self.cl.close()


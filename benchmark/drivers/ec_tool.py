"""The EC benchmark tool's protocol against the configuration's codec
(``tools/erasure_code_benchmark.py``, upstream
``ceph_erasure_code_benchmark``): each iteration encodes a batch of
objects in one device call (``--batch``), then decodes each object of
the batch through the codec's ``decode`` with its own seeded set of
erased chunks (``-w decode -e``). Results are materialised on the host,
as the tool does.

Set-up makes the batches from the seed, encodes each once (that parity
is what the decodes start from and what every later encode of the batch
must equal) and decodes every erasure pattern once, so that the window
finds every program and decode table ready. The checks hold each encode
of the window to the reference's parity and each decode to the chunks
it rebuilt.

The traffic file states ``object_size``, ``batch`` (objects per encode
call), ``erasures`` (chunks erased per decode) and ``distinct_batches``.
"""

from __future__ import annotations

import itertools
import time

import numpy as np

from .. import generator, reference
from ..generator import Op


class Load:
    def __init__(self, config: dict, traffic: dict, seed: int, log):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.log = log
        self.code = reference.Code(config["profile"])
        self.size = int(traffic["object_size"])
        self.batch = int(traffic["batch"])
        self.erasures = int(traffic["erasures"])

    def reseed(self, seed: int) -> None:
        """Draw the next window's erasures from `seed`, on the batches
        and warm state set up already."""
        self.seed = seed

    def setup(self, annotate) -> None:
        from ceph_tpu import registry
        profile = {k: v for k, v in self.config["profile"].items()
                   if not k.startswith("crush-") and k != "plugin"}
        self.codec = registry.factory(self.config["profile"]["plugin"],
                                      profile)
        k, n = self.code.k, self.code.n
        self.chunk = self.size // k
        if self.codec.get_chunk_size(self.size) != self.chunk:
            raise RuntimeError("codec chunk %d for %d B objects, not %d"
                               % (self.codec.get_chunk_size(self.size),
                                  self.size, self.chunk))
        r = generator.rng(self.seed, 4)
        self.data = [r.integers(0, 256, size=(self.batch, k, self.chunk),
                                dtype=np.uint8)
                     for _ in range(int(self.traffic["distinct_batches"]))]
        t = time.monotonic()
        with annotate("setup.encode"):
            self.parity = [np.asarray(self.codec.encode_batch(d))
                           for d in self.data]
        self.log("warm-up: %d encodes of %s in %.3f s"
                 % (len(self.data), self.data[0].shape, time.monotonic() - t))
        t = time.monotonic()
        patterns = list(itertools.combinations(range(n), self.erasures))
        with annotate("setup.decode"):
            for erased in patterns:
                self.codec.decode(set(erased), self.survivors(0, 0, erased))
        self.log("warm-up: %d erasure patterns decoded in %.3f s"
                 % (len(patterns), time.monotonic() - t))

    def chunk_of(self, b: int, j: int, c: int) -> np.ndarray:
        """Chunk c (by position) of object j of batch b, as encoded."""
        if c in self.code.data_positions:
            return self.data[b][j, self.code.data_positions.index(c)]
        return self.parity[b][j, self.code.parity_positions.index(c)]

    def survivors(self, b: int, j: int, erased) -> dict:
        return {c: self.chunk_of(b, j, c) for c in range(self.code.n)
                if c not in erased}

    def window(self, seconds: float, annotate) -> tuple:
        ops, counter = [], itertools.count()
        n = self.code.n
        t0, t1, holder = generator.hold_window(seconds, annotate)
        it = 0
        while time.monotonic() < t1:
            b = it % len(self.data)
            op = Op(index=next(counter), start=time.monotonic(),
                    kind="encode", nbytes=self.batch * self.size, obj=b)
            ops.append(op)
            try:
                with annotate("codec.encode"):
                    out = np.asarray(self.codec.encode_batch(self.data[b]))
                op.ok = np.array_equal(out, self.parity[b])
                op.error = "" if op.ok else "mismatch"
            except Exception as e:   # counted as failed, the run goes on
                op.error = repr(e)
            op.end = time.monotonic()
            r = generator.rng(self.seed, 5, it)
            for j in range(self.batch):
                erased = tuple(sorted(r.choice(n, self.erasures,
                                               replace=False)))
                op = Op(index=next(counter), start=time.monotonic(),
                        kind="decode", nbytes=self.size, obj=b)
                ops.append(op)
                try:
                    with annotate("codec.decode"):
                        got = self.codec.decode(set(erased),
                                                self.survivors(b, j, erased))
                    op.ok = all(np.array_equal(np.asarray(got[c]),
                                               self.chunk_of(b, j, c))
                                for c in erased)
                    op.error = "" if op.ok else "mismatch"
                except Exception as e:
                    op.error = repr(e)
                op.end = time.monotonic()
            it += 1
        holder.join()
        self.log("window: %d iterations" % it)
        return ops, t0, t1

    def counters(self) -> dict:
        return {}

    def spans(self) -> list:
        return []

    def checks(self, ops: list) -> dict:
        """name -> (value, limit): each must read at most its limit."""
        wrong_batches = set()
        for b, d in enumerate(self.data):
            for j in range(self.batch):
                if not np.array_equal(self.parity[b][j],
                                      self.code.parity(d[j])):
                    wrong_batches.add(b)
        failed = sum(1 for op in ops if not op.ok and op.error != "mismatch")
        parity = sum(1 for op in ops if op.kind == "encode" and (
            op.error == "mismatch" or op.obj in wrong_batches))
        decode = sum(1 for op in ops if op.kind == "decode" and (
            op.error == "mismatch" or op.obj in wrong_batches))
        return {"failed_ops": (failed, 0), "parity_mismatch": (parity, 0),
                "decode_mismatch": (decode, 0)}

    def close(self) -> None:
        pass

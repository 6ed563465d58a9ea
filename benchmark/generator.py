"""The one traffic generator: it reads a traffic mix's data file and
drives the system under test with it.

A traffic file (``benchmark/traffic/<name>.json``) names its
``driver``, a module ``benchmark/drivers/<driver>.py`` whose ``Load``
class sets up and runs the traffic the file's parameters describe:

- ``rados``: client ops against an in-process vstart cluster: an op
  ``mix`` (kinds and shares), ``arrival`` (a closed loop of
  ``in_flight`` ops, or an open loop at ``rate_per_s``), ``keys`` (how
  reads pick objects), object size, prefill, stopped OSDs, warm-up and
  check sample;
- ``ec_tool``: the protocol of the EC benchmark tool against the
  configuration's codec.

A traffic that these parameters describe is a new data file; one that
needs another op loop is a new driver file beside them.

Everything a run sends is drawn from ``--seed``: object contents, the
order of ops and of the objects they touch, erasures.
Every seed gets the same sizes, the same shares of each op kind, the
same concurrency or rate.
"""

from __future__ import annotations

import importlib
import itertools
import queue
import threading
import time
from dataclasses import dataclass

import numpy as np


@dataclass
class Op:
    """One client op or codec call of the window."""
    index: int
    start: float
    kind: str = ""
    nbytes: int = 0          # object bytes the op carries
    obj: int = -1            # object index, where the op names one
    end: float | None = None  # None: never finished
    ok: bool = False
    error: str = ""          # "" | "mismatch" | an exception's repr


def rng(seed: int, *stream: int) -> np.random.Generator:
    """The generator of one seeded stream; `seed` may exceed 64 bits."""
    return np.random.default_rng([seed % (1 << 63), seed >> 63, *stream])


def payloads(seed: int, count: int, size: int) -> list:
    """`count` distinct random buffers of `size` bytes."""
    r = rng(seed, 1)
    return [r.bytes(size) for _ in range(count)]


def tagged(payload: bytes, *tag: int) -> bytes:
    """The payload with its first bytes replaced by a unique tag."""
    head = np.array(tag, dtype=np.int64).tobytes()
    return head + payload[len(head):]


def hold_window(seconds: float, annotate):
    """Start the window now: a thread keeps the ``window`` host span
    open for exactly `seconds`. Returns (t0, t1, thread)."""
    ready = threading.Event()
    stamps = {}

    def keep():
        with annotate("window"):
            stamps["t0"] = time.monotonic()
            ready.set()
            time.sleep(max(0.0, stamps["t0"] + seconds - time.monotonic()))
    t = threading.Thread(target=keep, name="bench-window", daemon=True)
    t.start()
    ready.wait()
    return stamps["t0"], stamps["t0"] + seconds, t


def closed_loop(in_flight: int, seconds: float, op_fn, annotate,
                grace: float) -> tuple:
    """`in_flight` workers each issue their next op as soon as the last
    one returns, until the window closes; ops still running then get
    `grace` seconds to finish. op_fn(op) fills the op's kind, bytes and
    object, performs it and returns whether it succeeded (or raises).
    Returns (ops, t0, t1)."""
    ops, lock, counter = [], threading.Lock(), itertools.count()
    t0, t1, holder = hold_window(seconds, annotate)

    def worker():
        while True:
            with lock:
                if time.monotonic() >= t1:
                    return
                op = Op(index=next(counter), start=time.monotonic())
                ops.append(op)
            try:
                ok = op_fn(op)
            except Exception as e:   # the op failed; the run goes on
                ok, op.error = False, repr(e)
            op.ok = bool(ok)
            op.end = time.monotonic()
    workers = [threading.Thread(target=worker, name="bench-client-%d" % i,
                                daemon=True) for i in range(in_flight)]
    for w in workers:
        w.start()
    holder.join()
    for w in workers:
        w.join(max(0.0, t1 + grace - time.monotonic()))
    with lock:
        return list(ops), t0, t1


def open_loop(rate: float, max_in_flight: int, seconds: float, op_fn,
              annotate, grace: float) -> tuple:
    """Ops arrive at `rate` per second, evenly spaced, whatever the
    system does; up to `max_in_flight` run at once and the others queue. An op's latency
    counts from its arrival, so time spent queued shows in the tail.
    No op arrives after the window closes; those that arrived get
    `grace` seconds to finish. Returns (ops, t0, t1)."""
    ops, pending = [], queue.Queue()
    t0, t1, holder = hold_window(seconds, annotate)

    def worker():
        while True:
            op = pending.get()
            if op is None:
                return
            try:
                ok = op_fn(op)
            except Exception as e:   # the op failed; the run goes on
                ok, op.error = False, repr(e)
            op.ok = bool(ok)
            op.end = time.monotonic()
    workers = [threading.Thread(target=worker, name="bench-client-%d" % i,
                                daemon=True) for i in range(max_in_flight)]
    for w in workers:
        w.start()
    for i in itertools.count():
        at = t0 + (i + 1) / rate
        if at >= t1:
            break
        time.sleep(max(0.0, at - time.monotonic()))
        op = Op(index=i, start=at)
        ops.append(op)
        pending.put(op)
    for _ in workers:
        pending.put(None)
    holder.join()
    for w in workers:
        w.join(max(0.0, t1 + grace - time.monotonic()))
    return list(ops), t0, t1


def drive(arrival: dict, seconds: float, op_fn, annotate,
          grace: float) -> tuple:
    """The window's loop as the traffic's ``arrival`` states it."""
    if arrival["kind"] == "closed":
        return closed_loop(int(arrival["in_flight"]), seconds, op_fn,
                           annotate, grace)
    if arrival["kind"] == "open":
        return open_loop(float(arrival["rate_per_s"]),
                         int(arrival["max_in_flight"]), seconds, op_fn,
                         annotate, grace)
    raise ValueError("arrival kind %r" % arrival["kind"])


def concurrency(arrival: dict) -> int:
    """How many ops the traffic keeps in flight at most."""
    return int(arrival.get("in_flight") or arrival["max_in_flight"])


def kinds(mix: list, seed: int, n: int = 1 << 16, block: int = 100) -> list:
    """The index of the `mix` entry of each op: every block of `block`
    ops holds each entry's share of them exactly, in an order drawn from
    the seed."""
    counts = [round(e["share"] * block) for e in mix]
    if sum(counts) != block:
        raise ValueError("mix shares must sum to 1 in steps of 1/%d" % block)
    one = np.repeat(np.arange(len(mix)), counts)
    r = rng(seed, 6)
    return np.concatenate([r.permutation(one)
                           for _ in range(-(-n // block))])[:n].tolist()


def keys(spec: dict, count: int, seed: int, n: int = 1 << 20):
    """The object index each op touches, over `count` objects:
    ``uniform``; ``shuffled``, every object once in each pass of `count`
    ops, each pass in an order drawn from the seed (uniform over the
    objects, and every seed reads the same objects as often); or
    ``zipf`` with exponent ``s`` over the objects in an order drawn from
    the seed (the popular objects differ by seed, the shape of the skew
    does not)."""
    r = rng(seed, 2)
    if spec.get("dist", "uniform") == "uniform":
        return r.integers(0, count, size=n)
    if spec["dist"] == "shuffled":
        return np.concatenate([r.permutation(count)
                               for _ in range(-(-n // count))])[:n]
    if spec["dist"] == "zipf":
        p = 1.0 / np.arange(1, count + 1) ** float(spec["s"])
        rank = r.choice(count, size=n, p=p / p.sum())
        return rng(seed, 7).permutation(count)[rank]
    raise ValueError("key distribution %r" % spec["dist"])


def delta(before: dict, after: dict) -> dict:
    """Counters' change from `before` to `after` (a reading of
    `Load.counters`: numbers, or rows of numbers by daemon)."""
    out = {}
    for key, row in after.items():
        if isinstance(row, dict):
            old = before.get(key, {})
            out[key] = {n: v - old.get(n, 0) for n, v in row.items()}
        else:
            out[key] = row - before.get(key, 0)
    return out


def make(config: dict, traffic: dict, seed: int, log):
    """The `Load` of the traffic's driver, ``benchmark/drivers/<driver>.py``."""
    module = importlib.import_module("benchmark.drivers." + traffic["driver"])
    return module.Load(config, traffic, seed, log)

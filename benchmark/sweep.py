"""Offer a cell's traffic at fixed rates, one after another on one
set-up, to find the highest rate the system sustains (the knee that a
rated cell's ``arrival`` is set below).

    python3 -m benchmark.sweep --workload <cell> --seed <n> \\
        --seconds <s> --rates <r,r,...> [--max-in-flight <n>]

Each rate replaces the traffic's ``arrival`` with an open loop at that
many ops per second (even spacing) and runs one window of ``--seconds``
with its own seed. Prints one JSON line per rate: the rate, ops
offered and done inside the window, the rate achieved, MB/s, the p50
and p95 latency (from each op's arrival) and whether the window's
checks held. The benchmark's own runs never sweep.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

from . import generator, readers, run, trace


def sweep(cell, seed: int, seconds: float, rates, max_in_flight: int):
    load = generator.make(cell.config, cell.traffic, seed, run.log)
    try:
        load.setup(trace.annotate)
        for i, rate in enumerate(rates):
            load.reseed(seed + 1 + i)
            load.traffic["arrival"] = {"kind": "open", "rate_per_s": rate,
                                       "max_in_flight": max_in_flight}
            ops, t0, t1 = load.window(seconds, trace.annotate)
            checks = load.checks(ops)
            done = [op for op in ops
                    if op.ok and op.end is not None and op.end <= t1]
            lats = [readers.latency_s(op, t1) for op in ops]
            yield {"rate_per_s": rate, "offered": len(ops),
                   "done": len(done), "achieved_per_s": len(done) / (t1 - t0),
                   "MBps": sum(op.nbytes for op in done) / (t1 - t0) / 1e6,
                   "p50_ms": readers.percentile(lats, 50) * 1e3,
                   "p95_ms": readers.percentile(lats, 95) * 1e3,
                   "correct": all(v <= lim for v, lim in checks.values())}
    finally:
        load.close()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="benchmark.sweep",
                                description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--max-in-flight", type=int, default=64)
    args = p.parse_args(argv)
    from . import device
    try:
        cell = run.load_cell(args.workload)
        run.configure_cache()
        device.require_tpu(cell.chips)
        for line in sweep(cell, args.seed, args.seconds,
                          [float(r) for r in args.rates.split(",")],
                          args.max_in_flight):
            print(json.dumps(line), flush=True)
    except Exception:
        traceback.print_exc()
        return 1
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)

"""Run a cell's window for several seeds, one after another on one
set-up, to tell whether the seed changes the work: the same seed twice
against other seeds, and each under other key distributions.

    python3 -m benchmark.seeds --workload <cell> --seconds <s> \\
        --runs <seed:dist,seed:dist,...>

Each entry runs one window of ``--seconds`` with that seed's draws and
the traffic's ``keys`` replaced by ``{"dist": dist}`` (``-`` keeps the
traffic's own). Set-up uses the first entry's seed. Prints one JSON
line per window: seed, key distribution, MB/s, ops done inside the
window, the window's device decode input and host XOR rebuilds, ops done
per second, and whether the window's checks held. The benchmark's own
runs never use it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

from . import generator, run, trace


def windows(cell, entries, seconds: float):
    load = generator.make(cell.config, cell.traffic, entries[0][0], run.log)
    own = dict(cell.traffic.get("keys", {}))
    try:
        load.setup(trace.annotate)
        for seed, dist in entries:
            load.reseed(seed)
            load.traffic["keys"] = own if dist == "-" else {"dist": dist}
            before = load.counters()
            ops, t0, t1 = load.window(seconds, trace.annotate)
            moved = generator.delta(before, load.counters())
            checks = load.checks(ops)
            done = [op for op in ops
                    if op.ok and op.end is not None and op.end <= t1]
            per_s = [0] * int(t1 - t0 + 1)
            for op in done:
                per_s[int(op.end - t0)] += 1
            yield {"seed": seed, "keys": load.traffic["keys"],
                   "MBps": sum(op.nbytes for op in done) / (t1 - t0) / 1e6,
                   "done": len(done),
                   "device_decode_bytes": sum(
                       row.get("l_tpu_dec_bytes", 0)
                       for row in moved.values() if isinstance(row, dict)),
                   "xor_rebuilds": moved.get("xor_rebuilds"),
                   "done_per_s": per_s,
                   "correct": all(v <= lim for v, lim in checks.values())}
    finally:
        load.close()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="benchmark.seeds",
                                description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--runs", required=True)
    args = p.parse_args(argv)
    entries = [(int(s), d) for s, d in
               (e.split(":") for e in args.runs.split(","))]
    from . import device
    try:
        cell = run.load_cell(args.workload)
        run.configure_cache()
        device.require_tpu(cell.chips)
        for line in windows(cell, entries, args.seconds):
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

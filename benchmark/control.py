"""Runs of a cell with a fault planted under the timed path (see
`benchmark.faults`), to show on the chip, at the cell's own size and
load, that its checks turn `correct` false.

    python3 -m benchmark.control --fault <name> --workload <cell> \\
        --seeds <n,n,...> --seconds <s>

One set-up serves every seed: each seed then draws its own window
(names, order, erasures), run and checked with the fault in place.
Prints one JSON line per seed: the seed, `correct` and the checks. The
benchmark's own runs never plant a fault.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

from . import faults, generator, run, trace


def control(cell, seeds, seconds: float, fault: str):
    load = generator.make(cell.config, cell.traffic, seeds[0], run.log)
    try:
        load.setup(trace.annotate)
        for seed in seeds:
            load.reseed(seed)
            with faults.planted(fault):
                ops, _, _ = load.window(seconds, trace.annotate)
                checks = load.checks(ops)
            yield {"seed": seed, "fault": fault, "attempted": len(ops),
                   "correct": any(op.ok for op in ops)
                   and all(v <= lim for v, lim in checks.values()),
                   "checks": {k: {"value": v, "limit": lim}
                              for k, (v, lim) in checks.items()}}
    finally:
        load.close()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="benchmark.control",
                                description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--fault", required=True, choices=sorted(faults.FAULTS))
    args = p.parse_args(argv)
    from . import device
    try:
        cell = run.load_cell(args.workload)
        run.configure_cache()
        device.require_tpu(cell.chips)
        for line in control(cell, [int(s) for s in args.seeds.split(",")],
                            args.seconds, args.fault):
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

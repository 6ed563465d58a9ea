"""Run one benchmark cell once, on the chip this process finds.

    python3 -m benchmark.run --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything about a cell is data, found by name from ``BENCHMARK.json``:
the cell's configuration file (``configs[].file``), its traffic mix
(``benchmark/traffic/<traffic>.json``, driven by the module of
``benchmark/drivers/`` it names; see `benchmark.generator`) and one
reader per metric (``benchmark/metrics/<metric>.py``). The run:

1. keeps JAX's compile cache in ``.jax_cache/`` of this checkout;
2. fails, printing no result, without a TPU, with fewer chips than the
   cell asks for, or with a chip whose kind ``peaks.json`` lacks;
3. sets up (boot, prefill, failures, warm-up of every shape the window
   uses) and measures for ``--seconds``; with ``--trace 1`` the JAX
   profiler records the window;
4. checks what the window produced against ``benchmark/reference.py``;
5. prints every compared number beside its limit on stderr, and as
   its last stdout line one JSON object: ``correct``, ``attempted``,
   ``failed``, ``metrics`` (the cell's end-to-end metrics, or with
   ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
   ``breakdown``, and last ``checks``.
"""

from __future__ import annotations

import time

PROCESS_T0 = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    print("bench: %s" % msg, file=sys.stderr, flush=True)


# -- the cell, from data ----------------------------------------------------

def load_cell(name: str, root: str = ROOT) -> SimpleNamespace:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit("no workload %r in BENCHMARK.json" % name)
    cell = cells[name]
    conf_entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, conf_entry["file"])) as f:
        config = json.load(f)
    bench = os.path.join(root, spec["paths"][0])
    with open(os.path.join(bench, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]
    return SimpleNamespace(name=name, chips=int(cell["chips"]),
                           config=config, traffic=traffic, bench=bench,
                           end_to_end=mine(spec["end_to_end"]),
                           per_layer=mine(spec["per_layer"]))


def reader(bench: str, metric: str):
    """The `read(run)` function of metrics/<metric>.py."""
    path = os.path.join(bench, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark.metrics._" + metric.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# -- one run ------------------------------------------------------------------

def configure_cache(root: str = ROOT) -> str:
    """JAX's persistent compile cache at a fixed path in the checkout."""
    import jax
    path = os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)     # JAX writes into it, never makes it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    # every program, however quick to compile, so that a run after the
    # first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: it keeps an access-time file beside each entry, and
    # one missing file makes every later write fail
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def run_cell(cell, seed: int, seconds: float, traced: bool,
             devices: list, peaks: dict) -> dict:
    """Set up, measure, check and read the metrics; returns the result
    line's object."""
    from . import device, generator, trace

    events = device.CompileEvents()
    load = generator.make(cell.config, cell.traffic, seed, log)
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        load.setup(trace.annotate)
        setup_events = events.snapshot()
        before = load.counters()
        recording = trace.Recording(tmp) if traced else None
        if recording:
            recording.start()
        setup_seconds = time.monotonic() - PROCESS_T0
        ops, t0, t1 = load.window(seconds, trace.annotate)
        tr = trace.load(recording.stop()) if recording else None
        window_events = {k: v - setup_events[k]
                         for k, v in events.snapshot().items()}
        after = load.counters()
        spans = [s for s in load.spans() if t0 <= s["start"] <= t1] \
            if traced else []
        peak = device.memory_peak_bytes(devices)
        log("compile cache in set-up: %(hits)d hits, %(misses)d misses"
            % setup_events)
        log("compile cache in the window: %(hits)d hits, %(misses)d misses"
            % window_events)
        log("peak HBM in use %d B" % peak)
        checks = load.checks(ops)
    finally:
        load.close()
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)

    run = SimpleNamespace(ops=ops, t0=t0, t1=t1, seconds=t1 - t0,
                          setup_seconds=setup_seconds,
                          counters=generator.delta(before, after),
                          spans=spans, trace=tr, peaks=peaks,
                          config=cell.config, traffic=cell.traffic,
                          code=load.code, down=getattr(load, "down", []),
                          placement=getattr(load, "placement", {}))
    _log_ops(run)
    result = {"correct": any(op.ok for op in ops)
              and all(v <= lim for v, lim in checks.values()),
              "attempted": len(ops),
              "failed": sum(1 for op in ops if not op.ok),
              "metrics": {}}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = reader(cell.bench, m["name"])(run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devices), "memory_peak_bytes": peak}
    if traced:
        result["device"]["busy_s"] = trace.busy_s(tr)
        result["device"]["window_s"] = tr.window_s
        result["breakdown"] = trace.breakdown(tr)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print("check %s %s limit %s" % (k, v, lim), file=sys.stderr)
    return result


def _log_ops(run) -> None:
    kinds = {}
    for op in run.ops:
        row = kinds.setdefault(op.kind, [0, 0, 0])
        row[0] += 1
        row[1] += op.ok and op.end is not None and op.end <= run.t1
        row[2] += not op.ok
    for kind, (n, done, bad) in sorted(kinds.items()):
        log("window: %d %s ops, %d done inside it, %d failed"
            % (n, kind, done, bad))
    # a stall or a warm-up that reaches into the window shows here
    per_s = [0] * int(run.t1 - run.t0 + 1)
    for op in run.ops:
        if op.ok and op.end is not None and op.end <= run.t1:
            per_s[int(op.end - run.t0)] += 1
    log("window: ops done per second %s" % " ".join(map(str, per_s)))
    if "xor_rebuilds" in run.counters:
        dev = sum(row.get("l_tpu_dec_bytes", 0) for row in
                  run.counters.values() if isinstance(row, dict))
        log("window: device decode input %d B, host XOR rebuilds %d"
            % (dev, run.counters["xor_rebuilds"]))


# -- entry --------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="benchmark.run",
                                description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p


def measure(args) -> dict:
    """One run of the cell the arguments name, on the chips found."""
    from . import device
    cell = load_cell(args.workload)
    log("compile cache %s" % configure_cache())
    devices = device.require_tpu(cell.chips)
    peaks = device.peaks_for(devices[0].device_kind)
    return run_cell(cell, args.seed, args.seconds, bool(args.trace),
                    devices, peaks)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from . import device
    try:
        result = measure(args)
    except device.NoChip as e:
        log("no measurement: %s" % e)
        return 2
    except Exception:
        traceback.print_exc()
        log("FAILED")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of a stopped cluster must not hold the exit
    os._exit(rc)

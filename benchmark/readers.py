"""Arithmetic the metric readers share (``benchmark/metrics/*.py``).

A reader gets the run: its op records (``run.ops``, each an
`benchmark.generator.Op`), the window ``run.t0 .. run.t1`` on the host
monotonic clock, ``run.setup_seconds``, the window's counter deltas
(``run.counters``), the op spans the daemons recorded in the window
(``run.spans``), the reduced profiler trace of a traced run
(``run.trace``, a `benchmark.trace.Trace`, else None), the chip's
``run.peaks``, the cell's ``run.config`` and ``run.traffic``, and the
reference ``run.code`` (its ``k``, ``n``, data and parity positions).
A reader that finds nothing to read returns None, and the metric is
left out of the run's line.
"""

from __future__ import annotations

import math
import re

from . import trace as trace_mod


def mb_per_s(run, kinds) -> float | None:
    """Object bytes of the ops of these kinds that succeeded inside the
    window, over the window's length, in MB/s."""
    done = sum(op.nbytes for op in run.ops if op.kind in kinds and op.ok
               and op.end is not None and op.end <= run.t1)
    return done / (run.t1 - run.t0) / 1e6 if done else None


def latency_s(op, t1: float) -> float:
    """An op's latency as the tail sees it: a failed op is beyond any
    limit; one still running when the window closed counts its age
    then."""
    if not op.ok or op.end is None:
        return math.inf
    return min(op.end, t1) - op.start


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty sequence."""
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100 * len(ordered)))
    return ordered[rank - 1]


def p95_ms(run, kinds) -> float | None:
    lats = [latency_s(op, run.t1) for op in run.ops if op.kind in kinds]
    if not lats:
        return None
    p = percentile(lats, 95)
    # a failed op in the tail: report the whole window, the longest
    # latency a run can show (the run is not correct anyway)
    return (p if math.isfinite(p) else run.t1 - run.t0) * 1e3


def span_mean_ms(run, name: str, keep=None) -> float | None:
    """Mean duration of the window's spans called `name` (keep, if
    given, filters them) in ms."""
    durs = [s["duration"] for s in run.spans
            if s["name"] == name and (keep is None or keep(s))]
    return sum(durs) / len(durs) * 1e3 if durs else None


def counter_total(run, name: str) -> int:
    """A dispatcher counter's window delta, summed over the OSDs."""
    return sum(row.get(name, 0) for row in run.counters.values()
               if isinstance(row, dict))


def dispatches_per_op(run) -> float | None:
    ops = counter_total(run, "l_tpu_ops")
    return counter_total(run, "l_tpu_dispatches") / ops if ops else None


def device_idle_pct(run) -> float | None:
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace_mod.busy_s(run.trace) / run.trace.window_s)


def device_seconds(events) -> float:
    return sum(e - s for _, s, e in events) / 1e9


def gf_ops(rows_out: int, rows_in: int, length: int) -> int:
    """Operations of a GF(2^8) matrix, `rows_out` x `rows_in`, applied to
    rows of `length` bytes, counted as the bit-matrix product the chip
    runs it as (ops/xor_mm.py: int8 multiply-accumulates, 2 operations
    each, over 8 bits of every byte in and out)."""
    return 2 * (8 * rows_out) * (8 * rows_in) * length


def crc_ops(nbytes: int) -> int:
    """Operations of a 32-bit CRC over `nbytes`, counted as the GF(2)
    bit-matrix product it is (32 x 8 bits per byte, 2 operations per
    bit multiply-accumulate)."""
    return 2 * 32 * 8 * nbytes


def least_seconds(nbytes: float, ops: float, peaks: dict) -> tuple:
    """(seconds, bound): the least time the chip allows for the work,
    its bytes at the peak HBM bandwidth or its operations at the peak
    int8 rate, whichever is longer, and which of the two it is."""
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    by_ops = ops / peaks["int8_ops_per_s"]
    return (by_ops, "ops") if by_ops > by_bytes else (by_bytes, "bytes")


def roofline_pct(nbytes: float, ops: float, seconds: float,
                 run) -> float | None:
    """The least time the chip allows for the work (`least_seconds`)
    as a share of the `seconds` the device spent, in %."""
    if not nbytes or seconds <= 0:
        return None
    return 100.0 * least_seconds(nbytes, ops, run.peaks)[0] / seconds


def shard_of(span_name: str) -> int | None:
    m = re.match(r"sub_read\(shard=(\d+)\)", span_name)
    return int(m.group(1)) if m else None

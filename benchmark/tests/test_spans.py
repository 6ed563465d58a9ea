"""The span readers (benchmark/spans.py): the op trees, the critical-path
fold, the anchor from the host's monotonic clock onto the trace's, the
idle share with ops queued, and the clock check, on synthetic spans
and traces; and the recorded v5e trace through the same reductions."""

import os
from types import SimpleNamespace

import pytest

from benchmark import spans, trace

DATA = os.path.join(os.path.dirname(__file__), "data", "codec_v5e.xplane.pb")

T0 = 1000.0                     # the window's start, monotonic seconds


def _span(sid, parent, name, start, end, tid=7):
    return {"trace_id": tid, "span_id": sid, "parent_id": parent,
            "name": name, "start": T0 + start, "duration": end - start}


def _write_tree(tid=7, base=0):
    """An EC write at its primary and one replica, in ms from T0:
    stages abut, two sub-writes overlap."""
    ms = 1e-3
    rows = [
        (1, None, "osd_op", 0, 100),
        (2, 1, "ms_recv", 0, 4),
        (3, 1, "op_queue", 4, 10),
        (4, 1, "pg_do_op", 10, 12),
        (5, 1, "ec_wait", 12, 13),
        (6, 1, "ec_encode", 13, 60),
        (7, 6, "ec_assemble", 13, 15),
        (8, 6, "tpu_queue", 15, 20),
        (9, 6, "tpu_device", 20, 50),
        (10, 9, "compute", 22, 48),
        (11, 6, "tpu_resume", 50, 52),
        (12, 6, "ec_txns", 52, 56),
        (13, 6, "ec_hinfo", 56, 59),
        (14, 6, "ec_txns", 59, 60),
        (15, 1, "commit_wait", 61, 98),
        (16, 15, "sub_write(shard=0)", 61, 90),
        (17, 15, "sub_write(shard=1)", 61, 97),
        (18, 17, "ec_sub_write", 63, 95),
        (19, 18, "ms_recv", 63, 66),
        (20, 18, "store_apply", 66, 70),
    ]
    return [_span(base + sid, parent and base + parent, name,
                  s * ms, e * ms, tid)
            for sid, parent, name, s, e in rows]


def test_critical_path_fold():
    path = spans.critical_path(_write_tree())
    assert sum(path.values()) == pytest.approx(0.100)
    # the primary's and the critical shard's receipts
    assert path["ms_recv"] == pytest.approx(0.004 + 0.003)
    # the longer sub-write is on the path, the shorter one is not
    assert path["sub_write"] == pytest.approx(0.036 - 0.032)
    assert path["ec_sub_write"] == pytest.approx(0.032 - 0.007)
    assert path["ec_txns"] == pytest.approx(0.005)
    assert path["ec_encode"] == pytest.approx(0.0, abs=1e-12)
    assert path["tpu_device"] == pytest.approx(0.004)
    # osd_op's own: the gaps around commit_wait
    assert path["osd_op"] == pytest.approx(0.003)


def test_fold_agrees_with_the_mgr_trace_store():
    from ceph_tpu.mgr.trace_store import critical_path
    tree = _write_tree()
    mgr = dict(critical_path([dict(s, wall=s["start"]) for s in tree]))
    mine = spans.critical_path(tree)
    for stage in set(mgr) | set(mine):
        assert mgr.get(stage, 0.0) == pytest.approx(mine[stage], abs=1e-12)


def test_chain_takes_the_heaviest_set():
    kids = [_span(1, 0, "a", 0, 3), _span(2, 0, "b", 2, 4),
            _span(3, 0, "c", 3.5, 9), _span(4, 0, "d", 4, 5)]
    assert [s["name"] for s in spans.chain(kids)] == ["a", "c"]


def _run(spans_, t1=10.0, tr=None):
    return SimpleNamespace(spans=spans_, t0=T0, t1=T0 + t1, trace=tr)


def test_ops_keep_whole_trees_of_their_kind():
    done = _write_tree(tid=7)
    late = _write_tree(tid=8, base=100)
    for s in late:
        s["start"] += 9.95              # its root ends past the window
    read = [_span(201, None, "osd_op", 0, 0.02, tid=9),
            _span(202, 201, "read_gather", 0.001, 0.01, tid=9)]
    orphan = [_span(301, 999, "sub_write(shard=2)", 0, 0.01, tid=10)]
    run = _run(done + late + read + orphan)
    assert set(spans.trees(run.spans)) == {7, 8, 9}
    assert [g[0]["trace_id"] for g in spans.ops(run, "write")] == [7]
    assert [g[0]["trace_id"] for g in spans.ops(run, "read")] == [9]
    assert spans.stage_ms(run, "write", ("ms_recv",)) == pytest.approx(7.0)
    assert spans.stage_ms(run, "write", ("ec_assemble", "ec_hinfo",
                                         "ec_txns")) == pytest.approx(10.0)
    assert spans.stage_ms(run, "read", ("tpu_resume",),
                          keep=spans.reached_dispatcher) is None


def test_stage_ms_is_silent_without_the_spans():
    """A program that records no such span (an older one) reads None."""
    tree = [s for s in _write_tree() if s["name"] != "ms_recv"]
    assert spans.stage_ms(_run(tree), "write", ("ms_recv",)) is None


def test_anchor_maps_monotonic_onto_the_trace():
    tr = trace.Trace(lo=5_000_000_000, hi=5_030_000_000)
    run = _run([], tr=tr)
    assert spans.on_trace(run, T0) == tr.lo
    assert spans.on_trace(run, T0 + 0.25) == pytest.approx(5_250_000_000)
    run.spans = [_span(1, None, "tpu_queue", 0.001, 0.002),
                 _span(2, None, "tpu_queue", 0.001, 0.002, tid=8),
                 _span(3, None, "h2d", 0.003, 0.004)]
    got = spans.intervals(run, ("tpu_queue", "h2d"))
    assert got == [pytest.approx((tr.lo + 1e6, tr.lo + 2e6)),
                   pytest.approx((tr.lo + 3e6, tr.lo + 4e6))]


def test_idle_queued():
    # busy [100, 200] and [600, 700] of a [0, 1000] ns window: 800 ns
    # idle; queued [250, 450] (all idle) and h2d [650, 750] (50 idle)
    tr = trace.Trace(lo=0, hi=1000,
                     ops={0: [("x", 100, 200), ("y", 600, 700)]})
    ns = 1e-9
    run = _run([_span(1, None, "tpu_queue", 250 * ns, 450 * ns),
                _span(2, None, "h2d", 650 * ns, 750 * ns),
                _span(3, None, "compute", 100 * ns, 200 * ns)], tr=tr)
    assert spans.idle_queued_pct(run) == pytest.approx(100 * 250 / 800,
                                                       rel=1e-6)
    assert spans.idle_queued_pct(_run([], tr=tr)) is None
    assert spans.idle_queued_pct(_run(run.spans)) is None   # untraced


def test_clock_check():
    ns = 1e-9
    legs = [_span(1, None, "compute", 1000 * ns, 2000 * ns),
            _span(2, None, "compute", 5000 * ns, 6000 * ns, tid=8)]
    # the second execution overruns its leg by 50 ns: shifting the
    # spans by +50..+100 ns puts both inside; the third lies in no leg
    tr = trace.Trace(lo=0, hi=10_000, modules={0: [
        ("jit_a(1)", 1100, 1900), ("jit_a(2)", 5300, 6050),
        ("jit_a(3)", 8000, 8100)]})
    got = spans.clock_check(_run(legs, tr=tr), slack_ns=200)
    assert got["executions"] == 3
    assert got["inside_share"] == pytest.approx(2 / 3)
    assert got["fits"] == 2
    assert got["shift_ms"] == pytest.approx((50e-6, 100e-6), rel=1e-3)
    # a program the HBM tier's adoption runs lies in a tpu_finish leg
    legs.append(_span(3, None, "tpu_finish", 7900 * ns, 8200 * ns, tid=9))
    got = spans.clock_check(_run(legs, tr=tr), slack_ns=200)
    assert got["inside_share"] == 1.0 and got["fits"] == 3
    assert spans.clock_check(_run([], tr=tr)) is None


def test_recorded_trace_reduces_as_before():
    probe = trace.load(DATA, window=(0, 1))
    host = sorted(probe.host, key=lambda h: h[1])
    t = trace.load(DATA, window=(host[0][1], host[-1][2]))
    assert len(trace.executions(t, [r"^jit_matrix_encode$"])) == 4
    busy = trace.union_length(spans.device_busy(t), t.lo, t.hi)
    assert busy / 1e9 == pytest.approx(trace.busy_s(t))
    # a queued span over the whole window covers every idle stretch
    whole = _span(1, None, "tpu_queue", 0, t.window_s)
    run = SimpleNamespace(spans=[whole], t0=T0, t1=T0 + t.window_s,
                          trace=t)
    assert spans.idle_queued_pct(run) == pytest.approx(100.0)

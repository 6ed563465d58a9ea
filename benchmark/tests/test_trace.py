"""The reduction from a profiler trace to busy time, program time and
the breakdown: on hand-made intervals, and on a small trace recorded on
a TPU v5 lite (two encodes of [16, 8, 131072] and two decodes of one
1 MiB object with 3 erasures, RS k=8 m=3, each inside its
bench:codec.* host span)."""

import os
from types import SimpleNamespace

import pytest

from benchmark import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "codec_v5e.xplane.pb")


def test_merge_overlaps_and_edges():
    ivs = [(5, 7), (0, 2), (1, 3), (6, 9), (20, 30)]
    assert trace.merge(ivs, 0, 100) == [(0, 3), (5, 9), (20, 30)]
    # the window cuts intervals at its edges and drops those outside
    assert trace.merge(ivs, 2, 25) == [(2, 3), (5, 9), (20, 25)]
    assert trace.merge(ivs, 10, 19) == []
    assert trace.union_length(ivs, 0, 100) == 3 + 4 + 10
    assert trace.union_length(ivs, 2, 25) == 1 + 4 + 5


def test_gaps():
    ivs = [(5, 7), (1, 3), (6, 9)]
    assert trace.gaps(ivs, 0, 10) == [(0, 1), (3, 5), (9, 10)]
    assert trace.gaps(ivs, 2, 8) == [(3, 5)]
    assert trace.gaps([], 0, 4) == [(0, 4)]
    assert trace.gaps([(0, 10)], 2, 8) == []


def _hand_trace():
    return trace.Trace(
        lo=100, hi=200,
        modules={0: [("jit_a(1)", 90, 110), ("jit_b(2)", 120, 150),
                     ("jit_a(3)", 190, 230)]},
        ops={0: [("x", 90, 110), ("y", 120, 130), ("z", 140, 150),
                 ("w", 190, 230)]},
        host=[("client.write", 95, 125), ("client.write", 100, 160),
              ("client.read", 150, 195)])


def test_busy_and_executions_at_the_window_edge():
    t = _hand_trace()
    # ops clipped to [100, 200]: 10 + 10 + 10 + 10
    assert trace.busy_s(t) == pytest.approx(40e-9)
    # an execution counts when it starts inside the window
    names = [n for n, _, _ in trace.executions(t, [r"^jit_a$"])]
    assert names == ["jit_a(3)"]
    assert len(trace.executions(t, [r"^jit_"])) == 2


def test_breakdown_labels_gaps_with_host_spans():
    b = trace.breakdown(_hand_trace())
    assert b["device_ops"][0] == ["jit_b", pytest.approx(30e-9)]
    gaps = dict((round(s * 1e9), lbl) for lbl, s in b["idle_gaps"])
    assert set(gaps) == {40, 10}       # [150,190] and [130,140]
    assert gaps[40] == "client.read x1 client.write x1"


def test_recorded_trace():
    probe = trace.load(DATA, window=(0, 1))       # find the host spans
    spans = sorted(probe.host, key=lambda h: h[1])
    t = trace.load(DATA, window=(spans[0][1], spans[-1][2]))
    assert [n for n, _, _ in spans] == ["codec.encode", "codec.decode"] * 2
    ex = trace.executions(t, [r"^jit_matrix_encode$"])
    assert len(ex) == 4
    enc = trace.inside(ex, trace.host_spans(t, "codec.encode"))
    dec = trace.inside(ex, trace.host_spans(t, "codec.decode"))
    assert len(enc) == 2 and len(dec) == 2
    assert not set(enc) & set(dec)
    # every device event lies inside the host span that launched it:
    # the host and device planes share one clock
    assert sum(e - s for _, s, e in enc) == pytest.approx(521921, abs=2)
    assert sum(e - s for _, s, e in dec) == pytest.approx(166731, abs=2)
    assert 0 < trace.busy_s(t) < t.window_s
    b = trace.breakdown(t)
    assert b["device_ops"][0][0] == "jit_matrix_encode"
    assert len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][1] >= b["idle_gaps"][-1][1]


def test_roofline_reader_on_recorded_trace():
    """encode_roofline.codec over the recorded trace: 2 calls of
    16 x (8 + 3) x 131072 B in 521921 ns of device time."""
    from benchmark import run as run_mod
    probe = trace.load(DATA, window=(0, 1))
    spans = sorted(probe.host, key=lambda h: h[1])
    t = trace.load(DATA, window=(spans[0][1], spans[-1][2]))
    from benchmark.reference import Code
    r = SimpleNamespace(
        trace=t, peaks={"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12},
        traffic={"batch": 16, "object_size": 1 << 20, "erasures": 3},
        code=Code({"plugin": "jax_tpu", "technique": "reed_sol_van",
                   "k": "8", "m": "3"}))
    enc = run_mod.reader(run_mod.HERE, "encode_roofline.codec")(r)
    want = 100 * 2 * 16 * 11 * 131072 / 819e9 / 521921e-9
    assert enc == pytest.approx(want, rel=1e-4)
    dec = run_mod.reader(run_mod.HERE, "decode_roofline.codec")(r)
    assert dec == pytest.approx(100 * 2 * 11 * 131072 / 819e9 / 166731e-9,
                                rel=1e-4)
    assert 0 < dec < enc < 100

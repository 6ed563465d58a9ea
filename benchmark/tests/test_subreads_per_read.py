"""The subreads_per_read readers (benchmark/metrics/subreads_per_read.*)
on synthetic span groups: the mean count of `sub_read(shard=N)`
children over the window's read trees, write trees left out."""

import os
from types import SimpleNamespace

import pytest

from benchmark import run as run_mod

T0 = 1000.0
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _span(tid, sid, parent, name, start, end):
    return {"trace_id": tid, "span_id": sid, "parent_id": parent,
            "name": name, "start": T0 + start, "duration": end - start}


def _read(tid, shards):
    """A read's tree at its primary: the op, its gather, one sub-read
    per shard asked, and a replica's ec_sub_read under each."""
    out = [_span(tid, 1, None, "osd_op", 0.0, 0.02),
           _span(tid, 2, 1, "read_gather", 0.001, 0.015)]
    for i, shard in enumerate(shards):
        out.append(_span(tid, 10 + i, 2, "sub_read(shard=%d)" % shard,
                         0.001, 0.014))
        out.append(_span(tid, 30 + i, 10 + i, "ec_sub_read", 0.002, 0.013))
    return out


def _write(tid):
    """A write's tree: its sub-writes are no sub-reads."""
    return [_span(tid, 1, None, "osd_op", 0.0, 0.02),
            _span(tid, 2, 1, "commit_wait", 0.005, 0.019)] + \
        [_span(tid, 10 + s, 2, "sub_write(shard=%d)" % s, 0.005, 0.018)
         for s in range(6)]


@pytest.mark.parametrize("metric", ["subreads_per_read.rbd",
                                    "subreads_per_read.read"])
@pytest.mark.parametrize("per_read,expect", [
    ([[2]], 1.0),
    ([[0, 1, 2, 3]], 4.0),
    ([list(range(8)), list(range(8))], 8.0),
    # one narrowed read, one that asked the whole stripe: the mean
    ([[1], [0, 1, 2, 3]], 2.5),
])
def test_mean_subreads_over_read_trees(metric, per_read, expect):
    spans_ = [s for i, shards in enumerate(per_read)
              for s in _read(100 + i, shards)]
    spans_ += _write(7) + _write(8)
    run = SimpleNamespace(spans=spans_, t0=T0, t1=T0 + 10.0)
    assert run_mod.reader(BENCH, metric)(run) == pytest.approx(expect)


@pytest.mark.parametrize("metric", ["subreads_per_read.rbd",
                                    "subreads_per_read.read"])
def test_silent_without_read_trees(metric):
    read = run_mod.reader(BENCH, metric)
    assert read(SimpleNamespace(spans=[], t0=T0, t1=T0 + 10.0)) is None
    writes_only = SimpleNamespace(spans=_write(7), t0=T0, t1=T0 + 10.0)
    assert read(writes_only) is None

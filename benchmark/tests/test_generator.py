"""The generator's draws: op kinds in fixed shares, skewed keys, open
loop arrivals, and the driver each traffic file names."""

import json
import os
import time

import numpy as np
import pytest

from benchmark import generator, run

SEED = 3_000_000_019


def test_kinds_hold_each_share_in_every_block():
    mix = [{"op": "read", "share": 0.7}, {"op": "write_full", "share": 0.3}]
    a = np.array(generator.kinds(mix, SEED, n=1000))
    for block in a.reshape(10, 100):
        assert (block == 0).sum() == 70
    b = np.array(generator.kinds(mix, SEED + 1, n=1000))
    assert not np.array_equal(a, b)           # another order, same shares
    with pytest.raises(ValueError):
        generator.kinds([{"op": "read", "share": 0.5}], SEED)


def test_keys_uniform_and_zipf():
    u = generator.keys({"dist": "uniform"}, 64, SEED, n=20000)
    assert u.min() == 0 and u.max() == 63
    s = generator.keys({"dist": "shuffled"}, 64, SEED, n=20000)
    for block in s[:19968].reshape(-1, 64):
        assert sorted(block) == list(range(64))   # each object once a pass
    t = generator.keys({"dist": "shuffled"}, 64, SEED + 1, n=20000)
    assert not np.array_equal(s, t)           # another order, same objects
    z = generator.keys({"dist": "zipf", "s": 1.1}, 64, SEED, n=20000)
    counts = np.sort(np.bincount(z, minlength=64))[::-1]
    # the most popular object takes about 1 / H(64, 1.1) of the reads
    h = (1.0 / np.arange(1, 65) ** 1.1).sum()
    assert counts[0] / 20000 == pytest.approx(1 / h, rel=0.1)
    assert np.array_equal(z, generator.keys({"dist": "zipf", "s": 1.1}, 64,
                                            SEED, n=20000))


def test_open_loop_offers_its_rate_and_counts_the_queue():
    """20 ops/s for 1 s into one slow server: every op arrives on time,
    and latency counts from arrival, so the queue shows."""
    def slow(op):
        op.kind = "x"
        time.sleep(0.1)
        return True
    ops, t0, t1 = generator.open_loop(20.0, 1, 1.0, slow,
                                      lambda name: _null(), 5.0)
    assert len(ops) == 19                 # arrivals at 0.05 .. 0.95 s
    assert all(abs(op.start - (t0 + 0.05 * (op.index + 1))) < 1e-9
               for op in ops)
    last = max(ops, key=lambda op: op.index)
    assert last.end - last.start > 0.5    # it waited behind the others


def test_every_traffic_file_names_a_driver():
    tdir = os.path.join(run.HERE, "traffic")
    for name in os.listdir(tdir):
        with open(os.path.join(tdir, name)) as f:
            t = json.load(f)
        assert os.path.exists(os.path.join(run.HERE, "drivers",
                                           t["driver"] + ".py")), name


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False

"""A run with the timed path broken underneath comes out not correct:
each fault a cell can have, planted under a tiny CPU run of the cell
(the chip check left out, as in test_rehearsal)."""

import pytest

from benchmark import faults
from benchmark.tests.test_rehearsal import cpu_run, tiny

CASES = [
    ("rs_k8m3.write_4m", "parity", "shard_mismatch"),
    ("rs_k8m3.write_4m", "unapplied", "readback_mismatch"),
    ("lrc_k4m2l3.write_4m", "parity", "shard_mismatch"),
    ("lrc_k4m2l3.write_4m", "unapplied", "readback_mismatch"),
    ("rs_k8m3.degraded_read_4m", "read", "read_mismatch"),
    ("rs_k8m3.ecbench_1m", "half_batch", "parity_mismatch"),
    ("rs_k8m3.ecbench_1m", "codec_byte", "decode_mismatch"),
]


@pytest.mark.parametrize("name,fault,check", CASES)
def test_fault_is_caught(name, fault, check):
    with faults.planted(fault):
        res = cpu_run(tiny(name))
    assert res["correct"] is False
    assert res["checks"][check]["value"] > res["checks"][check]["limit"]


def test_control_runs_every_seed_on_one_set_up():
    """control.py's loop: one set-up, then a faulted window per seed,
    each not correct."""
    from benchmark import control
    cell = tiny("rs_k8m3.ecbench_1m")
    lines = list(control.control(cell, [11, 3_000_000_029], 1.0,
                                 "codec_byte"))
    assert [ln["seed"] for ln in lines] == [11, 3_000_000_029]
    assert all(ln["correct"] is False for ln in lines)

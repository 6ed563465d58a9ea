"""Each cell's traffic, rehearsed end to end on the CPU at a tiny size:
the cell's configuration and traffic files as committed, with only the
sizes shrunk, through `run.run_cell` with the chip check left out
(`run.main` itself fails without a TPU; see test_no_chip)."""

import json
import subprocess
import sys

import pytest

from benchmark import run

SEED = 3_000_000_019          # beyond 32 signed bits
CELLS = ["rs_k8m3.write_4m", "lrc_k4m2l3.write_4m",
         "rs_k8m3.degraded_read_4m", "rs_k8m3.ecbench_1m"]
CPU_PEAKS = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}


def tiny(name: str):
    """The cell as committed, with its sizes cut for the CPU."""
    cell = run.load_cell(name)
    cell.config["pool"]["pg_num"] = 16
    t = cell.traffic
    if t["driver"] == "rados":
        t.update(object_size=65536, distinct_payloads=4, warmup_ops=4,
                 check_objects=4,
                 prefill_objects=min(t["prefill_objects"], 16))
        t["arrival"] = dict(t["arrival"], in_flight=4)
    else:
        t.update(object_size=65536, batch=4, distinct_batches=2)
    return cell


def cpu_run(cell, seconds=2.0, traced=False, seed=SEED):
    import jax
    return run.run_cell(cell, seed, seconds, traced, jax.devices()[:1],
                        CPU_PEAKS)


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal(name):
    cell = tiny(name)
    res = cpu_run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == want
    assert res["metrics"]["setup_s"]["value"] > 0
    assert list(res)[-1] == "checks"
    json.dumps(res)


def test_traced_rehearsal():
    """A traced run on the CPU: the trace has no TPU plane, so the
    device readers find nothing and leave their metrics out."""
    cell = tiny("rs_k8m3.write_4m")
    res = cpu_run(cell, traced=True)
    assert res["correct"], res["checks"]
    assert "op_p95_ms.write" in res["metrics"]
    assert "osd_queue_ms.write" in res["metrics"]
    assert "ec_encode_ms.write" in res["metrics"]
    assert "device_idle.write" in res["metrics"]
    assert "fused_write_roofline" not in res["metrics"]
    assert res["device"]["window_s"] > 0


def test_no_chip():
    """Without a TPU the run exits non-zero and prints no result."""
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "rs_k8m3.write_4m", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=run.ROOT, capture_output=True, text=True,
        timeout=120, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU found" in p.stderr


def test_data_only_traffic_rehearsal():
    """A traffic the rados driver's parameters describe, with no code
    of its own: 70% whole-object reads and 30% 16 KiB range reads over
    Zipf-skewed objects with one OSD down, offered at a fixed rate, and
    writes mixed in; every op checked."""
    cell = tiny("rs_k8m3.degraded_read_4m")
    cell.traffic.update(
        mix=[{"op": "read", "share": 0.5},
             {"op": "read", "share": 0.3, "length": 16384},
             {"op": "write_full", "share": 0.2}],
        keys={"dist": "zipf", "s": 1.1}, down_osds=[0],
        arrival={"kind": "open", "rate_per_s": 20.0, "max_in_flight": 8})
    res = cpu_run(cell, seconds=3.0)
    assert res["correct"], res["checks"]
    assert {"read_mismatch", "readback_mismatch",
            "shard_mismatch"} <= set(res["checks"])
    # an open loop offers its rate whatever the system does
    assert res["attempted"] == 59
    assert {"read_MBps", "setup_s"} <= set(res["metrics"])


def test_sweep_offers_each_rate_on_one_set_up():
    """sweep.py's loop: one set-up, then an open-loop window per rate."""
    from benchmark import sweep
    lines = list(sweep.sweep(tiny("rs_k8m3.write_4m"), SEED, 1.0,
                             [5.0, 10.0], 8))
    assert [ln["rate_per_s"] for ln in lines] == [5.0, 10.0]
    assert [ln["offered"] for ln in lines] == [4, 9]
    assert all(ln["correct"] and ln["p95_ms"] > 0 for ln in lines)


def test_seeds_runs_each_window_on_one_set_up():
    """seeds.py's loop: one set-up, then a window per seed and key
    distribution, each with the window's decode counters."""
    from benchmark import seeds
    lines = list(seeds.windows(tiny("rs_k8m3.degraded_read_4m"),
                               [(SEED, "-"), (SEED + 1, "shuffled")], 1.0))
    assert [ln["seed"] for ln in lines] == [SEED, SEED + 1]
    assert lines[1]["keys"] == {"dist": "shuffled"}
    assert all(ln["correct"] and ln["done"] > 0 for ln in lines)
    assert all(ln["xor_rebuilds"] is not None for ln in lines)

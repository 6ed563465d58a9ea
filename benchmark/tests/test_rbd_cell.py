"""The RBD-on-EC cell: its files load by name, its traffic rehearses
end to end on the CPU at a tiny size, its three readers read a
synthetic run, and the reference's k=4 m=2 code is the program's
oracle's."""

import types

import numpy as np
import pytest

from benchmark import reference, run
from benchmark.drivers import rbd as rbd_driver

from .test_rehearsal import SEED, cpu_run

CELL = "rbd_ec_k4m2.randrw_4k"
LAYER = ("ec_rmw_read_ms.rbd", "rmw_read_bytes_per_write.rbd",
         "rbd_inflight.rbd")


def tiny():
    """The cell as committed, with its sizes cut for the CPU."""
    cell = run.load_cell(CELL)
    cell.config["pool"]["pg_num"] = 16
    cell.config["metadata_pool"]["pg_num"] = 8
    cell.config["image_size"] = 16 << 20
    cell.traffic.update(warmup_ops=64, check_blocks=64, check_objects=2,
                        distinct_payloads=4)
    cell.traffic["arrival"] = dict(cell.traffic["arrival"], in_flight=8)
    return cell


def test_cell_loads_by_name():
    cell = run.load_cell(CELL)
    assert cell.chips == 1
    assert cell.traffic["driver"] == "rbd"
    assert cell.traffic["keys"] == {"dist": "zipf", "s": 0.99}
    assert cell.traffic["arrival"] == {"kind": "closed", "in_flight": 32}
    assert [(e["op"], e["share"]) for e in cell.traffic["mix"]] == \
        [("read", 0.7), ("write", 0.3)]
    assert cell.config["image_size"] == 2 << 30
    assert cell.config["pool"] == dict(cell.config["pool"], pg_num=256,
                                       stripe_unit=4096)
    code = reference.Code(cell.config["profile"])
    assert (code.k, code.n) == (4, 6)
    assert {m["name"] for m in cell.end_to_end} == {"read_MBps", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(LAYER)


def test_rehearsal():
    res = cpu_run(tiny(), seconds=3.0)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["checks"]) == {"failed_ops", "read_mismatch",
                                  "readback_mismatch", "shard_mismatch"}
    assert set(res["metrics"]) == {"read_MBps", "setup_s"}


def test_traced_rehearsal_reads_every_layer_metric():
    res = cpu_run(tiny(), seconds=3.0, traced=True, seed=SEED + 1)
    assert res["correct"], res["checks"]
    got = res["metrics"]
    assert set(LAYER) <= set(got), got
    assert got["ec_rmw_read_ms.rbd"]["value"] > 0
    # a 4 KiB overwrite reads back its stripe's k = 4 chunks of 4 KiB
    assert got["rmw_read_bytes_per_write.rbd"]["value"] == 16384
    assert got["rbd_inflight.rbd"]["value"] > 2


def test_a_program_without_data_pools_fails_before_boot(monkeypatch):
    from ceph_tpu.client import rbd

    def create(ioctx, name, size, order=22, features=()):
        raise AssertionError("never called")
    monkeypatch.setattr(rbd.RBD, "create", staticmethod(create))
    cell = run.load_cell(CELL)
    with pytest.raises(TypeError):
        rbd_driver.Load(cell.config, cell.traffic, SEED, print)


def _reader(name):
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(run.HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _span(trace, sid, parent, name, start, dur):
    return {"trace_id": trace, "span_id": sid, "parent_id": parent,
            "name": name, "start": start, "duration": dur}


def synthetic_run(with_program=True):
    """Two window writes, one with a 4 ms read-back and one with a
    6 ms one; counters of two OSDs and the image handle."""
    spans = []
    for t, rmw in ((1, 0.004), (2, 0.006)):
        spans += [_span(t, 10 * t, 0, "osd_op", 1.0, 0.020),
                  _span(t, 10 * t + 1, 10 * t, "ec_wait", 1.0, 0.001),
                  _span(t, 10 * t + 2, 10 * t,
                        "ec_rmw_read" if with_program else "ec_wait",
                        1.001, rmw),
                  _span(t, 10 * t + 3, 10 * t, "ec_encode", 1.001 + rmw,
                        0.002),
                  _span(t, 10 * t + 4, 10 * t, "commit_wait", 1.003 + rmw,
                        0.005)]
    counters = {"osd.0": {"l_osd_ec_rmw_ops": 3,
                          "l_osd_ec_rmw_read_bytes": 3 * 16384},
                "osd.1": {"l_osd_ec_rmw_ops": 1,
                          "l_osd_ec_rmw_read_bytes": 16384},
                "librbd": {"l_librbd_rd": 70, "l_librbd_wr": 30,
                           "l_librbd_inflight_s": 300.0},
                "xor_rebuilds": 0}
    if not with_program:
        counters = {"osd.0": {"l_osd_ec_rmw_ops": 0,
                              "l_osd_ec_rmw_read_bytes": 0},
                    "librbd": {"l_librbd_rd": 0, "l_librbd_wr": 0,
                               "l_librbd_inflight_s": 0},
                    "xor_rebuilds": 0}
    return types.SimpleNamespace(spans=spans, counters=counters, t0=0.0,
                                 t1=10.0, seconds=10.0)


def test_readers_on_a_synthetic_run():
    r = synthetic_run()
    assert _reader("ec_rmw_read_ms.rbd")(r) == pytest.approx(5.0)
    assert _reader("rmw_read_bytes_per_write.rbd")(r) == 16384
    assert _reader("rbd_inflight.rbd")(r) == pytest.approx(30.0)


def test_readers_find_nothing_on_a_program_without_the_layer():
    r = synthetic_run(with_program=False)
    for name in LAYER:
        assert _reader(name)(r) is None, name


def test_reference_rs_k4m2_is_the_oracles_code():
    from ceph_tpu.ops import gf, gf_ref
    code = reference.Code({"plugin": "jax_tpu", "technique": "reed_sol_van",
                           "k": "4", "m": "2"})
    data = np.random.default_rng(26).integers(0, 256, (4, 4096),
                                              dtype=np.uint8)
    want = gf_ref.matrix_encode_ref(gf.rs_vandermonde_generator(4, 2, 8),
                                    data, 8)
    assert np.array_equal(code.parity(data), want)
    # and whole objects: stripes of k chunks, shards by position
    obj = np.random.default_rng(27).bytes(4 * 4096 * 3)
    shards = reference.shards(obj, code, 4096)
    raw = np.frombuffer(obj, np.uint8).reshape(3, 4, 4096)
    assert np.array_equal(shards[1], raw[:, 1].reshape(-1))
    assert np.array_equal(
        shards[4:], gf_ref.matrix_encode_ref(
            gf.rs_vandermonde_generator(4, 2, 8),
            raw.transpose(1, 0, 2).reshape(4, -1), 8))

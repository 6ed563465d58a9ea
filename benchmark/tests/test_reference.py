"""The reference code, the work counts of the kernel metrics, and the
peaks table."""

import itertools
import json
import os

import numpy as np
import pytest

from benchmark import device, readers, reference, run
from benchmark.codes import lrc, reed_sol_van

MiB = 1 << 20
RS = {"plugin": "jax_tpu", "technique": "reed_sol_van", "k": "8", "m": "3"}
LRC = {"plugin": "lrc_tpu", "k": "4", "m": "2", "l": "3"}


def _reader_module(name):
    import importlib.util
    path = os.path.join(run.HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_gf_field():
    for a in range(1, 256):
        assert reference.gf_mul(a, reference.gf_inv(a)) == 1
    assert reference.gf_mul(0x80, 2) == 0x1D      # x^8 = x^4+x^3+x^2+1


def test_rs_generator_is_mds_with_an_all_ones_row():
    k, m = 8, 3
    gen = reed_sol_van.rs_vandermonde(k, m)
    assert list(gen[0]) == [1] * k
    full = np.vstack([np.eye(k, dtype=np.uint8), gen])
    for rows in itertools.combinations(range(k + m), k):
        reference.invert(full[list(rows)])       # raises when singular


def test_lrc_layout_is_cephs_kml_expansion():
    mapping, layers = lrc.lrc_layout(4, 2, 3)
    assert mapping == "DD__DD__"
    assert layers == ["DDc_DDc_", "DDDc____", "____DDDc"]
    code = reference.Code(LRC)
    assert code.n == 8 and code.data_positions == [0, 1, 4, 5]
    data = np.random.default_rng(1).integers(0, 256, (4, 64), dtype=np.uint8)
    full = code.encode(data)
    # each local parity is the XOR of its group
    assert np.array_equal(full[3], full[0] ^ full[1] ^ full[2])
    assert np.array_equal(full[7], full[4] ^ full[5] ^ full[6])


def test_codes_are_found_by_plugin_and_technique():
    assert reference.code_module(RS) is reed_sol_van
    assert reference.code_module(dict(RS, plugin="jerasure")) is reed_sol_van
    assert reference.code_module(LRC) is lrc
    # the same technique name in another plugin is another code
    with pytest.raises(ValueError):
        reference.code_module(dict(RS, plugin="isa"))
    with pytest.raises(ValueError):
        reference.Code(dict(RS, technique="cauchy_good"))


@pytest.mark.parametrize("profile", [RS, dict(RS, plugin="jerasure"), LRC],
                         ids=["jax_tpu", "jerasure", "lrc_tpu"])
def test_reference_equals_program_codec_on_cpu(profile):
    """On the CPU the program's codec and the reference agree: the
    served checks then fail only where the program does."""
    from ceph_tpu import registry
    prof = dict(profile)
    codec = registry.factory(prof.pop("plugin"), prof)
    code = reference.Code(profile)
    data = np.random.default_rng(7).integers(0, 256, (3, code.k, 4096),
                                             dtype=np.uint8)
    got = np.asarray(codec.encode_batch(data))
    for b in range(3):
        assert np.array_equal(got[b], code.parity(data[b]))


def test_striping():
    obj = bytes(range(256)) * 512                  # 128 KiB
    rows = reference.stripe(obj, 8, 4096)
    assert rows.shape == (8, 16384)
    assert bytes(rows[1, :4096]) == obj[4096:8192]
    assert bytes(rows[0, 4096:8192]) == obj[32768:36864]


PEAKS = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}


def test_fused_write_work():
    m = _reader_module("fused_write_roofline")
    digests = 4 * 11 + 2 * 4 * 128 * 8
    assert m.work_bytes(4 * MiB, 8, 3, 4096) == 4194304 + 1572864 + digests
    # encode: 24 x 64 bit matrix over 512 KiB rows; crc32 over the 11
    # shards, crc32c over the 8 data shards: 32 x 8 bits a byte
    gf = 2 * 24 * 64 * 524288
    crc = 2 * 256 * (11 + 8) * 524288
    assert m.work_ops(4 * MiB, 8, 3) == gf + crc
    # the operations bind: 17.1 us at the int8 peak against 7.05 us of
    # HBM traffic
    least, bound = readers.least_seconds(m.work_bytes(4 * MiB, 8, 3, 4096),
                                         m.work_ops(4 * MiB, 8, 3), PEAKS)
    assert bound == "ops" and least == pytest.approx(17.076e-6, rel=1e-3)


def test_encode_work():
    m = _reader_module("encode_roofline.codec")
    # RS k=8 m=3, one 4 MiB object: data read once, parity written once
    assert m.work_bytes(1, 8, 3, 4 * MiB // 8) == 4194304 + 1572864
    assert m.work_bytes(16, 8, 3, MiB // 8) == 16 * 11 * 131072
    assert m.work_ops(16, 8, 3, MiB // 8) == 16 * 2 * 24 * 64 * 131072
    assert readers.least_seconds(m.work_bytes(16, 8, 3, MiB // 8),
                                 m.work_ops(16, 8, 3, MiB // 8),
                                 PEAKS)[1] == "bytes"


def test_decode_work():
    m = _reader_module("decode_roofline.codec")
    # a 1 MiB object, 3 erasures: 8 survivors read, 3 chunks written
    assert m.work_bytes(8, 3, MiB // 8) == 11 * 131072
    assert m.work_ops(8, 3, MiB // 8) == 2 * 24 * 64 * 131072
    r = _reader_module("decode_roofline.read")
    assert r.work_bytes(8, 2, 4 * MiB // 8) == 10 * 524288
    assert r.work_ops(8, 2, 4 * MiB // 8) == 2 * 16 * 64 * 524288
    for lost in (1, 2, 3):
        assert readers.least_seconds(r.work_bytes(8, lost, 524288),
                                     r.work_ops(8, lost, 524288),
                                     PEAKS)[1] == "bytes"
    assert r.on_device(2, False) and r.on_device(1, True)
    assert not r.on_device(1, False) and not r.on_device(0, True)


def test_lrc_encode_work():
    """LRC k=4 m=2 l=3: one 4 MiB object's 4 data chunks read and its
    4 parity chunks (2 global, 2 local) written."""
    m = _reader_module("encode_roofline.write")
    assert m.work_bytes(4 * MiB, 4, 8) == 8 * MiB
    assert m.work_ops(4 * MiB, 4, 8) == 2 * 32 * 32 * MiB
    assert readers.least_seconds(8 * MiB, 2 * 32 * 32 * MiB,
                                 PEAKS)[1] == "bytes"


def test_roofline_takes_the_longer_bound():
    run = type("R", (), {"peaks": PEAKS})()
    # 819 kB in 1 ms: 1 us of HBM traffic, the operations 0.5 us
    assert readers.roofline_pct(819e3, 196.5e6, 1e-3, run) == \
        pytest.approx(0.1)
    # the same bytes with 1.965e9 operations: 5 us
    assert readers.roofline_pct(819e3, 1.965e9, 1e-3, run) == \
        pytest.approx(0.5)
    assert readers.roofline_pct(0, 1, 1e-3, run) is None


def test_peaks_table():
    p = device.peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    with open(os.path.join(run.HERE, "peaks.json")) as f:
        assert "Google Cloud" in json.load(f)["source"]


def test_unknown_device_kind_fails():
    with pytest.raises(device.NoChip):
        device.peaks_for("TPU v9 imaginary")

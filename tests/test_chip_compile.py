"""The main path's kernels compile for a TPU v5e at real widths.

Nothing runs here: the v5e is described (jax.experimental.topologies)
and the TPU compiler, which is installed with jax, lowers and compiles
each kernel for it. What the chip's compiler would refuse (a program
that does not fit HBM, an unaligned slice, a kernel it cannot
partition) fails here at no chip time. The topology is described
inside a fixture so that importing this file loads no TPU library; the
persistent compile cache stays off around these compiles, since an
entry compiled for a described chip cannot be read back without one.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from ceph_tpu import registry

K, M, W = 8, 3, 8
GB = 1 << 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def compile_for(one_chip, no_cache):
    import jax

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compile_fn(fn, *specs, **static):
        args = [sds(shape, dtype) for shape, dtype in specs]
        return fn.lower(*args, **static).compile()
    return compile_fn


@pytest.fixture(scope="module")
def codec():
    return registry.factory("jax_tpu", {"technique": "reed_sol_van",
                                        "k": str(K), "m": str(M),
                                        "w": str(W)})


@pytest.mark.parametrize("bitmat_rows", [M * W, K * W, (K + M) * W],
                         ids=["encode", "decode_data", "decode_full"])
def test_matrix_encode_compiles_at_bench_shape(compile_for, bitmat_rows):
    """encode ([24,64] generator) and decode ([64,64] / [88,64] decode
    bitmatrices) over the EC benchmark's batch: 16 x 1 MiB objects."""
    from ceph_tpu.ops import xor_mm
    compiled = compile_for(xor_mm.matrix_encode.__wrapped__,
                           ((bitmat_rows, K * W), np.uint8),
                           ((16, K, 131072), np.uint8), w=W)
    assert compiled.memory_analysis().temp_size_in_bytes < GB


@pytest.mark.parametrize("shape", [(1, K, 524288), (128, K, 4096)],
                         ids=["one_stripe", "stripe_unit_4k"])
def test_fused_store_program_fits_beside_residency(compile_for, codec,
                                                   shape):
    """The fused write program for one 4 MiB object, in both batch
    layouts the OSD can stage, stays far below the chip's 16 GB (the
    OSDs' resident chunks share it).

    memory_analysis() for a described v5e reads 0.27 GB of temporaries
    in both layouts with the lane-major CRC tree, against 3.06 GB
    ([1,8,524288]) and 3.29 GB ([128,8,4096]) when every tree level
    ended in an axis of 2; the 1 GB bound holds that repair."""
    from ceph_tpu.osd import fused_transform as ft
    z = ft._poly_consts(ft._POLY_ZLIB)
    c = ft._poly_consts(ft._POLY_C)
    S, k, chunk = shape
    compiled = compile_for(
        ft._build_program(False),
        (shape, np.uint8), (codec._bitmat.shape, codec._bitmat.dtype),
        (z.table.shape, z.table.dtype), (z.inv.shape, z.inv.dtype),
        (c.table.shape, c.table.dtype), ((), np.uint32), ((), np.uint32),
        w=W, mode="store", required_milli=875, entropy_max_milli=7000,
        cap2=S * k * chunk, stripe_width=k * chunk)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 8 * GB
    assert temp < GB


@pytest.mark.parametrize("program", ["rmw_encode", "fused_object"])
def test_rs_k4m2_rbd_programs_compile(compile_for, program):
    """The RBD data pool's RS k=4 m=2 programs: the coalesced encode
    of eight 16 KiB sub-stripe read-modify-writes, and the fused write
    program of a whole 4 MiB image object (the prefill)."""
    from ceph_tpu.osd import fused_transform as ft
    from ceph_tpu.ops import xor_mm
    k, m = 4, 2
    rs42 = registry.factory("jax_tpu", {"technique": "reed_sol_van",
                                        "k": str(k), "m": str(m),
                                        "w": str(W)})
    if program == "rmw_encode":
        compiled = compile_for(xor_mm.matrix_encode.__wrapped__,
                               ((m * W, k * W), np.uint8),
                               ((8, k, 4096), np.uint8), w=W)
    else:
        z = ft._poly_consts(ft._POLY_ZLIB)
        c = ft._poly_consts(ft._POLY_C)
        shape = (256, k, 4096)
        compiled = compile_for(
            ft._build_program(False),
            (shape, np.uint8), (rs42._bitmat.shape, rs42._bitmat.dtype),
            (z.table.shape, z.table.dtype), (z.inv.shape, z.inv.dtype),
            (c.table.shape, c.table.dtype), ((), np.uint32),
            ((), np.uint32), w=W, mode="store", required_milli=875,
            entropy_max_milli=7000, cap2=256 * k * 4096,
            stripe_width=k * 4096)
    assert compiled.memory_analysis().temp_size_in_bytes < GB


def test_crush_indep_kernel_compiles(compile_for):
    """The bulk CRUSH indep kernel (x64 fixed-point draws) for a small
    two-level straw2 map: chooseleaf indep 11 over host. Its compile
    time (~25 s here) does not shrink with the map or batch size."""
    import jax

    from ceph_tpu.crush import batched
    from ceph_tpu.crush import map as cmap_mod
    from ceph_tpu.crush.map import CrushMap, Rule
    hosts, per, numrep = 12, 4, K + M
    m = CrushMap()
    m.type_names = {"osd": 0, "host": 1, "root": 2}
    ids = [m.add_bucket("straw2", 1, list(range(h * per, h * per + per)),
                        [0x10000] * per, id=-2 - h) for h in range(hosts)]
    m.add_bucket("straw2", 2, ids, [per * 0x10000] * hosts, id=-1,
                 name="default")
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSELEAF_INDEP, numrep, 1),
                           (cmap_mod.RULE_EMIT,)]))
    cm = batched.compile_map(m)
    kernel = batched._indep_kernel(cm, numrep, numrep, 1, True, 50, 1)
    with jax.enable_x64(True):
        compiled = compile_for(
            kernel.__wrapped__,
            (cm.items.shape, np.int64), (cm.ids.shape, np.int64),
            (cm.wsets.shape, np.int64), (cm.size.shape, np.int64),
            (cm.btype.shape, np.int64), ((1024,), np.int64),
            ((hosts * per,), np.int64), ((), np.int64))
    assert compiled.memory_analysis() is not None

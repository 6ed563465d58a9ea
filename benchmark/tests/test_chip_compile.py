"""The device programs of the cells compile for a described TPU v5e at
the cells' sizes. Nothing runs: the TPU compiler lowers each program
for the described chip, and what the chip's compiler would refuse
fails here. The topology is described inside a fixture, and the
persistent compile cache stays off around these compiles."""

from __future__ import annotations

import os

import numpy as np
import pytest

GB = 1 << 30
MiB = 1 << 20


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
def compile_for(topo):
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_fn(fn, *specs, **static):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in specs]
        return fn.lower(*args, **static).compile()
    yield compile_fn
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def test_fused_write_program_rs_4m(compile_for):
    """rs_k8m3.write_4m: one 4 MiB object, 128 stripes of 8 x 4096."""
    from ceph_tpu import registry
    from ceph_tpu.osd import fused_transform as ft
    codec = registry.factory("jax_tpu", {"technique": "reed_sol_van",
                                         "k": "8", "m": "3"})
    z, c = ft._poly_consts(ft._POLY_ZLIB), ft._poly_consts(ft._POLY_C)
    compiled = compile_for(
        ft._build_program(True),
        ((128, 8, 4096), np.uint8), (codec._bitmat.shape, np.uint8),
        (z.table.shape, z.table.dtype), (z.inv.shape, z.inv.dtype),
        (c.table.shape, c.table.dtype), ((), np.uint32), ((), np.uint32),
        w=8, mode="store", required_milli=875, entropy_max_milli=7000,
        cap2=4 * MiB, stripe_width=8 * 4096)
    assert compiled.memory_analysis().temp_size_in_bytes < GB


@pytest.mark.parametrize("bitmat,data", [
    ((24, 64), (16, 8, MiB // 8)),       # ecbench encode, 16 x 1 MiB
    ((88, 64), (1, 8, MiB // 8)),        # ecbench decode, one 1 MiB object
    ((32, 32), (8 * 256, 4, 4096)),      # lrc write, 8 coalesced objects
    ((88, 64), (8 * 128, 8, 4096)),      # degraded read, 8 coalesced reads
], ids=["ecbench_encode", "ecbench_decode", "lrc_encode_x8",
        "degraded_decode_x8"])
def test_matrix_programs(compile_for, bitmat, data):
    from ceph_tpu.ops import xor_mm
    compiled = compile_for(xor_mm.matrix_encode.__wrapped__,
                           (bitmat, np.uint8), (data, np.uint8), w=8)
    assert compiled.memory_analysis().temp_size_in_bytes < GB

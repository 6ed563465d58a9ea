"""XOR-matmul: the single TPU primitive behind every codec.

GF(2^w) erasure-code math decomposes into binary matrices applied to bit
vectors with XOR accumulation (see :mod:`ceph_tpu.ops.gf`). On TPU we
execute that as an int8 matmul on the MXU with int32 accumulation followed
by `& 1` — exact, and the compiler fuses the surrounding bit pack/unpack
(VPU shifts) into the same HBM pass.

Layouts (matching :mod:`ceph_tpu.ops.gf_ref`):
  - element layout (`matrix_encode`): chunk = flat little-endian w-bit
    elements; used by the Reed-Solomon matrix techniques.
  - packet layout (`bitmatrix_encode`): chunk = S superblocks x w packets
    x packetsize bytes; used by the Cauchy/Liberation bitmatrix techniques.

The batch dimension (many stripes in flight) is what the TPU feeds on: the
reference encodes stripe-by-stripe in a CPU loop
(/root/reference/src/osd/ECUtil.cc:100-139); here a whole batch is one
device program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..common.profiler import PROFILER


def xor_matmul(bitmat: jax.Array, bits: jax.Array) -> jax.Array:
    """out[..., r, f] = XOR_c bitmat[r, c] & bits[..., c, f].

    bitmat: [R, C] 0/1. bits: [..., C, F] 0/1. Returns [..., R, F] uint8.
    int8 x int8 -> int32 accumulation is exact (C <= 2^23), so the mod-2
    reduction is bit-exact.
    """
    acc = jnp.einsum(
        "rc,...cf->...rf",
        bitmat.astype(jnp.int8),
        bits.astype(jnp.int8),
        preferred_element_type=jnp.int32,
    )
    return (acc & 1).astype(jnp.uint8)


def unpack_element_bits(data: jax.Array, w: int) -> jax.Array:
    """[..., k, N] uint8 -> [..., k*w, N*8//w] bits (element-bit layout)."""
    *lead, k, n = data.shape
    wb = w // 8
    ne = n // wb
    x = data.reshape(*lead, k, ne, wb)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (x[..., None] >> shifts) & jnp.uint8(1)   # [..., k, ne, wb, 8]
    bits = jnp.moveaxis(bits, -3, -1)                # [..., k, wb, 8, ne]
    return bits.reshape(*lead, k * w, ne)


def pack_element_bits(bits: jax.Array, w: int) -> jax.Array:
    """[..., m*w, ne] bits -> [..., m, ne*w//8] uint8."""
    *lead, rows, ne = bits.shape
    wb = w // 8
    m = rows // w
    x = bits.reshape(*lead, m, wb, 8, ne).astype(jnp.uint8)
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape(8, 1)
    byts = jnp.sum(x << shifts, axis=-2, dtype=jnp.uint8)  # [..., m, wb, ne]
    byts = jnp.moveaxis(byts, -2, -1)                      # [..., m, ne, wb]
    return byts.reshape(*lead, m, ne * wb)


@functools.partial(jax.jit, static_argnames=("w",))
def matrix_encode(bitmat: jax.Array, data: jax.Array, w: int) -> jax.Array:
    """Element-layout GF(2^w) encode: [..., k, N] uint8 -> [..., m, N].

    bitmat is the [m*w, k*w] bitplane expansion of the generator
    (gf.generator_to_bitmatrix); passing it as data (not static) lets one
    compiled program serve every generator of the same shape — decode
    matrices included. This XLA path measures at ~0.95x of the v5e HBM
    roofline; the fused Pallas kernel was retired after three layouts
    (see ops.pallas_gf's postmortem) could not come within 300x of it.
    """
    bits = unpack_element_bits(data, w)
    out_bits = xor_matmul(bitmat, bits)
    return pack_element_bits(out_bits, w)


matrix_encode = PROFILER.wrap_jit("xor_mm.matrix_encode", matrix_encode)


@functools.partial(jax.jit, static_argnames=("w",))
def matrix_encode_multi(bitmats: jax.Array, data: jax.Array,
                        w: int) -> jax.Array:
    """Many independent encodes/decodes in ONE device program.

    bitmats: [P, R, C] — a DIFFERENT bitmatrix per lane (e.g. one
    decode matrix per erasure signature). data: [P, ..., k, N].
    Returns [P, ..., m, N]. This is the cross-op coalescing primitive:
    P concurrent OSD ops (each its own generator or decode matrix)
    become one dispatch, and on-device the lanes fill the MXU batch
    dimension."""
    return jax.vmap(lambda bm, d: matrix_encode(bm, d, w))(bitmats, data)


matrix_encode_multi = PROFILER.wrap_jit("xor_mm.matrix_encode_multi",
                                        matrix_encode_multi)


@functools.partial(jax.jit, static_argnames=("w", "packetsize"))
def bitmatrix_encode(bitmat: jax.Array, data: jax.Array, w: int,
                     packetsize: int) -> jax.Array:
    """Packet-layout bitmatrix encode: [..., k, N] uint8 -> [..., m, N].

    N must be a multiple of w*packetsize. Payload bytes are expanded to
    bits only inside the program; XLA fuses expansion into the matmul pass.
    """
    *lead, k, n = data.shape
    rows = bitmat.shape[0]
    m = rows // w
    p = packetsize
    s = n // (w * p)
    pk = data.reshape(*lead, k, s, w, p)
    pk = jnp.moveaxis(pk, -4, -3)                    # [..., s, k, w, p]
    pk = pk.reshape(*lead, s, k * w, p)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = ((pk[..., None] >> shifts) & jnp.uint8(1)).reshape(*lead, s, k * w, p * 8)
    out_bits = xor_matmul(bitmat, bits)              # [..., s, m*w, p*8]
    x = out_bits.reshape(*lead, s, m * w, p, 8)
    byts = jnp.sum(x << shifts, axis=-1, dtype=jnp.uint8)  # [..., s, m*w, p]
    byts = byts.reshape(*lead, s, m, w, p)
    byts = jnp.moveaxis(byts, -4, -3)                # [..., m, s, w, p]
    return byts.reshape(*lead, m, n)


bitmatrix_encode = PROFILER.wrap_jit("xor_mm.bitmatrix_encode",
                                     bitmatrix_encode)

"""Device-mesh sharding of the codec hot path.

EC stripes are embarrassingly parallel, so the natural mesh is 2D:

  - "stripe" axis: data parallelism over the batch of in-flight stripes
    (the TPU analog of the reference's per-PG sharded op queues,
    src/osd/OSD.h:1623 ShardedOpWQ).
  - "block" axis: intra-chunk parallelism over byte columns (the tensor
    axis; a single huge object's chunks split across chips).

The encode einsum partitions along both without any cross-device
collectives — parity bytes depend only on their own byte column. XLA
inserts collectives only for diagnostics/reductions (e.g. checksums),
which ride ICI.
"""

from __future__ import annotations

import numpy as np


def _factor2(n: int) -> tuple[int, int]:
    a = int(np.floor(np.sqrt(n)))
    while n % a:
        a -= 1
    return max(a, 1), n // max(a, 1)


def make_mesh(n_devices: int | None = None, axis_names=("stripe", "block")):
    """Build a 2D jax Mesh over the first n devices."""
    import jax
    from jax.sharding import Mesh
    devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    a, b = _factor2(n_devices)
    devs = np.array(devices[:n_devices]).reshape(a, b)
    return Mesh(devs, axis_names)


def encode_sharded(codec, data, mesh):
    """Encode a [B, k, N] batch sharded over (stripe, block).

    Returns parity with the same sharding. B must divide by the stripe
    axis size and N*8/w by the block axis size.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..ops import xor_mm

    stripe, block = mesh.axis_names
    data_sharding = NamedSharding(mesh, P(stripe, None, block))
    out_sharding = NamedSharding(mesh, P(stripe, None, block))
    bitmat = jnp.asarray(codec._bitmat)

    @jax.jit
    def step(bm, x):
        x = jax.lax.with_sharding_constraint(x, data_sharding)
        parity = xor_mm.matrix_encode(bm, x, codec.w)
        return jax.lax.with_sharding_constraint(parity, out_sharding)

    from ..common.profiler import PROFILER
    step = PROFILER.wrap_jit("mesh.encode_sharded", step)
    return step(bitmat, jnp.asarray(data))


def decode_sharded(codec, avail_rows, chunks, mesh):
    """Reconstruct all chunk rows from k available ones, sharded over
    (stripe, block) like encode_sharded: chunks [B, k, N] -> [B, n, N].

    The decode bitmatrix (from the codec's table cache / bank) is the
    same shape family as the generator, so the identical partitioning
    applies — byte columns decode independently, no collectives.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..ops import xor_mm

    stripe, block = mesh.axis_names
    data_sharding = NamedSharding(mesh, P(stripe, None, block))
    out_sharding = NamedSharding(mesh, P(stripe, None, block))
    entry = codec._decode_entry(tuple(avail_rows))
    bitmat = jnp.asarray(entry["bitmat"])

    @jax.jit
    def step(bm, x):
        x = jax.lax.with_sharding_constraint(x, data_sharding)
        full = xor_mm.matrix_encode(bm, x, codec.w)
        return jax.lax.with_sharding_constraint(full, out_sharding)

    from ..common.profiler import PROFILER
    step = PROFILER.wrap_jit("mesh.decode_sharded", step)
    return step(bitmat, jnp.asarray(chunks))


class MeshChecksumError(RuntimeError):
    """The psum checksum of the device-resident survivor chunks
    disagrees with the host sum taken when they were received: the
    bytes that reached the mesh are not the bytes the primary got."""


def recover_sharded(codec, avail_rows, chunks, target_row, mesh=None,
                    expected_sum=None):
    """Cross-chip recovery: reconstruct one missing row from k
    survivor chunk streams WITHOUT gathering them to the primary's
    device.

    chunks: [S, k, N] host survivors (rows ordered as avail_rows).
    The batch is sharded over (stripe, block), a psum checksum over
    the mesh is compared against `expected_sum` (host modular uint32
    sum of the survivors, computed here when not supplied), and the
    reconstruction runs via decode_sharded on the already-sharded
    buffers.  Returns the target row [S, N] as host uint8; raises
    MeshChecksumError when the checksum trips (the survivors were
    corrupted between receive and device residency).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    if mesh is None:
        mesh = make_mesh()
    chunks = np.asarray(chunks, dtype=np.uint8)
    if expected_sum is None:
        expected_sum = int(chunks.astype(np.uint64).sum()) % (1 << 32)
    stripe, block = mesh.axis_names
    s_ax = mesh.shape[stripe]
    b_ax = mesh.shape[block]
    s, _k, n = chunks.shape
    # pad to shardable multiples; zero stripes/columns decode to
    # zeros (the code is linear and byte columns are independent)
    # and are trimmed below
    padded = np.pad(chunks, ((0, (-s) % s_ax), (0, 0),
                             (0, (-n) % b_ax)))
    sharding = NamedSharding(mesh, P(stripe, None, block))
    dev = jax.device_put(jnp.asarray(padded), sharding)

    def _partial(x):
        return jax.lax.psum(jnp.sum(x.astype(jnp.uint32)),
                            (stripe, block))

    total = jax.shard_map(_partial, mesh=mesh,
                      in_specs=P(stripe, None, block),
                      out_specs=P())(dev)
    got = int(np.asarray(total)) % (1 << 32)
    if got != expected_sum % (1 << 32):
        raise MeshChecksumError(
            "mesh recovery checksum mismatch: device psum %d != "
            "host sum %d" % (got, expected_sum % (1 << 32)))
    full = decode_sharded(codec, avail_rows, dev, mesh)
    out = np.asarray(full)[:s, target_row, :n]
    return np.ascontiguousarray(out).astype(np.uint8)


def repair_sharded(codec, target, helpers, fractions, mesh=None,
                   expected_sum=None):
    """Mesh combine of MSR helper repair fractions (the repair analog
    of recover_sharded): [S, d, sub] stacked beta-fractions (rows in
    `helpers` order) -> rebuilt target chunks [S, d*sub/2] WITHOUT
    gathering full survivors anywhere.

    Same trust boundary as recover_sharded: a psum checksum of the
    device-resident fractions is compared against `expected_sum` (host
    modular uint32 sum, computed here when not supplied) before the
    combine matrix is applied sharded over (stripe, block). Raises
    MeshChecksumError on mismatch. Combine is linear per byte column,
    so zero-padded stripes/columns are trimmed after.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    if mesh is None:
        mesh = make_mesh()
    fractions = np.asarray(fractions, dtype=np.uint8)
    if expected_sum is None:
        expected_sum = int(fractions.astype(np.uint64).sum()) % (1 << 32)
    stripe, block = mesh.axis_names
    s_ax = mesh.shape[stripe]
    b_ax = mesh.shape[block]
    s, _d, sub = fractions.shape
    padded = np.pad(fractions, ((0, (-s) % s_ax), (0, 0),
                                (0, (-sub) % b_ax)))
    sharding = NamedSharding(mesh, P(stripe, None, block))
    dev = jax.device_put(jnp.asarray(padded), sharding)

    def _partial(x):
        return jax.lax.psum(jnp.sum(x.astype(jnp.uint32)),
                            (stripe, block))

    total = jax.shard_map(_partial, mesh=mesh,
                      in_specs=P(stripe, None, block),
                      out_specs=P())(dev)
    got = int(np.asarray(total)) % (1 << 32)
    if got != expected_sum % (1 << 32):
        raise MeshChecksumError(
            "mesh repair checksum mismatch: device psum %d != "
            "host sum %d" % (got, expected_sum % (1 << 32)))

    from ..ops import xor_mm
    entry = codec._combine_entry(target, tuple(helpers))
    bitmat = jnp.asarray(entry["bitmat"])
    out_sharding = NamedSharding(mesh, P(stripe, None, block))

    @jax.jit
    def step(bm, x):
        x = jax.lax.with_sharding_constraint(x, sharding)
        rebuilt = xor_mm.matrix_encode(bm, x, codec.w)
        return jax.lax.with_sharding_constraint(rebuilt, out_sharding)

    from ..common.profiler import PROFILER
    step = PROFILER.wrap_jit("mesh.repair_sharded", step)
    full = np.asarray(step(bitmat, dev))
    out = full[:s, :, :sub].reshape(s, -1)
    return np.ascontiguousarray(out).astype(np.uint8)

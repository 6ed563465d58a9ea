"""Stripe math + the batched encode/decode seam + integrity hashes.

Role of the reference's ECUtil (src/osd/ECUtil.{h,cc}):

  stripe_info_t   offset arithmetic between the logical object address
                  space and per-shard chunk address spaces
                  (ECUtil.h:31-84) — reproduced operation-for-operation
                  since every byte of RMW planning depends on it
  encode/decode   the reference loops one stripe_width per codec call
                  (ECUtil.cc:100-139, loop :116). Here the whole
                  multi-stripe payload is reshaped to [S, k, chunk] and
                  encoded in ONE batched device call — the structural
                  change the TPU design exists for
  HashInfo        cumulative per-shard crc xattr (ECUtil.h:105-163)

All byte movement stays in numpy; the codec's encode_batch/decode_batch
own the device.
"""

from __future__ import annotations

import time
import zlib

import numpy as np

from ..errors import ErasureCodeError

__all__ = ["StripeInfo", "encode", "encode_fused", "decode",
           "recover_cross_chip", "repair_fraction", "repair_combine",
           "repair_cross_chip", "HashInfo"]

CHUNK_ALIGNMENT = 64


class StripeInfo:
    """stripe_info_t: (stripe_count=k, stripe_width=k*chunk)."""

    def __init__(self, stripe_count: int, stripe_width: int):
        if stripe_width % stripe_count != 0:
            raise ValueError("stripe_width %d %% stripe_count %d != 0"
                             % (stripe_width, stripe_count))
        self.stripe_width = stripe_width
        self.chunk_size = stripe_width // stripe_count

    def logical_offset_is_stripe_aligned(self, logical: int) -> bool:
        return logical % self.stripe_width == 0

    def logical_to_prev_chunk_offset(self, offset: int) -> int:
        return (offset // self.stripe_width) * self.chunk_size

    def logical_to_next_chunk_offset(self, offset: int) -> int:
        return ((offset + self.stripe_width - 1) // self.stripe_width) \
            * self.chunk_size

    def logical_to_prev_stripe_offset(self, offset: int) -> int:
        return offset - (offset % self.stripe_width)

    def logical_to_next_stripe_offset(self, offset: int) -> int:
        rem = offset % self.stripe_width
        return offset - rem + self.stripe_width if rem else offset

    def aligned_logical_offset_to_chunk_offset(self, offset: int) -> int:
        assert offset % self.stripe_width == 0
        return (offset // self.stripe_width) * self.chunk_size

    def aligned_chunk_offset_to_logical_offset(self, offset: int) -> int:
        assert offset % self.chunk_size == 0
        return (offset // self.chunk_size) * self.stripe_width

    def aligned_offset_len_to_chunk(self, off_len: tuple) -> tuple:
        off, length = off_len
        return (self.aligned_logical_offset_to_chunk_offset(off),
                self.aligned_logical_offset_to_chunk_offset(length))

    def offset_len_to_stripe_bounds(self, off_len: tuple) -> tuple:
        off, length = off_len
        start = self.logical_to_prev_stripe_offset(off)
        return (start,
                self.logical_to_next_stripe_offset((off - start) + length))

    def columns(self, off: int, length: int) -> tuple:
        """The data columns (0..k-1) that logical bytes [off, off+length)
        touch: byte x lies in column (x mod stripe_width) // chunk_size.
        A stripe or more touches all k."""
        k = self.stripe_width // self.chunk_size
        if length >= self.stripe_width:
            return tuple(range(k))
        end = off + length - 1
        first = (off % self.stripe_width) // self.chunk_size
        last = (end % self.stripe_width) // self.chunk_size
        if off // self.stripe_width == end // self.stripe_width:
            return tuple(range(first, last + 1))
        # across one stripe boundary: the head's tail columns and the
        # next stripe's leading ones
        return tuple(sorted(set(range(first, k)) | set(range(last + 1))))


def encode(sinfo: StripeInfo, codec, data, want=None,
           dispatcher=None, trace=None, resident=None) -> dict:
    """Encode a stripe-aligned payload -> {shard: chunk bytes}.

    data: bytes/uint8 array whose length is a multiple of stripe_width.
    ONE batched device call for all stripes (vs the reference's
    per-stripe loop). Returns every shard unless `want` restricts it.
    With a dispatcher (osd/tpu_dispatch.py), concurrent callers sharing
    this codec coalesce into one fused device call.

    resident=(tier, key) retains the encode device-side in the
    HbmChunkTier: through the dispatcher the pipeline adopts the
    STAGED device arrays (zero extra transfers); without one the tier
    adopts the host arrays itself (that h2d is then the object's one
    crossing).

    A recording `trace` gets the chunk split back-filled as an
    ``ec_txns`` child (the first host leg after the codec's result).
    """
    arr = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)) else \
        np.asarray(data, dtype=np.uint8).reshape(-1)
    if arr.size % sinfo.stripe_width != 0:
        raise ErasureCodeError(
            22, "payload %d not stripe aligned (width %d)"
            % (arr.size, sinfo.stripe_width))
    if arr.size == 0:
        return {}
    k = codec.get_data_chunk_count()
    n = codec.get_chunk_count()
    stripes = arr.size // sinfo.stripe_width
    # [S, k, chunk]: stripes become the device batch dimension
    batch = arr.reshape(stripes, k, sinfo.chunk_size)
    if dispatcher is not None:
        parity = np.asarray(dispatcher.encode(codec, batch, trace=trace,
                                              resident=resident))
    else:
        parity = np.asarray(codec.encode_batch(batch))
        if resident is not None:
            tier, key = resident
            try:
                tier.adopt_encode(key, batch, parity, codec)
            except Exception:
                pass   # the tier is a cache: adoption never fails a write
    t = _split_start(trace)
    out = {}
    for i in range(n):
        idx = codec.chunk_index(i)
        if want is not None and idx not in want:
            continue
        src = batch[:, i, :] if i < k else parity[:, i - k, :]
        out[idx] = np.ascontiguousarray(src).reshape(-1)
    if t:
        trace.child_interval("ec_txns", t, time.monotonic())
    return out


def _split_start(trace) -> float:
    """When the chunk split began, for a recording trace (else 0)."""
    return time.monotonic() if trace is not None and trace.valid() \
        else 0.0


def encode_fused(sinfo: StripeInfo, codec, data, want=None,
                 dispatcher=None, trace=None, resident=None,
                 mode: str = "store", required_ratio: float = 0.875,
                 entropy_max_bits: float = 7.0) -> tuple:
    """Whole-object write through the fused device transform: per-chunk
    digests, the compressibility probe + compress-vs-store decision,
    and the EC encode run as ONE device program — one h2d of the raw
    payload, one fused program, one d2h of parity + digests (+ the
    compressed payload when the device chose to compress).

    Returns (shard_map, FusedResult).  shard_map is {shard: chunk
    stream} of what must LAND ON DISK — the compressed container's
    stripes when mode="compress" and the probe accepted, the raw
    stripes otherwise.  The FusedResult carries the device-computed
    per-shard crcs (HashInfo.set_device_hashes), the per-chunk
    crc32c/xxh32 digests, and the compression verdict the caller
    records in the hinfo xattr.

    resident=(tier, key) adopts the STORED rows + shard crcs into the
    HbmChunkTier (scrub-from-digest), exactly like encode()'s resident
    contract, and a recording `trace` gets the chunk split as encode()
    gives it.
    """
    from . import fused_transform
    arr = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)) else \
        np.asarray(data, dtype=np.uint8).reshape(-1)
    if arr.size % sinfo.stripe_width != 0:
        raise ErasureCodeError(
            22, "payload %d not stripe aligned (width %d)"
            % (arr.size, sinfo.stripe_width))
    if arr.size == 0:
        return {}, None
    k = codec.get_data_chunk_count()
    n = codec.get_chunk_count()
    stripes = arr.size // sinfo.stripe_width
    batch = arr.reshape(stripes, k, sinfo.chunk_size)
    if dispatcher is not None:
        r = dispatcher.fused_write(
            codec, batch, mode=mode, required_ratio=required_ratio,
            entropy_max_bits=entropy_max_bits, trace=trace,
            resident=resident)
    else:
        out = fused_transform.run_fused(
            codec, batch, mode=mode, required_ratio=required_ratio,
            entropy_max_bits=entropy_max_bits)
        r = fused_transform.finish_fused(out, stripes, k,
                                         sinfo.chunk_size, mode)
        if resident is not None:
            tier, key = resident
            try:
                rows = r.stored if r.stored is not None else batch
                tier.adopt_encode(
                    key, rows, r.parity, codec,
                    digests=np.asarray(r.shard_crcs, dtype=np.uint32))
            except Exception:
                pass   # the tier is a cache: adoption never fails
    t = _split_start(trace)
    rows = r.stored if r.stored is not None else batch
    parity = np.asarray(r.parity)
    shard_map = {}
    for i in range(n):
        idx = codec.chunk_index(i)
        if want is not None and idx not in want:
            continue
        src = rows[:, i, :] if i < k else parity[:, i - k, :]
        shard_map[idx] = np.ascontiguousarray(
            np.asarray(src)).reshape(-1)
    if t:
        trace.child_interval("ec_txns", t, time.monotonic())
    return shard_map, r


def decode(sinfo: StripeInfo, codec, to_decode: dict,
           want=None, dispatcher=None, trace=None) -> dict:
    """Reconstruct shards from per-shard chunk streams.

    to_decode: {shard: bytes of >= 1 chunks, equal lengths}. Returns
    {shard: bytes} for `want` (default: all shards). Batched across
    stripes in one device call (reference decode loops per stripe,
    ECUtil.cc:8-99). With a dispatcher, concurrent reads sharing an
    erasure signature coalesce into one fused device call (matrix
    codecs only — the locality codecs' want_rows plumbing stays
    direct).
    """
    if not to_decode:
        raise ErasureCodeError(22, "decode with no chunks")
    to_decode = {
        shard: (np.frombuffer(v, dtype=np.uint8)
                if isinstance(v, (bytes, bytearray, memoryview))
                else np.asarray(v, dtype=np.uint8).reshape(-1))
        for shard, v in to_decode.items()}
    lengths = {v.size for v in to_decode.values()}
    if len(lengths) != 1:
        raise ErasureCodeError(22, "chunks have unequal lengths %s" % lengths)
    total = lengths.pop()
    if total % sinfo.chunk_size != 0:
        raise ErasureCodeError(
            22, "chunk stream %d not chunk aligned (%d)"
            % (total, sinfo.chunk_size))
    k = codec.get_data_chunk_count()
    n = codec.get_chunk_count()
    want = set(range(n)) if want is None else set(want)
    stripes = total // sinfo.chunk_size

    inv = {codec.chunk_index(i): i for i in range(n)}
    logical = {inv[shard]: buf.reshape(stripes, sinfo.chunk_size)
               for shard, buf in to_decode.items()}

    have = set(to_decode)
    if want <= have:
        return {s: np.ascontiguousarray(
            logical[inv[s]]).reshape(-1) for s in want}

    # Single-erasure region-XOR shortcut (isa/xor_op analog), batched over
    # every stripe in the extent: if the one missing wanted shard is
    # covered by an XOR parity group that fully survived, reconstruct it
    # with one vectorized XOR instead of the matrix path.
    missing_want = want - have
    if len(missing_want) == 1 and hasattr(codec, "xor_plan"):
        m_phys = next(iter(missing_want))
        plan = codec.xor_plan(m_phys, have)
        if plan is not None:
            from ..models.table_cache import xor_recover
            rec = xor_recover({s: logical[inv[s]] for s in plan})
            codec.xor_fast_hits += 1
            out = {}
            for s in want:
                out[s] = (to_decode[s] if s in to_decode
                          else np.ascontiguousarray(rec).reshape(-1))
            return out

    if getattr(codec, "DECODE_BATCH_ANY", False):
        # locality codecs (lrc/shec) accept any recoverable subset and
        # need to know which rows are wanted (a local repair hands over
        # fewer than k shards; unwanted rows may come back as zeros)
        use = tuple(sorted(logical))
        stacked = np.stack([logical[i] for i in use], axis=1)
        full = np.asarray(codec.decode_batch(
            use, stacked,
            want_rows=tuple(sorted(inv[s] for s in want))))
    else:
        use = tuple(sorted(logical))[:k]
        if len(use) < k:
            raise ErasureCodeError(
                5, "not enough chunks to decode (%d < %d)"
                % (len(use), k))
        stacked = np.stack([logical[i] for i in use], axis=1)  # [S,k,chunk]
        if dispatcher is not None:
            full = np.asarray(dispatcher.decode(codec, use, stacked,
                                                trace=trace))
        else:
            full = np.asarray(codec.decode_batch(use, stacked))  # [S,n,chunk]
    out = {}
    for i in range(n):
        idx = codec.chunk_index(i)
        if idx not in want:
            continue
        if idx in to_decode:
            out[idx] = to_decode[idx]
        else:
            out[idx] = np.ascontiguousarray(full[:, i, :]).reshape(-1)
    return out


def recover_cross_chip(sinfo: StripeInfo, codec, to_decode: dict,
                       target_shard: int, mesh=None,
                       expected_sum=None):
    """Mesh-path recovery (ROADMAP direction D): reconstruct ONE
    missing shard with the survivor chunk streams sharded across the
    local device mesh (parallel.mesh.recover_sharded) instead of
    gathered onto the primary's chip.  A psum checksum over the mesh
    verifies the device-resident survivors against their host sum and
    raises MeshChecksumError on mismatch.

    Returns the target shard's bytes, or None when the mesh path does
    not apply (single device, locality codec, non-matrix codec, or a
    survivor set that isn't exactly k matrix rows) — the caller falls
    back to decode().
    """
    if getattr(codec, "DECODE_BATCH_ANY", False) or \
            not hasattr(codec, "_decode_entry"):
        return None
    if getattr(codec, "alpha", 1) > 1:
        # sub-symbol codecs (msr): the decode bitmatrix acts on
        # sub-symbol rows, not chunk rows, so the chunk-shaped
        # recover_sharded program does not apply — their mesh leg is
        # repair_cross_chip (beta-fraction combine), and full-survivor
        # decode falls back to the dispatcher/host path
        return None
    if mesh is None:
        try:
            import jax
            if len(jax.devices()) < 2:
                return None
        except Exception:
            return None
    to_decode = {
        shard: (np.frombuffer(v, dtype=np.uint8)
                if isinstance(v, (bytes, bytearray, memoryview))
                else np.asarray(v, dtype=np.uint8).reshape(-1))
        for shard, v in to_decode.items()}
    lengths = {v.size for v in to_decode.values()}
    if len(lengths) != 1:
        raise ErasureCodeError(22,
                               "chunks have unequal lengths %s" % lengths)
    total = lengths.pop()
    if total == 0 or total % sinfo.chunk_size != 0:
        return None
    k = codec.get_data_chunk_count()
    n = codec.get_chunk_count()
    if target_shard in to_decode:
        return np.ascontiguousarray(
            to_decode[target_shard]).tobytes()
    stripes = total // sinfo.chunk_size
    inv = {codec.chunk_index(i): i for i in range(n)}
    logical = {inv[shard]: buf.reshape(stripes, sinfo.chunk_size)
               for shard, buf in to_decode.items()}
    use = tuple(sorted(logical))[:k]
    if len(use) < k:
        raise ErasureCodeError(
            5, "not enough chunks to decode (%d < %d)"
            % (len(use), k))
    stacked = np.stack([logical[i] for i in use], axis=1)  # [S,k,chunk]
    # rateless path first (ROADMAP direction J): the survivor batch is
    # over-decomposed into micro-batches on the shared device work
    # queue, so one slow or dead chip takes fewer micro-batches
    # instead of gating the whole reconstruction.  Same trust boundary
    # as the fixed-shard path: the bytes about to hit the mesh are
    # checksummed against the host sum taken at receive time.
    from ..parallel import rateless as _rl
    disp = _rl.get_dispatcher() if mesh is None else None
    if disp is not None:
        if expected_sum is not None:
            got = int(stacked.astype(np.uint64).sum()) % (1 << 32)
            if got != expected_sum % (1 << 32):
                from ..parallel.mesh import MeshChecksumError
                raise MeshChecksumError(
                    "rateless recovery checksum mismatch: survivor "
                    "sum %d != expected %d"
                    % (got, expected_sum % (1 << 32)))
        full = disp.decode(codec, use, stacked)
        return np.ascontiguousarray(
            full[:, inv[target_shard], :]).reshape(-1).tobytes()
    from ..parallel.mesh import recover_sharded
    row = recover_sharded(codec, use, stacked, inv[target_shard],
                          mesh=mesh, expected_sum=expected_sum)
    return np.ascontiguousarray(row).reshape(-1).tobytes()


def repair_fraction(sinfo: StripeInfo, codec, target_shard: int,
                    chunk_stream, dispatcher=None, trace=None) -> bytes:
    """Helper-side beta projection for regenerating repair: one
    surviving shard's chunk stream -> the fraction stream it ships to
    the primary rebuilding `target_shard` (chunk/alpha bytes per
    chunk).  Batched across stripes in one device call; with a
    dispatcher the projection rides the staged pipeline on the
    helper's own pinned device."""
    arr = np.frombuffer(chunk_stream, dtype=np.uint8) if isinstance(
        chunk_stream, (bytes, bytearray, memoryview)) else \
        np.asarray(chunk_stream, dtype=np.uint8).reshape(-1)
    if arr.size == 0 or arr.size % sinfo.chunk_size != 0:
        raise ErasureCodeError(
            22, "chunk stream %d not chunk aligned (%d)"
            % (arr.size, sinfo.chunk_size))
    stripes = arr.size // sinfo.chunk_size
    batch = arr.reshape(stripes, sinfo.chunk_size)
    if dispatcher is not None:
        frac = np.asarray(dispatcher.repair_fraction(
            codec, target_shard, batch, trace=trace))
    else:
        frac = np.asarray(codec.repair_fraction_batch(
            target_shard, batch))
    return np.ascontiguousarray(frac).reshape(-1).tobytes()


def _stack_fractions(sinfo: StripeInfo, codec, fractions: dict):
    """{helper shard: fraction stream} -> (helpers tuple, [S, d, sub])."""
    d = codec.repair_helper_count()
    if len(fractions) != d:
        raise ErasureCodeError(
            5, "repair combine needs %d fractions, got %d"
            % (d, len(fractions)))
    helpers = tuple(sorted(fractions))
    bufs = {
        h: (np.frombuffer(v, dtype=np.uint8)
            if isinstance(v, (bytes, bytearray, memoryview))
            else np.asarray(v, dtype=np.uint8).reshape(-1))
        for h, v in fractions.items()}
    lengths = {v.size for v in bufs.values()}
    if len(lengths) != 1:
        raise ErasureCodeError(
            22, "fractions have unequal lengths %s" % lengths)
    total = lengths.pop()
    sub = codec.repair_sub_size(sinfo.chunk_size)
    if total == 0 or total % sub != 0:
        raise ErasureCodeError(
            22, "fraction stream %d not sub-symbol aligned (%d)"
            % (total, sub))
    stripes = total // sub
    stacked = np.stack([bufs[h].reshape(stripes, sub)
                        for h in helpers], axis=1)  # [S, d, sub]
    return helpers, stacked


def repair_combine(sinfo: StripeInfo, codec, target_shard: int,
                   fractions: dict, dispatcher=None,
                   trace=None) -> bytes:
    """Primary-side combine: the d helper fraction streams -> the
    rebuilt target shard's chunk stream (dispatcher/host path)."""
    helpers, stacked = _stack_fractions(sinfo, codec, fractions)
    if dispatcher is not None:
        out = np.asarray(dispatcher.repair_combine(
            codec, target_shard, helpers, stacked, trace=trace))
    else:
        out = np.asarray(codec.repair_combine_batch(
            target_shard, helpers, stacked))
    return np.ascontiguousarray(out).reshape(-1).tobytes()


def repair_cross_chip(sinfo: StripeInfo, codec, target_shard: int,
                      fractions: dict, mesh=None, expected_sum=None):
    """Mesh-path repair combine (the repair analog of
    recover_cross_chip): the stacked beta-fractions are sharded across
    the local device mesh, psum-checksummed against their host sum,
    and combined there (parallel.mesh.repair_sharded) — a rebuild
    storm never gathers full survivors anywhere.

    Returns the rebuilt shard's bytes, or None when the mesh path does
    not apply (single device, codec without fraction repair) — the
    caller falls back to repair_combine()."""
    if not getattr(codec, "supports_repair", lambda: False)() or \
            not hasattr(codec, "_combine_entry"):
        return None
    if mesh is None:
        try:
            import jax
            if len(jax.devices()) < 2:
                return None
        except Exception:
            return None
    helpers, stacked = _stack_fractions(sinfo, codec, fractions)
    # rateless path first (direction J): beta-fraction combine rides
    # the shared micro-batch queue; a straggling chip degrades the
    # combine proportionally instead of gating it
    from ..parallel import rateless as _rl
    disp = _rl.get_dispatcher() if mesh is None else None
    if disp is not None:
        if expected_sum is not None:
            got = int(stacked.astype(np.uint64).sum()) % (1 << 32)
            if got != expected_sum % (1 << 32):
                from ..parallel.mesh import MeshChecksumError
                raise MeshChecksumError(
                    "rateless repair checksum mismatch: fraction "
                    "sum %d != expected %d"
                    % (got, expected_sum % (1 << 32)))
        out = disp.repair_combine(codec, target_shard, helpers,
                                  stacked)
        return np.ascontiguousarray(out).reshape(-1).tobytes()
    from ..parallel.mesh import repair_sharded
    out = repair_sharded(codec, target_shard, helpers, stacked,
                         mesh=mesh, expected_sum=expected_sum)
    return np.ascontiguousarray(out).reshape(-1).tobytes()


def decode_concat(sinfo: StripeInfo, codec, to_decode: dict,
                  dispatcher=None, trace=None, columns=None) -> bytes:
    """Reconstruct and concatenate the data shards back into the logical
    payload (the read-path finish, ECUtil.cc:46-99).

    columns: the data columns (0..k-1) to reconstruct and lay into
    each stripe, all k by default; the others stay zero, so a caller
    that read only some columns slices inside them."""
    k = codec.get_data_chunk_count()
    columns = range(k) if columns is None else columns
    want = {codec.chunk_index(i) for i in columns}
    shards = decode(sinfo, codec, to_decode, want, dispatcher=dispatcher,
                    trace=trace)
    total = len(next(iter(shards.values())))
    stripes = total // sinfo.chunk_size
    out = np.zeros((stripes, k, sinfo.chunk_size), dtype=np.uint8)
    for i in columns:
        out[:, i, :] = np.asarray(shards[codec.chunk_index(i)]).reshape(
            stripes, sinfo.chunk_size)
    return out.tobytes()


class HashInfo:
    """Cumulative per-shard crc + size xattr (ECUtil.h:105-163).

    append() must be called with stripe-aligned same-length per-shard
    appends; the crc chains so any historical corruption is detectable
    on deep scrub.

    The fused write transform (osd/fused_transform.py) bypasses the
    host crc chain entirely: set_device_hashes() accepts the
    device-computed per-shard crcs wholesale for a full-object write,
    and comp_info records the on-device compression of the stored
    stream ({"alg", "orig_chunk_size", "comp_len", "padded_len"}) —
    when set, total_chunk_size is the STORED (compressed) per-shard
    stream length while logical sizes derive from orig_chunk_size.
    """

    def __init__(self, num_chunks: int = 0):
        self.total_chunk_size = 0
        self.cumulative_shard_hashes = [0] * num_chunks
        self.projected_total_chunk_size = 0
        self.comp_info: dict | None = None

    def has_chunk_hash(self) -> bool:
        return bool(self.cumulative_shard_hashes)

    def append(self, old_size: int, to_append: dict) -> None:
        assert old_size == self.total_chunk_size
        sizes = {len(np.asarray(v).reshape(-1)) for v in to_append.values()}
        assert len(sizes) == 1
        size = sizes.pop()
        if self.has_chunk_hash():
            assert len(to_append) == len(self.cumulative_shard_hashes)
            for shard, buf in to_append.items():
                data = np.asarray(buf, dtype=np.uint8).reshape(-1).tobytes()
                self.cumulative_shard_hashes[shard] = zlib.crc32(
                    data, self.cumulative_shard_hashes[shard]) & 0xFFFFFFFF
        self.total_chunk_size += size

    def set_device_hashes(self, shard_crcs, total_chunk_size: int,
                          comp_info: dict | None = None) -> None:
        """Accept device-computed cumulative shard crcs wholesale (the
        fused write transform's output) — valid only as a FULL-object
        (re)write, which is exactly when the fused path runs.  Zero
        host hashing: the crcs were computed beside the encode on
        device.  comp_info records (or, None, clears) the stored
        stream's compression."""
        self.cumulative_shard_hashes = [int(c) & 0xFFFFFFFF
                                        for c in shard_crcs]
        self.total_chunk_size = int(total_chunk_size)
        self.comp_info = dict(comp_info) if comp_info else None

    def get_chunk_hash(self, shard: int) -> int:
        return self.cumulative_shard_hashes[shard]

    def get_total_chunk_size(self) -> int:
        return self.total_chunk_size

    def get_total_logical_size(self, sinfo: StripeInfo) -> int:
        base = self.comp_info["orig_chunk_size"] \
            if self.comp_info is not None else self.total_chunk_size
        return base * (sinfo.stripe_width // sinfo.chunk_size)

    def get_projected_total_logical_size(self, sinfo: StripeInfo) -> int:
        return self.projected_total_chunk_size * (sinfo.stripe_width //
                                                  sinfo.chunk_size)

    def set_projected_total_logical_size(self, sinfo: StripeInfo,
                                         logical_size: int) -> None:
        assert sinfo.logical_offset_is_stripe_aligned(logical_size)
        self.projected_total_chunk_size = \
            sinfo.aligned_logical_offset_to_chunk_offset(logical_size)

    def clear(self) -> None:
        self.total_chunk_size = 0
        self.cumulative_shard_hashes = [0] * len(
            self.cumulative_shard_hashes)
        self.comp_info = None

    def to_dict(self) -> dict:
        d = {"total_chunk_size": self.total_chunk_size,
             "cumulative_shard_hashes": list(
                 self.cumulative_shard_hashes)}
        if self.comp_info is not None:
            # only compressed objects carry the key: hinfo xattrs
            # written before the fused transform stay byte-identical
            d["comp_info"] = dict(self.comp_info)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "HashInfo":
        h = cls(len(d["cumulative_shard_hashes"]))
        h.total_chunk_size = d["total_chunk_size"]
        h.cumulative_shard_hashes = list(d["cumulative_shard_hashes"])
        h.comp_info = dict(d["comp_info"]) if d.get("comp_info") \
            else None
        # projections live in LOGICAL space: a compressed object's
        # projected size derives from the raw-equivalent chunk size
        h.projected_total_chunk_size = \
            h.comp_info["orig_chunk_size"] if h.comp_info is not None \
            else h.total_chunk_size
        return h

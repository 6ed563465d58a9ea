"""Fused device-side write transform (ROADMAP direction F).

Ceph's write path runs checksum -> (compress) -> EC encode as separate
host passes; here the whole object write transform is ONE jitted device
program over the staged [S, k, chunk] batch:

  (a) per-chunk crc32c + xxh32 digests of the raw data,
  (b) an entropy-bound compressibility probe (256-bin histogram ->
      Shannon bound) plus a splittable bit-plane compression stage,
      with the compress-vs-store decision taken ON DEVICE,
  (c) EC encode of the (possibly compressed) stored stream, and
  (d) per-shard crc32 of the stored chunk streams in zlib polynomial —
      exactly what HashInfo/deep-scrub verify against on disk.

One h2d of raw data, one fused program, one d2h of parity + digests +
compressed payload. The CRC machinery is GF(2)-linear algebra: the
crc of each 256-byte segment is its 2048 bits times a [2048, 32] bit
matrix, one int8 matmul on the MXU (ops.xor_mm, as every codec), and
the segment crcs are folded 128 lanes at a time, each lane shifted
past its successors by a precomputed 32x32 "append n zero bytes"
matrix, so the whole digest is O(log L) vectorized levels instead of a
byte-serial loop, in a lane-major layout the TPU tiles densely. Dynamic
stored lengths (the compressed prefix) are handled by UN-shifting the
full-capacity CRC with inverse matrices selected by the pad's bits —
valid because the stored buffer is zero beyond the stored prefix and
x is invertible mod the CRC polynomial.

Compressed container layout (`alg=jax_device`, block B=64 bytes):
  [2*nb header bytes: (flags, consts) per block][stored planes, 8B each]
flags bit p set => bit-plane p stored raw; else constant, with its
value in consts bit p. Worst case 66/64 expansion; the device decision
stores raw beyond `required_ratio`. Decompression is a vectorized
numpy pass (read path / recovery are host-driven).

Only element-layout matrix codecs (Reed-Solomon family) fuse; other
codecs fall back to the separate path. Everything here must run on
both the TPU and CPU XLA backends (tier-1 runs JAX_PLATFORMS=cpu).
"""

from __future__ import annotations

import functools
import threading

import numpy as np

__all__ = ["FusedResult", "fused_supported", "run_fused",
           "bitplane_decompress", "bitplane_compress_host",
           "crc32c_host", "xxh32_host", "device_crc32",
           "shannon_bytes_per_byte", "COMP_ALG"]

COMP_ALG = "jax_device"
_BLOCK = 64

# -- GF(2) crc machinery (host precompute) ---------------------------------
#
# Column-mask convention: a 32x32 GF(2) matrix M is stored as 32 uint32
# columns, M[j] = M . e_j; apply(M, x) = XOR_{j: bit j of x} M[j].

_POLY_ZLIB = 0xEDB88320   # reflected crc32 (zlib/HashInfo/deep-scrub)
_POLY_C = 0x82F63B78      # reflected crc32c (Castagnoli)
_LEVELS = 31              # shift matrices for appends up to 2^30 bytes
_LANES = 128              # combine width per CRC tree level
_SEG = 256                # bytes per bit-matrix leaf of the CRC tree


def _crc_table(poly: int) -> np.ndarray:
    tab = np.zeros(256, dtype=np.uint64)
    for b in range(256):
        c = b
        for _ in range(8):
            c = (c >> 1) ^ (poly if c & 1 else 0)
        tab[b] = c
    return tab.astype(np.uint32)


def _mat_apply(mat: np.ndarray, x: int) -> int:
    r = 0
    j = 0
    while x:
        if x & 1:
            r ^= int(mat[j])
        x >>= 1
        j += 1
    return r


def _mat_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.array([_mat_apply(a, int(b[j])) for j in range(32)],
                    dtype=np.uint32)


def _mat_inv(mat: np.ndarray) -> np.ndarray:
    """GF(2) inverse by Gaussian elimination (rows as bit-vectors)."""
    # work in row form: row i as integer over columns
    m = [[(int(mat[j]) >> i) & 1 for j in range(32)] for i in range(32)]
    inv = [[1 if i == j else 0 for j in range(32)] for i in range(32)]
    for col in range(32):
        piv = next(r for r in range(col, 32) if m[r][col])
        m[col], m[piv] = m[piv], m[col]
        inv[col], inv[piv] = inv[piv], inv[col]
        for r in range(32):
            if r != col and m[r][col]:
                m[r] = [a ^ b for a, b in zip(m[r], m[col])]
                inv[r] = [a ^ b for a, b in zip(inv[r], inv[col])]
    out = np.zeros(32, dtype=np.uint32)
    for j in range(32):
        col = 0
        for i in range(32):
            col |= inv[i][j] << i
        out[j] = col
    return out


class _PolyConsts:
    """Per-polynomial host constants: byte table, append-2^l-zero-bytes
    matrices (and inverses), segment bit matrices, built once per
    process."""

    def __init__(self, poly: int):
        self.poly = poly
        self.table = _crc_table(poly)
        m1 = np.array([self._zero_byte_update(1 << j) for j in range(32)],
                      dtype=np.uint32)
        shifts = [m1]
        for _ in range(_LEVELS - 1):
            shifts.append(_mat_mul(shifts[-1], shifts[-1]))
        self.shift = np.stack(shifts)              # [.., 32]: append 2^l B
        self.inv = np.stack([_mat_inv(s) for s in shifts])
        self.lanes: dict = {}                      # (seg, q) -> [32, q]
        self.segs: dict = {}                       # s -> [8s, 32] int8

    def _zero_byte_update(self, state: int) -> int:
        return (state >> 8) ^ int(self.table[state & 0xFF])

    def shift_n(self, state: int, nbytes: int) -> int:
        """Host: crc register after appending nbytes zero bytes."""
        lvl = 0
        while nbytes:
            if nbytes & 1:
                state = _mat_apply(self.shift[lvl], state)
            nbytes >>= 1
            lvl += 1
        return state

    def lane_mats(self, seg: int, q: int) -> np.ndarray:
        """[32, q] column masks for one combine level: lane j holds the
        crc of the j-th of q consecutive seg-byte segments, and is
        shifted past the (q - 1 - j) segments that follow it."""
        with _CONSTS_LOCK:
            mats = self.lanes.get((seg, q))
            if mats is None:
                base = np.array([self.shift_n(1 << j, seg)
                                 for j in range(32)], dtype=np.uint32)
                powers = [np.array([1 << j for j in range(32)],
                                   dtype=np.uint32)]       # M^0 = I
                for _ in range(q - 1):
                    powers.append(_mat_mul(base, powers[-1]))
                mats = self.lanes.setdefault(
                    (seg, q), np.stack(powers[::-1], axis=1))
            return mats

    def seg_mat(self, s: int) -> np.ndarray:
        """[8s, 32] 0/1 int8: row 8p + b is the crc_raw of the s-byte
        stream that holds only bit b of byte p, so the crc_raw of any
        s-byte segment is its bits times this matrix over GF(2). Built
        by walking the zero-byte update back from the last byte's
        one-bit CRCs: s steps over the 8 bits."""
        with _CONSTS_LOCK:
            mat = self.segs.get(s)
            if mat is None:
                rows = np.empty((s, 8), dtype=np.uint32)
                state = self.table[1 << np.arange(8)]
                for p in range(s - 1, -1, -1):
                    rows[p] = state
                    state = (state >> 8) ^ self.table[state & 0xFF]
                bits = (rows.reshape(8 * s, 1)
                        >> np.arange(32, dtype=np.uint32)) & 1
                mat = self.segs.setdefault(s, bits.astype(np.int8))
            return mat


_CONSTS: dict = {}
_CONSTS_LOCK = threading.RLock()


def _poly_consts(poly: int) -> _PolyConsts:
    with _CONSTS_LOCK:
        pc = _CONSTS.get(poly)
        if pc is None:
            pc = _CONSTS.setdefault(poly, _PolyConsts(poly))
        return pc


# -- host oracles (tests, read path, scrub fallback) -----------------------

def crc32c_host(data, crc: int = 0) -> int:
    """crc32c (Castagnoli) of a byte buffer — the host oracle the device
    digests are verified against."""
    tab = _poly_consts(_POLY_C).table
    c = (crc ^ 0xFFFFFFFF) & 0xFFFFFFFF
    for b in bytes(data):
        c = int(tab[(c ^ b) & 0xFF]) ^ (c >> 8)
    return (c ^ 0xFFFFFFFF) & 0xFFFFFFFF


_XXP1, _XXP2, _XXP3 = 2654435761, 2246822519, 3266489917
_XXP4, _XXP5 = 668265263, 374761393
_M32 = 0xFFFFFFFF


def _rotl32(x: int, r: int) -> int:
    return ((x << r) | (x >> (32 - r))) & _M32


def xxh32_host(data, seed: int = 0) -> int:
    """Pure-python xxh32 (spec implementation; host oracle)."""
    data = bytes(data)
    n = len(data)
    i = 0
    if n >= 16:
        a1 = (seed + _XXP1 + _XXP2) & _M32
        a2 = (seed + _XXP2) & _M32
        a3 = seed & _M32
        a4 = (seed - _XXP1) & _M32
        while i + 16 <= n:
            for lane in range(4):
                w = int.from_bytes(data[i + 4 * lane:i + 4 * lane + 4],
                                   "little")
                if lane == 0:
                    a1 = (_rotl32((a1 + w * _XXP2) & _M32, 13) * _XXP1) & _M32
                elif lane == 1:
                    a2 = (_rotl32((a2 + w * _XXP2) & _M32, 13) * _XXP1) & _M32
                elif lane == 2:
                    a3 = (_rotl32((a3 + w * _XXP2) & _M32, 13) * _XXP1) & _M32
                else:
                    a4 = (_rotl32((a4 + w * _XXP2) & _M32, 13) * _XXP1) & _M32
            i += 16
        h = (_rotl32(a1, 1) + _rotl32(a2, 7) + _rotl32(a3, 12)
             + _rotl32(a4, 18)) & _M32
    else:
        h = (seed + _XXP5) & _M32
    h = (h + n) & _M32
    while i + 4 <= n:
        w = int.from_bytes(data[i:i + 4], "little")
        h = (_rotl32((h + w * _XXP3) & _M32, 17) * _XXP4) & _M32
        i += 4
    while i < n:
        h = (_rotl32((h + data[i] * _XXP5) & _M32, 11) * _XXP1) & _M32
        i += 1
    h ^= h >> 15
    h = (h * _XXP2) & _M32
    h ^= h >> 13
    h = (h * _XXP3) & _M32
    h ^= h >> 16
    return h


def shannon_bytes_per_byte(data) -> float:
    """Host entropy probe twin: Shannon bound in bits/byte / 8."""
    arr = np.frombuffer(bytes(data), dtype=np.uint8)
    if arr.size == 0:
        return 0.0
    counts = np.bincount(arr, minlength=256).astype(np.float64)
    p = counts[counts > 0] / arr.size
    return float(-(p * np.log2(p)).sum() / 8.0)


def bitplane_compress_host(data) -> tuple[bytes, int]:
    """Host twin of the device bit-plane stage (_bitplane_dev): same
    container, byte for byte. Returns (container, padded_len) — the
    compressor plugin and the tests use it as the oracle the fused
    program must match."""
    raw = np.frombuffer(bytes(data), dtype=np.uint8)
    padded = _roundup(max(raw.size, 1), _BLOCK)
    if padded != raw.size:
        raw = np.concatenate(
            [raw, np.zeros(padded - raw.size, dtype=np.uint8)])
    nb = padded // _BLOCK
    shifts = np.arange(8, dtype=np.uint8)
    bits = (raw.reshape(nb, 1, _BLOCK) >> shifts[None, :, None]) & 1
    b4 = bits.reshape(nb, 8, 8, 8)                           # [nb,p,g,t]
    packed = (b4.astype(np.uint16)
              << shifts[None, None, None, :].astype(np.uint16)
              ).sum(axis=-1).astype(np.uint8)                # [nb,p,g]
    all0 = np.all(packed == 0, axis=-1)
    all1 = np.all(packed == 0xFF, axis=-1)
    stored = ~(all0 | all1)
    pw = shifts.astype(np.uint32)
    flags = (stored.astype(np.uint32) << pw).sum(
        axis=-1).astype(np.uint8)
    consts = (all1.astype(np.uint32) << pw).sum(
        axis=-1).astype(np.uint8)
    header = np.stack([flags, consts], axis=1).reshape(2 * nb)
    payload = packed[stored].reshape(-1)                     # (nb,p) order
    return header.tobytes() + payload.tobytes(), padded


def bitplane_decompress(buf, padded_len: int) -> bytes:
    """Inverse of the device bit-plane stage (vectorized numpy).

    buf: the compressed container (comp_len bytes). padded_len: the
    64-aligned raw length the compressor saw; the caller trims to the
    original object length.
    """
    nb = padded_len // _BLOCK
    raw = np.frombuffer(bytes(buf), dtype=np.uint8)
    flags = raw[0:2 * nb:2]
    consts = raw[1:2 * nb:2]
    payload = raw[2 * nb:]
    shifts = np.arange(8, dtype=np.uint8)
    stored = ((flags[:, None] >> shifts) & 1).astype(bool)       # [nb, 8]
    planes = np.zeros((nb, 8, 8), dtype=np.uint8)                # [nb, p, g]
    cnt = int(stored.sum())
    planes[stored] = payload[:cnt * 8].reshape(cnt, 8)
    const_fill = np.where(((consts[:, None] >> shifts) & 1).astype(bool),
                          0xFF, 0).astype(np.uint8)              # [nb, 8]
    planes[~stored] = np.broadcast_to(
        const_fill[:, :, None], (nb, 8, 8))[~stored]
    bits = ((planes[:, :, :, None] >> shifts) & 1)               # [nb,p,g,t]
    byts = (bits.astype(np.uint16)
            << shifts[None, :, None, None].astype(np.uint16)).sum(axis=1)
    return byts.astype(np.uint8).reshape(-1).tobytes()           # [nb*64]


# -- fused program (jax) ---------------------------------------------------

def fused_supported(codec) -> bool:
    """Only element-layout matrix codecs on the jax backend fuse."""
    from ..models.matrix_base import MatrixErasureCode
    return (isinstance(codec, MatrixErasureCode)
            and getattr(codec, "backend", "") == "jax"
            and getattr(codec, "_bitmat", None) is not None)


def _roundup(x: int, a: int) -> int:
    return x + (a - x % a) % a if x % a else x


def _next_pow2(x: int) -> int:
    n = 1
    while n < x:
        n <<= 1
    return n


class FusedResult:
    """Host-side view of one fused write transform."""

    __slots__ = ("parity", "stored", "shard_crcs", "chunk_crc32c",
                 "chunk_xxh32", "compressed", "comp_len", "probe_ok",
                 "entropy_bpb", "used_stripes", "stored_len", "raw_len",
                 "padded_len", "dev_stored", "dev_parity")

    def as_dict(self) -> dict:
        return {s: getattr(self, s, None) for s in self.__slots__
                if not s.startswith("dev_")}


def _dev_consts(device=None):
    """Device copies of the CRC tables/matrices, cached per home device
    (same keying idiom as the codec bitmatrix constants)."""
    import jax
    import jax.numpy as jnp
    from ..models.table_cache import device_entry_key
    key = device_entry_key(device)
    with _CONSTS_LOCK:
        cache = _CONSTS.setdefault("dev", {})
        ent = cache.get(key)
        if ent is None:
            z, c = _poly_consts(_POLY_ZLIB), _poly_consts(_POLY_C)
            arrs = tuple(jnp.asarray(a) for a in
                         (z.table, z.inv, c.table))
            if device is not None:
                arrs = tuple(jax.device_put(a, device) for a in arrs)
            ent = cache.setdefault(key, arrs)
    return ent


def _mat_apply_dev(cols, x):
    """cols: [32] uint32 column masks (or [32, q], one matrix per lane
    of x's trailing axis); x: [...] uint32 -> M.x

    One select-and-xor per bit, all elementwise: XLA fuses the 32 steps
    into a single pass, so no [..., 32] intermediate is materialized
    (that expansion was ~800x the input in temporaries)."""
    import jax.numpy as jnp
    out = jnp.zeros_like(x)
    for j in range(32):
        bit = ((x >> jnp.uint32(j)) & jnp.uint32(1)).astype(bool)
        out = out ^ jnp.where(bit, cols[j], jnp.uint32(0))
    return out


def _combine_lanes(x, mats):
    """x: [..., q] uint32 crc_raw of q consecutive equal-length
    segments; mats: [32, q] (lane_mats). Returns [...]: crc_raw of
    their concatenation (each lane shifted past its successors, then
    XOR-reduced across the lane axis)."""
    import jax
    import jax.numpy as jnp
    out = _mat_apply_dev(mats, x)
    return jax.lax.reduce(out, jnp.uint32(0), jax.lax.bitwise_xor,
                          (out.ndim - 1,))


def _crc_raw_tree(streams, pc):
    """crc_raw (init 0, no xor-out) of each row of streams [..., L].

    The leaf is a GF(2) bit-matrix product on the MXU: each row is
    front-padded with zeros (a crc_raw no-op) to whole _SEG-byte
    segments, each segment's bits times pc.seg_mat gives its crc_raw
    (ops.xor_mm's int8 matmul, int32 accumulation, then & 1). Then
    log_128 combine levels each fold 128-lane rows of consecutive
    segments (lane-major, so the device layout stays dense)."""
    import jax
    import jax.numpy as jnp
    from ..ops import xor_mm
    L = streams.shape[-1]
    n = -(-L // _SEG)
    if n * _SEG != L:
        pad = [(0, 0)] * (streams.ndim - 1) + [(n * _SEG - L, 0)]
        streams = jnp.pad(streams, pad)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (streams.reshape(streams.shape[:-1] + (n, _SEG, 1))
            >> shifts) & jnp.uint8(1)                  # [..., n, s, 8]
    bits = jnp.swapaxes(
        bits.reshape(bits.shape[:-3] + (n, 8 * _SEG)), -1, -2)
    crc_bits = xor_mm.xor_matmul(
        jnp.asarray(pc.seg_mat(_SEG).T), bits)         # [..., 32, n]
    v = jax.lax.reduce(
        crc_bits.astype(jnp.uint32)
        << jnp.arange(32, dtype=jnp.uint32)[:, None],
        jnp.uint32(0), jax.lax.bitwise_or, (crc_bits.ndim - 2,))
    seg = _SEG
    while v.shape[-1] > 1:
        n = v.shape[-1]
        q = min(_LANES, n)
        p = -(-n // q)
        if p * q != n:
            # front zeros are a crc_raw no-op at every level
            pad = jnp.zeros(v.shape[:-1] + (p * q - n,), dtype=jnp.uint32)
            v = jnp.concatenate([pad, v], axis=-1)
        v = _combine_lanes(v.reshape(v.shape[:-1] + (p, q)),
                           jnp.asarray(pc.lane_mats(seg, q)))
        seg *= q
    return v[..., 0]


def _crc32_full(streams, pc, init_const):
    """Standard crc32 (init 0xFFFFFFFF, xor-out) of static-length rows.
    init_const = shift_L(0xFFFFFFFF), host-precomputed for the static L."""
    import jax.numpy as jnp
    return _crc_raw_tree(streams, pc) ^ init_const ^ jnp.uint32(0xFFFFFFFF)


def _crc_unshift(crcs, inv, pad_bytes):
    """Undo `pad_bytes` appended zero bytes on raw-register crcs by
    applying inverse shift matrices selected by the pad's bits."""
    import jax.numpy as jnp
    c = crcs
    for lvl in range(_LEVELS):
        bit = ((pad_bytes >> lvl) & 1).astype(bool)
        c = jnp.where(bit, _mat_apply_dev(inv[lvl], c), c)
    return c


def _xxh32_dev(chunks):
    """xxh32 (seed 0) of each row of chunks [B, L] uint8, L static."""
    import jax
    import jax.numpy as jnp
    B, L = chunks.shape
    u = jnp.uint32
    P1, P2, P3 = u(_XXP1), u(_XXP2), u(_XXP3)
    P4, P5 = u(_XXP4), u(_XXP5)

    def rotl(x, r):
        return (x << u(r)) | (x >> u(32 - r))

    nblk = L // 16
    if nblk:
        w = chunks[:, :nblk * 16].reshape(B, nblk, 4, 4).astype(jnp.uint32)
        scale = (u(1) << (u(8) * jnp.arange(4, dtype=jnp.uint32)))
        words = jnp.sum(w * scale, axis=-1, dtype=jnp.uint32)  # [B,nblk,4]
        acc0 = jnp.broadcast_to(
            jnp.array([(_XXP1 + _XXP2) & _M32, _XXP2, 0,
                       (-_XXP1) & _M32], dtype=jnp.uint32), (B, 4))

        def body(i, acc):
            wv = jax.lax.dynamic_index_in_dim(words, i, axis=1,
                                              keepdims=False)
            return rotl(acc + wv * P2, 13) * P1

        acc = jax.lax.fori_loop(0, nblk, body, acc0)
        h = (rotl(acc[:, 0], 1) + rotl(acc[:, 1], 7)
             + rotl(acc[:, 2], 12) + rotl(acc[:, 3], 18))
    else:
        h = jnp.full((B,), _XXP5, dtype=jnp.uint32)
    h = h + u(L)
    i = nblk * 16
    while i + 4 <= L:
        w4 = chunks[:, i:i + 4].astype(jnp.uint32)
        word = jnp.sum(
            w4 * (u(1) << (u(8) * jnp.arange(4, dtype=jnp.uint32))),
            axis=-1, dtype=jnp.uint32)
        h = rotl(h + word * P3, 17) * P4
        i += 4
    while i < L:
        h = rotl(h + chunks[:, i].astype(jnp.uint32) * P5, 11) * P1
        i += 1
    h = h ^ (h >> u(15))
    h = h * P2
    h = h ^ (h >> u(13))
    h = h * P3
    return h ^ (h >> u(16))


def _bitplane_dev(flat, payload_cap):
    """Device bit-plane stage over flat [Np] (Np % 64 == 0).
    Returns (header [2*nb], payload [payload_cap], comp_len)."""
    import jax.numpy as jnp
    Np = flat.shape[0]
    nb = Np // _BLOCK
    shifts8 = jnp.arange(8, dtype=jnp.uint8)
    x = flat.reshape(nb, _BLOCK)
    bits = (x[:, None, :] >> shifts8[None, :, None]) & jnp.uint8(1)
    b4 = bits.reshape(nb, 8, 8, 8)                       # [nb, p, g, t]
    packed = jnp.sum(
        b4.astype(jnp.uint32) << shifts8.astype(jnp.uint32), axis=-1,
        dtype=jnp.uint32).astype(jnp.uint8)              # [nb, p, g]
    all0 = jnp.all(packed == 0, axis=-1)                 # [nb, p]
    all1 = jnp.all(packed == 0xFF, axis=-1)
    stored = ~(all0 | all1)
    pw = (jnp.uint32(1) << shifts8.astype(jnp.uint32))
    flags = jnp.sum(stored.astype(jnp.uint32) * pw, axis=-1,
                    dtype=jnp.uint32).astype(jnp.uint8)  # [nb]
    consts = jnp.sum(all1.astype(jnp.uint32) * pw, axis=-1,
                     dtype=jnp.uint32).astype(jnp.uint8)
    header = jnp.stack([flags, consts], axis=1).reshape(2 * nb)
    sm = stored.reshape(nb * 8)
    smi = sm.astype(jnp.int32)
    pos = jnp.cumsum(smi) - smi                          # exclusive
    dest = jnp.where(sm, pos * 8, payload_cap)           # OOB -> dropped
    destb = (dest[:, None]
             + jnp.arange(8, dtype=jnp.int32)).reshape(-1)
    vals = packed.reshape(nb * 8, 8).reshape(-1)
    payload = jnp.zeros(payload_cap, dtype=jnp.uint8).at[destb].set(
        vals, mode="drop")
    comp_len = jnp.int32(2 * nb) + 8 * jnp.sum(smi)
    return header, payload, comp_len


def _encode_rows(bitmat, batch, w):
    """EC encode [S, k, chunk] -> parity [S, m, chunk] (element layout),
    inlined from ops.xor_mm so it fuses into the same program."""
    from ..ops import xor_mm
    bits = xor_mm.unpack_element_bits(batch, w)
    return xor_mm.pack_element_bits(xor_mm.xor_matmul(bitmat, bits), w)


def _build_program(donate: bool):
    import jax

    @functools.partial(
        jax.jit,
        static_argnames=("w", "mode", "required_milli",
                         "entropy_max_milli", "cap2", "stripe_width"),
        donate_argnums=(0,) if donate else ())
    def program(data, bitmat, tab_z, inv_z, tab_c,
                init_chunk_c, init_shard_z, *, w, mode, required_milli,
                entropy_max_milli, cap2, stripe_width):
        # tab_z and tab_c are not read (the CRC leaf is a bit-matrix
        # product); they stay so the positional signature holds
        import jax.numpy as jnp
        S, k, chunk = data.shape
        N = S * k * chunk
        flat = data.reshape(N)
        pz = _poly_consts(_POLY_ZLIB)
        # (a) per-chunk digests of the RAW chunks
        rows = data.reshape(S * k, chunk)
        chunk_crc32c = _crc32_full(rows, _poly_consts(_POLY_C),
                                   init_chunk_c).reshape(S, k)
        chunk_xxh32 = _xxh32_dev(rows).reshape(S, k)
        if mode == "store":
            parity = _encode_rows(bitmat, data, w)        # [S, m, chunk]
            all_rows = jnp.concatenate([data, parity], axis=1)
            streams = jnp.swapaxes(all_rows, 0, 1).reshape(
                all_rows.shape[1], S * chunk)
            shard_crcs = _crc32_full(streams, pz, init_shard_z)
            return {"parity": parity, "shard_crcs": shard_crcs,
                    "chunk_crc32c": chunk_crc32c,
                    "chunk_xxh32": chunk_xxh32}
        # (b) probe + bit-plane stage + on-device decision
        counts = jnp.zeros(256, dtype=jnp.int32).at[
            flat.astype(jnp.int32)].add(1)
        p = counts.astype(jnp.float32) / jnp.float32(N)
        ent = -jnp.sum(jnp.where(counts > 0,
                                 p * jnp.log2(jnp.maximum(p, 1e-12)),
                                 jnp.float32(0)))
        entropy_milli = (ent * 1000).astype(jnp.int32)    # bits/byte * 1e3
        probe_ok = entropy_milli <= jnp.int32(entropy_max_milli)
        Np = _roundup(N, _BLOCK)
        flat_p = flat if Np == N else jnp.concatenate(
            [flat, jnp.zeros(Np - N, dtype=jnp.uint8)])
        nb = Np // _BLOCK
        header, payload, comp_len = _bitplane_dev(flat_p, cap2 - 2 * nb)
        comp_full = jnp.concatenate([header, payload])    # [cap2]
        ratio_ok = comp_len * 1000 <= jnp.int32(N) * required_milli
        do_compress = probe_ok & ratio_ok
        raw_full = jnp.concatenate(
            [flat, jnp.zeros(cap2 - N, dtype=jnp.uint8)])
        stored_flat = jnp.where(do_compress, comp_full, raw_full)
        S_cap = cap2 // stripe_width
        stored = stored_flat.reshape(S_cap, k, chunk)
        # (c) EC encode of the stored stream (zero tail encodes to zero)
        parity = _encode_rows(bitmat, stored, w)          # [S_cap, m, chunk]
        # (d) per-shard crc32 of the stored prefix: full-capacity crc,
        # then un-shift the dynamic zero tail
        all_rows = jnp.concatenate([stored, parity], axis=1)
        streams = jnp.swapaxes(all_rows, 0, 1).reshape(
            all_rows.shape[1], S_cap * chunk)
        stored_len = jnp.where(do_compress, comp_len, jnp.int32(N))
        used = (stored_len + jnp.int32(stripe_width - 1)) \
            // jnp.int32(stripe_width)
        pad_bytes = ((jnp.int32(S_cap) - used)
                     * jnp.int32(chunk)).astype(jnp.uint32)
        reg = _crc_raw_tree(streams, pz) ^ init_shard_z
        shard_crcs = _crc_unshift(reg, inv_z, pad_bytes) \
            ^ jnp.uint32(0xFFFFFFFF)
        return {"parity": parity, "stored": stored,
                "shard_crcs": shard_crcs,
                "chunk_crc32c": chunk_crc32c, "chunk_xxh32": chunk_xxh32,
                "do_compress": do_compress, "comp_len": comp_len,
                "probe_ok": probe_ok, "entropy_milli": entropy_milli,
                "used_stripes": used}

    return program


_PROGRAMS: dict = {}
_PROGRAM_LOCK = threading.Lock()


def fused_program(donate: bool = False):
    """The process-wide jitted fused program (PROFILER-wrapped).
    Donation only pays (and only avoids per-compile warnings) on real
    accelerators — the dispatcher passes its donation probe through."""
    with _PROGRAM_LOCK:
        prog = _PROGRAMS.get(donate)
        if prog is None:
            from ..common.profiler import PROFILER
            prog = _PROGRAMS.setdefault(
                donate, PROFILER.wrap_jit("fused_transform.program",
                                          _build_program(donate)))
    return prog


def device_crc32(data, device=None) -> int:
    """zlib crc32 of ONE byte stream, computed on device through the
    GF(2) combine tree.  Deep scrub's audit leg for resident objects:
    the primary still READS the on-disk shard bytes (silent disk
    bitrot must stay catchable — the write-time digest only says what
    the bytes SHOULD be), but the hash itself runs on device, so the
    host never walks a crc loop."""
    import jax
    import jax.numpy as jnp
    buf = bytes(data)
    z = _poly_consts(_POLY_ZLIB)
    L = len(buf)
    L2 = _next_pow2(max(L, 1))
    raw = np.frombuffer(buf, dtype=np.uint8)
    if raw.size != L2:     # leading zeros are a crc_raw no-op; the
        raw = np.concatenate(  # init const carries the TRUE length
            [np.zeros(L2 - raw.size, dtype=np.uint8), raw])
    init = np.uint32(z.shift_n(0xFFFFFFFF, L))
    from ..models.table_cache import device_entry_key
    key = ("scrub_crc", device_entry_key(device))
    with _CONSTS_LOCK:
        cache = _CONSTS.setdefault("scrub_jit", {})
        fn = cache.get(key)
    if fn is None:
        def crc_fn(stream, init_c, _pc=z):
            return _crc_raw_tree(stream[None, :], _pc)[0] \
                ^ init_c ^ jnp.uint32(0xFFFFFFFF)

        from ..common.profiler import PROFILER
        fn = PROFILER.wrap_jit("fused_transform.scrub_crc",
                               jax.jit(crc_fn))
        with _CONSTS_LOCK:
            fn = cache.setdefault(key, fn)
    dev = raw if device is None else jax.device_put(raw, device)
    return int(jax.block_until_ready(fn(dev, init))) & 0xFFFFFFFF


def plan_capacity(n_bytes: int, stripe_width: int) -> int:
    """Static stored-buffer capacity: fits the worst-case 66/64 container
    AND the raw payload, stripe aligned."""
    nb = _roundup(n_bytes, _BLOCK) // _BLOCK
    return _roundup(max(66 * nb, n_bytes), stripe_width)


def run_fused(codec, batch, mode: str = "store",
              required_ratio: float = 0.875,
              entropy_max_bits: float = 7.0,
              device=None, data_dev=None, donate: bool = False):
    """Run the fused transform over one staged batch.

    batch: [S, k, chunk] uint8 (host or device array). data_dev, when
    given, is the already-staged device copy (the dispatcher's h2d leg);
    otherwise batch is transferred here (the one h2d). Returns the
    on-device output dict — callers d2h it in one device_get.
    """
    import jax
    import jax.numpy as jnp
    S, k, chunk = batch.shape
    w = codec.w
    sw = k * chunk
    N = S * k * chunk
    z, c = _poly_consts(_POLY_ZLIB), _poly_consts(_POLY_C)
    tab_z, inv_z, tab_c = _dev_consts(device)
    bitmat = codec._device_bitmat(device) if device is not None \
        else codec._device_bitmat()
    init_chunk_c = np.uint32(c.shift_n(0xFFFFFFFF, chunk))
    if mode == "store":
        init_shard_z = np.uint32(z.shift_n(0xFFFFFFFF, S * chunk))
        cap2 = N
    else:
        cap2 = plan_capacity(N, sw)
        init_shard_z = np.uint32(z.shift_n(0xFFFFFFFF,
                                           (cap2 // sw) * chunk))
    data = data_dev if data_dev is not None else jnp.asarray(
        np.ascontiguousarray(batch))
    if device is not None and data_dev is None:
        data = jax.device_put(data, device)
    return fused_program(donate)(
        data, bitmat, tab_z, inv_z, tab_c,
        jnp.uint32(init_chunk_c), jnp.uint32(init_shard_z),
        w=w, mode=mode, required_milli=int(required_ratio * 1000),
        entropy_max_milli=int(entropy_max_bits * 1000), cap2=cap2,
        stripe_width=sw)


def finish_fused(out, S: int, k: int, chunk: int, mode: str):
    """One d2h of the fused outputs -> FusedResult (host numpy views).

    The single jax.device_get here IS the fused path's one d2h; callers
    must not read individual outputs beforehand.
    """
    import jax
    host = jax.device_get({k_: v for k_, v in out.items()})
    return result_from_host(host, S, k, chunk, mode, dev_out=out)


def result_from_host(host: dict, S: int, k: int, chunk: int, mode: str,
                     dev_out=None):
    """Build a FusedResult from an already-transferred host dict (the
    dispatcher's d2h stage drains the whole output in one device_get
    and hands the host dict here). dev_out keeps the device-side
    outputs reachable for HBM-tier adoption."""
    r = FusedResult()
    r.raw_len = S * k * chunk
    r.padded_len = _roundup(r.raw_len, _BLOCK)
    r.chunk_crc32c = host["chunk_crc32c"]
    r.chunk_xxh32 = host["chunk_xxh32"]
    r.shard_crcs = [int(x) for x in host["shard_crcs"]]
    r.dev_parity = dev_out["parity"] if dev_out is not None else None
    if mode == "store":
        r.parity = host["parity"]
        r.stored = None
        r.dev_stored = None
        r.compressed = False
        r.comp_len = r.raw_len
        r.probe_ok = False
        r.entropy_bpb = None
        r.stored_len = r.raw_len
        r.used_stripes = S
        return r
    r.compressed = bool(host["do_compress"])
    r.comp_len = int(host["comp_len"])
    r.probe_ok = bool(host["probe_ok"])
    r.entropy_bpb = float(host["entropy_milli"]) / 8000.0
    r.used_stripes = int(host["used_stripes"])
    r.stored_len = r.comp_len if r.compressed else r.raw_len
    used = r.used_stripes
    r.parity = host["parity"][:used]
    r.stored = host["stored"][:used]
    r.dev_stored = dev_out["stored"] if dev_out is not None else None
    return r

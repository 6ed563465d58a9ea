"""Shared machinery for generator-matrix codecs (jerasure-style techniques).

Covers both encode styles of the reference jerasure plugin
(/root/reference/src/erasure-code/jerasure/ErasureCodeJerasure.cc):

  - MatrixErasureCode: element-layout GF(2^w) matrix codes
    (reed_sol_van, reed_sol_r6_op; jerasure_matrix_encode semantics).
  - BitmatrixErasureCode: packet-layout bitmatrix codes
    (cauchy_*, liberation, blaum_roth, liber8tion;
    jerasure_schedule_encode semantics with `packetsize`).

Both run on the same TPU primitive (ops.xor_mm): the generator (or cached
decode matrix — the analog of ErasureCodeIsaTableCache,
/root/reference/src/erasure-code/isa/ErasureCodeIsaTableCache.cc) expands
to a 0/1 bitplane matrix executed as an int8 MXU matmul.

Backends: "jax" (TPU hot path) and "numpy" (exact CPU reference; also the
monitor-side validation mode that must not require a device — the mon
instantiates plugins to validate profiles, SURVEY.md §3.5).
"""

from __future__ import annotations

import errno

import numpy as np

from ..ops import gf, gf_ref
from ..utils import profile as profile_util
from .base import ErasureCode, ErasureCodeError
from .table_cache import (TableCache, device_entry_key, xor_parity_rows,
                          xor_recover)

LARGEST_VECTOR_WORDSIZE = 16  # reference SIMD word (ErasureCodeJerasure.cc:31)

_bank_pick_fn = None


def _bank_pick(bank, i: int):
    """Device-side bank row select with the index TRACED (one compiled
    gather serves every signature). A static `bank[i]` would bake each
    distinct index into its own tiny executable, and each fresh
    compile stalls the decode that needed it."""
    global _bank_pick_fn
    if _bank_pick_fn is None:
        import jax

        from ..common.profiler import PROFILER
        _bank_pick_fn = PROFILER.wrap_jit(
            "matrix_base.bank_pick", jax.jit(lambda b, j: b[j]))
    import jax.numpy as jnp
    return _bank_pick_fn(bank, jnp.asarray(i, dtype=jnp.int32))


def _roundup(x: int, align: int) -> int:
    return x + (align - x % align) % align if x % align else x


class GeneratorCodec(ErasureCode):
    """Common k/m/w parsing + cached encode/decode dispatch."""

    technique = "generic"
    DEFAULT_K = "7"
    DEFAULT_M = "3"
    DEFAULT_W = "8"

    def __init__(self, backend: str = "jax"):
        super().__init__()
        self.backend = backend
        self.k = 0
        self.m = 0
        self.w = 0
        self.per_chunk_alignment = False
        self.coding: np.ndarray | None = None   # [m, k] GF generator
        self._bitmat: np.ndarray | None = None  # [m*w, k*w] encode bitmatrix
        self._bitmat_dev = None
        self._bitmat_dev_by: dict = {}  # device key -> committed copy
        self._decode_cache = TableCache()
        self._xor_rows: list[int] = []  # parity rows that are plain XORs
        self.xor_fast_hits = 0
        # device-resident decode-matrix bank (see _ensure_decode_bank)
        self._bank_state: str | None = None
        self._bank_index: dict | None = None
        self._bank_host = None
        self._bank_dev = None

    # -- profile -----------------------------------------------------------

    def parse(self, profile: dict, errors: list | None = None) -> None:
        super().parse(profile, errors)
        self.k = profile_util.to_int("k", profile, self.DEFAULT_K, errors)
        self.m = profile_util.to_int("m", profile, self.DEFAULT_M, errors)
        self.w = profile_util.to_int("w", profile, self.DEFAULT_W, errors)
        if self.chunk_mapping and len(self.chunk_mapping) != self.k + self.m:
            self.chunk_mapping = []
            raise ErasureCodeError(
                errno.EINVAL,
                "mapping maps %d chunks instead of the expected %d"
                % (len(profile.get("mapping", "")), self.k + self.m))
        self.sanity_check_k(self.k)
        if self.m < 1:
            raise ErasureCodeError(errno.EINVAL, "m=%d must be >= 1" % self.m)
        if self.w not in gf.PRIM_POLY:
            raise ErasureCodeError(
                errno.EINVAL, "w=%d must be in 2..32" % self.w)

    def get_chunk_count(self) -> int:
        return self.k + self.m

    def get_data_chunk_count(self) -> int:
        return self.k

    def get_alignment(self) -> int:
        raise NotImplementedError

    def get_chunk_size(self, object_size: int) -> int:
        # Shared by every jerasure-style technique
        # (ErasureCodeJerasure.cc:74-97).
        alignment = self.get_alignment()
        if self.per_chunk_alignment:
            chunk_size = -(-object_size // self.k)
            return _roundup(max(chunk_size, alignment), alignment)
        padded = _roundup(object_size, alignment)
        assert padded % self.k == 0
        return padded // self.k

    # -- generator ---------------------------------------------------------

    def make_generator(self) -> np.ndarray:
        raise NotImplementedError

    def prepare(self) -> None:
        try:
            self.coding = self.make_generator()
        except ValueError as e:
            # field-size violations (k+m > 2^w etc.) are profile errors
            raise ErasureCodeError(errno.EINVAL, str(e))
        self._bitmat = gf.generator_to_bitmatrix(self.coding, self.w)
        self._bitmat_dev = None
        self._bitmat_dev_by = {}
        self._decode_cache.clear()
        self.xor_fast_hits = 0
        self._xor_rows = xor_parity_rows(self._bitmat, self.k, self.w)
        self._bank_state = None
        self._bank_index = None
        self._bank_host = None
        self._bank_dev = None

    def _device_bitmat(self, device=None):
        if device is None:
            if self._bitmat_dev is None:
                import jax.numpy as jnp
                self._bitmat_dev = jnp.asarray(self._bitmat)
            return self._bitmat_dev
        key = device_entry_key(device)
        dev = self._bitmat_dev_by.get(key)
        if dev is None:
            import jax
            import jax.numpy as jnp
            dev = self._bitmat_dev_by.setdefault(
                key, jax.device_put(jnp.asarray(self._bitmat), device))
        return dev

    def _as_device(self, bitmat, entry: dict | None = None, device=None,
                   data=None):
        """Device copy of a bitmatrix, cached on the encode path or inside
        the decode-cache entry — keyed per HOME device (table_cache
        .device_entry_key), so a repeated erasure signature reuses the
        already-transferred constant on ITS chip and a second pinned
        device never consumes (or clobbers) the first device's copy.
        Inside a jit trace of the caller (`data` a tracer: the
        dispatcher jits encode_batch to donate its input) the matrix is
        a constant of that program and nothing is cached, or a tracer
        would leak into every later call."""
        import jax
        if isinstance(data, jax.core.Tracer):
            import jax.numpy as jnp
            return jnp.asarray(bitmat)
        if bitmat is self._bitmat:
            return self._device_bitmat(device)
        import jax.numpy as jnp
        if entry is not None:
            key = device_entry_key(device)
            dev = entry.get(key)
            if dev is None:
                bm = jnp.asarray(bitmat)
                if device is not None:
                    import jax
                    bm = jax.device_put(bm, device)
                dev = entry.setdefault(key, bm)
            return dev
        bm = jnp.asarray(bitmat)
        if device is not None:
            import jax
            bm = jax.device_put(bm, device)
        return bm

    def _full_decode_matrix(self, avail_rows: tuple) -> np.ndarray:
        """[k+m, k] GF matrix mapping k available chunks -> all chunks."""
        dec = gf.decode_matrix(self.coding, self.k, avail_rows, self.w)
        parity = gf.gf_matmul(self.coding, dec, self.w)
        return np.concatenate([dec, parity], axis=0)

    #: precompute + device-upload the whole decode bank when the
    #: pattern space is at most this many C(n, k) signatures
    DECODE_BANK_LIMIT = 512

    def _ensure_decode_bank(self) -> bool:
        """Build the device-resident decode-matrix BANK: every C(n,k)
        erasure signature's decode bitmatrix, stacked and uploaded in
        ONE transfer. A cache miss then costs a device-side slice
        instead of a host matrix build + per-miss H2D per fresh
        signature. The reference's ISA table cache
        (ErasureCodeIsaTableCache.cc) builds tables lazily per miss
        because the CPU consumes them in place; on an accelerator the
        bank trade (~1 MB resident for k=8,m=3) is the right one."""
        if self._bank_state is None:
            import math
            n = self.get_chunk_count()
            if self.backend != "jax" or \
                    math.comb(n, self.k) > self.DECODE_BANK_LIMIT:
                self._bank_state = "infeasible"
            else:
                import itertools

                import jax.numpy as jnp
                idx: dict = {}
                gfs, bms = [], []
                for avail in itertools.combinations(range(n), self.k):
                    full = self._full_decode_matrix(avail)
                    idx[avail] = len(gfs)
                    gfs.append(full)
                    bms.append(gf.generator_to_bitmatrix(full, self.w))
                self._bank_index = idx
                self._bank_host = (gfs, bms)
                self._bank_dev = jnp.asarray(np.stack(bms))
                self._bank_state = "built"
        return self._bank_state == "built"

    def _decode_entry(self, avail_rows: tuple):
        """Cache of per-erasure-signature decode matrices.

        The reference's ISA plugin keeps the same LRU-style cache of decode
        tables keyed by erasure signature
        (ErasureCodeIsaTableCache.{h,cc}); here the cached object also
        carries the device-side bitmatrix so repeated degraded reads hit a
        compiled program directly — served from the device-resident bank
        when the signature space is small enough (_ensure_decode_bank).
        """
        entry = self._decode_cache.get(avail_rows)
        if entry is None:
            if self._ensure_decode_bank() and \
                    avail_rows in self._bank_index:
                i = self._bank_index[avail_rows]
                gfs, bms = self._bank_host
                entry = self._decode_cache.put(
                    avail_rows,
                    {"gf": gfs[i], "bitmat": bms[i],
                     "bitmat_dev": _bank_pick(self._bank_dev, i)})
            else:
                full = self._full_decode_matrix(avail_rows)
                entry = self._decode_cache.put(
                    avail_rows,
                    {"gf": full,
                     "bitmat": gf.generator_to_bitmatrix(full, self.w)})
        return entry

    def table_cache_stats(self) -> dict:
        stats = self._decode_cache.stats()
        stats["xor_fast_hits"] = self.xor_fast_hits
        return stats

    # -- single-erasure XOR fast path ---------------------------------------

    def xor_group(self, missing_logical: int):
        """Logical chunk rows whose byte-wise XOR reproduces the missing
        row, or None when no plain-XOR parity covers it (isa/xor_op
        analog). Valid for a missing data row (any XOR parity row serves)
        or a missing XOR parity row itself."""
        if not self._xor_rows:
            return None
        if missing_logical < self.k:
            row = self._xor_rows[0]
        elif missing_logical - self.k in self._xor_rows:
            row = missing_logical - self.k
        else:
            return None
        group = set(range(self.k))
        group.add(self.k + row)
        group.discard(missing_logical)
        return group

    def xor_plan(self, missing_phys: int, available_phys) -> set | None:
        """Physical chunk set whose XOR reproduces `missing_phys`, or None.

        The single shared planner behind the region-XOR shortcut: maps
        the missing physical index through the chunk mapping, asks
        xor_group for the logical group, and checks every member
        survived in `available_phys`.
        """
        n = self.get_chunk_count()
        inv = {self.chunk_index(i): i for i in range(n)}
        ml = inv.get(missing_phys)
        group = self.xor_group(ml) if ml is not None else None
        if group is None:
            return None
        phys = {self.chunk_index(i) for i in group}
        return phys if phys <= set(available_phys) else None

    def minimum_to_decode(self, want_to_read: set, available: set) -> set:
        """Prefer the XOR group for a single erasure so the read path
        fetches exactly the shards the region-XOR shortcut needs (the
        reference's ISA plugin biases shard selection the same way)."""
        if want_to_read <= available:
            return set(want_to_read)
        missing = want_to_read - available
        if len(missing) == 1:
            plan = self.xor_plan(next(iter(missing)), available)
            if plan is not None:
                return plan
        return super().minimum_to_decode(want_to_read, available)

    def decode(self, want_to_read: set, chunks: dict) -> dict:
        """Single-erasure region-XOR shortcut before the matrix path.

        Fires when exactly one wanted chunk is missing and every member of
        its XOR group survived — whether the caller handed us all n-1
        survivors or just the k chunks minimum_to_decode asked for.
        """
        have = set(chunks)
        missing = want_to_read - have
        if len(missing) == 1:
            m_phys = next(iter(missing))
            plan = self.xor_plan(m_phys, have)
            if plan is not None:
                rec = xor_recover({i: chunks[i] for i in plan})
                self.xor_fast_hits += 1
                out = {m_phys: rec}
                for idx in have:  # base decode echoes survivors back too
                    out[idx] = np.asarray(chunks[idx], dtype=np.uint8)
                return out
        return super().decode(want_to_read, chunks)

    # -- batched device API -------------------------------------------------

    def _apply_matrix(self, gf_matrix: np.ndarray, bitmat: np.ndarray,
                      data: np.ndarray, entry: dict | None = None
                      ) -> np.ndarray:
        raise NotImplementedError

    def encode_batch(self, data: np.ndarray) -> np.ndarray:
        return self._apply_matrix(self.coding, self._bitmat, data)

    def decode_batch(self, avail_rows: tuple, chunks: np.ndarray) -> np.ndarray:
        if len(avail_rows) != self.k:
            raise ErasureCodeError(errno.EIO, "need exactly k chunks")
        entry = self._decode_entry(tuple(avail_rows))
        return self._apply_matrix(entry["gf"], entry["bitmat"], chunks,
                                  entry)


class MatrixErasureCode(GeneratorCodec):
    """Element-layout GF(2^w) matrix codec (Reed-Solomon family)."""

    def parse(self, profile: dict, errors: list | None = None) -> None:
        super().parse(profile, errors)
        self.per_chunk_alignment = profile_util.to_bool(
            "jerasure-per-chunk-alignment", profile, "false")

    def get_alignment(self) -> int:
        # ErasureCodeJerasure.cc:168-178.
        if self.per_chunk_alignment:
            return self.w * LARGEST_VECTOR_WORDSIZE
        if (self.w * 4) % LARGEST_VECTOR_WORDSIZE:
            return self.k * self.w * LARGEST_VECTOR_WORDSIZE
        return self.k * self.w * 4

    def _apply_matrix(self, gf_matrix, bitmat, data, entry=None):
        if self.backend == "numpy":
            data = np.asarray(data, dtype=np.uint8)
            return np.stack([
                gf_ref.matrix_encode_ref(gf_matrix, data[b], self.w)
                for b in range(data.shape[0])])
        import jax.numpy as jnp
        from ..ops import xor_mm
        out = xor_mm.matrix_encode(
            self._as_device(bitmat, entry, _committed_device(data), data),
            jnp.asarray(data), self.w)
        return out if _is_jax(data) else np.asarray(out)


class BitmatrixErasureCode(GeneratorCodec):
    """Packet-layout bitmatrix codec (Cauchy / Liberation families).

    Chunk layout: S superblocks x w packets x packetsize bytes
    (jerasure_schedule_encode semantics; packetsize default 2048,
    ErasureCodeJerasure.h:141). Decode converts the GF-domain decode
    matrix to a bitmatrix — valid because gf.generator_to_bitmatrix is a
    ring homomorphism, so the bitmatrix of the inverse is the inverse of
    the bitmatrix.
    """

    DEFAULT_PACKETSIZE = "2048"

    def __init__(self, backend: str = "jax"):
        super().__init__(backend)
        self.packetsize = 0

    def parse(self, profile: dict, errors: list | None = None) -> None:
        super().parse(profile, errors)
        self.packetsize = profile_util.to_int(
            "packetsize", profile, self.DEFAULT_PACKETSIZE, errors)
        if self.packetsize < 1:
            raise ErasureCodeError(
                errno.EINVAL, "packetsize=%d must be >= 1" % self.packetsize)
        self.per_chunk_alignment = profile_util.to_bool(
            "jerasure-per-chunk-alignment", profile, "false")

    def require_word_packetsize(self) -> None:
        """jerasure's liberation-family constraint: packetsize must cover
        whole machine words (shared by liberation/blaum_roth/liber8tion)."""
        if self.packetsize % 8:
            raise ErasureCodeError(
                errno.EINVAL,
                "packetsize=%d must be a multiple of 8" % self.packetsize)

    def get_alignment(self) -> int:
        # ErasureCodeJerasure.cc:273-287; per-chunk alignment must stay a
        # multiple of the w*packetsize superblock or encode would reject
        # its own chunk size (lcm, not roundup — same fix as the native
        # BitmatrixCodec::get_alignment)
        if self.per_chunk_alignment:
            import math
            return math.lcm(self.w * self.packetsize,
                            LARGEST_VECTOR_WORDSIZE)
        if (self.w * self.packetsize * 4) % LARGEST_VECTOR_WORDSIZE:
            return self.k * self.w * self.packetsize * LARGEST_VECTOR_WORDSIZE
        return self.k * self.w * self.packetsize * 4

    def _apply_matrix(self, gf_matrix, bitmat, data, entry=None):
        if self.backend == "numpy":
            data = np.asarray(data, dtype=np.uint8)
            return np.stack([
                gf_ref.bitmatrix_encode_ref(bitmat, data[b], self.w,
                                            self.packetsize)
                for b in range(data.shape[0])])
        import jax.numpy as jnp
        from ..ops import xor_mm
        out = xor_mm.bitmatrix_encode(
            self._as_device(bitmat, entry, _committed_device(data), data),
            jnp.asarray(data), self.w, self.packetsize)
        return out if _is_jax(data) else np.asarray(out)


def _is_jax(x) -> bool:
    return type(x).__module__.startswith("jax")


def _committed_device(x):
    """Home device of a committed single-device jax array — the pinned
    dispatcher's h2d stage commits staged batches to its home chip, and
    the codec constants must follow or XLA rejects the mixed-placement
    call.  None for host arrays, uncommitted placements, multi-device
    shardings, and the implicit default device (where the legacy
    un-keyed constants already live)."""
    if not _is_jax(x):
        return None
    try:
        if not getattr(x, "committed", False):
            return None
        devs = x.devices()
        if len(devs) != 1:
            return None
        dev = next(iter(devs))
        import jax
        return None if dev == jax.devices()[0] else dev
    except Exception:
        return None

"""HBM-resident EC chunk tier: object data crosses the pipe ONCE.

The architectural answer to "why ship data to the TPU at all" when the
host<->device link is the bottleneck: once an object's chunks are in
HBM, every downstream consumer — parity encode, deep-scrub digests,
shard reconstruction — reads the RESIDENT copy.  The reference runs
each of those as a separate CPU pass over host memory
(ECBackend::continue_recovery_op src/osd/ECBackend.cc:531 re-reads
shards; PGBackend::be_deep_scrub re-reads and re-digests); here the
host pays one H2D per object lifetime and tiny D2H for results
(digests are 8 bytes/chunk; recovery returns only the rebuilt shard).

Wired into the OSD (osd_daemon.py, osd_hbm_tier_enable): the
TpuDispatcher's pipeline ADOPTS each encode's staged data + computed
parity device-side (adopt_encode — zero extra transfers), keyed by
(pg, object); ECBackend recovery reconstruction, scrub repair
rebuilds, and (opt-in) repeat client reads then hit the resident copy
instead of re-crossing PCIe. Entries carry their codec, so one
OSD-wide tier serves every EC pool the daemon hosts. Any mutation of
an object invalidates its entry; a PG interval change (new acting
set) drops the whole PG's entries — a stale resident copy must never
survive a primaryship hand-off.

Capacity is bounded (HBM is small): inserts evict LRU objects — an
evicted object simply pays H2D again on its next op, exactly like any
cache.  Residency/utilization rides the l_hbm_* counters (telemetry
report + the `hbm status` asok command).

Digest: a vectorized Fletcher-style pair (sum, index-weighted sum)
over the chunk bytes, both mod 2^32.  Scrub only ever compares
digests computed by THIS tier (or its numpy twin `host_digest`), so
the algorithm needs to be deterministic and position-sensitive, not
crc32c-compatible; position sensitivity is what catches the
swapped-block corruption a plain sum misses.
"""

from __future__ import annotations

import threading

import numpy as np

__all__ = ["HbmChunkTier", "host_digest"]


def host_digest(chunks: np.ndarray) -> np.ndarray:
    """Numpy twin of the device digest: chunks [..., n] uint8 ->
    uint64 digest per chunk ((weighted_sum << 32) | sum)."""
    x = chunks.astype(np.uint64)
    n = x.shape[-1]
    w = (np.arange(n, dtype=np.uint64) % 0xFFFF) + 1
    s = x.sum(axis=-1) & 0xFFFFFFFF
    ws = (x * w).sum(axis=-1) & 0xFFFFFFFF
    return (ws << np.uint64(32)) | s


_device_digest = None


def _init_device_digest():
    """Module-level jitted digest: one compile per chunk shape no
    matter how many tier instances exist."""
    global _device_digest
    if _device_digest is not None:
        return
    import jax
    import jax.numpy as jnp

    @jax.jit
    def digest(chunks):
        x = chunks.astype(jnp.uint32)
        n = x.shape[-1]
        w = (jnp.arange(n, dtype=jnp.uint32) % 0xFFFF) + 1
        s = x.sum(axis=-1, dtype=jnp.uint32)
        ws = (x * w).sum(axis=-1, dtype=jnp.uint32)
        return s, ws
    from ..common.profiler import PROFILER
    _device_digest = PROFILER.wrap_jit("hbm_tier.digest", digest)


class _Batch:
    """One resident device array [B, k+m, n] shared by the B objects
    uploaded together.  Keeping BATCH granularity is what keeps the
    consumer dispatch count independent of object count: per-object
    device slices would turn a 48-object scrub into a 48-operand
    gather (dozens of device dispatches); per-batch arrays make it
    one take per batch."""

    __slots__ = ("arr", "live", "codec", "obj_bytes", "digests")

    def __init__(self, arr, live: int, codec=None, obj_bytes: int = 0,
                 digests=None):
        self.arr = arr
        self.live = live
        self.codec = codec
        self.obj_bytes = obj_bytes
        # per-object per-shard crc32 (zlib poly) rows [B, k+m], computed
        # ON DEVICE by the fused write transform and adopted beside the
        # chunks: deep-scrub of a resident object verifies against these
        # without hashing a single byte on the host
        self.digests = digests


class HbmChunkTier:
    """Keyed store of device-resident chunk arrays [k+m, chunk] with
    fused device programs for the consumers.  `codec` is the default
    for put_encode; entries adopted from the dispatcher carry their
    own codec, so one tier serves heterogeneous pools."""

    def __init__(self, codec=None, capacity_objects: int = 64,
                 device=None):
        _init_device_digest()
        self.codec = codec
        self.capacity = capacity_objects
        # home device (parallel/placement.py): uploads commit here and
        # residency is accounted under a per-device ledger category, so
        # N tiers on N chips never fight over one global gauge
        self.device = device
        from ..parallel.placement import device_label
        self._mem_category = "hbm_tier" if device is None \
            else "hbm_tier[%s]" % device_label(device)
        self._lock = threading.Lock()
        self._objs: dict = {}          # name -> (_Batch, row index)
        self._order: list = []         # LRU, oldest first
        self._resident_bytes = 0
        # residency/utilization gauges (telemetry pipeline: the OSD
        # report's status bag + an optional ctx.perf registration)
        from ..common.perf_counters import PerfCountersBuilder
        self.perf = (PerfCountersBuilder("osd_hbm")
                     .add_u64("l_hbm_resident_objects",
                              "objects resident in HBM")
                     .add_u64("l_hbm_resident_bytes",
                              "HBM bytes held by resident chunks")
                     .add_u64_counter("l_hbm_hits",
                                      "consumer reads served resident")
                     .add_u64_counter("l_hbm_misses",
                                      "lookups that missed residency")
                     .add_u64_counter("l_hbm_evictions",
                                      "objects evicted over capacity")
                     .add_u64_counter("l_hbm_adopted",
                                      "encodes adopted device-side "
                                      "from the dispatcher pipeline")
                     .create_perf_counters())

    # -- residency -----------------------------------------------------

    def _touch(self, name) -> None:
        if name in self._order:
            self._order.remove(name)
        self._order.append(name)

    def _drop_locked(self, name) -> None:
        ent = self._objs.pop(name, None)
        if ent is not None:
            ent[0].live -= 1
            self._resident_bytes -= ent[0].obj_bytes
            # HBM frees at batch granularity: the array goes when its
            # LAST object is evicted (documented coarseness)
            if ent[0].live <= 0:
                ent[0].arr = None
        if name in self._order:
            self._order.remove(name)

    def _evict_over_capacity(self) -> None:
        while len(self._objs) > self.capacity and self._order:
            self._drop_locked(self._order[0])
            self.perf.inc("l_hbm_evictions")

    def _update_gauges_locked(self) -> None:
        self.perf.set("l_hbm_resident_objects", len(self._objs))
        self.perf.set("l_hbm_resident_bytes", self._resident_bytes)
        # device-memory ledger: tier residency is the dominant HBM
        # category, so every gauge refresh updates the profiler too
        from ..common.profiler import PROFILER
        PROFILER.mem_set(self._mem_category, self._resident_bytes)

    def _insert_locked(self, name, batch: _Batch, row: int) -> None:
        if name in self._objs:
            self._drop_locked(name)
        self._objs[name] = (batch, row)
        self._resident_bytes += batch.obj_bytes
        self._touch(name)
        self._evict_over_capacity()

    def put_encode(self, names: list, data_host: np.ndarray,
                   codec=None):
        """THE one H2D: upload a batch of objects' data chunks
        [batch, k, n], encode parity on device, and retain the full
        [batch, k+m, n] array resident.  Returns the device parity
        [batch, m, n] (callers usually leave it on device)."""
        import jax.numpy as jnp
        codec = codec if codec is not None else self.codec
        if self.device is not None:
            import jax
            data_dev = jax.device_put(data_host, self.device)
        else:
            data_dev = jnp.asarray(data_host)   # single transfer
        parity = codec.encode_batch(data_dev)
        full = jnp.concatenate([data_dev, parity], axis=1)
        obj_bytes = int(full.shape[1]) * int(full.shape[2])
        batch = _Batch(full, len(names), codec, obj_bytes)
        with self._lock:
            for i, name in enumerate(names):
                self._insert_locked(name, batch, i)
            self._update_gauges_locked()
        return parity

    def adopt_encode(self, name, data_rows, parity_rows, codec,
                     digests=None) -> None:
        """Adopt one object's ALREADY-STAGED encode from the dispatcher
        pipeline: data_rows [S, k, chunk] (the staged h2d input) and
        parity_rows [S, m, chunk] (the compute output) are device
        arrays, so residency costs zero extra transfers — this is how
        "the data crosses the pipe once" becomes true on the production
        write path rather than only in the bench harness.  Host arrays
        are accepted too (the no-jax dispatcher path): adoption is then
        itself the one h2d.

        Stored layout matches put_encode: [k+m, S*chunk] — shard i's
        whole chunk stream is row i.

        digests, when given, is the fused transform's device-computed
        per-shard crc32 list (k+m entries, zlib poly over each shard's
        stored stream) — retained beside the rows for scrub-from-digest
        (shard_digests)."""
        import jax.numpy as jnp
        if self.device is not None and not (
                type(data_rows).__module__.startswith("jax")):
            # host-array adoption (no-jax dispatcher path): the one h2d
            # goes straight to the home device
            import jax
            data_dev = jax.device_put(data_rows, self.device)
            parity_dev = jax.device_put(parity_rows, self.device)
        else:
            data_dev = jnp.asarray(data_rows)
            parity_dev = jnp.asarray(parity_rows)
        # [S, k+m, chunk] -> [k+m, S, chunk] -> [k+m, S*chunk]
        full = jnp.concatenate([data_dev, parity_dev], axis=1)
        full = jnp.transpose(full, (1, 0, 2)).reshape(
            full.shape[1], -1)
        obj_bytes = int(full.shape[0]) * int(full.shape[1])
        dig = None if digests is None else np.asarray(
            digests, dtype=np.uint32)[None]
        batch = _Batch(full[None], 1, codec, obj_bytes, dig)
        with self._lock:
            self._insert_locked(name, batch, 0)
            self._update_gauges_locked()
        self.perf.inc("l_hbm_adopted")

    def _gather(self, names: list):
        """Stack the named objects' chunk arrays [len, k+m, n] in name
        order — one take per underlying batch run, not per object."""
        import jax.numpy as jnp
        parts = []
        i = 0
        while i < len(names):
            batch, idx = self._objs[names[i]]
            rows = [idx]
            j = i + 1
            while j < len(names) and \
                    self._objs[names[j]][0] is batch:
                rows.append(self._objs[names[j]][1])
                j += 1
            parts.append(jnp.take(
                batch.arr, jnp.asarray(rows, dtype=jnp.int32), axis=0))
            i = j
        return parts[0] if len(parts) == 1 else \
            jnp.concatenate(parts, axis=0)

    def resident(self, name) -> bool:
        with self._lock:
            return name in self._objs

    def get(self, name):
        with self._lock:
            ent = self._objs.get(name)
            if ent is None:
                self.perf.inc("l_hbm_misses")
                return None
            self._touch(name)
            self.perf.inc("l_hbm_hits")
            return ent[0].arr[ent[1]]

    def codec_of(self, name):
        """The codec an entry was encoded with (None when absent)."""
        with self._lock:
            ent = self._objs.get(name)
            return None if ent is None else (ent[0].codec or self.codec)

    def shard_digests(self, name):
        """Device-computed per-shard crc32 row for a resident object
        (uint32[k+m], zlib poly over each shard's stored stream), or
        None when the entry was adopted without digests.  This is the
        scrub-from-digest surface: a deep scrub that finds one here
        verifies the object with ZERO host hashing."""
        with self._lock:
            ent = self._objs.get(name)
            if ent is None or ent[0].digests is None:
                return None
            self._touch(name)
            self.perf.inc("l_hbm_hits")
            return np.asarray(ent[0].digests[ent[1]])

    def drop(self, name) -> None:
        with self._lock:
            self._drop_locked(name)
            self._update_gauges_locked()

    def drop_prefix(self, prefix) -> int:
        """Invalidate every entry whose tuple key starts with `prefix`
        (the PG interval-change hook: a primaryship hand-off must drop
        the PG's residency — another primary may have written since).
        Returns the number of entries dropped."""
        with self._lock:
            victims = [name for name in self._objs
                       if isinstance(name, tuple) and name
                       and name[0] == prefix]
            for name in victims:
                self._drop_locked(name)
            if victims:
                self._update_gauges_locked()
        return len(victims)

    # -- consumers (all read the RESIDENT copy) ------------------------

    def _digests(self, stacked):
        return _device_digest(stacked)

    def deep_scrub(self, names: list, device_out: bool = False):
        """Per-chunk digests of every named resident object, computed
        on device in one fused call per chunk shape; only the digests
        (8 bytes/chunk) cross back.  Returns {name: uint64[k+m]} — or,
        with device_out, the raw device (s, ws) pair so callers
        batching several consumers can defer every host read to the
        end (finalize_digests turns the pair into the dict; device_out
        requires a homogeneous shape across names)."""
        with self._lock:
            by_shape: dict = {}
            for name in names:
                ent = self._objs[name]
                shape = tuple(ent[0].arr.shape[1:])
                by_shape.setdefault(shape, []).append(name)
            gathered = [(group, self._gather(group))
                        for group in by_shape.values()]
        if device_out:
            if len(gathered) != 1:
                raise ValueError("device_out needs one chunk shape, "
                                 "got %d" % len(gathered))
            return self._digests(gathered[0][1])
        out: dict = {}
        for group, stacked in gathered:
            s, ws = self._digests(stacked)
            out.update(self.finalize_digests(group, s, ws))
        return out

    @staticmethod
    def finalize_digests(names: list, s, ws) -> dict:
        s = np.asarray(s).astype(np.uint64)
        ws = np.asarray(ws).astype(np.uint64)
        dig = (ws << np.uint64(32)) | s
        return {name: dig[i] for i, name in enumerate(names)}

    def reconstruct(self, name, lost_shards: tuple):
        """Rebuild the lost shard(s) from the RESIDENT survivors —
        zero host reads of chunk data (ECBackend recovery's read
        phase priced out).  Returns the device array of rebuilt rows
        [len(lost), n]."""
        import jax.numpy as jnp
        with self._lock:
            ent = self._objs.get(name)
            if ent is None:
                self.perf.inc("l_hbm_misses")
                raise KeyError(name)
            self._touch(name)
            self.perf.inc("l_hbm_hits")
            obj = ent[0].arr[ent[1]]
            codec = ent[0].codec or self.codec
        nn = codec.get_chunk_count()
        avail = tuple(i for i in range(nn) if i not in lost_shards)
        k = codec.get_data_chunk_count()
        survivors = jnp.take(obj[None],
                             jnp.asarray(avail[:k], dtype=jnp.int32),
                             axis=1)
        # decode_batch maps k survivors -> all k+m rows; keep the lost
        all_rows = codec.decode_batch(avail[:k], survivors)
        return jnp.take(all_rows[0],
                        jnp.asarray(lost_shards, dtype=jnp.int32),
                        axis=0)

    def reconstruct_batch(self, names: list, lost_per_name: list):
        """One fused device program rebuilding one lost shard per
        named object — per-lane decode matrices over the RESIDENT
        survivors (the shape the OSD coalesces concurrent recovery
        ops into).  Requires one codec/shape across names.  Returns
        the device array [len(names), n]."""
        import jax.numpy as jnp

        from ..ops import xor_mm
        with self._lock:
            codec = self._objs[names[0]][0].codec or self.codec
            stacked = self._gather(names)
        nn = codec.get_chunk_count()
        k = codec.get_data_chunk_count()
        bitmats = []
        avail_idx = []
        lost_pos = []
        for lost in lost_per_name:
            avail = tuple(i for i in range(nn) if i != lost)[:k]
            entry = codec._decode_entry(avail)
            bitmats.append(entry["bitmat"])
            avail_idx.append(avail)
            lost_pos.append(lost)
        bitmats_dev = jnp.asarray(np.stack(bitmats))
        idx = jnp.asarray(np.asarray(avail_idx, dtype=np.int32))
        survivors = jnp.take_along_axis(stacked, idx[:, :, None],
                                        axis=1)
        out = xor_mm.matrix_encode_multi(bitmats_dev,
                                         survivors[:, None],
                                         codec.w)[:, 0]
        lp = jnp.asarray(np.asarray(lost_pos, dtype=np.int32))
        return jnp.take_along_axis(out, lp[:, None, None],
                                   axis=1)[:, 0]

    def stats(self) -> dict:
        from ..parallel.placement import device_label
        with self._lock:
            hits = self.perf.get("l_hbm_hits")
            misses = self.perf.get("l_hbm_misses")
            return {"device": device_label(self.device),
                    "resident_objects": len(self._objs),
                    "resident_bytes": self._resident_bytes,
                    "capacity": self.capacity,
                    "occupancy": round(len(self._objs) / self.capacity,
                                       4) if self.capacity else 0.0,
                    "hits": hits,
                    "misses": misses,
                    "hit_rate": round(hits / (hits + misses), 3)
                    if hits + misses else 0.0,
                    "adopted": self.perf.get("l_hbm_adopted"),
                    "digested": sum(
                        1 for ent in self._objs.values()
                        if ent[0].digests is not None),
                    "evictions": self.perf.get("l_hbm_evictions")}

    def occupancy(self) -> float:
        """Occupancy ratio for the DEVICE_MEM_NEARFULL feed (objects
        over capacity — the eviction trigger is object-count, so the
        pressure signal keys on the same axis)."""
        with self._lock:
            return len(self._objs) / self.capacity \
                if self.capacity else 0.0

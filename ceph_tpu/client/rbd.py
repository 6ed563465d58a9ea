"""Block-image layer over RADOS (librbd analog).

Rendition of the reference's librbd surface
(/root/reference/src/librbd/, image format per doc/dev/rbd-layering.rst):
an image is a header object (`rbd_header.<name>`) holding size/order
plus an encoded metadata trailer (snapshots, parent pointer), a
pool-wide directory object (`rbd_directory`) listing images in its
omap, and data blocks (`rbd_data.<name>.%016x`) of 2^order bytes each —
the striping degenerate case stripe_count=1, like rbd's default layout.
Sparse blocks read as zeros; discard removes whole blocks and
zero-fills partials.

Feature bits (librbd features): `journaling` (event journal +
mirroring), `exclusive-lock` (single active writer arbitrated by
cls_lock + watch/notify — ManagedLock/ExclusiveLock role, including
the break-lock steal of a dead owner), and `object-map` (per-block
state map maintained under the lock; `du` and fast-diff answer from
the map without touching data objects — ObjectMap.cc role).

Snapshots ride RADOS self-managed snaps (librbd's model): snap_create
allocates a snap id from the monitor and image writes carry the
image's own SnapContext, so block objects COW into clones; snap reads
and rollback resolve per block. Clones (rbd-layering) are new images
whose header records (parent image, parent snap id): reads fall
through to the parent's snap for blocks the child hasn't copied; the
first child write copies the parent block up (copy-up), and flatten()
severs the dependency.

Data pool (`rbd create --data-pool`, the Luminous layout for images
on an erasure-coded pool with overwrites): the header, directory,
object map and journal stay in the image's own (replicated) pool,
and the data blocks — with the self-managed snap ids that version
them — live in the data pool the header names.

IO on one handle runs concurrently (librbd's AIO model): the per-handle
op lock covers only the exclusive-lock check, the object-map
pre-update and the journal append, and an in-flight count lets the
exclusive lock's pre-release and the whole-image ops (snapshots,
resize, flatten) block new IO and drain what is in flight.
"""

from __future__ import annotations

import collections
import contextlib
import errno as _errno
import struct
import threading
import time

from .. import encoding
from ..common.perf_counters import PerfCountersBuilder
from .striper import FileLayout


def _enoent(e: Exception) -> bool:
    """True only for a genuinely missing object; timeouts/EIO are real
    failures and must surface, not read as sparse holes."""
    return isinstance(e, OSError) and e.errno == _errno.ENOENT

__all__ = ["RBD", "Image", "ImageNotFound", "ImageExists"]

DIR_OID = "rbd_directory"
DEFAULT_ORDER = 22          # 4 MiB objects (rbd_default_order)
KNOWN_FEATURES = frozenset(("journaling", "exclusive-lock",
                            "object-map"))


class ImageNotFound(Exception):
    pass


class ImageExists(Exception):
    pass


def _header_oid(name: str) -> str:
    return "rbd_header.%s" % name


def _data_oid(name: str, block: int) -> str:
    return "rbd_data.%s.%016x" % (name, block)


def _pack_header(size: int, order: int, meta: dict) -> bytes:
    return struct.pack("<QB", size, order) + encoding.encode_any(meta)


def _unpack_header(hdr: bytes):
    size, order = struct.unpack("<QB", hdr[:9])
    meta = {"snaps": {}, "parent": None}
    if len(hdr) > 9:
        try:
            meta.update(encoding.decode_any(hdr[9:]))
        except encoding.DecodeError:
            pass
    return size, order, meta


def _journal_id(name: str) -> str:
    return "rbd.%s" % name


def _object_map_oid(name: str, snap_id: int | None = None) -> str:
    base = "rbd_object_map.%s" % name
    return base if snap_id is None else "%s.%d" % (base, snap_id)


# object-map block states (src/librbd/ObjectMap.cc / cls_rbd object
# map): EXISTS means "written since the last snapshot" (dirty), which
# is what makes fast-diff a map scan instead of an object scan
OBJECT_NONEXISTENT = 0
OBJECT_EXISTS = 1
OBJECT_EXISTS_CLEAN = 3


class ExclusiveLock:
    """Write-lock arbitration on the header object
    (src/librbd/ManagedLock.cc + src/librbd/exclusive_lock/): an
    advisory cls_lock held by the active writer, cooperative handoff
    via watch/notify ("request_lock" asks the owner to release), and a
    STEAL of an owner that no longer answers notifies — the analog of
    ManagedLock.cc:810's break_lock path (the reference also
    blacklists the dead client; here its lock cookie is broken, and
    any zombie writes it might still send are unprotected exactly like
    the reference before blacklisting landed)."""

    LOCK_NAME = "rbd_lock"

    def __init__(self, image: "Image"):
        import uuid
        self.img = image
        self.cookie = "rbd-lock-%s" % uuid.uuid4().hex[:12]
        self.owned = False

    def _hdr(self) -> str:
        return _header_oid(self.img.name)

    def try_acquire(self) -> bool:
        try:
            self.img.ioctx.exec(
                self._hdr(), "lock", "lock", encoding.encode_any({
                    "name": self.LOCK_NAME, "cookie": self.cookie,
                    "type": "exclusive", "duration": 0}))
        except OSError as e:
            if e.errno == _errno.EBUSY:
                return False
            raise
        self.owned = True
        self.img._on_lock_acquired()
        return True

    def acquire(self, timeout: float = 15.0) -> None:
        import time
        deadline = time.monotonic() + timeout
        while True:
            if self.try_acquire():
                return
            # ask the owner (watching the header) to hand over
            res = self.img.ioctx.notify(
                self._hdr(), encoding.encode_any({
                    "type": "request_lock", "cookie": self.cookie}),
                timeout=2.0)
            owner_answered = any(
                reply == b"released"
                for reply in res.get("replies", {}).values())
            if self.try_acquire():
                return
            if not owner_answered:
                # no watcher claimed the lock: the owner is dead —
                # break its cookie and take over
                info = encoding.decode_any(self.img.ioctx.exec(
                    self._hdr(), "lock", "get_info",
                    encoding.encode_any({"name": self.LOCK_NAME})))
                for cookie in list(info.get("lockers", {})):
                    try:
                        self.img.ioctx.exec(
                            self._hdr(), "lock", "break_lock",
                            encoding.encode_any({
                                "name": self.LOCK_NAME,
                                "cookie": cookie}))
                    except OSError as e:
                        if e.errno != _errno.ENOENT:
                            raise
                if self.try_acquire():
                    return
            if time.monotonic() >= deadline:
                raise OSError(_errno.EBUSY,
                              "could not acquire exclusive lock on %s"
                              % self.img.name)
            time.sleep(0.05)

    def release(self) -> None:
        if not self.owned:
            return
        self.owned = False
        try:
            self.img.ioctx.exec(
                self._hdr(), "lock", "unlock", encoding.encode_any({
                    "name": self.LOCK_NAME, "cookie": self.cookie}))
        except OSError as e:
            if e.errno != _errno.ENOENT:
                raise                  # already broken/stolen: fine


class ObjectMap:
    """Per-block existence bitmap (src/librbd/ObjectMap.cc +
    cls_rbd's object map): maintained under the exclusive lock, one
    state byte per data block.  `du` and fast-diff read the map —
    O(blocks) in memory — instead of stat-ing every data object."""

    def __init__(self, image: "Image"):
        self.img = image
        self.states = None             # np.ndarray uint8

    def _nblocks(self) -> int:
        return -(-self.img.size() // self.img.block_size)

    def load(self) -> None:
        import numpy as np
        n = self._nblocks()
        try:
            raw = self.img.ioctx.read(_object_map_oid(self.img.name))
            arr = np.frombuffer(raw, dtype=np.uint8).copy()
        except OSError as e:
            if not _enoent(e):
                raise
            arr = np.zeros(0, dtype=np.uint8)
        if arr.size < n:
            arr = np.concatenate(
                [arr, np.zeros(n - arr.size, dtype=np.uint8)])
        self.states = arr[:n].copy()

    def save(self) -> None:
        self.img.ioctx.write_full(_object_map_oid(self.img.name),
                                  self.states.tobytes())

    def update(self, exists=(), absent=()) -> None:
        """Batch state flip with at most ONE save: an op spanning many
        blocks (discard, big write) must not rewrite the whole map per
        block — that is O(blocks^2) bytes through the data pool."""
        dirty = False
        for blk in exists:
            if blk < self.states.size and \
                    self.states[blk] != OBJECT_EXISTS:
                self.states[blk] = OBJECT_EXISTS
                dirty = True
        for blk in absent:
            if blk < self.states.size and \
                    self.states[blk] != OBJECT_NONEXISTENT:
                self.states[blk] = OBJECT_NONEXISTENT
                dirty = True
        if dirty:
            self.save()

    def mark_exists(self, blocks) -> None:
        self.update(exists=blocks)

    def mark_absent(self, blocks) -> None:
        self.update(absent=blocks)

    def resize(self, new_nblocks: int) -> None:
        import numpy as np
        if new_nblocks < self.states.size:
            self.states = self.states[:new_nblocks].copy()
        elif new_nblocks > self.states.size:
            self.states = np.concatenate(
                [self.states,
                 np.zeros(new_nblocks - self.states.size,
                          dtype=np.uint8)])
        self.save()

    def snapshot(self, snap_id: int) -> None:
        """snap_create: freeze a copy under the snap id, then demote
        every EXISTS block to EXISTS_CLEAN — fast-diff's 'unchanged
        since this snapshot' marker."""
        self.img.ioctx.write_full(
            _object_map_oid(self.img.name, snap_id),
            self.states.tobytes())
        self.states[self.states == OBJECT_EXISTS] = OBJECT_EXISTS_CLEAN
        self.save()

    def load_snap(self, snap_id: int):
        import numpy as np
        try:
            raw = self.img.ioctx.read(
                _object_map_oid(self.img.name, snap_id))
            return np.frombuffer(raw, dtype=np.uint8).copy()
        except OSError as e:
            if _enoent(e):
                return np.zeros(0, dtype=np.uint8)
            raise

    def used_bytes(self) -> int:
        import numpy as np
        size = self.img.size()
        bs = self.img.block_size
        present = self.states != OBJECT_NONEXISTENT
        total = int(np.count_nonzero(present)) * bs
        # the tail block may be partial
        last = self.states.size - 1
        if last >= 0 and present[last] and size - last * bs < bs:
            total -= bs - (size - last * bs)
        return total


class RBD:
    """Pool-level image operations (librbd.h rbd_create/list/remove)."""

    @staticmethod
    def create(ioctx, name: str, size: int,
               order: int = DEFAULT_ORDER,
               features: tuple = (), data_pool: str | None = None) -> None:
        """data_pool names the pool of the image's data blocks (an EC
        pool, typically); None keeps them beside the header."""
        if name in RBD.list(ioctx):
            raise ImageExists(name)
        unknown = set(features) - KNOWN_FEATURES
        if unknown:
            raise ValueError("unknown image feature(s): %s (known: %s)"
                             % (sorted(unknown),
                                sorted(KNOWN_FEATURES)))
        if "object-map" in features and "exclusive-lock" not in features:
            raise ValueError("object-map requires exclusive-lock "
                             "(librbd feature dependency)")
        if "journaling" in features:
            # the journal exists BEFORE the header advertises it: a
            # crash in between leaves an orphan journal, never a
            # journaled image without a journal (unopenable). An
            # orphan found here (no image exists — the check above
            # passed) is wiped so create stays crash-RETRYABLE
            from ..services.journal import JournalExists, Journaler
            j = Journaler(ioctx, _journal_id(name))
            try:
                j.create()
            except JournalExists:
                j.open()
                j.remove()
                j.create()
            j.register_client("")     # the master position
        meta = {"snaps": {}, "parent": None, "features": list(features)}
        if data_pool is not None:
            meta["data_pool"] = ioctx.client.pool_id(data_pool)
        ioctx.write_full(_header_oid(name),
                         _pack_header(size, order, meta))
        ioctx.omap_set(DIR_OID, {name: b"1"})

    @staticmethod
    def clone(ioctx, parent_name: str, snap_name: str,
              clone_name: str, data_pool: str | None = None) -> None:
        """rbd clone (rbd-layering.rst): a new image COW-backed by the
        parent's snapshot, whose blocks it reads from the parent's
        data pool."""
        parent = Image(ioctx, parent_name)
        snap = parent.meta["snaps"].get(snap_name)
        if snap is None:
            raise ImageNotFound("%s@%s" % (parent_name, snap_name))
        if clone_name in RBD.list(ioctx):
            raise ImageExists(clone_name)
        meta = {"snaps": {},
                "parent": {"image": parent_name, "snap_id": snap["id"],
                           "snap_name": snap_name,
                           "size": snap["size"],
                           "data_pool": parent.meta.get("data_pool")}}
        if data_pool is not None:
            meta["data_pool"] = ioctx.client.pool_id(data_pool)
        ioctx.write_full(_header_oid(clone_name), _pack_header(
            snap["size"], parent.order, meta))
        ioctx.omap_set(DIR_OID, {clone_name: b"1"})

    @staticmethod
    def list(ioctx) -> list[str]:
        try:
            return sorted(ioctx.omap_get(DIR_OID))
        except OSError as e:
            if _enoent(e):
                return []  # directory object not created yet
            raise  # a transient failure must not read as "no images"

    @staticmethod
    def remove(ioctx, name: str) -> None:
        """Data blocks and header go first; the directory entry is only
        dropped once they are really gone — otherwise a later create
        with the same name would resurrect stale block data."""
        img = Image(ioctx, name)   # raises ImageNotFound
        nblocks = -(-img.size() // img.block_size)
        for b in range(nblocks):
            try:
                img.data_ioctx.remove(_data_oid(name, b))
            except OSError as e:
                if not _enoent(e):
                    raise
        if "journaling" in img.meta.get("features", []):
            from ..services.journal import Journaler
            j = Journaler(ioctx, _journal_id(name))
            try:
                j.open()
                j.remove()
            except Exception:
                pass              # a half-created journal is no blocker
        if "object-map" in img.meta.get("features", []):
            for snap in img.meta["snaps"].values():
                try:
                    ioctx.remove(_object_map_oid(name, snap["id"]))
                except OSError as e:
                    if not _enoent(e):
                        raise
            try:
                ioctx.remove(_object_map_oid(name))
            except OSError as e:
                if not _enoent(e):
                    raise
        img.close()
        ioctx.remove(_header_oid(name))
        # targeted key removal: a read-modify-write of the whole
        # directory would erase concurrently created images
        ioctx.omap_rm_keys(DIR_OID, [name])


def _serialized(fn):
    """Whole-image ops (snapshots, resize, flatten) run with the
    handle's IO quiesced and hold its op lock; the cooperative-handoff
    release does the same, so the exclusive lock can never be yanked
    out from under an op already past _ensure_lock (exclusive_lock's
    pre-release op quiesce)."""
    import functools

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._quiesced(), self._op_lock:
            return fn(self, *args, **kwargs)
    return wrapper


def _data_ioctx(ioctx, pool_id):
    """The IoCtx of a data pool by id; None is the image's own pool."""
    if pool_id is None or pool_id == ioctx.pool_id:
        return ioctx
    from .rados import IoCtx
    return IoCtx(ioctx.client, pool_id)


class Image:
    """One open image (librbd Image): offset-addressed block IO."""

    def __init__(self, ioctx, name: str, read_only: bool = False):
        # guards the exclusive-lock check, the object map and the
        # journal append; data IO runs outside it
        self._op_lock = threading.RLock()
        # IO in flight on this handle, and the one thread (if any)
        # that holds it quiesced
        self._io_cond = threading.Condition()
        self._inflight = 0
        self._quiescer = None
        self._inflight_since = time.monotonic()
        self.perf = (PerfCountersBuilder("librbd-%s" % name)
                     .add_u64_counter("l_librbd_rd", "reads completed")
                     .add_u64_counter("l_librbd_wr", "writes completed")
                     .add_time("l_librbd_inflight_s",
                               "time integral of the IOs in flight: its "
                               "change over a window, divided by the "
                               "window, is the mean queue depth")
                     .create_perf_counters())
        # journal tids appended but not yet committed, in append order
        self._jorder: collections.deque = collections.deque()
        self._japplied: set = set()
        self._jlock = threading.Lock()
        self._copyup_lock = threading.Lock()
        self.ioctx = ioctx
        self.name = name
        self.read_only = read_only
        try:
            hdr = ioctx.read(_header_oid(name))
        except OSError as e:
            if _enoent(e):
                raise ImageNotFound(name)
            raise
        if len(hdr) < 9:
            raise ImageNotFound(name)
        self._size, self.order, self.meta = _unpack_header(hdr)
        self.data_ioctx = _data_ioctx(ioctx, self.meta.get("data_pool"))
        self.block_size = 1 << self.order
        self.layout = FileLayout(self.block_size, 1, self.block_size)
        # journaling feature (librbd RBD_FEATURE_JOURNALING): every
        # mutation appends an EventEntry to the image journal BEFORE
        # applying, the master commit position advances after apply,
        # and opening the image replays anything in between (the
        # crash-recovery half of librbd::Journal::open)
        self._journal = None
        self._replaying = False
        # exclusive-lock + object-map features (librbd feature bits):
        # the lock arbitrates the single active writer via cls_lock +
        # watch/notify; the object map is maintained under it
        features = self.meta.get("features", [])
        self._lock = None
        self._omap = None
        self._watch_cookie = None
        self._map_cb = None
        if not read_only and "exclusive-lock" in features:
            self._lock = ExclusiveLock(self)
            self._watch_cookie = ioctx.watch(_header_oid(name),
                                             self._header_notify)
            # a PG primary change drops the watch server-side; without
            # re-watching, a live owner goes notify-deaf and a
            # contender's steal path breaks its lock (split brain).
            # Re-assert the watch on every map change (the linger
            # resend; rados.py documents it as the client's burden).
            def _rewatch(_newmap):
                if self._watch_cookie is None:
                    return
                try:
                    self.ioctx._op(_header_oid(self.name),
                                   [("watch", self._watch_cookie)])
                except Exception:
                    pass               # next map change retries
            self._map_cb = _rewatch
            ioctx.client.mon_client.map_callbacks.append(_rewatch)
        if "object-map" in features:
            self._omap = ObjectMap(self)
            self._omap.load()
        if not read_only \
                and "journaling" in self.meta.get("features", []):
            # read_only opens (mirror daemons, inspectors) must NOT
            # touch the journal: replay would make a remote READER a
            # journal WRITER racing the primary's own apply path
            from ..services.journal import JournalNotFound, Journaler
            self._journal = Journaler(ioctx, _journal_id(name))
            try:
                self._journal.open(for_append=True)
            except JournalNotFound:
                # self-heal a lost/half-created journal rather than
                # brick the image (any unjournaled tail is gone either
                # way; a fresh journal restores the invariant)
                self._journal.create()
                self._journal.register_client("")
            self._replay_pending()

    # -- exclusive lock / object map ----------------------------------

    def _header_notify(self, notify_id, payload):
        """Header watch callback: a contender's request_lock triggers
        the cooperative handoff (exclusive_lock's
        handle_request_lock) — release once new IO is blocked and the
        in-flight IO has drained, and answer 'released'."""
        try:
            ev = encoding.decode_any(payload) if payload else {}
        except encoding.DecodeError:
            return None
        if ev.get("type") == "request_lock" and self._lock is not None \
                and self._lock.owned:
            # the callback runs on the messenger reader thread: a
            # synchronous unlock op here would deadlock waiting for
            # its own reply.  Hand off to a thread — which blocks new
            # IO and drains the in-flight IO before releasing — and
            # answer now; the requester retries until the unlock
            # lands.
            def _handoff():
                with self._quiesced(), self._op_lock:
                    self._lock.release()

            threading.Thread(target=_handoff, daemon=True).start()
            return b"released"
        return None

    def _on_lock_acquired(self) -> None:
        """A fresh owner must see the PREVIOUS owner's world: re-read
        the header (size/snaps may have moved) and the object map."""
        try:
            hdr = self.ioctx.read(_header_oid(self.name))
            self._size, self.order, self.meta = _unpack_header(hdr)
        except OSError:
            pass
        if self._omap is not None:
            self._omap.load()

    def _ensure_lock(self) -> None:
        if self.read_only:
            # every mutating path runs through here: a read-only
            # handle must never write data OR clobber the owner's
            # object map with its stale copy
            raise OSError(_errno.EROFS, self.name)
        if self._lock is not None and not self._lock.owned:
            self._lock.acquire()

    def lock_owned(self) -> bool:
        return self._lock is not None and self._lock.owned

    # -- IO in flight (librbd's AsyncOperation tracking) ----------------

    def _count_inflight(self, step: int) -> None:
        """Advance the in-flight time integral to now, then move the
        count by `step` (the caller holds _io_cond)."""
        now = time.monotonic()
        self.perf.inc("l_librbd_inflight_s",
                      self._inflight * (now - self._inflight_since))
        self._inflight_since = now
        self._inflight += step

    @contextlib.contextmanager
    def _io(self, counter: str):
        """One IO in flight: it waits while another thread holds the
        handle quiesced, and counts `counter` when it completes."""
        me = threading.get_ident()
        with self._io_cond:
            while self._quiescer not in (None, me):
                self._io_cond.wait()
            self._count_inflight(1)
        try:
            yield
            self.perf.inc(counter)
        finally:
            with self._io_cond:
                self._count_inflight(-1)
                self._io_cond.notify_all()

    @contextlib.contextmanager
    def _quiesced(self):
        """Block new IO on this handle and wait for the in-flight IO to
        drain (exclusive_lock's pre-release block_writes); the holding
        thread may itself issue IO, and may nest."""
        me = threading.get_ident()
        with self._io_cond:
            if self._quiescer == me:
                nested = True
            else:
                nested = False
                while self._quiescer is not None:
                    self._io_cond.wait()
                self._quiescer = me
                while self._inflight:
                    self._io_cond.wait()
        try:
            yield
        finally:
            if not nested:
                with self._io_cond:
                    self._quiescer = None
                    self._io_cond.notify_all()

    def perf_counters(self) -> dict:
        """The image's librbd counters, the in-flight integral brought
        up to now."""
        with self._io_cond:
            self._count_inflight(0)
        return self.perf.dump()

    def close(self) -> None:
        if self._map_cb is not None:
            try:
                self.ioctx.client.mon_client.map_callbacks.remove(
                    self._map_cb)
            except ValueError:
                pass
            self._map_cb = None
        if self._watch_cookie is not None:
            try:
                self.ioctx.unwatch(_header_oid(self.name),
                                   self._watch_cookie)
            except OSError:
                pass
            self._watch_cookie = None
        if self._lock is not None:
            self._lock.release()

    def _omap_blocks(self, offset: int, length: int):
        first = offset // self.block_size
        last = (offset + length - 1) // self.block_size
        return range(first, last + 1)

    def du(self) -> int:
        """Provisioned bytes actually stored (rbd du).  With an
        object map this is a pure map scan — no object stats."""
        if self._omap is not None:
            return self._omap.used_bytes()
        total = 0
        nblocks = -(-self._size // self.block_size)
        for blk in range(nblocks):
            try:
                self.data_ioctx.stat(_data_oid(self.name, blk))
            except OSError as e:
                if not _enoent(e):
                    raise
                continue
            total += min(self.block_size,
                         self._size - blk * self.block_size)
        return total

    def fast_diff(self, from_snap: str | None = None) -> list:
        """Changed extents since from_snap (None = image creation),
        computed from object maps alone (librbd fast-diff /
        diff_iterate whole_object=true): returns
        [(offset, length, exists_now)] per changed block."""
        if self._omap is None:
            raise OSError(_errno.EOPNOTSUPP,
                          "fast-diff needs the object-map feature")
        import numpy as np
        cur = self._omap.states
        if from_snap is None:
            base = np.zeros(cur.size, dtype=np.uint8)
            later_maps = []
        else:
            snap = self.meta["snaps"].get(from_snap)
            if snap is None:
                raise ImageNotFound("%s@%s" % (self.name, from_snap))
            base = self._omap.load_snap(snap["id"])
            # dirty bits in every snapshot AFTER from_snap also mark
            # changes (a block can be rewritten then frozen clean by a
            # later snap_create)
            later_maps = [self._omap.load_snap(s["id"])
                          for s in self.meta["snaps"].values()
                          if s["id"] > snap["id"]]
        bs = self.block_size

        def fit(arr):
            padded = np.zeros(cur.size, dtype=np.uint8)
            m = min(cur.size, arr.size)
            padded[:m] = arr[:m]
            return padded

        base = fit(base)
        changed = cur == OBJECT_EXISTS        # dirty since last snap
        for m in later_maps:
            changed |= fit(m) == OBJECT_EXISTS
        changed |= (base == OBJECT_NONEXISTENT) != \
            (cur == OBJECT_NONEXISTENT)
        return [(int(blk) * bs, min(bs, self._size - int(blk) * bs),
                 bool(cur[blk] != OBJECT_NONEXISTENT))
                for blk in np.nonzero(changed)[0]]

    # -- journaling (librbd journal/Types.h EventEntry) ----------------

    def _replay_pending(self) -> None:
        """Apply journaled events newer than the master commit
        position — a crash between append and apply left them
        un-applied (journal::Replay)."""
        j = self._journal
        done = j.committed("")
        self._replaying = True
        try:
            for tid, tag, payload in j.iterate(done):
                self._apply_event(encoding.decode_any(payload))
                j.commit("", tid)
        finally:
            self._replaying = False
        j.trim()

    def _apply_event(self, ev: dict) -> None:
        """Idempotent event application (journal/Replay.cc handlers —
        AioWriteEvent, AioDiscardEvent, ResizeEvent, Snap*Event)."""
        kind = ev["type"]
        if kind == "write":
            self.write(ev["offset"], ev["data"])
        elif kind == "discard":
            self.discard(ev["offset"], ev["length"])
        elif kind == "resize":
            self.resize(ev["size"])
        elif kind == "snap_create":
            if ev["name"] not in self.meta["snaps"]:
                self.snap_create(ev["name"])
        elif kind == "snap_remove":
            if ev["name"] in self.meta["snaps"]:
                self.snap_remove(ev["name"])
        elif kind == "snap_rollback":
            self.snap_rollback(ev["name"])

    def _journal_event(self, ev: dict):
        """Append the event pre-apply; returns the tid to commit
        post-apply (None when journaling is off or we ARE the
        replay)."""
        if self._journal is None or self._replaying:
            return None
        tid = self._journal.append("rbd", encoding.encode_any(ev))
        self._jorder.append(tid)       # under the op lock: in tid order
        return tid

    def _journal_commit(self, tid) -> None:
        """Mark the event applied; the commit position advances over
        the longest run of applied events, so concurrent IO finishing
        out of order never commits past an unapplied one."""
        if tid is None:
            return
        j = self._journal
        with self._jlock:
            self._japplied.add(tid)
            done = []
            while self._jorder and self._jorder[0] in self._japplied:
                done.append(self._jorder.popleft())
                self._japplied.discard(done[-1])
            if not done:
                return
            j.commit("", done[-1])
            # trim only at object-set boundaries: a set becomes
            # removable every splay_width*entries_per_object entries,
            # so per-write trims are pure round-trip overhead
            per_set = j.splay_width * j.entries_per_object
            if any((t + 1) % per_set == 0 for t in done):
                j.trim()

    def size(self) -> int:
        return self._size

    def stat(self) -> dict:
        return {"size": self._size, "order": self.order,
                "block_name_prefix": "rbd_data.%s" % self.name,
                "data_pool": self.meta.get("data_pool"),
                "num_objs": -(-self._size // self.block_size),
                "parent": self.meta.get("parent")}

    # -- snapshots (librbd snap_create/list/rollback/remove) -----------

    def _save_header(self) -> None:
        self.ioctx.write_full(_header_oid(self.name), _pack_header(
            self._size, self.order, self.meta))

    def _image_snapc(self) -> tuple:
        ids = sorted((s["id"] for s in self.meta["snaps"].values()),
                     reverse=True)
        return (ids[0] if ids else 0, tuple(ids))

    def _apply_snapc(self) -> None:
        # image writes carry THIS image's SnapContext (librbd keeps a
        # per-image snap context, not the pool's)
        seq, ids = self._image_snapc()
        self.data_ioctx.set_snap_context(seq, ids)

    @_serialized
    def snap_create(self, snap_name: str) -> int:
        if snap_name in self.meta["snaps"]:
            raise ImageExists("%s@%s" % (self.name, snap_name))
        self._ensure_lock()
        jtid = self._journal_event({"type": "snap_create",
                                    "name": snap_name})
        # snap ids version the data blocks: the data pool allocates them
        snap_id = self.data_ioctx.selfmanaged_snap_create()
        self.meta["snaps"][snap_name] = {"id": snap_id,
                                         "size": self._size}
        self._save_header()
        if self._omap is not None:
            self._omap.snapshot(snap_id)
        self._journal_commit(jtid)
        return snap_id

    def snap_list(self) -> list:
        return sorted(
            ({"name": n, "id": s["id"], "size": s["size"]}
             for n, s in self.meta["snaps"].items()),
            key=lambda s: s["id"])

    @_serialized
    def snap_remove(self, snap_name: str) -> None:
        if snap_name not in self.meta["snaps"]:
            raise ImageNotFound("%s@%s" % (self.name, snap_name))
        self._ensure_lock()
        jtid = self._journal_event({"type": "snap_remove",
                                    "name": snap_name})
        snap = self.meta["snaps"].pop(snap_name)
        self._save_header()
        # retire the id: OSDs trim the block clones it pinned
        self.data_ioctx.selfmanaged_snap_remove(snap["id"])
        if self._omap is not None:
            try:
                self.ioctx.remove(_object_map_oid(self.name,
                                                  snap["id"]))
            except OSError as e:
                if not _enoent(e):
                    raise
        self._journal_commit(jtid)

    @_serialized
    def snap_rollback(self, snap_name: str) -> None:
        snap = self.meta["snaps"].get(snap_name)
        if snap is None:
            raise ImageNotFound("%s@%s" % (self.name, snap_name))
        self._ensure_lock()
        jtid = self._journal_event({"type": "snap_rollback",
                                    "name": snap_name})
        snap_id, snap_size = snap["id"], snap["size"]
        self._apply_snapc()
        parented = self.meta.get("parent") is not None
        nblocks = -(-max(self._size, snap_size) // self.block_size)
        for blk in range(nblocks):
            oid = _data_oid(self.name, blk)
            if blk * self.block_size >= snap_size:
                if parented:
                    # mask, don't remove: removing would re-expose the
                    # parent's bytes through the COW fall-through
                    self.data_ioctx.write(oid, b"\0" * self.block_size, 0)
                    continue
                try:
                    self.data_ioctx.remove(oid)
                except OSError as e:
                    if not _enoent(e):
                        raise
                continue
            try:
                self.data_ioctx.rollback_id(oid, snap_id)
            except OSError as e:
                if not _enoent(e):
                    raise    # block absent at snap AND now: nothing
        if self._size != snap_size:
            self._size = snap_size
            self._save_header()
        if self._omap is not None:
            # the image content just became the snap's content: adopt
            # the snap's map, with every present block dirty (it
            # changed relative to whatever was there before)
            import numpy as np
            snapm = self._omap.load_snap(snap_id)
            n = -(-self._size // self.block_size)
            arr = np.zeros(n, dtype=np.uint8)
            m = min(n, snapm.size)
            arr[:m] = snapm[:m]
            arr[arr == OBJECT_EXISTS_CLEAN] = OBJECT_EXISTS
            self._omap.states = arr
            self._omap.save()
        self._journal_commit(jtid)

    # -- layering (clone reads / copy-up / flatten) --------------------

    def _parent_block(self, blk: int) -> bytes | None:
        parent = self.meta.get("parent")
        if parent is None:
            return None
        off = blk * self.block_size
        if off >= parent["size"]:
            return None
        pio = _data_ioctx(self.ioctx, parent.get("data_pool"))
        try:
            return pio.read(_data_oid(parent["image"], blk),
                            self.block_size, 0, snap=parent["snap_id"])
        except OSError as e:
            if _enoent(e):
                return None
            raise

    def _copy_up(self, blk: int) -> None:
        """First write to an un-copied block of a clone pulls the
        parent's bytes in (librbd copy-up)."""
        data = self._parent_block(blk)
        if data:
            self.data_ioctx.write(_data_oid(self.name, blk), data, 0)
            if self._omap is not None:
                with self._op_lock:
                    self._omap.mark_exists([blk])

    @_serialized
    def flatten(self) -> None:
        """Copy every still-inherited block; drop the parent link."""
        if self.meta.get("parent") is None:
            return
        self._ensure_lock()
        self._apply_snapc()
        nblocks = -(-self._size // self.block_size)
        for blk in range(nblocks):
            oid = _data_oid(self.name, blk)
            try:
                self.data_ioctx.stat(oid)
                continue             # child already owns this block
            except OSError as e:
                if not _enoent(e):
                    raise
            data = self._parent_block(blk)
            if data:
                self.data_ioctx.write(oid, data, 0)
                if self._omap is not None:
                    self._omap.mark_exists([blk])
        self.meta["parent"] = None
        self._save_header()

    def _check_extent(self, offset: int, length: int) -> None:
        if offset < 0 or length < 0 or offset + length > self._size:
            raise ValueError("extent %d~%d outside image size %d"
                             % (offset, length, self._size))

    def _copy_up_if_absent(self, blk: int) -> None:
        """Before a partial write to a possibly-inherited block: copy
        the parent bytes up so the rest of the block keeps its COW
        content (librbd copy-up), once, whatever IO races for it."""
        with self._copyup_lock:
            try:
                self.data_ioctx.stat(_data_oid(self.name, blk))
            except OSError as e:
                if not _enoent(e):
                    raise
                self._copy_up(blk)

    def write(self, offset: int, data: bytes) -> int:
        self._check_extent(offset, len(data))
        with self._io("l_librbd_wr"):
            with self._op_lock:
                self._ensure_lock()
                if self._omap is not None:
                    # object map goes EXISTS before the data write
                    # lands (ObjectMap's pre-update ordering: a map
                    # that lies "absent" about a written block
                    # corrupts fast-diff; one that lies "exists" about
                    # an absent block only costs a stat)
                    self._omap.mark_exists(self._omap_blocks(offset,
                                                             len(data)))
                jtid = self._journal_event({"type": "write",
                                            "offset": offset,
                                            "data": bytes(data)})
                self._apply_snapc()
                parented = self.meta.get("parent") is not None
            for blk, blk_off, n, foff in self.layout.map_extent(
                    offset, len(data)):
                if parented and (blk_off != 0 or n != self.block_size):
                    self._copy_up_if_absent(blk)
                self.data_ioctx.write(_data_oid(self.name, blk),
                                      data[foff - offset:foff - offset + n],
                                      blk_off)
            self._journal_commit(jtid)
        return len(data)

    def read(self, offset: int, length: int) -> bytes:
        self._check_extent(offset, length)
        out = bytearray(length)
        with self._io("l_librbd_rd"):
            for blk, blk_off, n, foff in self.layout.map_extent(
                    offset, length):
                try:
                    piece = self.data_ioctx.read(_data_oid(self.name, blk),
                                                 n, blk_off)
                except OSError as e:
                    if not _enoent(e):
                        raise  # timeout/EIO must not read as zeros
                    # clone: fall through to the parent's snapshot
                    inherited = self._parent_block(blk)
                    piece = (inherited[blk_off:blk_off + n]
                             if inherited else b"")
                out[foff - offset:foff - offset + len(piece)] = piece
        return bytes(out)

    def discard(self, offset: int, length: int) -> None:
        """Free whole blocks; zero partial block edges (rbd_discard).
        On a clone, discarded blocks are MASKED with zeros rather than
        removed, or the parent's bytes would resurface."""
        self._check_extent(offset, length)
        with self._io("l_librbd_wr"):
            with self._op_lock:
                self._ensure_lock()
                jtid = self._journal_event({"type": "discard",
                                            "offset": offset,
                                            "length": length})
                self._apply_snapc()
                parented = self.meta.get("parent") is not None
            # accumulate touched blocks and flip the object map ONCE at
            # the end (as write() does): per-block mark+save was
            # O(blocks^2) map bytes for a large discard
            absent: list = []
            exists: list = []
            for blk, blk_off, n, _ in self.layout.map_extent(offset,
                                                              length):
                oid = _data_oid(self.name, blk)
                if blk_off == 0 and n == self.block_size and not parented:
                    try:
                        self.data_ioctx.remove(oid)
                    except OSError as e:
                        if not _enoent(e):
                            raise
                    absent.append(blk)
                else:
                    exists.append(blk)
                    if parented and (blk_off != 0 or n != self.block_size):
                        self._copy_up_if_absent(blk)
                    self.data_ioctx.write(oid, b"\0" * n, blk_off)
            if self._omap is not None:
                with self._op_lock:
                    self._omap.update(exists=exists, absent=absent)
            self._journal_commit(jtid)

    @_serialized
    def resize(self, new_size: int) -> None:
        self._ensure_lock()
        jtid = self._journal_event({"type": "resize",
                                    "size": new_size})
        self._apply_snapc()
        parented = self.meta.get("parent") is not None
        if new_size < self._size:
            first_dead = -(-new_size // self.block_size)
            last = -(-self._size // self.block_size)
            for blk in range(first_dead, last):
                oid = _data_oid(self.name, blk)
                if parented:
                    # mask, don't remove: a later grow must read zeros
                    # here, not the parent's bytes resurfacing
                    self.data_ioctx.write(oid, b"\0" * self.block_size, 0)
                    continue
                try:
                    self.data_ioctx.remove(oid)
                except OSError as e:
                    if not _enoent(e):
                        raise
            # zero the tail of the new boundary block; on a clone the
            # head of that block may still be inherited — copy it up
            # first or the zeros would sit in an otherwise-absent
            # object and shadow the parent bytes below new_size
            if new_size % self.block_size:
                blk = new_size // self.block_size
                tail_off = new_size % self.block_size
                if parented:
                    self._copy_up_if_absent(blk)
                self.data_ioctx.write(
                    _data_oid(self.name, blk),
                    b"\0" * (self.block_size - tail_off), tail_off)
        self._size = new_size
        self._save_header()
        if self._omap is not None:
            self._omap.resize(-(-new_size // self.block_size))
        self._journal_commit(jtid)

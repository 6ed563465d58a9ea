"""RadosClient + IoCtx + the op-tracking Objecter core.

Reference shape (src/librados/librados.cc C API over IoCtxImpl over
Objecter): IoCtx carries a pool; each op computes its target
(object_to_pg -> pg_to_up_acting_osds -> primary), ships a typed MOSDOp,
and blocks on the reply with resend-on-new-map (Objecter::op_submit
:2253, _calc_target :2749, resends on map change). The inflight-ops
throttle mirrors objecter_inflight_ops.
"""

from __future__ import annotations

import itertools
import threading
import time

from ..common import Context
from ..common.throttle import Throttle
from ..common.tracer import SpanCollector, trace_ctx
from ..mon.mon_client import MonClient
from ..msg.message import MOSDOp, MWatchNotifyAck, OSD_READ_OPS
from ..msg.async_messenger import create_messenger
from ..msg.messenger import Dispatcher

__all__ = ["RadosClient", "IoCtx", "RadosError"]


class RadosError(OSError):
    pass


class _InflightOp:
    def __init__(self, tid):
        self.tid = tid
        self.event = threading.Event()
        self.result = None
        self.data = None


class RadosClient(Dispatcher):
    def __init__(self, monmap: dict, ctx: Context | None = None,
                 client_id: int = 0):
        self.ctx = ctx if ctx is not None else Context(
            name="client.%d" % client_id)
        self.client_id = client_id
        # cephx: the authorizer factory closes over the session that
        # connect(entity=..., secret=...) establishes; on auth-less
        # clusters it stays None and the banner carries no authorizer
        self.cephx = None

        def _factory(challenge=None):
            if self.cephx is None:
                return None
            return self.cephx.build_authorizer("osd", challenge)

        def _key_fn():
            return self.cephx.tickets["osd"]["session_key"] \
                if self.cephx else None

        self.msgr = create_messenger(("client", client_id),
                                     conf=self.ctx.conf,
                                     authorizer_factory=_factory,
                                     session_key_fn=_key_fn)
        self.msgr.start()
        self.msgr.add_dispatcher_head(self)
        self.mon_client = MonClient(monmap, self.msgr,
                                    "client.%d" % client_id)
        self._tids = itertools.count(1)
        self._lock = threading.Lock()
        self._inflight: dict[int, _InflightOp] = {}
        self._throttle = Throttle(
            "objecter", self.ctx.conf.get_val("objecter_inflight_ops"))
        self._watches: dict = {}      # cookie -> (oid, callback)
        # per-client nonce: (session, tid) is globally unique even
        # when client ids and tid counters restart across processes
        import uuid
        self.session = uuid.uuid4().hex
        # dmclock distributed feedback (optional): an object with
        # stamp(osd) -> (delta, rho) and observe(osd, phase) — when
        # armed, every MOSDOp carries the service this client received
        # cluster-wide since its previous op to that OSD, so each OSD's
        # queue compensates for work its peers already served
        self.qos_feedback = None
        # op tracing (ZTracer client role): the root span of every
        # traced op starts HERE, and its context rides the MOSDOp
        # envelope so OSD-side spans stitch under it
        self.tracer = SpanCollector(conf=self.ctx.conf,
                                    endpoint="client.%d" % client_id)

    # -- lifecycle -----------------------------------------------------

    def connect(self, timeout: float = 10.0, entity: str | None = None,
                secret: str | None = None) -> None:
        if entity is not None:
            # cephx first: the ticket arms the authorizer factory for
            # every subsequent OSD dial, and registers this session's
            # mon caps for the command path.  On an auth-less cluster
            # the handshake returns a ticket-less client ("auth none")
            # — leave cephx unarmed or every OSD dial would fail
            # minting an authorizer it cannot build.
            c = self.mon_client.authenticate(
                entity, secret, service="osd", timeout=timeout)
            self.cephx = c if c.tickets else None
        self.mon_client.sub_want()
        self.mon_client.wait_for_map(1, timeout)

    def shutdown(self) -> None:
        self.msgr.shutdown()
        self.ctx.shutdown()

    @property
    def osdmap(self):
        return self.mon_client.osdmap

    # -- pools ---------------------------------------------------------

    def pool_id(self, name: str) -> int:
        for pool_id, pool in self.osdmap.pools.items():
            if pool.name == name:
                return pool_id
        raise RadosError(2, "pool %r does not exist" % name)

    def open_ioctx(self, pool_name: str) -> "IoCtx":
        return IoCtx(self, self.pool_id(pool_name))

    def mon_command(self, cmd: dict, timeout: float = 10.0):
        return self.mon_client.command(cmd, timeout)

    # -- dispatch ------------------------------------------------------

    def ms_dispatch(self, msg) -> bool:
        if msg.get_type() == "MOSDOpReply":
            with self._lock:
                op = self._inflight.pop(msg.tid, None)
            if op is not None:
                if self.qos_feedback is not None:
                    src = getattr(msg, "from_name", None)
                    self.qos_feedback.observe(
                        src[1] if src else -1,
                        getattr(msg, "qos_phase", ""))
                op.result = msg.result
                op.data = msg.data
                op.event.set()
                # the throttle slot is released by submit_op's finally
                # (exactly once per op, however many resends/replies)
            return True
        if msg.get_type() == "MWatchNotify":
            with self._lock:
                watch = self._watches.get(msg.cookie)
            reply = b""
            if watch is not None:
                _, callback = watch
                try:
                    reply = callback(msg.notify_id, msg.payload) or b""
                except Exception:
                    reply = b""
            self.msgr.send_message(MWatchNotifyAck(
                pgid=msg.pgid, oid=msg.oid, cookie=msg.cookie,
                notify_id=msg.notify_id, reply=bytes(reply)),
                msg.from_addr)
            return True
        return False

    # -- op submission (Objecter::op_submit collapsed) ------------------

    # op kinds that never mutate; anything else makes the message a
    # write for tier-overlay routing purposes (shared with the OSD so
    # client routing and server handling can never disagree)
    READ_KINDS = OSD_READ_OPS

    def _resolve_overlay(self, pool_id: int, ops: list,
                         ignore_overlay: bool) -> int:
        """Cache-tier overlay redirect (Objecter::_calc_target,
        src/osdc/Objecter.cc: reads target the pool's read_tier, writes
        its write_tier, unless CEPH_OSD_FLAG_IGNORE_OVERLAY rides the
        op — which is how flush/promote IO reaches the base pool)."""
        if ignore_overlay:
            return pool_id
        pool = self.osdmap.pools.get(pool_id)
        if pool is None:
            return pool_id
        is_write = any(op[0] not in self.READ_KINDS for op in ops)
        tgt = pool.write_tier if is_write else pool.read_tier
        if tgt >= 0 and tgt in self.osdmap.pools:
            return tgt
        return pool_id

    def _target_for(self, pool_id: int, oid: str):
        m = self.osdmap
        raw_pg = m.object_to_pg(pool_id, oid)
        pool = m.pools[pool_id]
        pgid = pool.raw_pg_to_pg(raw_pg)
        up, upp, acting, actp = m.pg_to_up_acting_osds(pgid)
        return pgid, actp

    def submit_op(self, pool_id: int, oid: str, ops: list,
                  timeout: float = 30.0, pgid=None,
                  snapc=None, snap: int = 0,
                  ignore_overlay: bool = False,
                  flags: int = 0):
        """Send; resend on EAGAIN/timeout slices until deadline.

        pgid pins the target PG explicitly (PG-scoped ops like list);
        otherwise the object name hashes to its PG. snapc rides on
        writes (SnapContext), snap selects the read snapshot."""
        deadline = time.monotonic() + timeout
        backoff = 0.05
        fixed_pgid = pgid
        # ONE tid for the op's whole lifetime: every resend reuses it,
        # so the OSD's (client, tid) dedup can recognize retransmits —
        # a fresh tid per retry would double-apply non-idempotent ops
        # (append) whenever a reply was merely slow (Objecter reqid
        # semantics)
        tid = next(self._tids)
        op = _InflightOp(tid)
        span = self.tracer.start_trace("client_op")
        span.keyval("oid", oid)
        span.keyval("op", ",".join(o[0] for o in ops if o))
        ms_span = None
        self._throttle.get()
        with self._lock:
            self._inflight[tid] = op
        try:
            while True:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise RadosError(110, "op on %r timed out" % oid)
                if fixed_pgid is not None:
                    pgid = fixed_pgid
                    _, _, _, primary = \
                        self.osdmap.pg_to_up_acting_osds(pgid)
                else:
                    # overlay resolves per attempt: a tier change in a
                    # newer map must retarget the resend
                    eff_pool = self._resolve_overlay(pool_id, ops,
                                                     ignore_overlay)
                    pgid, primary = self._target_for(eff_pool, oid)
                if primary == -1:
                    time.sleep(min(backoff, remaining))
                    backoff = min(backoff * 2, 0.5)
                    continue
                addrs = self.osdmap.get_addr(primary)
                addr = addrs.get("public") if isinstance(addrs, dict) \
                    else addrs
                if addr is None:
                    time.sleep(min(backoff, remaining))
                    continue
                # one messenger span per attempt: send -> reply (the
                # OSD's osd_op span nests under it via the envelope)
                if ms_span is not None:
                    ms_span.finish()
                ms_span = span.child("messenger")
                ms_span.keyval("osd", primary)
                t_id, p_id = trace_ctx(ms_span)
                qd = qr = 0.0
                if self.qos_feedback is not None:
                    qd, qr = self.qos_feedback.stamp(primary)
                self.msgr.send_message(
                    MOSDOp(client_id=self.client_id, tid=tid, pgid=pgid,
                           oid=oid, ops=ops,
                           map_epoch=self.osdmap.epoch,
                           snapc=snapc or (0, ()), snap=snap,
                           session=self.session, flags=flags,
                           trace_id=t_id, parent_span=p_id,
                           qos_delta=qd, qos_rho=qr), addr)
                # wait a slice, then re-send (map may have changed)
                if op.event.wait(min(remaining, 1.0)):
                    if op.result == -11:  # EAGAIN: wrong/unready primary
                        with self._lock:
                            op.event.clear()
                            op.result = None
                            self._inflight[tid] = op
                        time.sleep(min(backoff, 0.2))
                        backoff = min(backoff * 2, 0.5)
                        continue
                    span.keyval("result", op.result)
                    return op.result, op.data
                with self._lock:
                    self._inflight[tid] = op   # re-arm for the resend
                # renew the map subscription too — repeated slice
                # timeouts often mean our map is stale because the
                # mon's push was lost on a lossy link
                self.mon_client.renew_subs()
        finally:
            if ms_span is not None:
                ms_span.finish()
            span.finish()
            with self._lock:
                self._inflight.pop(tid, None)
            self._throttle.put()


class IoCtx:
    """Per-pool IO interface (librados IoCtx surface subset)."""

    def __init__(self, client: RadosClient, pool_id: int):
        self.client = client
        self.pool_id = pool_id
        self._snapc = None            # self-managed SnapContext override
        self._read_snap = 0           # snap id reads resolve against
        # CEPH_OSD_FLAG_IGNORE_OVERLAY analog: ops on this ioctx bypass
        # any cache-tier overlay and hit the pool directly
        self.ignore_overlay = False
        # CEPH_OSD_FLAG_IGNORE_CACHE analog: the addressed PG runs the
        # op locally even on a cache-tier pool (no promote/proxy)
        self.ignore_cache = False

    def _pool(self):
        return self.client.osdmap.pools.get(self.pool_id) \
            if self.client.osdmap else None

    def _write_snapc(self) -> tuple:
        if self._snapc is not None:
            return self._snapc
        pool = self._pool()
        return pool.snap_context() if pool is not None else (0, ())

    def _op(self, oid: str, ops: list, timeout: float = 30.0,
            snap_override: int | None = None):
        from ..msg.message import OSD_FLAG_IGNORE_CACHE
        result, data = self.client.submit_op(
            self.pool_id, oid, ops, timeout,
            snapc=self._write_snapc(),
            snap=self._read_snap if snap_override is None
            else snap_override,
            ignore_overlay=self.ignore_overlay,
            flags=OSD_FLAG_IGNORE_CACHE if self.ignore_cache else 0)
        if result < 0:
            raise RadosError(-result, "op on %r failed: %d"
                             % (oid, result))
        return data

    # -- watch / notify (librados watch surface) -----------------------

    def watch(self, oid: str, callback) -> int:
        """Register interest in notifications on oid
        (rados_watch3). callback(notify_id, payload) -> optional reply
        bytes; runs on the messenger reader thread. Returns the watch
        cookie. After a primary change, re-watch (the reference's
        linger resend is the client's burden here too)."""
        cookie = next(self.client._tids)
        with self.client._lock:
            self.client._watches[cookie] = (oid, callback)
        try:
            self._op(oid, [("watch", cookie)])
        except Exception:
            with self.client._lock:
                self.client._watches.pop(cookie, None)
            raise
        return cookie

    def unwatch(self, oid: str, cookie: int) -> None:
        with self.client._lock:
            self.client._watches.pop(cookie, None)
        self._op(oid, [("unwatch", cookie)])

    def notify(self, oid: str, payload: bytes = b"",
               timeout: float = 3.0) -> dict:
        """Notify every watcher; blocks until all ack or the timeout
        (rados_notify2). Returns {"replies": {cookie: bytes},
        "timed_out": [cookie, ...]}."""
        return self._op(oid, [("notify", bytes(payload), timeout)],
                        timeout=timeout + 10.0)

    # -- snapshots (librados snap surface) -----------------------------

    def set_snap_context(self, seq: int, snaps) -> None:
        """Self-managed SnapContext for subsequent writes
        (rados_ioctx_selfmanaged_snap_set_write_ctx)."""
        self._snapc = (seq, tuple(sorted(snaps, reverse=True)))

    def snap_set_read(self, snap_id: int) -> None:
        """Reads resolve against this snap (rados_ioctx_snap_set_read;
        0 = head)."""
        self._read_snap = snap_id

    def selfmanaged_snap_create(self) -> int:
        """Allocate a self-managed snap id from the monitor."""
        pool = self._pool()
        res, outs, snap_id = self.client.mon_command({
            "prefix": "osd pool selfmanaged-snap-create",
            "pool": pool.name if pool else ""})
        if res != 0:
            raise RadosError(-res, outs)
        self._wait_pool(lambda p: p.snap_seq >= snap_id)
        return snap_id

    def create_snap(self, name: str) -> int:
        """Pool snapshot (rados_ioctx_snap_create / rados mksnap)."""
        pool = self._pool()
        res, outs, snap_id = self.client.mon_command({
            "prefix": "osd pool mksnap",
            "pool": pool.name if pool else "", "snap": name})
        if res != 0:
            raise RadosError(-res, outs)
        self._wait_pool(lambda p: name in (p.snaps or {}))
        return snap_id

    def remove_snap(self, name: str) -> None:
        pool = self._pool()
        res, outs, _ = self.client.mon_command({
            "prefix": "osd pool rmsnap",
            "pool": pool.name if pool else "", "snap": name})
        if res != 0:
            raise RadosError(-res, outs)
        self._wait_pool(lambda p: name not in (p.snaps or {}))

    def _wait_pool(self, pred, timeout: float = 10.0) -> None:
        """Block until the client's map shows the snap change (the
        mon's commit propagates via the subscription)."""
        import time as _t
        deadline = _t.monotonic() + timeout
        while _t.monotonic() < deadline:
            pool = self._pool()
            if pool is not None and pred(pool):
                return
            self.client.mon_client.renew_subs()
            _t.sleep(0.02)
        raise RadosError(110, "pool snap change never propagated")

    def lookup_snap(self, name: str) -> int:
        pool = self._pool()
        snap_id = (pool.snaps or {}).get(name) if pool else None
        if snap_id is None:
            raise RadosError(2, "snap %r does not exist" % name)
        return snap_id

    def rollback(self, oid: str, snap_name: str) -> None:
        """rados_ioctx_snap_rollback: head becomes the snap's state."""
        self._op(oid, [("rollback", self.lookup_snap(snap_name))])

    def rollback_id(self, oid: str, snap_id: int) -> None:
        """Rollback against a self-managed snap id
        (rados_ioctx_selfmanaged_snap_rollback)."""
        self._op(oid, [("rollback", snap_id)])

    def selfmanaged_snap_remove(self, snap_id: int) -> None:
        """Retire a self-managed snap id; OSDs trim its clones."""
        pool = self._pool()
        res, outs, _ = self.client.mon_command({
            "prefix": "osd pool selfmanaged-snap-remove",
            "pool": pool.name if pool else "", "snap_id": snap_id})
        if res != 0:
            raise RadosError(-res, outs)
        self._wait_pool(lambda p: snap_id in p.removed_snaps)

    def list_snaps(self, oid: str) -> dict:
        """Per-object clone listing (rados listsnaps)."""
        return self._op(oid, [("list_snaps",)])

    # -- writes --------------------------------------------------------

    def write_full(self, oid: str, data: bytes,
                   timeout: float = 30.0) -> None:
        self._op(oid, [("writefull", bytes(data))], timeout=timeout)

    def write(self, oid: str, data: bytes, offset: int = 0) -> None:
        self._op(oid, [("write", offset, bytes(data))])

    def append(self, oid: str, data: bytes) -> None:
        self._op(oid, [("append", bytes(data))])

    def truncate(self, oid: str, size: int) -> None:
        self._op(oid, [("truncate", size)])

    def remove(self, oid: str) -> None:
        self._op(oid, [("remove",)])

    def set_xattr(self, oid: str, name: str, value: bytes) -> None:
        self._op(oid, [("setxattr", name, value)])

    def rm_xattr(self, oid: str, name: str) -> None:
        self._op(oid, [("rmxattr", name)])

    def omap_set(self, oid: str, kv: dict) -> None:
        self._op(oid, [("omap_set", kv)])

    def omap_rm_keys(self, oid: str, keys) -> None:
        self._op(oid, [("omap_rm", list(keys))])

    def omap_clear(self, oid: str) -> None:
        self._op(oid, [("omap_clear",)])

    def exec(self, oid: str, cls: str, method: str,
             data: bytes = b"") -> bytes:
        """Invoke an in-OSD object-class method (rados_exec)."""
        return self._op(oid, [("call", cls, method, bytes(data))])

    # -- reads ---------------------------------------------------------

    def read(self, oid: str, length: int = 0, offset: int = 0,
             snap: int | None = None, timeout: float = 30.0) -> bytes:
        data = self._op(oid, [("read", offset, length)], timeout=timeout,
                        snap_override=snap)
        return bytes(data) if data is not None else b""

    def stat(self, oid: str) -> dict:
        return self._op(oid, [("stat",)])

    def get_xattrs(self, oid: str) -> dict:
        """All user xattrs (rados_getxattrs / CEPH_OSD_OP_GETXATTRS)."""
        return self._op(oid, [("getxattrs",)])

    def cache_flush(self, oid: str, timeout: float = 30.0) -> None:
        """Write a dirty cache-tier object back to its base pool
        (rados_cache_flush, CEPH_OSD_OP_CACHE_FLUSH). Target the cache
        pool directly."""
        self._op(oid, [("cache_flush",)], timeout)

    def cache_try_flush(self, oid: str, timeout: float = 30.0) -> None:
        """Non-blocking flavor: fails EBUSY instead of waiting for a
        racing writer (CEPH_OSD_OP_CACHE_TRY_FLUSH)."""
        self._op(oid, [("cache_try_flush",)], timeout)

    def cache_evict(self, oid: str, timeout: float = 30.0) -> None:
        """Drop a CLEAN object from the cache tier
        (rados_cache_evict, CEPH_OSD_OP_CACHE_EVICT); EBUSY when dirty,
        watched, or snapshotted."""
        self._op(oid, [("cache_evict",)], timeout)

    def get_xattr(self, oid: str, name: str) -> bytes:
        return self._op(oid, [("getxattr", name)])

    def omap_get(self, oid: str) -> dict:
        return self._op(oid, [("omap_get",)])

    def list_objects(self) -> list:
        """Union of object listings across the pool's PG primaries."""
        from ..osd.osd_map import PGID
        pool = self.client.osdmap.pools[self.pool_id]
        seen = set()
        for ps in range(pool.pg_num):
            try:
                result, data = self.client.submit_op(
                    self.pool_id, "", [("list",)], timeout=5.0,
                    pgid=PGID(self.pool_id, ps))
            except RadosError:
                continue
            if result == 0:
                seen.update(data or [])
        return sorted(seen)

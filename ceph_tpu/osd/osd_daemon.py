"""The OSD daemon.

Role of the reference's OSD (src/osd/OSD.{h,cc}): boot (mount store,
announce to mon, catch up on maps — OSD::init :2373), fast-dispatch
incoming messages onto a sharded op queue keyed by PG (ms_fast_dispatch
:6688 -> ShardedOpWQ, OSD.h:1623), heartbeat peers and report failures
(handle_osd_ping :4731 / failure reports to the mon), react to new maps
by re-peering every hosted PG, and serve the client/cluster/heartbeat
traffic classes on separate messengers (src/ceph_osd.cc:461-483).
"""

from __future__ import annotations

import logging
import threading
import time

from ..common import Context
from ..common.reserver import AsyncReserver
from ..common.throttle import BackoffThrottle
from ..common.workqueue import Finisher, SafeTimer, ShardedThreadPool
from ..mon.mon_client import MonClient
from ..msg.message import (MOSDBoot, MOSDFailure, MOSDOpReply, MPing,
                           MPingReply)
from ..msg.async_messenger import create_messenger
from ..msg.messenger import Dispatcher
from ..store.mem_store import MemStore
from ..common.lockdep import make_rlock
from ..common.tracer import SpanCollector, TailSampler
from .op_queue import QosShardedOpWQ, make_op_queue
from .op_request import OpTracker
from .osd_map import OSDMap
from .pg import PG

__all__ = ["OSDDaemon"]


class OSDDaemon(Dispatcher):
    def __init__(self, whoami: int, monmap: dict,
                 ctx: Context | None = None, store=None,
                 auth: dict | None = None):
        self.whoami = whoami
        self.ctx = ctx or Context(name="osd.%d" % whoami)
        conf = self.ctx.conf
        self.finisher = Finisher("osd%d-fin" % whoami)
        self.store = store or MemStore(self.finisher)
        # a handed-over store (daemon restart over the same data) must
        # deliver completions through THIS daemon's finisher — its
        # creator's finisher died with the old daemon, and callbacks
        # queued there black-hole (no commit acks => wedged writes)
        self.store._finisher = self.finisher
        # arm store fault injection from the objectstore_inject_*
        # knobs (store/faults.py; a handed-over store keeps any marks
        # the previous incarnation's tests planted)
        faults = getattr(self.store, "faults", None)
        if faults is not None:
            faults.configure(conf)
        # cephx: when the cluster runs with auth, client + peer
        # connections must present "osd"-service authorizers (the
        # heartbeat messenger stays open, documented: heartbeats carry
        # no data).  The authorizer factory closes over the cephx
        # session established during init's in-band mon handshake.
        self.auth = auth
        self._cephx = None             # CephxClient after authenticate
        verifier = None
        factory = None
        key_fn = None
        if auth is not None:
            from ..auth import CephxServiceHandler
            verifier = CephxServiceHandler(
                "osd", auth["service_secrets"]["osd"])

            def factory(challenge=None):
                if self._cephx is None:
                    return None
                return self._cephx.build_authorizer("osd", challenge)

            def key_fn():
                return self._cephx.tickets["osd"]["session_key"] \
                    if self._cephx else None

        self.public_msgr = create_messenger(
            ("osd", whoami), conf=conf, auth_verifier=verifier,
            authorizer_factory=factory, session_key_fn=key_fn)
        self.cluster_msgr = create_messenger(
            ("osd", whoami), conf=conf, auth_verifier=verifier,
            authorizer_factory=factory, session_key_fn=key_fn)
        self.hb_msgr = create_messenger(("osd", whoami), conf=conf)
        self.monmap = dict(monmap)
        self.mon_client = MonClient(monmap, self.public_msgr,
                                    "osd.%d" % whoami)
        # map-advance throttle (ISSUE 19): the MonClient parks incoming
        # incrementals and applies at most this many epochs per drain
        # tick, so a 1000-epoch catch-up peers in slices
        self.mon_client.map_max_advance = \
            conf.get_val("osd_map_max_advance")
        self.osdmap = OSDMap()
        # client ops stamped with a map epoch we have not seen yet
        self._waiting_for_map: list = []
        self.pgs: dict = {}
        # (session, tid) -> None (executing) | (result, data)
        from ..common.bounded import BoundedDict
        self._op_replies: BoundedDict = BoundedDict()
        self.lock = make_rlock("osd:%d" % whoami)
        # op scheduling: QoS discipline per osd_op_queue (wpq default,
        # like the reference's luminous OSD), plain FIFO as fallback
        if conf.get_val("osd_op_queue") == "fifo":
            self.op_wq = ShardedThreadPool(
                "osd%d-op" % whoami, conf.get_val("osd_op_num_shards"),
                self.ctx.hbmap)
        else:
            self.op_wq = QosShardedOpWQ(
                "osd%d-op" % whoami, conf.get_val("osd_op_num_shards"),
                lambda: make_op_queue(conf), self.ctx.hbmap)
        # pool -> (res, wgt, lim) profiles already pushed into the
        # shards, so map churn doesn't re-post unchanged rates
        self._pool_qos_applied: dict = {}
        self.client_op_priority = conf.get_val("osd_client_op_priority")
        self.recovery_op_priority = conf.get_val("osd_recovery_op_priority")
        # per-op event history + slow-request detection (OpTracker);
        # slow_size is the flight recorder's N-slowest ring
        self.op_tracker = OpTracker(
            history_size=conf.get_val("osd_op_history_size"),
            history_duration=conf.get_val("osd_op_history_duration"),
            complaint_time=conf.get_val("osd_op_complaint_time"),
            slow_size=conf.get_val("osd_op_history_slow_size"))
        # recovery/backfill reservation slots (the reference OSDService's
        # local_reserver/remote_reserver pairs): a primary must win its
        # LOCAL slot and every replica's REMOTE slot before its pushes
        # may enter the recovery op class (osd/pg.py reservation round)
        max_backfills = conf.get_val("osd_max_backfills")
        max_recovery = conf.get_val("osd_recovery_max_active")
        self.reservations = {
            "local_recovery": AsyncReserver("local_recovery",
                                            max_recovery),
            "remote_recovery": AsyncReserver("remote_recovery",
                                             max_recovery),
            "local_backfill": AsyncReserver("local_backfill",
                                            max_backfills),
            "remote_backfill": AsyncReserver("remote_backfill",
                                             max_backfills),
        }
        # peering storm control (ISSUE 19): peering itself rides a
        # reserver lane so a map-churn burst re-peers at most
        # osd_peering_max_active PGs at once instead of flooding the
        # op queue and starving client IO.  0 disables the gate
        # (pg.start_recovery bypasses the lane).
        peering_slots = conf.get_val("osd_peering_max_active")
        self.peering_gate = peering_slots > 0
        self.reservations["peering"] = AsyncReserver(
            "peering", max(1, peering_slots))
        # peering duration samples for the p99 lane
        # (ceph_pg_peering_seconds): ring of the last 256 completed
        # interval peerings, summarized in _telemetry_status
        from collections import deque
        self._peering_durations = deque(maxlen=256)
        # osd_recovery_sleep delay shaping: pushes acquire a unit for
        # the duration of the push, and BackoffThrottle injects an
        # occupancy-scaled sleep — the closer concurrent pushes sit to
        # the recovery budget, the longer each one yields to client IO
        sleep = conf.get_val("osd_recovery_sleep")
        self.recovery_throttle = BackoffThrottle(
            "osd%d-recovery-sleep" % whoami,
            max_=max(1, max_recovery),
            low_threshold=0.0, high_threshold=1.0,
            low_delay=sleep * 0.1, high_delay=sleep) \
            if sleep > 0 else None
        # full-ratio ladder thresholds (mon_osd_*_ratio; the mon ranks
        # the reported used_ratio against the same options)
        self._full_ratios = (
            conf.get_val("mon_osd_nearfull_ratio"),
            conf.get_val("mon_osd_backfillfull_ratio"),
            conf.get_val("mon_osd_full_ratio"))
        self._used_stat_cache = (0.0, -1e9)   # (ratio, stamp)
        # device-runtime profiler (common/profiler.py): process-global
        # by design (module-level jit sites have no daemon home), so
        # configure() just applies this daemon's knobs
        from ..common.profiler import PROFILER
        PROFILER.configure(conf)
        # ZTracer-style span collector, config-gated (osd_tracing with
        # an osd_tracing_sample hot-path knob); spans stitch across
        # daemons via the message-envelope (trace_id, parent_span)
        self.tracer = SpanCollector(conf=conf,
                                    endpoint="osd.%d" % whoami)
        # tail-based trace retention (SLO forensics): keep/drop at op
        # completion; finished spans buffer here pending the root's
        # verdict and kept traces ship to the mgr as MTraceFragments
        self.tail = TailSampler(conf=conf)
        self.tracer.tail = self.tail
        self._tail_expired_synced = 0
        # kept-trace wire work (verdict broadcast + mgr shipment) runs
        # on its own lane: the verdict itself is cheap, but encoding
        # span payloads on the commit path would tax every op that
        # completes behind a kept one
        from collections import deque as _deque
        self._trace_ship_cond = threading.Condition()
        self._trace_ship_q = _deque()
        self._trace_ship_stop = False
        self._trace_ship_thread = threading.Thread(
            target=self._trace_ship_loop,
            name="trace-ship-%d" % whoami, daemon=True)
        self._trace_ship_thread.start()
        if self.ctx.admin_socket is not None:
            self.op_tracker.register_admin_commands(self.ctx.admin_socket)
            self.tracer.register_admin_commands(self.ctx.admin_socket)
            # store-specific commands (BlockStore: 'bluefs stats',
            # 'bluestore fsck' — the reference's asok surface)
            register_store = getattr(self.store,
                                     "register_admin_commands", None)
            if register_store is not None:
                register_store(self.ctx.admin_socket)
        self.timer = SafeTimer("osd%d-timer" % whoami)
        # cross-op EC device-call coalescing (osd/tpu_dispatch.py):
        # concurrent PG encodes sharing a codec ride one dispatch
        # mesh-native placement (parallel/placement.py, direction D):
        # resolve this OSD's home device once — the dispatcher
        # pipeline and the HBM chunk tier both pin to it, so N
        # daemons land one-per-chip with no global device lock
        from ..parallel.placement import PLACEMENT
        self.home_device = PLACEMENT.resolve(
            whoami, conf.get_val("osd_device_index"))
        # rateless mesh dispatch (parallel/rateless.py, direction J):
        # honour the conf gate so a daemon started with
        # osd_mesh_rateless=false never pulls the process-global
        # work-stealing dispatcher into its decode paths
        try:
            from ..parallel import rateless
            rateless.set_enabled(
                bool(conf.get_val("osd_mesh_rateless")))
        except Exception:
            pass
        if conf.get_val("osd_tpu_coalesce"):
            from .tpu_dispatch import TpuDispatcher
            self.tpu_dispatcher = TpuDispatcher(
                max_batch=conf.get_val("osd_tpu_coalesce_max_batch"),
                max_delay=conf.get_val(
                    "osd_tpu_coalesce_max_delay_ms") / 1e3,
                tracer=self.tracer,
                device=self.home_device)
            # l_tpu_* device-segment counters ride the daemon's perf
            # collection (mgr report -> prometheus)
            self.ctx.perf.add(self.tpu_dispatcher.perf)
        else:
            self.tpu_dispatcher = None
        # HBM-resident chunk tier (osd/hbm_tier.py, ROADMAP direction
        # A): the dispatcher pipeline adopts each EC encode's staged
        # data + parity device-side keyed by (pg, object); scrub-repair
        # rebuilds and recovery reconstruction read the resident copy
        # instead of re-crossing PCIe. Gated on jax being importable —
        # the tier is pure device residency and has no host fallback.
        self.hbm_tier = None
        if conf.get_val("osd_hbm_tier_enable"):
            try:
                from .hbm_tier import HbmChunkTier
                self.hbm_tier = HbmChunkTier(
                    capacity_objects=conf.get_val(
                        "osd_hbm_tier_capacity"),
                    device=self.home_device)
                self.ctx.perf.add(self.hbm_tier.perf)
            except Exception:
                self.hbm_tier = None
        self.hbm_serve_reads = conf.get_val("osd_hbm_tier_serve_reads")
        # fused write transform (osd/fused_transform.py, direction F):
        # ec_backend reads these via getattr, so a missing option
        # degrades to the classic path rather than failing startup
        try:
            if not conf.get_val("osd_fused_transform"):
                self.fused_mode = "off"
            elif conf.get_val("osd_fused_compression_mode") in (
                    "", "none", None):
                self.fused_mode = "store"
            else:
                self.fused_mode = "compress"
            self.fused_required_ratio = float(
                conf.get_val("osd_fused_required_ratio"))
            self.fused_entropy_max = float(
                conf.get_val("osd_fused_probe_entropy_max"))
        except Exception:
            self.fused_mode = "off"
            self.fused_required_ratio = 0.875
            self.fused_entropy_max = 7.0
        if self.ctx.admin_socket is not None:
            # residency + pipeline introspection (`ceph daemon osd.N
            # hbm status` / `dispatch status`)
            self.ctx.admin_socket.register(
                "hbm status",
                lambda args: (self.hbm_tier.stats()
                              if self.hbm_tier is not None
                              else {"enabled": False}),
                "HBM chunk-tier residency, hit rate and evictions")
            self.ctx.admin_socket.register(
                "dispatch status",
                lambda args: (self.tpu_dispatcher.dispatch_status()
                              if self.tpu_dispatcher is not None
                              else {"enabled": False}),
                "TPU dispatcher pipeline ring occupancy + coalescing")
            # device-runtime profiler surface: stall-attribution
            # verdict, jit registry, device-memory ledger
            self.ctx.admin_socket.register(
                "dispatch profile",
                lambda args: (self.tpu_dispatcher.dispatch_profile()
                              if self.tpu_dispatcher is not None
                              else {"enabled": False}),
                "pipeline stall attribution (busy/idle/blocked per "
                "stage + bound-stage verdict)")
            self.ctx.admin_socket.register(
                "profile dump",
                lambda args: self._profile_dump(),
                "device-runtime profiler: jit compiles/cache hits, "
                "device-memory ledger, dispatch stall attribution")
            self.ctx.admin_socket.register(
                "profile reset",
                lambda args: self._profile_reset(),
                "reset the device-runtime profiler's registries and "
                "restart the stall-attribution window")
            self.ctx.admin_socket.register(
                "mesh status",
                lambda args: self._mesh_status(),
                "device placement: local mesh, this OSD's home "
                "device, and every placement-registry assignment")
            self.ctx.admin_socket.register(
                "dump_reservations",
                lambda args: {name: r.dump()
                              for name, r in self.reservations.items()},
                "recovery/backfill reservation slots: granted holders "
                "+ priority-ordered waiters per reserver")
            self.ctx.admin_socket.register(
                "perf query dump",
                lambda args: {"queries": self.perf_query.list_queries(),
                              "results": self.perf_query.dump()},
                "live perf-query subscriptions + per-key tables "
                "(ops/bytes/latency per client/pool/pg key)")
            self.ctx.admin_socket.register(
                "dump_op_queue",
                lambda args: self._dump_op_queue(),
                "QoS op-queue state: per-class/per-pool depth, served "
                "and limit-throttle wait merged across shards")
            self.ctx.admin_socket.register(
                "osdmap status",
                lambda args: self._osdmap_status(),
                "map pipeline state: applied epoch, mon epoch, lag, "
                "inc backlog depth, peering lane occupancy + p99")
        self.hb_peers: dict = {}       # osd -> last reply stamp
        self.hb_pending: dict = {}     # osd -> first unacked ping stamp
        # cache tiering: base-pool IO runs on dedicated threads with an
        # internal RadosClient (the reference OSD's objecter), never on
        # an op-shard worker (the base PG may live on THIS osd)
        self._tier_pool = None
        self._tier_client = None
        self.mgr_addr = None           # set when an mgr joins the cluster
        # delta-encoded mgr telemetry: ship only changed counters once
        # the mgr acks a full baseline (common/telemetry.py)
        from ..common.telemetry import DeltaReporter
        self._mgr_reporter = DeltaReporter()
        self._boot_sent_epoch = -1     # epoch of the last MOSDBoot sent
        self._boot_sent_at = 0.0       # for boot retransmit rate-limit
        # l_osd_* counters (OSD.cc's PerfCounters), streamed to the mgr
        from ..common.perf_counters import PerfCountersBuilder
        self.perf = (PerfCountersBuilder("osd")
                     .add_u64_counter("op", "client operations")
                     .add_u64_counter("op_r", "client read operations")
                     .add_u64_counter("op_w", "client write operations")
                     .add_u64_counter("op_in_bytes", "client bytes written")
                     .add_u64_counter("op_out_bytes",
                                      "client bytes read back")
                     .add_time_avg("op_latency", "client op latency")
                     .add_u64_counter("read_err",
                                      "shard read errors (EIO/bad crc) "
                                      "seen on the EC read path "
                                      "(l_osd_read_err)")
                     .add_u64_counter("repaired",
                                      "shards rewritten by read-repair "
                                      "or scrub repair (l_osd_repaired)")
                     # EC partial overwrites: writes whose stripes the
                     # primary read back before re-encoding, and the
                     # chunk bytes it asked the shards for
                     .add_u64_counter("l_osd_ec_rmw_ops",
                                      "EC writes that read back their "
                                      "stripes (read-modify-write)")
                     .add_u64_counter("l_osd_ec_rmw_read_bytes",
                                      "chunk bytes read back for EC "
                                      "read-modify-writes")
                     # EC client reads: each asks the data shards of
                     # the columns its extent touches (ec_backend)
                     .add_u64_counter("l_osd_ec_reads",
                                      "EC client reads planned")
                     .add_u64_counter("l_osd_ec_read_shards",
                                      "shards the EC client reads "
                                      "asked")
                     .add_u64_counter("l_osd_ec_partial_reads",
                                      "EC client reads whose extent "
                                      "touched fewer than k data "
                                      "shards")
                     # recovery/backfill accounting (OSD.cc
                     # l_osd_recovery_ops/_bytes, l_osd_backfill):
                     # incremented per pushed shard on the recovery
                     # lane (peer re-reported missing) vs the backfill
                     # lane (inventory reconcile after remap)
                     .add_u64_counter("l_osd_recovery_ops",
                                      "recovery push operations "
                                      "completed")
                     .add_u64_counter("l_osd_recovery_bytes",
                                      "bytes pushed by recovery")
                     .add_u64_counter("l_osd_backfill_ops",
                                      "backfill push operations "
                                      "completed")
                     .add_u64_counter("l_osd_backfill_bytes",
                                      "bytes pushed by backfill")
                     # regenerating-code repair accounting (ROADMAP
                     # direction C): helper-side bytes read from disk
                     # and beta-fraction bytes shipped to the primary,
                     # primary-side bytes of survivor traffic AVOIDED
                     # vs a full k-chunk decode — the recovery-traffic
                     # ratio gauge derives from shipped/(shipped+saved)
                     .add_u64_counter("l_osd_repair_bytes_read",
                                      "shard bytes read by repair "
                                      "fraction requests (helper side)")
                     .add_u64_counter("l_osd_repair_bytes_shipped",
                                      "beta-fraction bytes shipped to "
                                      "the rebuilding primary")
                     .add_u64_counter("l_osd_repair_bytes_saved",
                                      "survivor bytes NOT moved vs a "
                                      "full k-chunk decode")
                     # reservation observability (dump_reservations
                     # asok / prometheus ceph_osd_reservation_*):
                     # granted + preempted are lifetime totals across
                     # the four reservers, waiting is the current
                     # queue depth — synced from the reservers at
                     # report time (_sync_reservation_perf)
                     .add_u64("l_osd_reservation_granted",
                              "reservation grants (lifetime, all "
                              "reservers)")
                     .add_u64("l_osd_reservation_waiting",
                              "reservation requests currently queued")
                     .add_u64("l_osd_reservation_preempted",
                              "reservation holders preempted by "
                              "higher priority (lifetime)")
                     # dispatch-side admission control: cumulative time
                     # client connections spent blocked on the message
                     # count/size throttles (TCP backpressure)
                     .add_time_avg("l_osd_throttle_wait",
                                   "client dispatch throttle wait")
                     # span-derived per-phase op timing (the tracing
                     # spine's aggregate view; always on — a tinc is
                     # cheap even when span objects are not minted)
                     .add_time_avg("l_osd_op_trace_queue",
                                   "op wait in the sharded op queue")
                     .add_time_avg("l_osd_op_trace_pg",
                                   "pg do_op planning/submit time")
                     .add_time_avg("l_osd_op_trace_total",
                                   "client op end-to-end on this osd")
                     .add_histogram("l_osd_op_trace_us",
                                    "op latency histogram, microseconds")
                     # dynamic per-principal perf queries
                     # (osd/perf_query.py): live subscription + key
                     # table gauges, lifetime sample/eviction totals
                     .add_u64("l_osd_pq_queries",
                              "perf queries currently subscribed")
                     .add_u64("l_osd_pq_keys",
                              "live perf-query keys across all "
                              "subscriptions (bounded by "
                              "osd_perf_query_max_keys per query)")
                     .add_u64_counter("l_osd_pq_samples",
                                      "client ops accounted into at "
                                      "least one perf query")
                     .add_u64_counter("l_osd_pq_evictions",
                                      "perf-query keys LRU-evicted at "
                                      "the table bound")
                     # map-churn observability (ISSUE 19): per-interval
                     # peering wall time (histogram in microseconds —
                     # hinc buckets are integer powers of two) and the
                     # epochs this daemon trails the mon's newest map
                     .add_histogram("l_osd_peering_us",
                                    "per-interval peering duration, "
                                    "microseconds (start_peering to "
                                    "activate)")
                     .add_u64("l_osd_map_lag_epochs",
                              "osdmap epochs this daemon trails the "
                              "monitor (backlog + unfetched)")
                     # tail-based trace retention (SLO forensics):
                     # verdicts by reason, plus the replica-side
                     # pending-buffer churn
                     .add_u64_counter("l_osd_trace_tail_kept_slo",
                                      "traces kept: op latency over "
                                      "the pool's SLO threshold")
                     .add_u64_counter("l_osd_trace_tail_kept_error",
                                      "traces kept: op errored or a "
                                      "span logged an error event")
                     .add_u64_counter("l_osd_trace_tail_kept_reservoir",
                                      "traces kept by the baseline "
                                      "reservoir draw")
                     .add_u64_counter("l_osd_trace_tail_dropped",
                                      "traces judged drop at "
                                      "completion (zero wire bytes)")
                     .add_u64_counter("l_osd_trace_tail_shipped_spans",
                                      "span fragments shipped to the "
                                      "mgr trace store")
                     .add_u64_counter("l_osd_trace_tail_expired",
                                      "pending replica fragments "
                                      "reaped by the verdict TTL")
                     .create_perf_counters())
        self.ctx.perf.add(self.perf)
        # per-principal perf-query engine (osd/perf_query.py): the
        # mgr subscribes queries via MOSDPerfQuery; pg.do_op wraps
        # reply callables through it when any query is live
        from .perf_query import PerfQueryEngine
        self.perf_query = PerfQueryEngine(conf=conf, perf=self.perf)
        # messenger admission control (tentpole leg 3): over-budget
        # client connections block in the reader — TCP backpressure —
        # instead of ballooning the op queue.  Public messenger only:
        # cluster/heartbeat traffic must never be throttled behind
        # client bytes.
        self.public_msgr.enable_dispatch_throttle(
            conf.get_val("osd_client_message_cap"),
            conf.get_val("osd_client_message_size_cap"),
            wait_cb=lambda dt: self.perf.tinc(
                "l_osd_throttle_wait", dt))
        # cluster log channel (the reference's clog): operator-facing
        # events (shard EIO, scrub errors, repairs) go to the mon's
        # replicated LogMonitor and surface via 'ceph log last'
        from ..common.clog import ClogChannel
        self.clog = ClogChannel(self.public_msgr, monmap,
                                "osd.%d" % whoami)
        self._running = False
        self.stopped_pgs = False

    # -- lifecycle -----------------------------------------------------

    def init(self) -> None:
        self.store.mount()
        # BlockStore: the l_bluefs_* counters exist only after mount;
        # register them so 'perf dump'/'perf schema', the mgr report,
        # and PrometheusModule all carry them
        bluefs = getattr(self.store, "bluefs", None)
        if bluefs is not None and getattr(bluefs, "perf", None) \
                is not None:
            self.ctx.perf.add(bluefs.perf)
        for msgr in (self.public_msgr, self.cluster_msgr, self.hb_msgr):
            msgr.bind()
            msgr.add_dispatcher_head(self)
            msgr.start()
        self.finisher.start()
        self.op_wq.start()
        self.timer.init()
        self._running = True
        self.mon_client.map_callbacks.append(self._on_osdmap)
        if self.auth is not None:
            # in-band cephx with the mon BEFORE any cluster dial: peer
            # OSDs demand an authorizer minted from this ticket
            self._cephx = self.mon_client.authenticate(
                "osd.%d" % self.whoami, self.auth["secret"],
                service="osd")
        self.mon_client.sub_want()
        self._boot()
        self._hb_tick()
        self._agent_tick()
        self._mgr_report_tick()

    def _send_mon(self, msg) -> None:
        """One-way control traffic (boot, failure reports, pg stats)
        broadcast to EVERY monitor: peons forward to the leader and
        the services are idempotent/deduping, so the message survives
        any minority of dead mons — including the old leader.  A
        single fixed target (the old monmap[min] behavior) wedged
        reviving OSDs forever when exactly that mon was the one that
        died."""
        for rank in sorted(self.monmap):
            self.public_msgr.send_message(msg, self.monmap[rank])

    def _boot(self, epoch: int | None = None) -> None:
        # record the epoch of the map that PROMPTED this boot (the new
        # map is not installed yet when called from _on_osdmap)
        self._boot_sent_epoch = self.map_epoch() if epoch is None \
            else epoch
        self._boot_sent_at = time.monotonic()
        self._send_mon(
            MOSDBoot(osd_id=self.whoami,
                     public_addr=self.public_msgr.my_addr,
                     cluster_addr=self.cluster_msgr.my_addr,
                     hb_addr=self.hb_msgr.my_addr))

    def shutdown(self) -> None:
        self._running = False
        with self._trace_ship_cond:
            self._trace_ship_stop = True
            self._trace_ship_cond.notify()
        self.timer.shutdown()
        if self.tpu_dispatcher is not None:
            self.tpu_dispatcher.shutdown()
        with self.lock:
            tier_pool, self._tier_pool = self._tier_pool, None
            tier_client, self._tier_client = self._tier_client, None
        if tier_pool is not None:
            tier_pool.shutdown(wait=False, cancel_futures=True)
        if tier_client is not None:
            tier_client.shutdown()
        self.op_wq.stop()
        self.finisher.stop()
        for msgr in (self.public_msgr, self.cluster_msgr, self.hb_msgr):
            msgr.shutdown()
        self.store.umount()
        self.ctx.shutdown()

    # -- map handling --------------------------------------------------

    def map_epoch(self) -> int:
        return self.osdmap.epoch

    def ec_profile_for(self, pool) -> dict:
        """Resolve the pool's EC profile from the published osdmap."""
        prof = self.osdmap.ec_profiles.get(pool.erasure_code_profile)
        if prof is None:
            raise KeyError("no EC profile %r" % pool.erasure_code_profile)
        return prof

    def _on_osdmap(self, newmap) -> None:
        if newmap is None:
            return
        # the map says we're dead but we're clearly not: re-boot (the
        # reference OSD does the same when it sees itself marked down —
        # covers a late failure report racing a quick restart). Only
        # once per epoch: a boot is already in flight for maps at or
        # below the epoch we last booted against.
        if self._running and newmap.exists(self.whoami) \
                and newmap.is_down(self.whoami) \
                and newmap.epoch > self._boot_sent_epoch:
            self._boot(epoch=newmap.epoch)
        with self.lock:
            self.osdmap = newmap
            pgs = list(self.pgs.values())
            ready = [m for m in self._waiting_for_map
                     if m.map_epoch <= newmap.epoch]
            self._waiting_for_map = [m for m in self._waiting_for_map
                                     if m.map_epoch > newmap.epoch]
        self._apply_pool_qos(newmap)
        for pg in pgs:
            self.op_wq.queue(pg.pgid, pg.on_map_change)
        self._scan_for_new_pgs()
        # ops that waited for this map queue behind its PG map changes
        for msg in ready:
            self._enqueue_client_op(msg)

    def _apply_pool_qos(self, m) -> None:
        """Push pool dmclock profiles from the osdmap into every op
        shard: a pool with a profile gets its own "client:<name>"
        class so another pool's flood cannot consume its reservation."""
        if not isinstance(self.op_wq, QosShardedOpWQ):
            return
        for pool in m.pools.values():
            if not getattr(pool, "has_qos", lambda: False)():
                continue
            prof = (pool.qos_reservation, pool.qos_weight or 500.0,
                    pool.qos_limit)
            if self._pool_qos_applied.get(pool.name) == prof:
                continue
            if self.op_wq.set_pool_qos(pool.name, *prof):
                self._pool_qos_applied[pool.name] = prof

    def _qos_class_for(self, pool) -> str:
        """Op class for a client op: per-pool when the pool carries a
        QoS profile (bounded cardinality — one extra class per
        profiled pool), plain "client" otherwise."""
        if pool is not None and getattr(pool, "has_qos",
                                        lambda: False)():
            return "client:%s" % pool.name
        return "client"

    def _dump_op_queue(self) -> dict:
        if isinstance(self.op_wq, QosShardedOpWQ):
            classes = self.op_wq.dump()
        else:
            classes = {}
        return {"discipline": self.ctx.conf.get_val("osd_op_queue"),
                "num_shards": self.ctx.conf.get_val("osd_op_num_shards"),
                "classes": classes,
                "pool_profiles": dict(self._pool_qos_applied)}

    def _scan_for_new_pgs(self) -> None:
        """Instantiate PGs this OSD is acting in (load_pgs analog)."""
        from .osd_map import PGID
        m = self.osdmap
        for pool_id, pool in m.pools.items():
            for ps in range(pool.pg_num):
                pgid = PGID(pool_id, ps)
                with self.lock:
                    if pgid in self.pgs:
                        continue
                up, upp, acting, actp = m.pg_to_up_acting_osds(pgid)
                if self.whoami in acting or self.whoami in up:
                    self._get_pg(pgid, pool)

    def _get_pg(self, pgid, pool=None):
        with self.lock:
            pg = self.pgs.get(pgid)
            if pg is None:
                if pool is None:
                    pool = self.osdmap.pools.get(pgid.pool)
                    if pool is None:
                        return None
                pg = self.pgs[pgid] = PG(self, pgid, pool)
                self.op_wq.queue(pgid, pg.on_map_change)
        return pg

    def scrub_pg(self, pgid, deep: bool = False,
                 repair: bool = False) -> bool:
        """Kick a (deep) scrub of one PG ('ceph pg scrub' /
        'ceph pg deep-scrub' surface); runs on the op queue at scrub
        class priority.  repair=True is the 'ceph pg repair' spelling:
        rebuild what the scrub flags even when osd_scrub_auto_repair
        is off."""
        pg = self.pgs.get(pgid)
        if pg is None:
            return False
        # the seq bump + queued marker happen synchronously and under
        # the PG lock: callers polling scrub_stats must never read a
        # PREVIOUS scrub's terminal state as this scrub's result, and a
        # superseded scrub (or its deep worker) must never write stats
        # over a newer one's
        with pg.lock:
            pg._scrub_seq = getattr(pg, "_scrub_seq", 0) + 1
            seq = pg._scrub_seq
            pg.scrub_stats = {"state": "queued"}
        self.op_wq.queue(pg.pgid, pg.scrub, seq, deep, repair,
                         klass="scrub",
                         priority=self.recovery_op_priority)
        return True

    # -- cache tiering plumbing ----------------------------------------

    def tier_submit(self, fn, *args) -> None:
        """Run blocking cross-pool tier IO on the dedicated tier
        threads (lazily created; most OSDs never host a tier PG).
        Work arriving after shutdown began is dropped — recreating the
        pool post-teardown would leak threads past daemon stop."""
        with self.lock:
            if not self._running:
                return
            if self._tier_pool is None:
                from concurrent.futures import ThreadPoolExecutor
                self._tier_pool = ThreadPoolExecutor(
                    max_workers=2,
                    thread_name_prefix="osd%d-tier" % self.whoami)
            pool = self._tier_pool
        pool.submit(self._tier_run, fn, *args)

    @staticmethod
    def _tier_run(fn, *args) -> None:
        try:
            fn(*args)
        except Exception:
            logging.getLogger("ceph_tpu.osd").exception(
                "tier operation failed")

    def tier_client(self):
        """The OSD-internal RadosClient the tier path uses for base-
        pool IO (the reference OSD's own Objecter)."""
        with self.lock:
            if not self._running:
                raise RuntimeError("osd.%d shutting down" % self.whoami)
            client = self._tier_client
        if client is not None:
            return client
        from ..client.rados import RadosClient
        fresh = RadosClient(self.monmap, client_id=100000 + self.whoami)
        fresh.connect()
        with self.lock:
            if self._running and self._tier_client is None:
                self._tier_client = fresh
                fresh = None
            client = self._tier_client
        if fresh is not None:
            fresh.shutdown()    # lost the creation race / shutting down
        if client is None:
            raise RuntimeError("osd.%d shutting down" % self.whoami)
        return client

    def _agent_tick(self) -> None:
        """Periodic tier-agent pass over primary cache-tier PGs
        (OSD::tick -> agent_entry role)."""
        if not self._running:
            return
        with self.lock:
            pgs = list(self.pgs.values())
        for pg in pgs:
            pool = pg.pool
            if pool.is_tier() \
                    and pool.cache_mode in ("writeback", "readproxy") \
                    and pg.is_primary() and pg.peer_state == "active" \
                    and (pool.target_max_objects > 0
                         or pool.target_max_bytes > 0):
                self.tier_submit(pg._tier().agent_scan)
        self.timer.add_event_after(
            self.ctx.conf.get_val("osd_agent_interval"),
            self._agent_tick)

    def queue_recovery(self, pg) -> None:
        self.op_wq.queue(pg.pgid, pg.start_recovery,
                         klass="recovery",
                         priority=self.recovery_op_priority)

    # -- sends ---------------------------------------------------------

    def _osd_addr(self, osd: int, kind: str):
        addrs = self.osdmap.get_addr(osd)
        if isinstance(addrs, dict):
            return addrs.get(kind)
        return addrs

    def send_to_client(self, addr, msg) -> None:
        """Push a message to a client's advertised address (the
        watch/notify path rides the public messenger)."""
        self.public_msgr.send_message(msg, addr)

    def send_to_osd_cluster(self, osd: int, msg) -> None:
        addr = self._osd_addr(osd, "cluster")
        if addr is not None:
            self.cluster_msgr.send_message(msg, addr)

    # -- heartbeats ----------------------------------------------------

    def _hb_tick(self) -> None:
        if not self._running:
            return
        conf = self.ctx.conf
        now = time.monotonic()
        # the boot message is one-shot: on a lossy link a dropped
        # MOSDBoot would strand the OSD forever, so retransmit while
        # the map doesn't show us up (rate-limited)
        if not self.osdmap.is_up(self.whoami) \
                and now - self._boot_sent_at >= 1.0:
            self._boot()
        # likewise the mon's map pushes are one-shot: renew the
        # subscription periodically so a dropped MOSDMap doesn't leave
        # this OSD on a stale map (PGs never instantiated -> every
        # client op bounces with EAGAIN)
        self.mon_client.renew_subs()
        grace = conf.get_val("osd_heartbeat_grace")
        peers = [o for o in self.osdmap.get_up_osds()
                 if o != self.whoami]
        # an unacked stamp of a peer the map shows down belongs to its
        # dead incarnation: kept, it would report the restarted peer
        # failed on its first tick back up and flap it down again
        for osd in set(self.hb_pending) - set(peers):
            self.hb_pending.pop(osd, None)
        for osd in peers:
            addr = self._osd_addr(osd, "hb")
            if addr is None:
                continue
            self.hb_pending.setdefault(osd, now)
            self.hb_msgr.send_message(
                MPing(stamp=now, epoch=self.map_epoch()), addr)
            # the reply handler may pop the entry between the send and
            # this read (it raced a KeyError here once): a popped entry
            # means the ping was acked — nothing is unacked
            first_unacked = self.hb_pending.get(osd, now)
            if now - first_unacked > grace:
                self.ctx.dout("osd", 1,
                              "osd.%d no reply from osd.%d for %.2fs -> "
                              "reporting failure"
                              % (self.whoami, osd, now - first_unacked))
                self._send_mon(
                    MOSDFailure(reporter=self.whoami, target=osd,
                                failed_for=now - first_unacked,
                                epoch=self.map_epoch()))
                self.hb_pending[osd] = now  # don't spam
        # pg stats to the mon on the same cadence (MPGStats): primaries
        # report scrub errors + rough usage so the HealthMonitor can
        # derive OSD_SCRUB_ERRORS / POOL_FULL mon-side
        self._report_pg_stats()
        self.timer.add_event_after(
            conf.get_val("osd_heartbeat_interval"), self._hb_tick)

    def _mgr_report_tick(self) -> None:
        """The mgr telemetry stream (DaemonServer's MMgrReport role)
        on its OWN cadence — mgr_stats_period, decoupled from the
        heartbeat so operators can tune (or pin off, period=0) the
        report volume without touching failure detection.  Reports are
        delta-encoded (ISSUE 18): after the mgr acks a full baseline
        only changed counters travel, and the schema rides only on the
        first report / hash change; status, pg stats and perf-query
        values still ship whole each period."""
        if not self._running:
            return
        period = self.ctx.conf.get_val("mgr_stats_period")
        if period <= 0:
            # reporting pinned off; poll cheaply for a config change
            self.timer.add_event_after(1.0, self._mgr_report_tick)
            return
        try:
            if self.mgr_addr is not None:
                from ..msg.message import MMgrReport
                rep = self._mgr_reporter.prepare(
                    self.ctx.perf.perf_dump(),
                    self.ctx.perf.perf_schema())
                self.public_msgr.send_message(
                    MMgrReport(daemon_name="osd.%d" % self.whoami,
                               daemon_type="osd",
                               perf=rep["perf"],
                               metadata={"id": self.whoami},
                               status=self._telemetry_status(),
                               pg_stats=self._collect_pg_stats(),
                               perf_schema=rep["schema"],
                               perf_query=(self.perf_query.dump()
                                           if self.perf_query.active
                                           else {}),
                               report_seq=rep["seq"],
                               incarnation=rep["incarnation"],
                               schema_hash=rep["schema_hash"],
                               delta_base=rep["delta_base"]),
                    self.mgr_addr)
        finally:
            # a failed report must never kill the tick chain — the
            # stream self-heals on the next period
            self.timer.add_event_after(period, self._mgr_report_tick)

    def _profile_dump(self) -> dict:
        """The `profile dump` asok payload: every profiler leg in one
        document (what `ceph_cli daemon osd.N profile dump` renders)."""
        from ..common.profiler import PROFILER
        doc = PROFILER.dump()
        if self.tpu_dispatcher is not None:
            doc["dispatch"] = self.tpu_dispatcher.dispatch_profile()
        tier = getattr(self, "hbm_tier", None)
        if tier is not None:
            try:
                doc["hbm"] = tier.stats()
            except Exception:
                pass
        return doc

    def _profile_reset(self) -> dict:
        from ..common.profiler import PROFILER
        PROFILER.reset()
        if self.tpu_dispatcher is not None:
            self.tpu_dispatcher.profile_reset()
        return {"reset": True}

    def _mesh_status(self) -> dict:
        """The `mesh status` asok payload: the local device mesh, this
        OSD's resolved home device, and the whole placement registry
        (every co-resident daemon's assignment)."""
        from ..parallel.placement import PLACEMENT, device_label
        doc = PLACEMENT.assignments()
        doc["whoami"] = self.whoami
        doc["home_device"] = device_label(
            getattr(self, "home_device", None))
        if self.tpu_dispatcher is not None:
            doc["dispatcher_device"] = device_label(
                self.tpu_dispatcher.device)
        tier = getattr(self, "hbm_tier", None)
        if tier is not None:
            doc["hbm_tier_device"] = device_label(tier.device)
        try:
            from ..parallel import rateless
            disp = rateless.get_dispatcher(create=False)
            if disp is not None:
                # per-device health table: ewma_ms / inflight / stolen /
                # redispatched / blacklisted / probation per chip
                doc["rateless"] = disp.status()
        except Exception:
            pass
        return doc

    def _telemetry_status(self) -> dict:
        """The gauge bag riding MMgrReport.status: store capacity
        truth plus device-utilization (dispatch queue depth,
        coalescing, rolling per-codec MB/s, HBM residency)."""
        status: dict = {}
        try:
            status["statfs"] = self.store.statfs()
        except Exception:
            pass
        if self.tpu_dispatcher is not None:
            try:
                status["tpu"] = self.tpu_dispatcher.telemetry()
            except Exception:
                pass
            try:
                # ring occupancy + stall attribution for the mgr's
                # prometheus exposition (ceph_tpu_stage_* series)
                status["dispatch"] = \
                    self.tpu_dispatcher.dispatch_status()
            except Exception:
                pass
        tier = getattr(self, "hbm_tier", None)
        if tier is not None:
            try:
                status["hbm"] = tier.stats()
            except Exception:
                pass
        try:
            from ..parallel import rateless
            disp = rateless.get_dispatcher(create=False)
            if disp is not None:
                status["mesh"] = disp.status()
        except Exception:
            pass
        try:
            if isinstance(self.op_wq, QosShardedOpWQ):
                status["op_queue"] = self.op_wq.dump()
        except Exception:
            pass
        try:
            # map-churn lane (ISSUE 19): the mgr's prometheus module
            # emits ceph_osdmap_epoch{ceph_daemon}, ceph_osd_map_lag_
            # epochs and the ceph_pg_peering_seconds p99 from this bag
            status["osdmap"] = {
                "epoch": self.osdmap.epoch,
                "lag_epochs": self.mon_client.map_lag_epochs(),
                "peering_p99": self.peering_p99(),
            }
        except Exception:
            pass
        return status

    # -- map-churn observability (ISSUE 19) ---------------------------

    def note_peering_done(self, seconds: float) -> None:
        """One interval's peering completed (start_peering ->
        activate): feed the histogram + the p99 ring."""
        try:
            self.perf.hinc("l_osd_peering_us", int(seconds * 1e6))
        except Exception:
            pass
        self._peering_durations.append(seconds)

    def peering_p99(self) -> float:
        """p99 of the last completed interval peerings (seconds)."""
        samples = sorted(self._peering_durations)
        if not samples:
            return 0.0
        return samples[min(len(samples) - 1,
                           int(0.99 * len(samples)))]

    def _osdmap_status(self) -> dict:
        """The `osdmap status` asok payload: applied epoch vs the
        mon's newest, inc-backlog depth behind the advance throttle,
        and the peering lane's occupancy."""
        mc = self.mon_client
        with mc._advance_lock:
            backlog = len(mc._inc_backlog)
        res = self.reservations["peering"].dump()
        return {
            "epoch": self.osdmap.epoch,
            "mon_epoch": mc.mon_epoch,
            "lag_epochs": mc.map_lag_epochs(),
            "inc_backlog": backlog,
            "map_max_advance": mc.map_max_advance,
            "peering_gate": self.peering_gate,
            "peering_active": len(res.get("granted", [])),
            "peering_waiting": len(res.get("waiting", [])),
            "peering_p99": self.peering_p99(),
        }

    # -- fullness ladder ----------------------------------------------

    def used_ratio(self) -> float:
        """Store occupancy fraction from statfs, cached ~0.5s — the
        full/backfillfull gates sit on the client-op and reservation
        hot paths and must not statfs per op."""
        now = time.monotonic()
        ratio, stamp = self._used_stat_cache
        if now - stamp < 0.5:
            return ratio
        try:
            st = self.store.statfs()
            total = st.get("total", 0)
            ratio = (st.get("used", 0) / total) if total else 0.0
        except Exception:
            ratio = 0.0
        self._used_stat_cache = (ratio, now)
        return ratio

    def is_nearfull(self) -> bool:
        return self.used_ratio() >= self._full_ratios[0]

    def is_backfillfull(self) -> bool:
        return self.used_ratio() >= self._full_ratios[1]

    def is_full(self) -> bool:
        return self.used_ratio() >= self._full_ratios[2]

    def reserve_refusal(self, lane: str) -> str | None:
        """Fullness veto on incoming remote-reservation requests: a
        backfillfull OSD refuses new backfill (the primary parks in
        backfill_toofull), and recovery into a FULL osd pauses until
        it drains.  None = no objection."""
        if lane == "backfill" and self.is_backfillfull():
            return "toofull"
        if lane == "recovery" and self.is_full():
            return "toofull"
        return None

    def _sync_reservation_perf(self) -> None:
        granted = waiting = preempted = 0
        for r in self.reservations.values():
            granted += r.granted_total
            waiting += r.num_waiting()
            preempted += r.preempted_total
        self.perf.set("l_osd_reservation_granted", granted)
        self.perf.set("l_osd_reservation_waiting", waiting)
        self.perf.set("l_osd_reservation_preempted", preempted)
        try:
            self.perf.set("l_osd_map_lag_epochs",
                          self.mon_client.map_lag_epochs())
        except Exception:
            pass

    def _collect_pg_stats(self) -> dict:
        """Primary PGs' stat rows (shared by the mon MPGStats report
        and the mgr telemetry report)."""
        with self.lock:
            pgs = [pg for pg in self.pgs.values() if pg.is_primary()]
        stats = {}
        for pg in pgs:
            try:
                stats[str(pg.pgid)] = pg.get_stats()
            except Exception:
                continue
        return stats

    def _report_pg_stats(self) -> None:
        """Primary PGs' stats to the mon (MPGStats).  Rate-limited to
        1s and skipped entirely while nothing changed cheaply-visibly
        would be nicer, but at framework scale the report is a few
        dict copies; the mon dedups derived-state churn itself."""
        now = time.monotonic()
        if now - getattr(self, "_last_pg_report", 0.0) < 1.0:
            return
        self._last_pg_report = now
        stats = self._collect_pg_stats()
        # slow-request count rides the same report (OSD_SLOW_OPS feed);
        # it must go out even with no primary-PG stats so a wedged op
        # on a just-demoted primary still surfaces
        slow = self.op_tracker.slow_ops_count()
        # device-runtime health feeds ride the same report: in-window
        # recompile count (DEVICE_RECOMPILE_STORM) and HBM tier
        # occupancy (DEVICE_MEM_NEARFULL)
        recompiles = 0
        from ..common.profiler import PROFILER
        if PROFILER.enabled:
            try:
                recompiles = PROFILER.storm_count()
            except Exception:
                pass
        nearfull = 0.0
        tier = getattr(self, "hbm_tier", None)
        if tier is not None:
            try:
                occ = tier.occupancy()
                if occ >= self.ctx.conf.get_val(
                        "osd_hbm_nearfull_ratio"):
                    nearfull = occ
            except Exception:
                pass
        # store occupancy rides every report too: the HealthMonitor
        # ranks it against the mon_osd_*_ratio ladder (OSD_NEARFULL /
        # OSD_BACKFILLFULL / OSD_FULL) — an over-threshold ratio keeps
        # reports flowing via the alert latch so the check can CLEAR
        used = self.used_ratio()
        # blacklisted mesh devices ride the report too (DEVICE_DEGRADED);
        # the alert latch keeps reports flowing after probation re-admits
        # the chip so the mon sees the zero and clears the check
        degraded = 0
        try:
            from ..parallel import rateless
            disp = rateless.get_dispatcher(create=False)
            if disp is not None:
                degraded = disp.degraded()
        except Exception:
            pass
        self._sync_reservation_perf()
        alerting = slow or recompiles or nearfull or degraded \
            or used >= self._full_ratios[0]
        if not stats and not alerting \
                and not getattr(self, "_alert_reported", False):
            return
        self._alert_reported = bool(alerting)
        from ..msg.message import MPGStats
        self._send_mon(MPGStats(osd_id=self.whoami, pg_stats=stats,
                                epoch=self.map_epoch(), slow_ops=slow,
                                recompiles=recompiles,
                                mem_nearfull=nearfull,
                                used_ratio=used,
                                devices_degraded=degraded))

    # -- dispatch ------------------------------------------------------

    def ms_dispatch(self, msg) -> bool:
        t = msg.get_type()
        if t == "MPing":
            self.hb_msgr.send_message(
                MPingReply(stamp=msg.stamp, epoch=self.map_epoch()),
                msg.from_addr)
            return True
        if t == "MPingReply":
            osd = msg.from_name[1] if msg.from_name else None
            if osd is not None:
                self.hb_peers[osd] = msg.stamp
                self.hb_pending.pop(osd, None)
            return True
        if t == "MOSDOp":
            self._enqueue_client_op(msg)
            return True
        if t == "MOSDPerfQuery":
            self._handle_perf_query(msg)
            return True
        if t == "MMgrReportAck":
            self._mgr_reporter.ack(msg.ack_seq, resync=msg.resync)
            return True
        if t == "MTraceFragment":
            self._handle_trace_verdict(msg)
            return True
        if t in ("MOSDECSubOpWrite", "MOSDECSubOpWriteReply",
                 "MOSDECSubOpRead", "MOSDECSubOpReadReply",
                 "MOSDECSubOpRepairRead", "MOSDECSubOpRepairReadReply",
                 "MOSDRepOp", "MOSDRepOpReply", "MOSDPGScan",
                 "MOSDPGPush", "MOSDPGPull", "MOSDPGQuery",
                 "MOSDPGNotify", "MOSDPGLog", "MWatchNotifyAck",
                 "MBackfillReserve"):
            self._enqueue_sub_op(msg)
            return True
        return False

    def _handle_perf_query(self, msg) -> None:
        """mgr -> OSD perf-query subscription control
        (MOSDPerfQuery add/remove/list)."""
        from ..msg.message import MOSDPerfQueryReply
        result = 0
        if msg.op == "add":
            self.perf_query.add_query(msg.query_id, msg.spec)
        elif msg.op == "remove":
            if not self.perf_query.remove_query(msg.query_id):
                result = -2            # ENOENT
        queries = (self.perf_query.list_queries()
                   if msg.op == "list" else {})
        if msg.from_addr is not None:
            self.public_msgr.send_message(
                MOSDPerfQueryReply(query_id=msg.query_id,
                                   result=result, queries=queries),
                msg.from_addr)

    # -- tail-based trace retention (SLO forensics) --------------------

    def _trace_tail_verdict(self, pg, span, op, result,
                            op_type: str) -> tuple[bool, str]:
        """Root-side keep/drop for a completed client op's trace.
        Returns (kept, reason).  On keep: this daemon's buffered
        fragments ship to the mgr and the verdict broadcasts to the
        acting set so replicas release theirs.  Spans still open at
        reply time (a synchronous read's pg_do_op) miss the shipment —
        the same snapshot boundary the flight recorder has."""
        tail = self.tail
        spans = tail.take(span.trace_id) or []
        pool_name = ""
        if pg is not None:
            pool = self.osdmap.pools.get(pg.pgid.pool)
            if pool is not None:
                pool_name = pool.name
        duration = op.duration
        kept, reason = tail.verdict(pool_name, duration, result, spans)
        self._sync_tail_perf()
        if not kept:
            self.perf.inc("l_osd_trace_tail_dropped")
            return False, ""
        self.perf.inc("l_osd_trace_tail_kept_" + reason)
        # slo/error keeps are forensic: pull the replicas' fragments
        # for a full cross-daemon tree.  Reservoir keeps are the
        # baseline latency population — the root's own tree suffices,
        # and skipping the broadcast keeps the steady-state sampling
        # cost at one shipment per kept op (replica fragments TTL out)
        if pg is not None and reason != "reservoir":
            from ..msg.message import MTraceFragment
            for peer in getattr(pg, "acting", ()):
                if peer == self.whoami:
                    continue
                self._trace_ship_enqueue("osd", peer, MTraceFragment(
                    op="verdict", trace_id=span.trace_id,
                    daemon_name="osd.%d" % self.whoami,
                    pool=pool_name, op_type=op_type, keep=True,
                    reason=reason, duration=duration))
        self._ship_trace_fragments(span.trace_id, spans, pool_name,
                                   op_type, duration, reason)
        return True, reason

    def _ship_trace_fragments(self, trace_id: int, spans: list,
                              pool: str, op_type: str, duration: float,
                              reason: str) -> None:
        """OSD -> mgr: one MTraceFragment with this daemon's span
        dumps for a kept trace, anchored so the mgr can place the
        sender's monotonic stamps on a shared wall axis.  The anchor
        pair is stamped HERE (one instant) — the ship lane may send
        it later, which cannot skew the alignment."""
        if not spans:
            return
        from ..msg.message import MTraceFragment
        self.perf.inc("l_osd_trace_tail_shipped_spans", len(spans))
        # bulk diagnostic payload: pack the span records into ONE
        # opaque blob so the wire codec prices a single bytes value,
        # not hundreds of tagged ones (json round-trips the compact
        # dump_wire lists; exotic keyval types fall back to raw)
        try:
            import json as _json
            spans = _json.dumps(spans,
                                separators=(",", ":")).encode()
        except (TypeError, ValueError):
            pass
        self._trace_ship_enqueue("mgr", None, MTraceFragment(
            op="ship", trace_id=trace_id,
            daemon_name="osd.%d" % self.whoami,
            pool=pool, op_type=op_type, keep=True,
            reason=reason, duration=duration, spans=spans,
            anchor_wall=time.time(),
            anchor_mono=time.monotonic()))

    def _trace_ship_enqueue(self, kind: str, target, msg) -> None:
        with self._trace_ship_cond:
            self._trace_ship_q.append((kind, target, msg))
            self._trace_ship_cond.notify()

    def _trace_ship_loop(self) -> None:
        while True:
            with self._trace_ship_cond:
                while not self._trace_ship_q and \
                        not self._trace_ship_stop:
                    self._trace_ship_cond.wait(0.5)
                if self._trace_ship_stop and not self._trace_ship_q:
                    return
                batch = list(self._trace_ship_q)
                self._trace_ship_q.clear()
            for kind, target, msg in batch:
                try:
                    if kind == "osd":
                        self.send_to_osd_cluster(target, msg)
                    elif self.mgr_addr is not None:
                        self.public_msgr.send_message(msg,
                                                      self.mgr_addr)
                except Exception:
                    pass       # a lost fragment is a lost fragment

    def _handle_trace_verdict(self, msg) -> None:
        """Replica side: the root's keep verdict arrived — ship the
        fragments buffered under that trace_id (drop verdicts are
        never sent; the pending TTL reaps those fragments)."""
        spans = self.tail.take(msg.trace_id)
        if msg.keep and spans:
            self._ship_trace_fragments(msg.trace_id, spans, msg.pool,
                                       msg.op_type, msg.duration,
                                       msg.reason)
        self._sync_tail_perf()

    def _sync_tail_perf(self) -> None:
        """Fold the TailSampler's TTL-reap count into the perf stream
        (the sampler itself has no perf handle)."""
        expired = self.tail.stats["pending_expired"]
        delta = expired - self._tail_expired_synced
        if delta > 0:
            self._tail_expired_synced = expired
            self.perf.inc("l_osd_trace_tail_expired", delta)

    WRITE_OP_KINDS = frozenset((
        "create", "write", "writefull", "append", "zero", "truncate",
        "remove", "setxattr", "rmxattr", "omap_set", "omap_rm",
        "omap_clear", "resetxattrs", "watch", "unwatch", "notify",
        "rollback", "call"))

    #: mutating ops still admitted on a FULL osd: they free space (or
    #: add none), and rejecting them would wedge a full cluster full
    #: forever (the reference admits deletes on a full pool the same
    #: way)
    FULL_EXEMPT_OP_KINDS = frozenset((
        "remove", "rmxattr", "omap_rm", "omap_clear", "truncate",
        "zero", "unwatch"))

    def _check_op_caps(self, msg) -> str | None:
        """OSDCap enforcement (src/osd/OSDCap.cc is_capable, called
        from PrimaryLogPG::do_op's cap check): the connection's
        verified ticket caps must cover the op's rwx needs on the
        target pool, and the ticket's key version must clear the
        authmap revocation watermark.  Returns a denial reason, or
        None when allowed (always None on auth-less clusters)."""
        if self.auth is None or msg.pgid is None:
            return None               # pgid-less op: EAGAIN path below
        info = getattr(msg, "auth_info", None)
        if not info:
            return "unauthenticated connection"
        authmap = self.mon_client.authmap or {}
        floor = authmap.get("revoked", {}).get(info["entity"], 0)
        if info.get("key_version", 1) < floor:
            return "key revoked for %s" % info["entity"]
        caps = info.get("_parsed_caps")
        if caps is None:
            from ..auth.caps import parse_caps
            try:
                caps = parse_caps(info.get("caps") or "")
            except Exception:
                return "malformed caps"
            info["_parsed_caps"] = caps   # per-connection cache
        pgid = self._normalize_pgid(msg.pgid)
        pool = self.osdmap.pools.get(pgid.pool)
        pool_name = pool.name if pool is not None else None
        from ..msg.message import OSD_READ_OPS
        need = set()
        for op in msg.ops:
            if not op:
                continue
            if op[0] == "call":
                need.add("x")
            elif op[0] in OSD_READ_OPS:
                need.add("r")
            else:
                # fail CLOSED: every mutating op kind — and any kind
                # this table has never heard of — demands 'w'.  The
                # old shape defaulted unknown kinds to 'r', so a new
                # op added to the PG without a matching entry here
                # (omap_clear once) silently bypassed write caps.
                need.add("w")
        if not caps.is_capable("".join(sorted(need)), pool_name):
            return "caps %r do not cover %s on pool %r" % (
                info.get("caps", ""), "".join(sorted(need)), pool_name)
        return None

    def _enqueue_client_op(self, msg) -> None:
        # an op stamped with a newer map than ours waits for that map
        # (the reference's require_same_or_newer_map): a write sent
        # right after a pool snapshot, or a read retargeted to a new
        # primary, must not run against the map before the change
        with self.lock:
            if getattr(msg, "map_epoch", 0) > self.osdmap.epoch:
                self._waiting_for_map.append(msg)
                return
        denial = self._check_op_caps(msg)
        if denial is not None:
            import errno as _errno
            self.public_msgr.send_message(
                MOSDOpReply(tid=msg.tid, result=-_errno.EACCES,
                            data=denial.encode(),
                            map_epoch=self.map_epoch()),
                msg.from_addr)
            return
        pg = self._get_pg(msg.pgid and self._normalize_pgid(msg.pgid))
        client_addr = msg.from_addr
        # retransmit dedup for non-idempotent ops (the client resends
        # with the SAME tid on slow replies): an op still executing is
        # dropped (the eventual reply satisfies the client); a finished
        # one replays its recorded reply (PG log reqid dedup role)
        mutating = any(op and op[0] in self.WRITE_OP_KINDS
                       for op in msg.ops)
        # full-ratio protection: a FULL osd rejects writes at admission
        # with ENOSPC — reads keep flowing (the data is still there)
        # and space-freeing ops stay admitted so the operator can dig
        # the cluster out
        if mutating and self.is_full() and \
                any(op and op[0] in self.WRITE_OP_KINDS
                    and op[0] not in self.FULL_EXEMPT_OP_KINDS
                    for op in msg.ops):
            import errno as _errno
            self.public_msgr.send_message(
                MOSDOpReply(tid=msg.tid, result=-_errno.ENOSPC,
                            data=b"osd full",
                            map_epoch=self.map_epoch()),
                client_addr)
            return
        dedup_key = ((getattr(msg, "session", "") or msg.client_id,
                      msg.tid) if mutating else None)
        if dedup_key is not None:
            with self.lock:
                cached = self._op_replies.get(dedup_key, False)
                if cached is False:
                    # atomically claim execution (a racing duplicate
                    # must not also execute)
                    self._op_replies[dedup_key] = None
            if cached is None:
                return                 # in flight: drop the duplicate
            if cached is not False:
                self.public_msgr.send_message(
                    MOSDOpReply(tid=msg.tid, result=cached[0],
                                data=cached[1],
                                map_epoch=self.map_epoch()),
                    client_addr)
                return
        op = self.op_tracker.create_request(
            "osd_op(tid=%s pg=%s %s)" % (msg.tid, msg.pgid,
                                         getattr(msg, "op", "?")))
        # perf-query latency anchor: attribution measures from the
        # op_request's initiation, not from whenever pg.do_op first
        # ran — queue wait is part of what the client experienced
        msg._pq_start = op.initiated_mono
        # stitch under the client's trace when the envelope carries a
        # context; a context-less op (old client, tracing off there)
        # still gets an OSD-rooted trace subject to local sampling.
        # The span starts at the messenger's receipt (OpRequest's
        # initiated stamp), its ms_recv child covering up to dispatch
        recv = getattr(msg, "recv_stamp", None) or None
        span = self.tracer.continue_trace(
            "osd_op", getattr(msg, "trace_id", 0),
            getattr(msg, "parent_span", 0), start=recv)
        if not span.valid():
            span = self.tracer.start_trace("osd_op", start=recv)
        if recv:
            span.child_interval("ms_recv", recv, msg.dispatch_stamp)
        span.keyval("tid", msg.tid)
        span.keyval("pg", str(msg.pgid))
        msg.trace = span   # receive-side annotation: the PG and the
        #                    backends hang their spans off it

        replied = [False]
        # dispatch-throttle hand-off: the messenger attached an
        # idempotent release closure and would put the units back right
        # after ms_dispatch returns — adopting moves the release to the
        # REPLY, so queued-but-unserved ops keep holding their budget
        # (that occupancy is exactly what backpressures the reader)
        throttle_release = getattr(msg, "throttle_release", None)

        self.perf.inc("op")
        # read/write split + real payload accounting: the op's byte
        # operands ARE the write payload (MOSDOp carries no top-level
        # data field — the old getattr(msg, "data") read always 0)
        in_bytes = sum(len(arg) for op_t in msg.ops for arg in op_t
                       if isinstance(arg, (bytes, bytearray)))
        self.perf.inc("op_w" if mutating else "op_r")
        self.perf.inc("op_in_bytes", in_bytes)

        def reply(result, data):
            if replied[0]:
                return
            replied[0] = True
            if throttle_release is not None:
                throttle_release()
            if dedup_key is not None:
                with self.lock:
                    if result == -11:
                        # EAGAIN is not an outcome: the client retries
                        # the same tid and it must execute next time
                        self._op_replies.pop(dedup_key, None)
                    else:
                        self._op_replies[dedup_key] = (result, data)
            if isinstance(data, (bytes, bytearray)):
                self.perf.inc("op_out_bytes", len(data))
            elif isinstance(data, list):
                self.perf.inc("op_out_bytes", sum(
                    len(d) for d in data
                    if isinstance(d, (bytes, bytearray))))
            self.perf.tinc("op_latency", op.duration)
            self.perf.tinc("l_osd_op_trace_total", op.duration)
            self.perf.hinc("l_osd_op_trace_us",
                           max(0, int(op.duration * 1e6)))
            op.mark_commit_sent()
            # dmclock phase stamp (set by the QoS shard at dequeue):
            # reservation-phase completions feed the client's rho
            self.public_msgr.send_message(
                MOSDOpReply(tid=msg.tid, result=result, data=data,
                            map_epoch=self.map_epoch(),
                            qos_phase=getattr(msg, "_qos_phase", "")),
                client_addr)
            span.keyval("result", result)
            span.finish()
            # tail-sampler verdict (SLO forensics): judge the finished
            # trace HERE, where latency and result are known — keep
            # ships this daemon's fragments to the mgr and the verdict
            # to the acting set; drop sends nothing anywhere (replica
            # TTLs reap the unjudged fragments)
            kept, reason = False, ""
            if span.valid():
                try:
                    kept, reason = self._trace_tail_verdict(
                        pg, span, op, result,
                        "write" if mutating else "read")
                except Exception:
                    pass
            # flight recorder: snapshot the finished trace tree onto
            # the op BEFORE mark_done files it into history — the
            # historic dump keeps the cross-daemon tree even after the
            # live span ring rolls over
            if span.valid():
                try:
                    op.set_trace(span.trace_id,
                                 self.tracer.dump(
                                     trace_id=span.trace_id),
                                 kept=kept, reason=reason)
                except Exception:
                    pass
            op.mark_done()

        if pg is None:
            op.mark_event("no_pg")
            reply(-11, None)
            return
        op.mark_event("queued_for_pg")
        q0 = time.monotonic()

        def run(m, r):
            t_run = time.monotonic()
            self.perf.tinc("l_osd_op_trace_queue", t_run - q0)
            span.child_interval("op_queue", q0, t_run)
            op.mark_event("reached_pg")
            op.mark_started()
            # planning only: the backend ends the stage where it takes
            # the op (its own spans follow as siblings)
            pg_span = span.stage("pg_do_op")
            try:
                pg.do_op(m, r)
            except Exception:
                # never leak the op as in-flight-forever or leave the
                # client hanging: fail it with EIO
                op.mark_event("exception")
                reply(-5, None)
                raise
            finally:
                pg_span.finish()
                self.perf.tinc("l_osd_op_trace_pg",
                               time.monotonic() - t_run)

        if throttle_release is not None:
            msg._throttle_adopted = True
        self.op_wq.queue(pg.pgid, run, msg, reply,
                         klass=self._qos_class_for(pg.pool),
                         priority=self.client_op_priority,
                         cost=in_bytes,
                         delta=getattr(msg, "qos_delta", 0.0),
                         rho=getattr(msg, "qos_rho", 0.0),
                         qos_obj=msg)

    def _normalize_pgid(self, raw_pgid):
        pool = self.osdmap.pools.get(raw_pgid.pool)
        if pool is None:
            return raw_pgid
        return pool.raw_pg_to_pg(raw_pgid)

    def _enqueue_sub_op(self, msg) -> None:
        pg = self._get_pg(msg.pgid)
        if pg is None:
            return
        t = msg.get_type()

        def run():
            backend = pg.backend
            if t == "MOSDECSubOpWrite":
                backend.handle_sub_write(msg)
            elif t == "MOSDECSubOpWriteReply":
                backend.handle_sub_write_reply(msg)
            elif t == "MOSDECSubOpRead":
                backend.handle_sub_read(msg)
            elif t == "MOSDECSubOpReadReply":
                backend.handle_sub_read_reply(msg)
            elif t == "MOSDECSubOpRepairRead":
                backend.handle_repair_read(msg)
            elif t == "MOSDECSubOpRepairReadReply":
                backend.handle_repair_read_reply(msg)
            elif t == "MOSDRepOp":
                backend.handle_rep_op(msg)
            elif t == "MOSDRepOpReply":
                backend.handle_rep_op_reply(msg)
            elif t == "MOSDPGScan":
                pg.handle_scan(msg)
            elif t == "MOSDPGPush":
                pg.handle_push(msg)
            elif t == "MOSDPGPull":
                pg.handle_pull(msg)
            elif t == "MOSDPGQuery":
                pg.handle_query(msg)
            elif t == "MOSDPGNotify":
                pg.handle_notify(msg)
            elif t == "MOSDPGLog":
                pg.handle_log(msg)
            elif t == "MWatchNotifyAck":
                pg.handle_notify_ack(msg)
            elif t == "MBackfillReserve":
                pg.handle_reserve(msg)

        # recovery data movement (push/pull/scan — and the regenerating
        # repair fraction reads, which only exist to rebuild a shard)
        # must ride the recovery class or QoS settings have no effect
        # on actual backfill traffic
        if t in ("MOSDPGPush", "MOSDPGScan", "MOSDPGPull",
                 "MOSDPGQuery", "MOSDPGNotify", "MOSDPGLog",
                 "MOSDECSubOpRepairRead", "MOSDECSubOpRepairReadReply",
                 "MBackfillReserve"):
            self.op_wq.queue(msg.pgid, run, klass="recovery",
                             priority=self.recovery_op_priority)
        else:
            self.op_wq.queue(msg.pgid, run, klass="osd_subop",
                             priority=self.client_op_priority)

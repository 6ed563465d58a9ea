"""Typed option schema.

Role of the reference's src/common/options.cc: every config option is a
schema entry with type, default, level, and description; daemons read
through a typed get. This module carries the subset the framework uses,
plus the machinery to declare more. Schema names follow the reference
(erasure_code_dir: options.cc:295, osd_erasure_code_plugins: :1714,
fault-injection options: :1250-3953).
"""

from __future__ import annotations

from dataclasses import dataclass, field

__all__ = ["Option", "SCHEMA", "add_option"]

LEVEL_BASIC = "basic"
LEVEL_ADVANCED = "advanced"
LEVEL_DEV = "dev"


@dataclass(frozen=True)
class Option:
    name: str
    type: type                  # str | int | float | bool
    default: object
    level: str = LEVEL_ADVANCED
    description: str = ""

    def cast(self, value):
        if self.type is bool and isinstance(value, str):
            if value.lower() in ("true", "1", "yes", "on"):
                return True
            if value.lower() in ("false", "0", "no", "off"):
                return False
            raise ValueError("invalid bool %r for %s" % (value, self.name))
        return self.type(value)


SCHEMA: dict[str, Option] = {}


def add_option(name, type_, default, level=LEVEL_ADVANCED, description=""):
    opt = Option(name, type_, default, level, description)
    SCHEMA[name] = opt
    return opt


def _declare_defaults():
    o = add_option
    # erasure code
    o("erasure_code_dir", str, "", LEVEL_ADVANCED,
      "directory for erasure-code plugins (dlopen path in the reference)")
    o("osd_erasure_code_plugins", str, "jerasure isa lrc shec jax_tpu",
      LEVEL_ADVANCED, "plugins preloaded at daemon start")
    o("ec_batch_max_stripes", int, 64, LEVEL_ADVANCED,
      "max stripes coalesced into one device encode call")
    o("ec_batch_linger_us", int, 200, LEVEL_ADVANCED,
      "how long the batching queue waits to fill a device batch")
    # logging
    o("log_to_stderr", bool, False, LEVEL_BASIC)
    o("log_max_recent", int, 500, LEVEL_ADVANCED,
      "size of the in-memory ring dumped on crash")
    o("debug_ec", int, 1, LEVEL_ADVANCED)
    o("debug_osd", int, 1, LEVEL_ADVANCED)
    o("debug_crush", int, 1, LEVEL_ADVANCED)
    o("debug_ms", int, 0, LEVEL_ADVANCED)
    o("debug_mon", int, 1, LEVEL_ADVANCED)
    # osd
    o("osd_pool_default_size", int, 3, LEVEL_BASIC)
    o("osd_pool_default_pg_num", int, 8, LEVEL_BASIC)
    o("osd_heartbeat_interval", float, 0.25, LEVEL_ADVANCED,
      "seconds between peer pings (scaled down for in-process clusters)")
    o("osd_heartbeat_grace", float, 1.0, LEVEL_ADVANCED,
      "seconds without a reply before reporting a peer failed")
    o("osd_max_write_size", int, 90 << 20, LEVEL_ADVANCED)
    o("osd_client_op_priority", int, 63, LEVEL_ADVANCED)
    o("osd_recovery_op_priority", int, 3, LEVEL_ADVANCED)
    o("osd_op_num_shards", int, 4, LEVEL_ADVANCED,
      "ShardedOpWQ shard count (src/osd/OSD.h:1623)")
    o("osd_op_queue", str, "wpq", LEVEL_ADVANCED,
      "op scheduling discipline: wpq | mclock_opclass | fifo")
    o("osd_op_queue_mclock_client_res", float, 0.0, LEVEL_ADVANCED,
      "dmclock reservation (ops/s) for client ops; 0 = none")
    o("osd_op_queue_mclock_client_wgt", float, 500.0, LEVEL_ADVANCED)
    o("osd_op_queue_mclock_client_lim", float, 0.0, LEVEL_ADVANCED,
      "dmclock limit (ops/s) for client ops; 0 = unlimited")
    o("osd_op_queue_mclock_recovery_res", float, 0.0, LEVEL_ADVANCED)
    o("osd_op_queue_mclock_recovery_wgt", float, 1.0, LEVEL_ADVANCED)
    o("osd_op_queue_mclock_recovery_lim", float, 0.0, LEVEL_ADVANCED)
    o("osd_op_queue_mclock_scrub_res", float, 0.0, LEVEL_ADVANCED)
    o("osd_op_queue_mclock_scrub_wgt", float, 1.0, LEVEL_ADVANCED)
    o("osd_op_queue_mclock_scrub_lim", float, 0.0, LEVEL_ADVANCED)
    o("osd_op_queue_mclock_snaptrim_res", float, 0.0, LEVEL_ADVANCED)
    o("osd_op_queue_mclock_snaptrim_wgt", float, 1.0, LEVEL_ADVANCED)
    o("osd_op_queue_mclock_snaptrim_lim", float, 0.0, LEVEL_ADVANCED)
    o("mds_beacon_interval", float, 0.25, LEVEL_ADVANCED,
      "seconds between MDS -> mon beacons (options.cc mds_beacon_interval, "
      "scaled for in-process clusters)")
    o("mds_beacon_grace", float, 1.5, LEVEL_ADVANCED,
      "seconds without a beacon before the mon fails an active MDS")
    o("osd_agent_interval", float, 0.25, LEVEL_ADVANCED,
      "seconds between tier-agent flush/evict passes "
      "(osd_agent_delay_time role, scaled for in-process clusters)")
    o("osd_tpu_coalesce", bool, True, LEVEL_ADVANCED,
      "batch concurrent EC device calls sharing a codec/decode matrix "
      "into one dispatch (osd/tpu_dispatch.py)")
    o("osd_tpu_coalesce_max_batch", int, 8, LEVEL_ADVANCED,
      "max ops fused into one device dispatch")
    o("osd_tpu_coalesce_max_delay_ms", float, 1.0, LEVEL_ADVANCED,
      "max milliseconds an op waits for batch-mates before dispatch")
    o("osd_device_index", int, -1, LEVEL_ADVANCED,
      "home device for this OSD's dispatcher/HBM tier pipeline "
      "(parallel/placement.py; ROADMAP direction D): an index into "
      "jax.local_devices() (modulo the device count); -1 = round-robin "
      "by osd id, so an 8-OSD MiniCluster on an 8-chip mesh lands one "
      "OSD per chip without per-daemon conf")
    o("osd_mesh_rateless", bool, True, LEVEL_ADVANCED,
      "route bulk mesh encode/decode/repair-combine jobs through the "
      "rateless micro-batch work queue (parallel/rateless.py; ROADMAP "
      "direction J): idle devices steal micro-batches so a slow chip "
      "takes fewer instead of gating the batch; off = the fixed-shard "
      "mesh paths")
    o("osd_mesh_microbatch_factor", int, 4, LEVEL_ADVANCED,
      "micro-batches per device a bulk mesh job over-decomposes into "
      "(queue length = factor * n_devices): higher = finer-grained "
      "stealing and smoother straggler degradation, at more dispatch "
      "overhead per job")
    o("osd_mesh_microbatch_timeout_ms", float, 0.0, LEVEL_ADVANCED,
      "fixed per-micro-batch deadline before speculative re-dispatch; "
      "0 (default) derives the deadline from the executing device's "
      "rolling latency EWMA (osd_mesh_* deadline multiplier, "
      "parallel/rateless.py)")
    o("osd_mesh_blacklist_strikes", int, 3, LEVEL_ADVANCED,
      "consecutive timeouts/errors that move a device from healthy to "
      "the blacklist (probation re-admits it after an exponential "
      "backoff with one canary micro-batch)")
    o("osd_mesh_probation_base_ms", float, 50.0, LEVEL_ADVANCED,
      "base blacklist backoff; doubles per blacklist episode up to a "
      "bounded max before the probation canary is attempted")
    o("osd_hbm_tier_enable", bool, True, LEVEL_ADVANCED,
      "retain EC encode results device-resident in the HbmChunkTier "
      "keyed by (pg, object): scrub-repair rebuilds and recovery "
      "reconstruction read the resident copy instead of re-crossing "
      "PCIe (osd/hbm_tier.py; ROADMAP direction A)")
    o("osd_hbm_tier_capacity", int, 64, LEVEL_ADVANCED,
      "objects the HBM chunk tier keeps resident; inserts beyond it "
      "evict LRU (an evicted object pays h2d again on its next "
      "repair/recovery, exactly like any cache)")
    o("osd_hbm_tier_serve_reads", bool, False, LEVEL_ADVANCED,
      "serve whole-object EC client reads from the resident copy "
      "(zero sub-reads, zero decode). Default off: residency masks "
      "store-level fault injection and removes the sub_read/ec_decode "
      "spans observability tooling keys on, so reads-from-HBM is an "
      "explicit opt-in (scrub/recovery residency hits ride "
      "osd_hbm_tier_enable alone)")
    # fused write transform (osd/fused_transform.py, ROADMAP
    # direction F): one jitted program per staged batch computes
    # shard crcs + chunk digests + compressibility probe +
    # bit-plane compression + EC encode — one h2d, one d2h
    o("osd_fused_transform", bool, True, LEVEL_ADVANCED,
      "route whole-object EC writes through the fused device "
      "transform (digest + probe + compress + encode in one jitted "
      "program). Off = the classic host-hash + separate-encode path")
    o("osd_fused_compression_mode", str, "none", LEVEL_ADVANCED,
      "inline device compression for fused writes: 'none' stores "
      "raw (digests + encode still fused); 'bitplane' lets the "
      "device decide compress-vs-store per object from the entropy "
      "probe and the required ratio")
    o("osd_fused_required_ratio", float, 0.875, LEVEL_ADVANCED,
      "stored/raw ratio the device compression must beat for a "
      "fused write to store the compressed stream (compressor "
      "required_ratio analog, decided on device)")
    o("osd_fused_probe_entropy_max", float, 7.0, LEVEL_ADVANCED,
      "byte-entropy (bits/byte) above which the fused probe "
      "declares the object incompressible and stores raw without "
      "attempting bit-plane compression")
    # repair-bandwidth-optimal recovery (models/msr.py +
    # osd/ec_backend.py, ROADMAP direction C)
    o("osd_ec_repair_enable", bool, True, LEVEL_ADVANCED,
      "rebuild single lost EC shards from beta-fraction helper reads "
      "when the pool's codec is a regenerating code (plugin=msr): "
      "each of d helpers computes and ships chunk/alpha bytes instead "
      "of the primary pulling k whole chunks. Off = always the "
      "classic full-survivor decode. The product-matrix MSR knobs are "
      "derived from k, not free parameters: sub-packetization "
      "alpha = k-1, repair degree d = 2(k-1) (needs m >= k-1 so d "
      "helpers exist beside the target), per-helper fraction "
      "beta = chunk/alpha — so a repair moves d*chunk/alpha = "
      "2*chunk bytes vs k*chunk for a decode")
    o("osd_op_history_size", int, 20, LEVEL_ADVANCED,
      "completed ops kept for dump_historic_ops")
    o("osd_op_history_duration", float, 600.0, LEVEL_ADVANCED,
      "seconds a completed op stays in history")
    o("osd_op_complaint_time", float, 30.0, LEVEL_ADVANCED,
      "age after which an in-flight op counts as a slow request")
    o("osd_op_history_slow_size", int, 20, LEVEL_ADVANCED,
      "N slowest completed ops retained by the flight recorder "
      "(osd_op_history_slow_op_size role: the `dump_historic_ops` "
      "slowest_ops ring, kept beside the most-recent ring)")
    # device-runtime profiler (common/profiler.py)
    o("osd_profiler", bool, True, LEVEL_ADVANCED,
      "device-runtime profiler: per-(kernel, shape-signature) "
      "jit compile/cache-hit accounting, device-memory ledger, "
      "recompile-storm detection. Off = one attribute check per "
      "wrapped call (the bench cluster row pins this False like "
      "osd_tracing for methodology constancy)")
    o("osd_profiler_recompile_window", float, 60.0, LEVEL_ADVANCED,
      "sliding window (seconds) for the recompile-storm detector")
    o("osd_profiler_recompile_threshold", int, 24, LEVEL_ADVANCED,
      "compiles of ONE kernel within the window that raise "
      "DEVICE_RECOMPILE_STORM (per-kernel, so legitimate warm-up "
      "compiles spread across kernels never trip it)")
    o("osd_hbm_nearfull_ratio", float, 0.85, LEVEL_ADVANCED,
      "HBM chunk-tier occupancy (resident/capacity) above which the "
      "OSD reports device-memory pressure and the monitor raises "
      "DEVICE_MEM_NEARFULL (mon_osd_nearfull_ratio analog for the "
      "device tier)")
    # tracing (TracepointProvider/blkin gating).  The legacy
    # `trace_enable` option (utils.trace gate) is retired: the op-path
    # SpanCollector rides osd_tracing and the tail sampler below.
    o("osd_tracing", bool, True, LEVEL_ADVANCED,
      "collect ZTracer-style op spans end to end (client -> messenger "
      "-> op queue -> PG -> per-shard sub-ops -> store -> TPU device); "
      "default on at framework scale, false = the zero-allocation "
      "NULL_SPAN fast path")
    o("osd_tracing_sample", int, 1, LEVEL_ADVANCED,
      "trace 1 in N root ops (hot-path sampling knob; 1 = every op)")
    o("osd_tracing_max_spans", int, 8192, LEVEL_ADVANCED,
      "per-daemon bounded span ring capacity (oldest spans drop)")
    # tail-based trace retention (SLO forensics): the keep/drop call
    # happens at op COMPLETION on the root daemon, so slow and errored
    # ops are always kept and dropped traces cost zero wire bytes
    o("osd_trace_tail_sample_rate", float, 0.0, LEVEL_ADVANCED,
      "per-pool reservoir probability that a FAST, clean op's trace is "
      "still shipped to the mgr trace store (the baseline population "
      "behind the always-kept SLO-slow and errored traces); 0 ships "
      "only slow/errored traces, 1 ships everything")
    o("osd_trace_pending_ttl", float, 5.0, LEVEL_ADVANCED,
      "seconds a replica holds a trace's span fragments waiting for "
      "the root daemon's keep/drop verdict; expired fragments drop "
      "silently (the root died or dropped the trace)")
    o("mgr_trace_store_bytes", int, 4 << 20, LEVEL_ADVANCED,
      "byte budget for the mgr trace store (stitched cross-daemon "
      "trees); over budget the coldest/fastest traces evict first, "
      "slowest-N and errored traces last")
    o("mgr_trace_protect_slowest", int, 16, LEVEL_ADVANCED,
      "per-pool slowest-N traces protected from trace-store eviction "
      "(the flight-recorder slowest_ops discipline, cluster-wide)")
    # per-principal perf queries (osd/perf_query.py + mgr/perf_query.py)
    o("osd_perf_query_max_keys", int, 256, LEVEL_ADVANCED,
      "bound on distinct keys one OSD-side perf query accumulates; "
      "beyond it the least-recently-updated key is evicted, so a "
      "million clients cannot grow OSD memory past the table "
      "(osd_perf_query top-K table role)")
    o("osd_perf_query_key_age", float, 30.0, LEVEL_ADVANCED,
      "seconds a perf-query key may sit idle before the OSD drops it "
      "(a disconnected client's key stops riding MMgrReport)")
    o("mgr_perf_query_client_age", float, 10.0, LEVEL_ADVANCED,
      "seconds without fresh samples before a client/pool key ages "
      "out of the mgr's merged iotop views and the prometheus page")
    o("mgr_perf_query_prom_top_n", int, 10, LEVEL_ADVANCED,
      "labeled per-client series exported to prometheus: only the "
      "top-N keys by op rate get ceph_client_* series, so exposition "
      "cardinality stays capped by construction")
    o("mgr_slo_pool_targets", str, "", LEVEL_ADVANCED,
      "per-pool latency SLOs as 'pool:latency_ms:objective' entries "
      "separated by commas (e.g. 'rbd:50:0.99,cold:200:0.95'): ops "
      "slower than latency_ms count as violations; when the rolling "
      "violation fraction exceeds 1-objective the burn ratio passes "
      "1.0 and POOL_SLO_VIOLATION raises")
    o("mgr_slo_window", float, 10.0, LEVEL_ADVANCED,
      "rolling window (seconds) over which the per-pool SLO "
      "violation fraction is computed")
    # adaptive QoS: mgr bumps a burning pool's dmclock reservation
    o("mgr_qos_adaptive", bool, False, LEVEL_ADVANCED,
      "when a pool's SLO burn ratio exceeds 1.0, post 'osd pool set "
      "<pool> qos_reservation' raising its dmclock reservation so the "
      "op queues shift capacity toward the burning pool")
    o("mgr_qos_adapt_min_res", float, 50.0, LEVEL_ADVANCED,
      "floor (ops/s) for an adaptively-granted pool reservation")
    o("mgr_qos_adapt_factor", float, 1.5, LEVEL_ADVANCED,
      "multiplicative bump applied to the current reservation each "
      "time the pool is still burning after the cooldown")
    o("mgr_qos_adapt_max_res", float, 10000.0, LEVEL_ADVANCED,
      "ceiling (ops/s) on adaptive reservations, so a miscalibrated "
      "SLO cannot starve every other class")
    o("mgr_qos_adapt_cooldown", float, 5.0, LEVEL_ADVANCED,
      "seconds between adaptive reservation bumps for one pool (the "
      "previous bump must propagate via osdmap before re-judging)")
    # mgr telemetry (the MMgrReport stream + the mgr-side aggregation)
    o("mgr_stats_period", float, 0.5, LEVEL_BASIC,
      "seconds between a daemon's MMgrReport perf/telemetry reports "
      "to the mgr (options.cc mgr_stats_period, scaled for in-process "
      "clusters); 0 disables reporting entirely — the bench cluster "
      "row pins this like osd_tracing=False for methodology constancy")
    o("mgr_stats_stale_after", float, 10.0, LEVEL_ADVANCED,
      "seconds without a report before a daemon's series age out of "
      "the mgr's aggregation and the prometheus exposition "
      "(DaemonStateIndex staleness window)")
    o("mgr_metrics_history", int, 128, LEVEL_ADVANCED,
      "timestamped perf snapshots the MetricsAggregator retains per "
      "daemon (the rate/percentile derivation ring)")
    o("mgr_metrics_window", float, 5.0, LEVEL_ADVANCED,
      "default lookback window (seconds) for derived rates — "
      "`ceph iostat`, per-daemon op rates, device MB/s gauges")
    o("mgr_metrics_mem_budget", int, 64 << 20, LEVEL_ADVANCED,
      "hard byte budget for the mgr's whole telemetry store (raw "
      "rings + rollup tiers + status/pg/pq payloads, byte-accounted "
      "per daemon); exceeding a shard's slice squeezes then evicts "
      "the coldest series first")
    o("mgr_metrics_tiers", str, "5:24,60:30,600:18", LEVEL_ADVANCED,
      "downsampling rollup tiers as 'bucket_seconds:buckets_kept' "
      "pairs — each tier keeps per-counter min/max/sum/count and the "
      "last histogram fills so derived rates/percentiles read "
      "transparently past the raw ring")
    o("mgr_ingest_shards", int, 4, LEVEL_ADVANCED,
      "ingest worker shards MMgrReport handling is hashed onto by "
      "daemon name (lock per shard, batched fold); 0 folds reports "
      "inline on the dispatch thread (the legacy single-threaded "
      "path)")
    o("mgr_ingest_lag_warn", float, 2.0, LEVEL_ADVANCED,
      "seconds of ingest lag p99 (report enqueue -> folded) above "
      "which the mgr raises MGR_INGEST_LAG")
    o("mgr_metrics_budget_full_ratio", float, 0.95, LEVEL_ADVANCED,
      "tracked-bytes / mem-budget occupancy at or above which the "
      "mgr raises MGR_MEM_BUDGET_FULL (eviction pressure is actively "
      "squeezing fresh series)")
    o("mgr_prom_series_cap", int, 2000, LEVEL_ADVANCED,
      "per-metric sample cap on the prometheus exposition: excess "
      "labeled series fold into one {overflow=\"true\"} bucket and "
      "count into ceph_mgr_series_dropped_total")
    o("mgr_progress", bool, True, LEVEL_BASIC,
      "mgr progress module: narrate recovery/backfill convergence as "
      "progress events ('Rebalancing after osd.N marked out') with a "
      "monotone completion fraction and ETA; False pins the module "
      "off (the bench cluster row pins this beside osd_tracing for "
      "methodology constancy)")
    o("mgr_progress_max_completed", int, 32, LEVEL_ADVANCED,
      "completed progress events retained in the bounded ring "
      "(progress module mgr_progress history window)")
    # mon
    o("mon_osd_down_out_interval", float, 2.0, LEVEL_ADVANCED,
      "seconds after down before an osd is marked out")
    o("mon_osd_min_down_reporters", int, 1, LEVEL_ADVANCED)
    # incremental-osdmap pipeline (ISSUE 19: map churn at scale)
    o("mon_min_osdmap_epochs", int, 500, LEVEL_ADVANCED,
      "committed osdmap incrementals each mon retains in its epoch->"
      "inc ring; a subscriber whose epoch falls behind the ring's "
      "trim floor gets exactly ONE full map instead of an inc chain "
      "(the OSDMonitor full/inc trim policy)")
    o("osd_map_message_max", int, 40, LEVEL_ADVANCED,
      "max incrementals batched into one MOSDMap frame; a rejoining "
      "subscriber catches up in ceil(behind/this) bounded messages, "
      "re-subscribing at its new epoch after each frame")
    o("osd_map_max_advance", int, 150, LEVEL_ADVANCED,
      "max osdmap epochs a daemon applies per advance slice; further "
      "incrementals queue in the MonClient backlog and drain on the "
      "next tick, so a long catch-up cannot stall op dispatch or "
      "re-peer every PG in one stop-the-world step")
    o("osd_peering_max_active", int, 64, LEVEL_ADVANCED,
      "peering slots per OSD (AsyncReserver lane beside recovery/"
      "backfill): a map-churn storm re-peers PGs in waves of this "
      "size instead of flooding the op queue; 0 disables the gate")
    o("paxos_propose_interval", float, 0.05, LEVEL_ADVANCED)
    o("ms_type", str, "simple", LEVEL_ADVANCED,
      "messenger transport: simple (thread-per-connection) | async "
      "(event-loop, the AsyncMessenger analog)")
    o("cephx_sign_messages", bool, True, LEVEL_ADVANCED,
      "HMAC-sign every post-auth frame with the connection's cephx "
      "session key; a bad signature resets the connection "
      "(CephxSessionHandler sign_message/check_message_signature)")
    # fault injection (dev-level, like options.cc:1250-3953)
    o("ms_inject_socket_failures", int, 0, LEVEL_DEV,
      "drop 1 in N messages at the messenger")
    o("ms_inject_delay_max", float, 0.0, LEVEL_DEV,
      "random extra delivery delay upper bound, seconds")
    o("objectstore_inject_read_err", bool, False, LEVEL_DEV,
      "make reads of marked objects return EIO")
    o("objectstore_inject_eio", int, 0, LEVEL_DEV,
      "object reads fail EIO for 1 in N objects (seeded hash "
      "selection; store/faults.py FaultSet)")
    o("objectstore_inject_bitrot", int, 0, LEVEL_DEV,
      "object reads return silently flipped bytes for 1 in N objects")
    o("objectstore_fault_seed", int, 0, LEVEL_DEV,
      "seed for the deterministic store fault selection")
    o("osd_inject_failure_on_write", float, 0.0, LEVEL_DEV,
      "probability a sub-write is dropped before commit")
    # scrub / repair
    o("osd_scrub_auto_repair", bool, True, LEVEL_ADVANCED,
      "scrub repairs inconsistencies it finds; False = detect only "
      "(errors persist as OSD_SCRUB_ERRORS until 'pg repair'). "
      "Default True keeps the historical always-repair behavior; the "
      "reference defaults false and repairs only on command.")
    # mon cluster log
    o("mon_log_max", int, 500, LEVEL_ADVANCED,
      "cluster log entries the LogMonitor keeps ('ceph log last' "
      "window; mon_cluster_log_* role)")
    o("mon_events_max", int, 500, LEVEL_ADVANCED,
      "structured cluster events the EventMonitor keeps ('ceph "
      "events last' / 'ceph events watch' window: health "
      "transitions, osdmap changes, progress open/close, thrash "
      "actions)")
    # bluestore / bluefs
    o("store_fsck_on_umount", bool, True, LEVEL_ADVANCED,
      "BlockStore.umount() cross-checks BlueFS extents, blob extents "
      "and the free list for overlap/leak and raises on errors — every "
      "store test doubles as an allocator check "
      "(bluestore_fsck_on_umount role; the reference defaults false)")
    o("bluefs_log_compact_threshold", int, 1 << 20, LEVEL_ADVANCED,
      "BlueFS journal extent size; when the log outgrows it the file "
      "table is compacted into a fresh extent "
      "(bluefs_log_compact_min_size role)")
    # filestore
    o("filestore_compression", str, "none", LEVEL_ADVANCED,
      "checkpoint blob compression: none|zlib|zstd|snappy|lz4")
    o("filestore_compression_required_ratio", float, 0.875,
      LEVEL_ADVANCED,
      "store compressed only if <= input * ratio "
      "(bluestore_compression_required_ratio analog)")
    # throttles
    o("objecter_inflight_ops", int, 1024, LEVEL_ADVANCED)
    o("osd_client_message_cap", int, 256, LEVEL_ADVANCED,
      "max undispatched+inflight client messages a public messenger "
      "admits before the reader stops pulling frames off the socket "
      "(dispatch-side Throttle -> TCP backpressure; "
      "Messenger::Policy throttler_messages role)")
    o("osd_client_message_size_cap", int, 256 << 20, LEVEL_ADVANCED,
      "max bytes of undispatched+inflight client message payload "
      "before the reader blocks (throttler_bytes role); 0 = unlimited")
    # recovery/backfill reservations (AsyncReserver slots)
    o("osd_max_backfills", int, 1, LEVEL_ADVANCED,
      "backfill reservations one OSD grants concurrently, local "
      "(primary) and remote (replica) sides each "
      "(options.cc osd_max_backfills)")
    o("osd_recovery_max_active", int, 3, LEVEL_ADVANCED,
      "log-based recovery reservations one OSD grants concurrently "
      "(osd_recovery_max_active role, counted in PGs not ops at "
      "framework scale)")
    o("osd_recovery_sleep", float, 0.0, LEVEL_ADVANCED,
      "baseline delay (seconds) injected before each recovery/backfill "
      "push through a BackoffThrottle: the effective sleep scales from "
      "this value toward 10x as concurrent pushes approach the "
      "reservation slot budget; 0 disables shaping")
    # cluster full-ratio ladder (mon-side thresholds against each
    # OSD's reported statfs utilization)
    o("mon_osd_nearfull_ratio", float, 0.85, LEVEL_ADVANCED,
      "store utilization above which an OSD raises OSD_NEARFULL "
      "(warning only)")
    o("mon_osd_backfillfull_ratio", float, 0.90, LEVEL_ADVANCED,
      "store utilization above which an OSD refuses NEW remote "
      "backfill reservations (PGs targeting it stall in "
      "backfill_toofull)")
    o("mon_osd_full_ratio", float, 0.95, LEVEL_ADVANCED,
      "store utilization above which the OSD rejects client writes "
      "with ENOSPC at admission (reads still served) and recovery "
      "into it pauses")


_declare_defaults()

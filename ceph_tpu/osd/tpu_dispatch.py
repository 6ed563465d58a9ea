"""Cross-op device-call coalescing + overlapped pipeline for the OSD's
EC hot path.

Role: the twin of the native bridge (native/src/tpu_bridge.cc) inside
the Python OSD. The reference's ECBackend enters the codec once per op
(src/osd/ECBackend.cc:1437 submit_transaction -> ECUtil::encode per
transaction); under concurrency each op would pay its own device
dispatch. Stripes are embarrassingly parallel, so concurrent ops that
share a generator (same pool/codec) or a decode matrix (same erasure
signature) CONCATENATE along the stripe axis and ride ONE device
program — N dispatches become ceil(N / max_batch).

The dispatcher is an overlapped depth-N pipeline (ROADMAP direction A:
the TPU historically spent >99% of streaming wall-clock waiting on the
host because every dispatch serialized h2d -> compute -> d2h):

    collector ──> [h2d stage] ──> [compute stage] ──> [d2h stage]
                 stage batch n+1    run batch n       drain batch n-1

Each stage runs on its own thread; the bounded queues between them ARE
the staging ring (at most `pipeline_depth` fused batches in flight per
stage). While batch n computes, batch n+1's host->device transfer is
already in progress and batch n-1's results are draining back — the
transfer wall hides behind compute, which is the whole point. Decode
dispatches additionally pre-stage their decode table (matrix inversion
+ bitmatrix upload) in the h2d stage, so a fresh erasure signature's
table cost overlaps the previous batch's compute instead of serializing
in front of its own.

The device input buffer staged by the h2d stage is dispatcher-private,
so for jax-backed codecs the compute stage donates it to the device
program (jax.jit donate_argnums) — HBM holds one buffer per stage
instead of two, and submitters' HOST arrays are never donated (no
use-after-donate is possible from the caller's side). Donation is
skipped when the dispatch adopts its results into the HbmChunkTier
(adoption needs the staged input alive after compute) and on backends
that cannot honor it.

Facades: submit_async()/encode_async()/decode_async() return futures;
encode()/decode() keep the original blocking surface, so the EC
pipeline's ordering guarantees are untouched — only the device traffic
is batched and overlapped. Errors propagate strictly per batch: a
failed stage fails ONLY that fused batch's submitters; batches behind
it keep flowing.

Knobs ride the options schema: osd_tpu_coalesce (default on),
osd_tpu_coalesce_max_batch, osd_tpu_coalesce_max_delay_ms. The ring
depth is a constructor argument (default 2, at least 2).
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque

import numpy as np

from ..common.perf_counters import PerfCountersBuilder
from ..common.profiler import PROFILER
from ..common.tracer import NULL_SPAN

__all__ = ["TpuDispatcher"]

# pipeline stages in flow order; the three device stages carry the
# bound-stage verdict, the collector carries the starvation verdict
_STAGES = ("collector", "h2d", "compute", "d2h")
_STATES = ("busy", "idle", "blocked")


class _Pending:
    """One submitter's slot in a fused dispatch — and the future the
    async API hands back (result()/done()/exception())."""

    __slots__ = ("batch", "event", "out", "error", "trace", "t_submit",
                 "resident", "t_done", "t_wake")

    def __init__(self, batch, trace=NULL_SPAN, resident=None):
        self.batch = batch
        self.event = threading.Event()
        self.out = None
        self.error = None
        self.trace = trace if trace is not None else NULL_SPAN
        self.t_submit = time.monotonic()
        self.resident = resident     # (tier, key, codec) adoption ask
        self.t_done = 0.0            # when its device legs ended
        self.t_wake = 0.0            # when the dispatcher set the event

    # -- future surface ------------------------------------------------

    def done(self) -> bool:
        return self.event.is_set()

    def exception(self):
        return self.error if self.event.is_set() else None

    def result(self, timeout: float = 120.0):
        if not self.event.wait(timeout=timeout):
            raise TimeoutError("tpu dispatcher wedged")
        if self.t_wake and self.trace.valid():
            if self.t_done:
                # the dispatcher's work after the device legs: result
                # slicing, HBM-tier adoption, accounting
                self.trace.child_interval("tpu_finish", self.t_done,
                                          self.t_wake)
            # from the event set to this thread running again
            self.trace.child_interval("tpu_resume", self.t_wake,
                                      time.monotonic())
            self.t_wake = 0.0
        if self.error is not None:
            raise self.error
        return self.out


def _wake(pend) -> None:
    """Hand a dispatch's outcome to its submitters: set each one's
    event, stamped for its tpu_resume span."""
    now = time.monotonic()
    for p in pend:
        p.t_wake = now
        p.event.set()


class _Dispatch:
    """One fused device program moving through the pipeline stages."""

    __slots__ = ("key", "fn", "pend", "kind", "prefetch", "stacked",
                 "dev", "out_dev", "t_take", "seg", "mem_bytes")

    def __init__(self, key, fn, pend, kind, prefetch=None):
        self.key = key
        self.fn = fn
        self.pend = pend
        self.kind = kind             # "enc" | "dec" | other
        self.prefetch = prefetch     # () -> None decode-table staging
        self.stacked = None          # host ndarray (kept for fallback)
        self.dev = None              # staged device input
        self.out_dev = None          # device output
        self.t_take = time.monotonic()
        self.seg = {}                # stage -> (t_start, t_end)
        self.mem_bytes = 0           # staged bytes on the mem ledger


class _JaxDevOps:
    """Explicit h2d / compute / d2h legs on a jax device. Each leg
    blocks — in its OWN pipeline thread, which is what lets leg X of
    batch n overlap leg Y of batch m.

    `device` is the dispatcher's home device (parallel/placement.py):
    h2d commits the staged buffer there explicitly, so N dispatchers
    pinned to N chips stage and compute concurrently instead of
    funnelling through jax's implicit default device. None keeps the
    historical un-pinned behavior."""

    def __init__(self, device=None):
        self.device = device

    def h2d(self, host):
        import jax
        if self.device is None:
            return jax.block_until_ready(jax.device_put(host))
        return jax.block_until_ready(jax.device_put(host, self.device))

    def run(self, fn, dev):
        import jax
        return jax.block_until_ready(fn(dev))

    def d2h(self, out):
        if isinstance(out, dict):
            # fused-transform output dict: ONE device_get drains parity
            # + digests + compressed payload together (the fused path's
            # single d2h)
            import jax
            return jax.device_get(out)
        return np.asarray(out)


class _StageProf:
    """Per-stage wall-clock state machine: every instant a stage thread
    is in exactly one of busy (doing its leg's work) / idle (waiting on
    its upstream ring) / blocked (waiting to push downstream).  enter()
    folds the elapsed interval into the outgoing state's bucket;
    snapshot() is non-destructive and folds the in-progress interval
    in, so attribution is exact even mid-long-op."""

    __slots__ = ("lock", "acc", "state", "since")

    def __init__(self):
        self.lock = threading.Lock()
        self.acc = {s: 0.0 for s in _STATES}
        self.state = "idle"
        self.since = time.monotonic()

    def enter(self, state: str, now: float | None = None) -> None:
        now = time.monotonic() if now is None else now
        with self.lock:
            self.acc[self.state] += max(0.0, now - self.since)
            self.state = state
            self.since = now

    def snapshot(self, now: float | None = None) -> dict:
        now = time.monotonic() if now is None else now
        with self.lock:
            acc = dict(self.acc)
            acc[self.state] += max(0.0, now - self.since)
        return acc

    def reset(self, now: float | None = None) -> None:
        now = time.monotonic() if now is None else now
        with self.lock:
            for s in self.acc:
                self.acc[s] = 0.0
            self.since = now


class _RingQueue(queue.Queue):
    """Bounded stage ring with an occupancy time-integral: each mutation
    advances integral(qsize dt), so integral/wall is the ring's average
    occupancy over the profile window — the queue-theory complement to
    the stage state machine (a persistently full staging ring + an idle
    compute stage reads 'h2d-bound' before anyone eyeballs thread
    stacks).  _put/_get run under queue.Queue's own mutex."""

    def __init__(self, maxsize: int):
        super().__init__(maxsize)
        self._occ_integral = 0.0
        self._occ_t_last = time.monotonic()

    def _advance_locked(self, now: float) -> None:
        self._occ_integral += len(self.queue) \
            * max(0.0, now - self._occ_t_last)
        self._occ_t_last = now

    def _put(self, item) -> None:
        self._advance_locked(time.monotonic())
        super()._put(item)

    def _get(self):
        self._advance_locked(time.monotonic())
        return super()._get()

    def occupancy_integral(self) -> float:
        with self.mutex:
            self._advance_locked(time.monotonic())
            return self._occ_integral

    def occupancy_reset(self) -> None:
        with self.mutex:
            self._occ_integral = 0.0
            self._occ_t_last = time.monotonic()


class TpuDispatcher:
    """Coalesces same-key codec calls into single device dispatches and
    overlaps consecutive dispatches' h2d / compute / d2h legs.

    Key = (codec identity, kind, per-stripe shape): ops whose batches
    stack along axis 0 into one well-formed [S_total, k, chunk] call.

    Observability: with a tracer whose collection is enabled, each
    submitter's span grows a queue-delay child plus a device span split
    into h2d / compute / d2h segments. The segments are the MEASURED
    stage intervals (monotonic stamps), so spans from consecutive
    dispatches visibly overlap. The l_tpu_* PerfCounters aggregate the
    same segments; stamping them costs no extra device sync.
    """

    def __init__(self, max_batch: int = 8, max_delay: float = 0.002,
                 tracer=None, pipeline_depth: int = 2, device=None):
        self.max_batch = max_batch
        self.max_delay = max_delay
        self.tracer = tracer
        self.device = device        # home device (None = implicit default)
        if int(pipeline_depth) < 2:
            raise ValueError("pipeline_depth must be at least 2, got %r"
                             % (pipeline_depth,))
        self.pipeline_depth = int(pipeline_depth)
        self.lock = threading.Lock()
        self.cv = threading.Condition(self.lock)
        self.queues: dict = {}     # key -> (fn, [_Pending])
        self.stats = {"ops": 0, "dispatches": 0, "coalesced": 0}
        # per-codec throughput ledger: label -> {enc/dec bytes + a
        # bounded (t, bytes) window for the rolling-MB/s gauges the
        # telemetry report exports with codec labels}
        self.codec_stats: dict = {}
        self._telemetry_window = 10.0
        # l_tpu_* counters: device-segment attribution (exported via
        # the daemon's PerfCountersCollection -> mgr -> prometheus)
        self.perf = (PerfCountersBuilder("osd_tpu")
                     .add_time_avg("l_tpu_h2d",
                                   "host->device transfer time")
                     .add_time_avg("l_tpu_compute",
                                   "device compute (block_until_ready)")
                     .add_time_avg("l_tpu_d2h",
                                   "device->host transfer time")
                     .add_time_avg("l_tpu_dispatch_queue",
                                   "op wait in the coalescing queue")
                     .add_u64_counter("l_tpu_ops", "codec ops submitted")
                     .add_u64_counter("l_tpu_dispatches",
                                      "device programs dispatched")
                     .add_u64_counter("l_tpu_coalesced",
                                      "ops that shared a dispatch")
                     .add_u64("l_tpu_queue_depth",
                              "ops waiting in the coalescing queues")
                     .add_u64_counter("l_tpu_enc_bytes",
                                      "bytes through device encode")
                     .add_u64_counter("l_tpu_dec_bytes",
                                      "bytes through device decode")
                     .add_u64_counter("l_tpu_donated",
                                      "dispatches whose staged input "
                                      "was donated to the program")
                     .add_u64_counter("l_tpu_fused_dispatches",
                                      "fused write-transform programs "
                                      "dispatched")
                     .add_u64_counter("l_tpu_fused_bytes_in",
                                      "raw bytes into the fused write "
                                      "transform")
                     .add_u64_counter("l_tpu_fused_bytes_out",
                                      "stored+parity bytes out of the "
                                      "fused transform")
                     .add_u64_counter("l_tpu_fused_compressed",
                                      "fused writes stored compressed")
                     .add_u64_counter("l_tpu_fused_probe_rejects",
                                      "fused writes whose entropy probe "
                                      "rejected compression")
                     .add_u64_avg("l_tpu_fused_ratio_milli",
                                  "stored/raw size ratio per fused "
                                  "write (x1000)"))
        # stall-attribution counters: cumulative per-stage wall time in
        # each state, synced from the _StageProf machines on telemetry
        # ticks so they ride MMgrReport -> mgr -> prometheus
        for stage in _STAGES:
            for state in _STATES:
                self.perf.add_time(
                    "l_tpu_stage_%s_%s" % (stage, state),
                    "%s stage wall seconds %s" % (stage, state))
        self.perf = self.perf.create_perf_counters()
        # rolling dispatch-wall EWMA (submit -> results landed): the
        # straggler-wait heuristic in _take_group scales its coalesce
        # window from THIS instead of always burning the full
        # max_delay, so the window tracks what a dispatch actually
        # costs on this device (ROADMAP direction J satellite)
        self._lat_ewma: float | None = None
        self._lat_alpha = 0.25
        # device leg implementations (tests substitute a fake here)
        self._devops = _JaxDevOps(self.device)
        self._donate_fns: dict = {}   # key -> jitted donating fn | False
        self._donate_ok = self._probe_donation()
        # fused write-transform ledger (dispatch_status "fused" section)
        self._fused_seq = 0
        self.fused_stats = {"dispatches": 0, "bytes_in": 0,
                            "bytes_out": 0, "compressed": 0,
                            "probe_rejects": 0, "ratio_milli_sum": 0}
        # stall attribution: one state machine per pipeline stage plus
        # the profile window anchor (profile_reset() restarts both)
        self._stage_prof = {s: _StageProf() for s in _STAGES}
        self._profile_t0 = time.monotonic()
        self._stop = False
        self._threads: list = []
        # the staging ring: bounded hand-off queues between stages.
        # depth bounds how many fused batches are in flight per stage;
        # the collector blocks when the ring is full.
        self._q_h2d: queue.Queue = _RingQueue(self.pipeline_depth)
        self._q_compute: queue.Queue = _RingQueue(self.pipeline_depth)
        self._q_d2h: queue.Queue = _RingQueue(self.pipeline_depth)
        for name, fn in (("tpu-h2d", self._h2d_loop),
                         ("tpu-compute", self._compute_loop),
                         ("tpu-d2h", self._d2h_loop)):
            t = threading.Thread(target=fn, name=name, daemon=True)
            t.start()
            self._threads.append(t)
        self._thread = threading.Thread(
            target=self._run, name="tpu-dispatch", daemon=True)
        self._thread.start()
        self._threads.append(self._thread)

    def _probe_donation(self) -> bool:
        """Donation is only honored on real accelerators; the CPU
        backend ignores it (with a warning per compile), so don't ask.
        The probe checks the PINNED device's platform — a mixed host
        could pin one OSD to an accelerator and another to cpu."""
        import jax
        dev = self.device if self.device is not None \
            else jax.devices()[0]
        return dev.platform != "cpu"

    # -- public API ----------------------------------------------------

    @staticmethod
    def _codec_key(codec):
        """Identity BY VALUE: every PG backend holds its own codec
        instance, so keying on id() would never coalesce across PGs.
        Codecs with the same generator bitmatrix (and layout params)
        compute the same function."""
        cached = getattr(codec, "_dispatch_key", None)
        if cached is not None:
            return cached
        bm = getattr(codec, "_bitmat", None)
        if bm is not None:
            # full digest, not hash(): a 64-bit hash collision between
            # two generators of the same shape would silently coalesce
            # different codecs into one dispatch and return wrong bytes
            import hashlib
            key = (type(codec).__name__, getattr(codec, "w", 0),
                   getattr(codec, "packetsize", 0), bm.shape,
                   hashlib.sha256(bm.tobytes()).digest())
        else:
            key = ("id", id(codec))
        try:
            codec._dispatch_key = key
        except AttributeError:
            pass
        return key

    @staticmethod
    def _codec_label(codec):
        """Stable human label for per-codec telemetry series
        (prometheus codec= label): class name + layout params."""
        cached = getattr(codec, "_dispatch_label", None)
        if cached is not None:
            return cached
        label = type(codec).__name__
        try:
            k = codec.get_data_chunk_count()
            m = codec.get_chunk_count() - k
            label = "%s_k%dm%d" % (label, k, m)
        except Exception:
            pass
        try:
            codec._dispatch_label = label
        except AttributeError:
            pass
        return label

    def _account_codec(self, codec, kind: str, nbytes: int) -> None:
        now = time.monotonic()
        with self.lock:
            row = self.codec_stats.setdefault(
                self._codec_label(codec),
                {"enc_bytes": 0, "dec_bytes": 0, "window": deque()})
            row[kind + "_bytes"] += nbytes
            w = row["window"]
            w.append((now, kind, nbytes))
            cutoff = now - self._telemetry_window
            while w and w[0][0] < cutoff:
                w.popleft()
        self.perf.inc("l_tpu_%s_bytes" % kind, nbytes)

    def encode_async(self, codec, batch: np.ndarray, trace=NULL_SPAN,
                     resident=None) -> _Pending:
        """Async codec.encode_batch(batch): returns a future whose
        result() is the parity array. resident=(tier, key) asks the
        pipeline to adopt the staged data + computed parity into the
        HbmChunkTier device-side (zero extra transfers)."""
        key = (self._codec_key(codec), "enc", batch.shape[1:],
               str(batch.dtype))
        self._account_codec(codec, "enc",
                            getattr(batch, "nbytes", 0))
        if resident is not None:
            resident = (resident[0], resident[1], codec)
        return self._submit_async(key, codec.encode_batch, batch, trace,
                                  kind="enc", resident=resident)

    def decode_async(self, codec, avail_rows: tuple,
                     chunks: np.ndarray, trace=NULL_SPAN) -> _Pending:
        """Async codec.decode_batch for one erasure signature; the
        decode table (inversion + device upload) is pre-staged in the
        pipeline's h2d stage so a fresh signature's table cost overlaps
        the previous dispatch's compute."""
        avail_rows = tuple(avail_rows)
        key = (self._codec_key(codec), "dec", avail_rows,
               chunks.shape[1:], str(chunks.dtype))
        self._account_codec(codec, "dec",
                            getattr(chunks, "nbytes", 0))
        prefetch = None
        entry_fn = getattr(codec, "_decode_entry", None)
        if entry_fn is not None:
            def prefetch(avail=avail_rows, entry_fn=entry_fn):
                self._stage_entry(entry_fn(avail))
        return self._submit_async(
            key, lambda stacked: codec.decode_batch(avail_rows, stacked),
            chunks, trace, kind="dec", prefetch=prefetch)

    def _stage_entry(self, entry: dict) -> None:
        """Stage a TableCache entry's bitmatrix onto this dispatcher's
        home device. The copy is keyed per HOME device: a second pinned
        dispatcher stages its own copy, never consuming (or clobbering)
        the first device's."""
        if not (isinstance(entry, dict) and "bitmat" in entry):
            return
        from ..models.table_cache import device_entry_key
        devkey = device_entry_key(self.device)
        if devkey not in entry:
            import jax
            import jax.numpy as jnp
            bm = jnp.asarray(entry["bitmat"])
            if self.device is not None:
                bm = jax.device_put(bm, self.device)
            entry.setdefault(devkey, bm)

    def repair_fraction_async(self, codec, target: int,
                              chunks: np.ndarray,
                              trace=NULL_SPAN) -> _Pending:
        """Async codec.repair_fraction_batch: the helper-side beta
        projection of [B, chunk] survivor streams into [B, chunk/alpha]
        repair fractions for rebuilding `target`. The [1, alpha]
        projection matrix is pre-staged like a decode table; repair
        work accounts as decode-direction codec traffic."""
        key = (self._codec_key(codec), "rfrac", target,
               chunks.shape[1:], str(chunks.dtype))
        self._account_codec(codec, "dec",
                            getattr(chunks, "nbytes", 0))
        prefetch = None
        entry_fn = getattr(codec, "_fraction_entry", None)
        if entry_fn is not None:
            def prefetch(target=target, entry_fn=entry_fn):
                self._stage_entry(entry_fn(target))
        return self._submit_async(
            key,
            lambda stacked: codec.repair_fraction_batch(target, stacked),
            chunks, trace, kind="dec", prefetch=prefetch)

    def repair_combine_async(self, codec, target: int, helpers: tuple,
                             fractions: np.ndarray,
                             trace=NULL_SPAN) -> _Pending:
        """Async codec.repair_combine_batch: [B, d, sub] stacked helper
        fractions (rows in `helpers` order) -> rebuilt [B, chunk]
        target chunks, with the per-(target, helper-set) combine matrix
        pre-staged in the h2d stage."""
        helpers = tuple(helpers)
        key = (self._codec_key(codec), "rcomb", target, helpers,
               fractions.shape[1:], str(fractions.dtype))
        self._account_codec(codec, "dec",
                            getattr(fractions, "nbytes", 0))
        prefetch = None
        entry_fn = getattr(codec, "_combine_entry", None)
        if entry_fn is not None:
            def prefetch(target=target, helpers=helpers,
                         entry_fn=entry_fn):
                self._stage_entry(entry_fn(target, helpers))
        return self._submit_async(
            key,
            lambda stacked: codec.repair_combine_batch(
                target, helpers, stacked),
            fractions, trace, kind="dec", prefetch=prefetch)

    def repair_fraction(self, codec, target: int, chunks: np.ndarray,
                        trace=NULL_SPAN) -> np.ndarray:
        """Blocking facade over repair_fraction_async."""
        return self.repair_fraction_async(codec, target, chunks,
                                          trace).result()

    def repair_combine(self, codec, target: int, helpers: tuple,
                       fractions: np.ndarray,
                       trace=NULL_SPAN) -> np.ndarray:
        """Blocking facade over repair_combine_async."""
        return self.repair_combine_async(codec, target, helpers,
                                         fractions, trace).result()

    def encode(self, codec, batch: np.ndarray, trace=NULL_SPAN,
               resident=None) -> np.ndarray:
        """codec.encode_batch(batch), coalesced across submitters —
        the blocking facade over encode_async (EC pipeline ordering
        untouched)."""
        return self.encode_async(codec, batch, trace,
                                 resident=resident).result()

    def decode(self, codec, avail_rows: tuple,
               chunks: np.ndarray, trace=NULL_SPAN) -> np.ndarray:
        """codec.decode_batch for one erasure signature, coalesced with
        ops sharing the same signature (same decode matrix)."""
        return self.decode_async(codec, avail_rows, chunks,
                                 trace).result()

    def fused_supported(self, codec) -> bool:
        """Whether whole-object writes through this codec can ride the
        fused write transform (jax backend + matrix codec)."""
        from . import fused_transform
        return fused_transform.fused_supported(codec)

    def fused_write_async(self, codec, batch: np.ndarray,
                          mode: str = "store",
                          required_ratio: float = 0.875,
                          entropy_max_bits: float = 7.0,
                          trace=NULL_SPAN, resident=None) -> _Pending:
        """Async fused write transform over one whole-object batch:
        digests + compressibility decision + EC encode in ONE device
        program (one h2d, one program, one d2h).

        Fused dispatches never coalesce across submitters — the
        compression decision and the per-shard crc chains are
        per OBJECT — but consecutive fused writes still overlap
        through the h2d/compute/d2h pipeline stages. The future's
        result() is the fused host output dict (the caller builds a
        FusedResult via fused_transform.result_from_host)."""
        from . import fused_transform
        batch = np.asarray(batch)
        self._account_codec(codec, "enc", getattr(batch, "nbytes", 0))
        donate = self._donate_ok and (mode == "compress"
                                      or resident is None)

        def fn(dev, _codec=codec, _mode=mode, _rr=required_ratio,
               _em=entropy_max_bits, _donate=donate):
            return fused_transform.run_fused(
                _codec, dev, mode=_mode, required_ratio=_rr,
                entropy_max_bits=_em, device=self.device,
                data_dev=dev if not isinstance(dev, np.ndarray)
                else None, donate=_donate)

        with self.lock:
            self._fused_seq += 1
            seq = self._fused_seq
        key = (self._codec_key(codec), "fused", mode, seq)
        if resident is not None:
            resident = (resident[0], resident[1], codec)
        return self._submit_async(key, fn, batch, trace, kind="fused",
                                  resident=resident)

    def fused_write(self, codec, batch: np.ndarray, mode: str = "store",
                    required_ratio: float = 0.875,
                    entropy_max_bits: float = 7.0,
                    trace=NULL_SPAN, resident=None):
        """Blocking facade over fused_write_async -> FusedResult."""
        from . import fused_transform
        batch = np.asarray(batch)
        S, k, chunk = batch.shape
        host = self.fused_write_async(
            codec, batch, mode=mode, required_ratio=required_ratio,
            entropy_max_bits=entropy_max_bits, trace=trace,
            resident=resident).result()
        return fused_transform.result_from_host(host, S, k, chunk, mode)

    def telemetry(self) -> dict:
        """The device-utilization gauge bag the OSD ships in its mgr
        report: live queue depth, lifetime coalescing ratio, and
        rolling per-codec encode/decode MB/s (bytes through the
        dispatcher over the last telemetry window)."""
        now = time.monotonic()
        with self.lock:
            depth = sum(len(e[1]) for e in self.queues.values())
            ops = self.stats["ops"]
            disp = self.stats["dispatches"]
            codecs = {}
            cutoff = now - self._telemetry_window
            for label, row in self.codec_stats.items():
                enc_b = dec_b = 0
                for t, kind, nb in row["window"]:
                    if t < cutoff:
                        continue
                    if kind == "enc":
                        enc_b += nb
                    else:
                        dec_b += nb
                codecs[label] = {
                    "enc_bytes": row["enc_bytes"],
                    "dec_bytes": row["dec_bytes"],
                    "enc_MBps": round(
                        enc_b / self._telemetry_window / 1e6, 3),
                    "dec_MBps": round(
                        dec_b / self._telemetry_window / 1e6, 3)}
        self.perf.set("l_tpu_queue_depth", depth)
        from ..parallel.placement import device_label
        return {"queue_depth": depth,
                "device": device_label(self.device),
                "ops": ops, "dispatches": disp,
                "coalesce_ratio": round(disp / ops, 3) if ops else 1.0,
                "fused": self._fused_summary(),
                "codecs": codecs}

    def _fused_summary(self) -> dict:
        """The fused-write ledger: dispatch count, bytes through the
        fused program, compress decisions and the mean stored/raw
        ratio. Rides telemetry() (mgr report) and `dispatch status`."""
        with self.lock:
            st = dict(self.fused_stats)
        ratio_sum = st.pop("ratio_milli_sum")
        n = st["dispatches"]
        st["ratio_avg"] = round(ratio_sum / n / 1000.0, 4) if n else 1.0
        return st

    def dispatch_status(self) -> dict:
        """The `dispatch status` asok payload: pipeline shape, ring
        occupancy per stage, and the coalescing ledger."""
        ring = {"staging": self._q_h2d.qsize(),
                "computing": self._q_compute.qsize(),
                "draining": self._q_d2h.qsize()}
        tel = self.telemetry()
        return {"pipeline_depth": self.pipeline_depth,
                "device": tel["device"],
                "ring": ring,
                "queue_depth": tel["queue_depth"],
                "ops": tel["ops"],
                "dispatches": tel["dispatches"],
                "coalesce_ratio": tel["coalesce_ratio"],
                "lat_ewma_ms": round(self._lat_ewma * 1e3, 3)
                if self._lat_ewma is not None else None,
                "coalesce_wait_ms": round(
                    self._coalesce_wait() * 1e3, 3),
                "donated_dispatches": self.perf.get("l_tpu_donated"),
                "fused": tel["fused"],
                "segments_s": {
                    "h2d_avg": self.perf.avg("l_tpu_h2d"),
                    "compute_avg": self.perf.avg("l_tpu_compute"),
                    "d2h_avg": self.perf.avg("l_tpu_d2h"),
                    "queue_avg": self.perf.avg("l_tpu_dispatch_queue")},
                "profile": self.dispatch_profile()}

    def dispatch_profile(self) -> dict:
        """Stall attribution over the current profile window: per-stage
        busy/idle/blocked wall seconds and fractions, ring occupancy
        time-averages, and a one-line verdict.

        The verdict logic: the device stage with the highest busy
        fraction is the wall ("h2d-bound 71%") — unless no stage is
        busy even half the window AND the collector out-idles it, in
        which case the device isn't the problem, the feed is
        ("collector-starved 88%": submitters aren't producing work)."""
        now = time.monotonic()
        wall = max(1e-9, now - self._profile_t0)
        stages = {}
        for name, prof in self._stage_prof.items():
            acc = prof.snapshot(now)
            row = {}
            for state in _STATES:
                row[state + "_s"] = round(acc[state], 6)
                row[state + "_frac"] = round(
                    min(1.0, acc[state] / wall), 4)
            stages[name] = row
            # cumulative counters ride MMgrReport with the next tick
            for state in _STATES:
                self.perf.set("l_tpu_stage_%s_%s" % (name, state),
                              acc[state])
        occupancy = {
            "staging": round(self._q_h2d.occupancy_integral() / wall, 4),
            "computing": round(
                self._q_compute.occupancy_integral() / wall, 4),
            "draining": round(self._q_d2h.occupancy_integral() / wall, 4)}
        device = ("h2d", "compute", "d2h")
        bound = max(device, key=lambda s: stages[s]["busy_frac"])
        attribution = stages[bound]["busy_frac"]
        collector_idle = stages["collector"]["idle_frac"]
        if attribution < 0.5 and collector_idle > attribution:
            bound = "collector"
            attribution = collector_idle
            verdict = "collector-starved %d%%" \
                % round(collector_idle * 100)
        else:
            verdict = "%s-bound %d%%" % (bound,
                                         round(attribution * 100))
        return {"window_s": round(wall, 6),
                "verdict": verdict,
                "bound": bound,
                "attribution": attribution,
                "stages": stages,
                "queue_occupancy_avg": occupancy}

    def profile_reset(self) -> None:
        """Restart the attribution window (asok `profile reset`)."""
        now = time.monotonic()
        for prof in self._stage_prof.values():
            prof.reset(now)
        self._profile_t0 = now
        self._q_h2d.occupancy_reset()
        self._q_compute.occupancy_reset()
        self._q_d2h.occupancy_reset()

    def shutdown(self) -> None:
        with self.cv:
            self._stop = True
            self.cv.notify_all()
        # sentinels flush the stage threads in order
        self._q_h2d.put(None)
        for t in self._threads:
            t.join(timeout=5)

    # -- internals -----------------------------------------------------

    def _submit_async(self, key, fn, batch, trace=NULL_SPAN,
                      kind: str = "enc", prefetch=None,
                      resident=None) -> _Pending:
        p = _Pending(np.asarray(batch), trace, resident=resident)
        with self.cv:
            q = self.queues.get(key)
            if q is None:
                q = self.queues[key] = (fn, [], kind, prefetch)
            q[1].append(p)
            self.stats["ops"] += 1
            depth = sum(len(e[1]) for e in self.queues.values())
            self.cv.notify_all()
        self.perf.set("l_tpu_queue_depth", depth)
        return p

    def _note_dispatch_wall(self, wall: float) -> None:
        """Fold one dispatch's submit->results wall into the latency
        EWMA the coalesce window scales from."""
        if wall <= 0:
            return
        # single-writer per stage thread; a torn read in the window
        # heuristic only mis-sizes one wait, so no lock (and the
        # collector calls _coalesce_wait while HOLDING self.cv's lock)
        prev = self._lat_ewma
        self._lat_ewma = wall if prev is None \
            else (1.0 - self._lat_alpha) * prev \
            + self._lat_alpha * wall

    def _coalesce_wait(self) -> float:
        """Adaptive straggler wait: half the rolling dispatch-wall
        EWMA, floored at max_delay/8 and CAPPED at max_delay — a fast
        device stops burning the full fixed window on every dispatch,
        and a known-slow device can never stretch the window beyond
        the configured max (the pre-EWMA failure mode: one wedged
        h2d inflating every subsequent coalesce wait)."""
        ewma = self._lat_ewma
        if ewma is None:
            return self.max_delay
        return min(self.max_delay,
                   max(self.max_delay / 8.0, 0.5 * ewma))

    def _take_group(self):
        """Pick the fullest queue; wait up to the EWMA-scaled coalesce
        window for stragglers unless it is already at max_batch."""
        deadline = None
        while True:
            with self.cv:
                if self._stop:
                    return None
                best_key, best = None, None
                for key, entry in self.queues.items():
                    pend = entry[1]
                    if pend and (best is None or
                                 len(pend) > len(best[1])):
                        best_key, best = key, entry
                if best is None:
                    deadline = None
                    self.cv.wait(0.5)
                    continue
                if len(best[1]) >= self.max_batch or (
                        deadline is not None
                        and time.monotonic() >= deadline):
                    fn, pend, kind, prefetch = best
                    take = pend[:self.max_batch]
                    del pend[:len(take)]
                    if not pend:
                        self.queues.pop(best_key, None)
                    deadline = None
                    return _Dispatch(best_key, fn, take, kind, prefetch)
                wait = self._coalesce_wait()
                if deadline is None:
                    deadline = time.monotonic() + wait
                self.cv.wait(wait)

    def _run(self):
        """Collector: group submitters into fused dispatches and feed
        the pipeline."""
        prof = self._stage_prof["collector"]
        while True:
            # idle = waiting for submitters (or stragglers): a starved
            # collector is the "upstream can't feed the device" verdict
            prof.enter("idle")
            d = self._take_group()
            if d is None:
                return
            prof.enter("busy")
            self.stats["dispatches"] += 1
            self.perf.inc("l_tpu_dispatches")
            self.perf.inc("l_tpu_ops", len(d.pend))
            if len(d.pend) > 1:
                self.stats["coalesced"] += len(d.pend)
                self.perf.inc("l_tpu_coalesced", len(d.pend))
            # blocks when the staging ring is full: that back-pressure
            # IS the depth-N bound
            prof.enter("blocked")
            self._q_h2d.put(d)

    # -- pipelined stages ----------------------------------------------

    def _fail(self, d: _Dispatch, e: BaseException) -> None:
        """Strict per-batch error propagation: the failed stage fails
        ONLY this fused batch's submitters; later batches proceed."""
        if d.mem_bytes:
            PROFILER.mem_sub("staging_ring", d.mem_bytes)
            d.mem_bytes = 0
        for p in d.pend:
            p.error = e
        _wake(d.pend)

    def _h2d_loop(self) -> None:
        prof = self._stage_prof["h2d"]
        while True:
            prof.enter("idle")
            d = self._q_h2d.get()
            if d is None:
                self._q_compute.put(None)
                return
            prof.enter("busy")
            try:
                t0 = time.monotonic()
                d.stacked = d.pend[0].batch if len(d.pend) == 1 \
                    else np.concatenate([p.batch for p in d.pend])
                d.dev = self._devops.h2d(d.stacked)
                d.mem_bytes = int(getattr(d.stacked, "nbytes", 0))
                PROFILER.mem_add("staging_ring", d.mem_bytes)
                if d.prefetch is not None:
                    # decode-table staging rides the h2d stage: the
                    # inversion + bitmatrix upload of THIS dispatch
                    # overlap the PREVIOUS dispatch's compute
                    d.prefetch()
                d.seg["h2d"] = (t0, time.monotonic())
            except BaseException as e:
                self._fail(d, e)
                continue
            prof.enter("blocked")
            self._q_compute.put(d)

    def _compute_loop(self) -> None:
        prof = self._stage_prof["compute"]
        while True:
            prof.enter("idle")
            d = self._q_compute.get()
            if d is None:
                self._q_d2h.put(None)
                return
            prof.enter("busy")
            try:
                t0 = time.monotonic()
                d.out_dev = self._run_compute(d)
                d.seg["compute"] = (t0, time.monotonic())
            except BaseException as e:
                self._fail(d, e)
                continue
            prof.enter("blocked")
            self._q_d2h.put(d)

    def _d2h_loop(self) -> None:
        prof = self._stage_prof["d2h"]
        while True:
            prof.enter("idle")
            d = self._q_d2h.get()
            if d is None:
                return
            prof.enter("busy")
            try:
                t0 = time.monotonic()
                out = self._devops.d2h(d.out_dev)
                d.seg["d2h"] = (t0, time.monotonic())
                self._slice_results(d, out)
                self._adopt_residents(d, d.dev, d.out_dev)
                self._account(d)
                if d.kind == "fused":
                    self._account_fused(d)
            except BaseException as e:
                self._fail(d, e)
                continue
            finally:
                if d.mem_bytes:
                    PROFILER.mem_sub("staging_ring", d.mem_bytes)
                    d.mem_bytes = 0
            self._note_dispatch_wall(
                time.monotonic() - min(p.t_submit for p in d.pend))
            _wake(d.pend)

    def _run_compute(self, d: _Dispatch):
        """Run the fused program, donating the staged input when safe.

        The staged buffer is dispatcher-private (h2d made a fresh device
        copy; submitters only ever hold their host arrays), so donation
        can never invalidate caller-visible data. It is skipped when the
        dispatch adopts into the HBM tier — adoption reads the staged
        input after compute."""
        wants_adopt = any(p.resident is not None for p in d.pend)
        # encode only: an encode fn is one trace per (codec, shape),
        # but a decode fn closes over its erasure signature — jitting
        # it per signature would pay a fresh trace/compile for every
        # new pattern, exactly the cost the table bank exists to avoid
        if self._donate_ok and d.kind == "enc" and not wants_adopt:
            dfn = self._donate_fns.get(d.key)
            fresh_trace = dfn is None
            if dfn is None:
                import jax
                if len(self._donate_fns) >= 256:
                    # bounded: distinct (codec, kind, shape, signature)
                    # keys grow without limit on a long-lived OSD
                    self._donate_fns.clear()
                dfn = self._donate_fns.setdefault(
                    d.key, jax.jit(d.fn, donate_argnums=(0,)))
            if dfn is not False:
                try:
                    nbytes = int(getattr(d.dev, "nbytes", 0))
                    PROFILER.mem_add("donated_buffers", nbytes)
                    try:
                        t0 = time.perf_counter()
                        out = self._devops.run(dfn, d.dev)
                        if fresh_trace and PROFILER.enabled:
                            # first run of a fresh donate fn IS its
                            # trace+compile; register the event so the
                            # storm detector sees dispatcher churn too
                            PROFILER.record_compile(
                                "tpu_dispatch.donate",
                                ("key", hash(d.key)),
                                time.perf_counter() - t0)
                    finally:
                        PROFILER.mem_sub("donated_buffers", nbytes)
                    self.perf.inc("l_tpu_donated")
                    return out
                except BaseException:
                    # not traceable / donation rejected: remember, and
                    # re-stage (the donated buffer may be gone) for the
                    # plain call
                    self._donate_fns[d.key] = False
                    d.dev = self._devops.h2d(d.stacked)
        return self._devops.run(d.fn, d.dev)

    def _slice_results(self, d: _Dispatch, out) -> None:
        if len(d.pend) == 1:
            d.pend[0].out = out
            return
        off = 0
        for p in d.pend:
            s = p.batch.shape[0]
            p.out = out[off:off + s]
            off += s

    def _adopt_residents(self, d: _Dispatch, data_src, parity_src
                         ) -> None:
        """Hand the staged data rows + computed parity rows to the HBM
        tier for any submitter that asked — the arrays are already
        device-side, so residency costs ZERO extra transfers. Adoption
        failures never fail the submitter (the tier is a cache)."""
        if d.kind == "fused":
            # one submitter per fused dispatch (the key is unique):
            # adopt what was actually STORED — the compressed rows when
            # the device chose to compress, the staged raw rows when it
            # chose store — and keep the device-computed shard crcs
            # beside them for scrub-from-digest
            p = d.pend[0]
            if p.resident is None or not isinstance(p.out, dict):
                return
            tier, key, codec = p.resident
            host = p.out
            out = parity_src if isinstance(parity_src, dict) else host
            try:
                if "do_compress" in host:
                    # compress-mode runs adopt from the program's
                    # stored buffer (== raw when the device chose
                    # store): the staged input may have been DONATED
                    # to the fused program and must not be read
                    used = int(host["used_stripes"])
                    rows, par = out["stored"][:used], \
                        out["parity"][:used]
                else:
                    rows = data_src
                    par = out["parity"][:data_src.shape[0]]
                tier.adopt_encode(
                    key, rows, par, codec,
                    digests=np.asarray(host["shard_crcs"],
                                       dtype=np.uint32))
            except Exception:
                pass
            return
        off = 0
        for p in d.pend:
            s = p.batch.shape[0]
            if p.resident is not None:
                tier, key, codec = p.resident
                try:
                    tier.adopt_encode(key, data_src[off:off + s],
                                      parity_src[off:off + s], codec)
                except Exception:
                    pass
            off += s

    def _account(self, d: _Dispatch) -> None:
        """Fold one dispatch's measured stage intervals into the
        l_tpu_* counters and back-fill queue/device spans under every
        participating op's trace (the segments are shared: a fused
        dispatch ran once for all of them). The intervals are REAL
        wall stamps, so spans from consecutive dispatches overlap —
        that overlap is the proof the pipeline works
        (tests/test_tpu_dispatch.py checks it)."""
        seg = d.seg
        if not seg:
            return
        h0, h1 = seg.get("h2d", (d.t_take, d.t_take))
        c0, c1 = seg.get("compute", (h1, h1))
        d0, d1 = seg.get("d2h", (c1, c1))
        self.perf.tinc("l_tpu_h2d", h1 - h0)
        self.perf.tinc("l_tpu_compute", c1 - c0)
        self.perf.tinc("l_tpu_d2h", d1 - d0)
        for p in d.pend:
            self.perf.tinc("l_tpu_dispatch_queue",
                           max(0.0, d.t_take - p.t_submit))
            if not p.trace.valid():
                continue
            p.trace.child_interval("tpu_queue", p.t_submit, d.t_take)
            p.t_done = d1
            dev = p.trace.child_interval(
                "tpu_device", h0, d1,
                batch=int(sum(q.batch.shape[0] for q in d.pend)),
                coalesced=len(d.pend))
            dev.child_interval("h2d", h0, h1)
            dev.child_interval("compute", c0, c1)
            dev.child_interval("d2h", d0, d1)

    def _account_fused(self, d: _Dispatch) -> None:
        """Fold one fused write's outcome into the l_tpu_fused_*
        counters and the fused_stats bag (the `dispatch status` fused
        section + the ceph_tpu_fused_* Prometheus series)."""
        p = d.pend[0]
        host = p.out
        if not isinstance(host, dict):
            return
        raw = int(getattr(p.batch, "nbytes", 0))
        compressed = bool(host.get("do_compress", False))
        stored = int(host["comp_len"]) if compressed else raw
        par = host.get("parity")
        m_chunk = int(par.shape[1]) * int(par.shape[2]) \
            if par is not None and getattr(par, "ndim", 0) == 3 else 0
        stripes = int(host["used_stripes"]) if "used_stripes" in host \
            else (raw // (p.batch.shape[1] * p.batch.shape[2])
                  if raw else 0)
        out_bytes = stored + stripes * m_chunk
        probe_reject = "probe_ok" in host and not bool(host["probe_ok"])
        ratio_milli = (stored * 1000) // raw if raw else 1000
        self.perf.inc("l_tpu_fused_dispatches")
        self.perf.inc("l_tpu_fused_bytes_in", raw)
        self.perf.inc("l_tpu_fused_bytes_out", out_bytes)
        if compressed:
            self.perf.inc("l_tpu_fused_compressed")
        if probe_reject:
            self.perf.inc("l_tpu_fused_probe_rejects")
        self.perf.tinc("l_tpu_fused_ratio_milli", ratio_milli)
        with self.lock:
            st = self.fused_stats
            st["dispatches"] += 1
            st["bytes_in"] += raw
            st["bytes_out"] += out_bytes
            st["compressed"] += int(compressed)
            st["probe_rejects"] += int(probe_reject)
            st["ratio_milli_sum"] += ratio_milli

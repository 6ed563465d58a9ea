"""Cross-op device-call coalescing (osd/tpu_dispatch.py).

The dispatcher batches concurrent EC codec calls sharing a generator
(or decode matrix) into single device dispatches — the Python twin of
native/src/tpu_bridge.cc, shadowing the per-op entry at
src/osd/ECBackend.cc:1437. Results must be bit-exact and the dispatch
count measurably below the op count.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from ceph_tpu import registry
from ceph_tpu.osd.tpu_dispatch import TpuDispatcher

PROFILE = {"technique": "reed_sol_van", "k": "4", "m": "2", "w": "8"}


@pytest.fixture()
def dispatcher():
    # generous window: on a loaded 1-core box thread start latency can
    # exceed a tight delay, splitting batches and flaking exact-count
    # assertions
    d = TpuDispatcher(max_batch=8, max_delay=0.5)
    yield d
    d.shutdown()


def _codec():
    return registry.factory("jax_tpu", dict(PROFILE))


class TestCoalescing:
    def test_concurrent_encodes_fuse_and_stay_bit_exact(self, dispatcher):
        codec = _codec()
        rng = np.random.default_rng(1)
        batches = [rng.integers(0, 256, size=(3, 4, 512), dtype=np.uint8)
                   for _ in range(8)]
        direct = [np.asarray(codec.encode_batch(b)) for b in batches]
        outs = [None] * 8

        def worker(i):
            outs[i] = np.asarray(dispatcher.encode(codec, batches[i]))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        for i in range(8):
            assert np.array_equal(outs[i], direct[i]), i
        assert dispatcher.stats["ops"] == 8
        assert dispatcher.stats["dispatches"] < 8
        assert dispatcher.stats["coalesced"] > 0

    def test_distinct_codec_instances_same_profile_coalesce(self,
                                                            dispatcher):
        """Every PG backend holds its own codec instance; identity is
        by VALUE (generator bitmatrix), so cross-PG ops still fuse."""
        c1, c2 = _codec(), _codec()
        assert c1 is not c2
        rng = np.random.default_rng(2)
        b1 = rng.integers(0, 256, size=(2, 4, 512), dtype=np.uint8)
        b2 = rng.integers(0, 256, size=(2, 4, 512), dtype=np.uint8)
        res = {}

        def w(tag, c, b):
            res[tag] = np.asarray(dispatcher.encode(c, b))

        t1 = threading.Thread(target=w, args=("a", c1, b1))
        t2 = threading.Thread(target=w, args=("b", c2, b2))
        t1.start(); t2.start(); t1.join(30); t2.join(30)
        assert np.array_equal(res["a"], np.asarray(c1.encode_batch(b1)))
        assert np.array_equal(res["b"], np.asarray(c1.encode_batch(b2)))
        # <= 2 tolerates a straggler thread missing the window under
        # extreme load; the by-value codec key is what is under test
        assert dispatcher.stats["dispatches"] <= 2

    def test_varying_stripe_counts_concatenate(self, dispatcher):
        """Ops with different stripe counts (same per-stripe shape)
        concatenate along axis 0."""
        codec = _codec()
        rng = np.random.default_rng(3)
        batches = [rng.integers(0, 256, size=(s, 4, 512), dtype=np.uint8)
                   for s in (1, 4, 2)]
        outs = [None] * 3

        def worker(i):
            outs[i] = np.asarray(dispatcher.encode(codec, batches[i]))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        for i, b in enumerate(batches):
            assert outs[i].shape == (b.shape[0], 2, 512)
            assert np.array_equal(outs[i],
                                  np.asarray(codec.encode_batch(b))), i

    def test_decode_coalesces_per_signature(self, dispatcher):
        codec = _codec()
        rng = np.random.default_rng(4)
        data = rng.integers(0, 256, size=(2, 4, 512), dtype=np.uint8)
        parity = np.asarray(codec.encode_batch(data))
        full = np.concatenate([data, parity], axis=1)
        avail = (0, 2, 3, 5)
        chunks = full[:, list(avail), :]
        res = {}

        def w(tag):
            res[tag] = np.asarray(
                dispatcher.decode(codec, avail, chunks))

        t1 = threading.Thread(target=w, args=("a",))
        t2 = threading.Thread(target=w, args=("b",))
        t1.start(); t2.start(); t1.join(30); t2.join(30)
        assert np.array_equal(res["a"], full)
        assert np.array_equal(res["b"], full)
        assert dispatcher.stats["dispatches"] <= 2

    def test_error_propagates_to_every_submitter(self, dispatcher):
        class Boom:
            _bitmat = None

            def encode_batch(self, b):
                raise RuntimeError("device on fire")

        codec = Boom()
        errs = []

        def w():
            try:
                dispatcher.encode(codec, np.zeros((1, 2, 64), np.uint8))
            except RuntimeError as e:
                errs.append(str(e))

        threads = [threading.Thread(target=w) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert errs == ["device on fire"] * 3


class TestOsdIntegration:
    def test_concurrent_ec_writes_need_fewer_dispatches(self):
        """End to end: N concurrent EC writes through the cluster
        complete bit-exact with measurably fewer device dispatches
        than ops (the SURVEY §7 step-3 queue)."""
        from .cluster_util import MiniCluster
        FAST = {"osd_heartbeat_interval": 0.1,
                "osd_heartbeat_grace": 0.6,
                "mon_osd_down_out_interval": 1.0,
                "paxos_propose_interval": 0.02,
                "osd_tpu_coalesce_max_delay_ms": 15.0,
                "osd_tpu_coalesce_max_batch": 8,
                # this row prices the classic coalescing queue; the
                # fused write transform never coalesces (per-object
                # compress decision + crc chains) and is priced in
                # test_fused_transform
                "osd_fused_transform": False}
        cluster = MiniCluster(num_mons=1, num_osds=3,
                              conf_overrides=FAST).start()
        try:
            client = cluster.client()
            cluster.create_ec_pool(
                client, "coalesce",
                {"plugin": "jax_tpu", "technique": "reed_sol_van",
                 "k": "2", "m": "1", "w": "8"}, pg_num=8)
            ioctx = client.open_ioctx("coalesce")
            payloads = {("obj-%d" % i): (b"%02d" % i) * 2048
                        for i in range(16)}
            errs: list = []

            def writer(oid, data):
                try:
                    ioctx.write_full(oid, data, timeout=60)
                except Exception as e:
                    errs.append(e)

            threads = [threading.Thread(target=writer, args=(o, d))
                       for o, d in payloads.items()]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not errs, errs
            for oid, data in payloads.items():
                assert ioctx.read(oid) == data, oid
            ops = sum(o.tpu_dispatcher.stats["ops"]
                      for o in cluster.osds.values()
                      if o.tpu_dispatcher)
            dispatches = sum(o.tpu_dispatcher.stats["dispatches"]
                             for o in cluster.osds.values()
                             if o.tpu_dispatcher)
            assert ops >= 16
            assert dispatches < ops, (dispatches, ops)
        finally:
            cluster.stop()


class _FakeDevOps:
    """Deterministic fake device: records the order h2d/compute legs
    are ISSUED in and lets the test hold the compute stage closed, so
    'h2d of batch n+1 runs before compute of batch n completes' is an
    assertion, not a race."""

    def __init__(self):
        self.lock = threading.Lock()
        self.events = []             # ("h2d" | "compute", seq)
        self.h2d_count = 0
        self.compute_count = 0
        self.second_h2d_issued = threading.Event()
        self.compute_gate = threading.Event()   # test opens this

    def h2d(self, host):
        with self.lock:
            self.h2d_count += 1
            self.events.append(("h2d", self.h2d_count))
            if self.h2d_count >= 2:
                self.second_h2d_issued.set()
        return host

    def run(self, fn, x):
        self.compute_gate.wait(10)
        with self.lock:
            self.compute_count += 1
            self.events.append(("compute", self.compute_count))
        return fn(x)

    def d2h(self, out):
        return np.asarray(out)


class TestPipeline:
    """The overlapped depth-N dispatcher (ROADMAP direction A): h2d of
    batch n+1 concurrent with compute of n and d2h of n-1, future API,
    donation safety, strict per-batch error isolation."""

    def test_submit_async_future_api(self):
        d = TpuDispatcher(max_batch=4, max_delay=0.001,
                          pipeline_depth=2)
        try:
            codec = _codec()
            rng = np.random.default_rng(10)
            batch = rng.integers(0, 256, size=(2, 4, 512),
                                 dtype=np.uint8)
            fut = d.encode_async(codec, batch)
            out = fut.result(30)
            assert fut.done() and fut.exception() is None
            assert np.array_equal(out, np.asarray(
                codec.encode_batch(batch)))
        finally:
            d.shutdown()

    def test_concurrent_submitter_slicing_integrity(self):
        """Many submitters with DIFFERENT stripe counts fused through
        the pipeline: every submitter gets exactly its slice back,
        bit-exact, regardless of how the collector grouped them."""
        d = TpuDispatcher(max_batch=8, max_delay=0.05,
                          pipeline_depth=3)
        try:
            codec = _codec()
            rng = np.random.default_rng(11)
            sizes = [1, 4, 2, 3, 1, 5, 2, 1, 3, 4, 2, 1]
            batches = [rng.integers(0, 256, size=(s, 4, 512),
                                    dtype=np.uint8) for s in sizes]
            direct = [np.asarray(codec.encode_batch(b))
                      for b in batches]
            outs = [None] * len(batches)

            def worker(i):
                outs[i] = np.asarray(d.encode(codec, batches[i]))

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(len(batches))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            for i in range(len(batches)):
                assert outs[i].shape == direct[i].shape, i
                assert np.array_equal(outs[i], direct[i]), i
        finally:
            d.shutdown()

    def test_per_batch_error_isolation(self):
        """A failed stage fails ONLY its batch's submitters; batches
        behind it keep flowing through the pipeline."""
        class Boom:
            _bitmat = None

            def encode_batch(self, b):
                raise RuntimeError("stage on fire")

        d = TpuDispatcher(max_batch=8, max_delay=0.001,
                          pipeline_depth=2)
        try:
            codec = _codec()
            rng = np.random.default_rng(12)
            good_batch = rng.integers(0, 256, size=(2, 4, 512),
                                      dtype=np.uint8)
            bad = d.encode_async(Boom(), np.zeros((1, 2, 64),
                                                  np.uint8))
            good = d.encode_async(codec, good_batch)
            with pytest.raises(RuntimeError, match="stage on fire"):
                bad.result(30)
            # the batch behind the failed one completes normally
            assert np.array_equal(
                np.asarray(good.result(30)),
                np.asarray(codec.encode_batch(good_batch)))
            # and the dispatcher is still alive for new work
            again = d.encode(codec, good_batch)
            assert np.array_equal(np.asarray(again),
                                  np.asarray(
                                      codec.encode_batch(good_batch)))
        finally:
            d.shutdown()

    def test_donation_safety_host_array_intact(self):
        """Donation (when active) only ever consumes the dispatcher's
        PRIVATE staged device buffer — a submitter's host array is
        untouched and reusable after the call."""
        d = TpuDispatcher(max_batch=4, max_delay=0.001,
                          pipeline_depth=2)
        try:
            codec = _codec()
            rng = np.random.default_rng(13)
            batch = rng.integers(0, 256, size=(3, 4, 512),
                                 dtype=np.uint8)
            before = batch.tobytes()
            out1 = np.asarray(d.encode(codec, batch))
            assert batch.tobytes() == before      # no use-after-donate
            # the SAME host array resubmitted produces the same parity
            out2 = np.asarray(d.encode(codec, batch))
            assert np.array_equal(out1, out2)
        finally:
            d.shutdown()

    def test_jitted_encode_leaves_the_codec_usable(self):
        """The donating path jits codec.encode_batch. The codec's cached
        device matrix must not capture that trace's tracer, or the next
        batch shape and every un-jitted encode after it fail
        (UnexpectedTracerError): sub-stripe writes reach the dispatcher
        in batches of 1 to max_batch stripes."""
        d = TpuDispatcher(max_batch=1, max_delay=0.0, pipeline_depth=2)
        d._donate_ok = True           # as on a chip that donates
        try:
            codec = _codec()
            rng = np.random.default_rng(15)
            for n in (1, 2, 3):
                batch = rng.integers(0, 256, size=(n, 4, 512),
                                     dtype=np.uint8)
                got = np.asarray(d.encode(codec, batch))
                assert np.array_equal(
                    got, np.asarray(codec.encode_batch(batch)))
            assert d.perf.get("l_tpu_donated") == 3
        finally:
            d.shutdown()

    def test_fake_device_h2d_overlaps_compute(self):
        """Deterministic overlap proof: with the compute stage held
        closed, the h2d stage still stages the NEXT batch — h2d(n+1)
        is issued before compute(n) completes."""
        d = TpuDispatcher(max_batch=1, max_delay=0.0,
                          pipeline_depth=2)
        fake = _FakeDevOps()
        d._devops = fake
        d._donate_ok = False          # route through the plain fn path
        try:
            codec = _codec()
            rng = np.random.default_rng(14)
            b1 = rng.integers(0, 256, size=(1, 4, 512), dtype=np.uint8)
            b2 = rng.integers(0, 256, size=(2, 4, 512), dtype=np.uint8)
            f1 = d.encode_async(codec, b1)
            f2 = d.encode_async(codec, b2)
            # compute(1) is blocked on the gate; the pipeline must
            # still issue h2d(2) — THE overlap this PR exists for
            assert fake.second_h2d_issued.wait(10), \
                "h2d of batch 2 never issued while compute(1) pending"
            assert fake.compute_count == 0        # compute(1) not done
            fake.compute_gate.set()
            out1, out2 = f1.result(30), f2.result(30)
            assert np.array_equal(np.asarray(out1), np.asarray(
                codec.encode_batch(b1)))
            assert np.array_equal(np.asarray(out2), np.asarray(
                codec.encode_batch(b2)))
            # issue order on the fake device: second h2d before the
            # first compute retires
            assert fake.events.index(("h2d", 2)) \
                < fake.events.index(("compute", 1))
        finally:
            fake.compute_gate.set()
            d.shutdown()

    def test_stage_intervals_recorded_and_status_shape(self):
        """Pipelined dispatches record real stage intervals into the
        l_tpu_* counters (free instrumentation) and `dispatch status`
        reports the ring."""
        d = TpuDispatcher(max_batch=4, max_delay=0.001,
                          pipeline_depth=2)
        try:
            codec = _codec()
            rng = np.random.default_rng(15)
            for _ in range(3):
                d.encode(codec, rng.integers(0, 256, size=(2, 4, 512),
                                             dtype=np.uint8))
            dump = d.perf.dump()
            assert dump["l_tpu_h2d"]["avgcount"] >= 1
            assert dump["l_tpu_compute"]["avgcount"] >= 1
            assert dump["l_tpu_d2h"]["avgcount"] >= 1
            status = d.dispatch_status()
            assert status["pipeline_depth"] == 2
            assert set(status["ring"]) == {"staging", "computing",
                                           "draining"}
            assert status["dispatches"] >= 1
            assert "segments_s" in status
        finally:
            d.shutdown()

    @pytest.mark.parametrize("depth", [0, 1])
    def test_depth_below_two_is_refused(self, depth):
        """The staging ring is the only dispatch loop: a ring shallower
        than two batches per stage cannot overlap, so it is refused."""
        with pytest.raises(ValueError, match="pipeline_depth"):
            TpuDispatcher(pipeline_depth=depth)

class _SleepyDevOps:
    """Deterministic fake device with a configurable latency per stage,
    so the test can make ANY stage the pipeline's bottleneck and assert
    the profiler names it."""

    def __init__(self, h2d_s=0.0, compute_s=0.0, d2h_s=0.0):
        self.h2d_s, self.compute_s, self.d2h_s = h2d_s, compute_s, d2h_s

    def h2d(self, host):
        if self.h2d_s:
            time.sleep(self.h2d_s)
        return host

    def run(self, fn, x):
        if self.compute_s:
            time.sleep(self.compute_s)
        return fn(x)

    def d2h(self, out):
        if self.d2h_s:
            time.sleep(self.d2h_s)
        return np.asarray(out)


class TestStallAttribution:
    """`dispatch profile` stall attribution: make each stage the
    bottleneck in turn on a deterministic fake device and assert the
    verdict names the correct stage with majority attribution."""

    def _profile_with(self, devops, n=12, submit_gap=0.0):
        d = TpuDispatcher(max_batch=1, max_delay=0.0, pipeline_depth=2)
        d._devops = devops
        d._donate_ok = False
        try:
            codec = _codec()
            rng = np.random.default_rng(21)
            batches = [rng.integers(0, 256, size=(1, 4, 256),
                                    dtype=np.uint8) for _ in range(n)]
            # warm the codec's jit outside the profiled window: the
            # one-time trace/compile would otherwise dominate compute
            d.encode(codec, batches[0])
            d.profile_reset()
            futs = []
            for b in batches:
                futs.append(d.encode_async(codec, b))
                if submit_gap:
                    time.sleep(submit_gap)
            for f in futs:
                f.result(60)
            return d.dispatch_profile()
        finally:
            d.shutdown()

    def test_slow_h2d_is_h2d_bound(self):
        prof = self._profile_with(_SleepyDevOps(h2d_s=0.03))
        assert prof["bound"] == "h2d", prof
        assert prof["attribution"] >= 0.5, prof
        assert prof["verdict"].startswith("h2d-bound"), prof

    def test_slow_compute_is_compute_bound(self):
        prof = self._profile_with(_SleepyDevOps(compute_s=0.03))
        assert prof["bound"] == "compute", prof
        assert prof["attribution"] >= 0.5, prof
        assert prof["verdict"].startswith("compute-bound"), prof

    def test_slow_d2h_is_d2h_bound(self):
        prof = self._profile_with(_SleepyDevOps(d2h_s=0.03))
        assert prof["bound"] == "d2h", prof
        assert prof["attribution"] >= 0.5, prof
        assert prof["verdict"].startswith("d2h-bound"), prof

    def test_slow_submitters_are_collector_starved(self):
        """Fast device + trickling submitters: the device is NOT the
        wall and the verdict must say so instead of blaming a stage."""
        prof = self._profile_with(_SleepyDevOps(), n=10,
                                  submit_gap=0.03)
        assert prof["bound"] == "collector", prof
        assert prof["attribution"] >= 0.5, prof
        assert prof["verdict"].startswith("collector-starved"), prof

    def test_profile_shape_and_reset(self):
        d = TpuDispatcher(max_batch=4, max_delay=0.001,
                          pipeline_depth=2)
        try:
            codec = _codec()
            rng = np.random.default_rng(22)
            d.encode(codec, rng.integers(0, 256, size=(2, 4, 256),
                                         dtype=np.uint8))
            prof = d.dispatch_profile()
            assert set(prof) == {"window_s", "verdict", "bound",
                                 "attribution", "stages",
                                 "queue_occupancy_avg"}
            for stage in ("collector", "h2d", "compute", "d2h"):
                row = prof["stages"][stage]
                for state in ("busy", "idle", "blocked"):
                    assert 0.0 <= row[state + "_frac"] <= 1.0
            # the stage counters ride the perf dump for MMgrReport
            dump = d.perf.dump()
            assert "l_tpu_stage_h2d_busy" in dump
            assert "l_tpu_stage_collector_idle" in dump
            # reset restarts the window
            d.profile_reset()
            prof2 = d.dispatch_profile()
            assert prof2["window_s"] < prof["window_s"] + 0.5
            assert prof2["stages"]["h2d"]["busy_s"] <= \
                prof["stages"]["h2d"]["busy_s"] + 1e-6
        finally:
            d.shutdown()

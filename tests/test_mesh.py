"""Multi-device mesh tests on the suite's virtual 8-device CPU platform.

The compute-plane sharding story in-suite (the driver's external
dryrun_multichip is a second check, no longer the only one): sharded
encode/decode must be bit-equal to the single-device path across mesh
shapes, reductions ride psum, and the bulk CRUSH sweep partitions over
the mesh while staying equal to the scalar oracle.
"""

from __future__ import annotations

import numpy as np
import pytest

from ceph_tpu import registry
from ceph_tpu.parallel import mesh as pmesh

K, M, W = 4, 2, 8


@pytest.fixture(scope="module")
def codec():
    return registry.factory("jax_tpu", {"technique": "reed_sol_van",
                                        "k": str(K), "m": str(M),
                                        "w": str(W)})


@pytest.fixture(scope="module")
def payload():
    rng = np.random.default_rng(42)
    # B=8 divides every stripe-axis size; N=4096 divides every block size
    return rng.integers(0, 256, size=(8, K, 4096), dtype=np.uint8)


def test_eight_virtual_devices():
    import jax
    assert len(jax.devices()) == 8
    assert jax.devices()[0].platform == "cpu"


def test_sharded_encode_bit_equal_full_mesh(codec, payload):
    m = pmesh.make_mesh(8)                      # 2 x 4 (stripe, block)
    single = np.asarray(codec.encode_batch(payload))
    sharded = np.asarray(pmesh.encode_sharded(codec, payload, m))
    assert np.array_equal(single, sharded)


@pytest.mark.parametrize("n_devices", [2, 4, 8])
def test_sharded_encode_bit_equal_across_mesh_shapes(codec, payload,
                                                     n_devices):
    m = pmesh.make_mesh(n_devices)
    single = np.asarray(codec.encode_batch(payload))
    sharded = np.asarray(pmesh.encode_sharded(codec, payload, m))
    assert np.array_equal(single, sharded)


def test_sharded_encode_is_actually_distributed(codec, payload):
    m = pmesh.make_mesh(8)
    out = pmesh.encode_sharded(codec, payload, m)
    # the parity must live sharded across all 8 devices, not replicated
    assert len(out.sharding.device_set) == 8
    shard_shapes = {s.data.shape for s in out.addressable_shards}
    assert shard_shapes == {(8 // 2, M, 4096 // 4)}


def test_sharded_decode_bit_equal(codec, payload):
    m = pmesh.make_mesh(8)
    parity = np.asarray(codec.encode_batch(payload))
    full = np.concatenate([payload, parity], axis=1)
    for avail in [(0, 1, 2, 3), (1, 2, 4, 5), (0, 2, 3, 5)]:
        chunks = full[:, list(avail), :]
        sharded = np.asarray(pmesh.decode_sharded(codec, avail, chunks, m))
        single = np.asarray(codec.decode_batch(avail, chunks))
        assert np.array_equal(sharded, single), avail
        assert np.array_equal(sharded, full), avail


def test_psum_reduction_over_mesh(codec, payload):
    """A cross-shard reduction (per-chunk byte checksums, the deep-scrub
    shape) rides psum over the mesh and matches numpy."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    m = pmesh.make_mesh(8)
    parity = pmesh.encode_sharded(codec, payload, m)

    @jax.jit
    def chunk_sums(x):
        def local(block):
            s = jnp.sum(block.astype(jnp.int64), axis=(0, 2))
            return jax.lax.psum(jax.lax.psum(s, "block"), "stripe")
        return jax.shard_map(
            local, mesh=m,
            in_specs=P("stripe", None, "block"),
            out_specs=P())(x)

    got = np.asarray(chunk_sums(parity))
    want = np.asarray(parity).astype(np.int64).sum(axis=(0, 2))
    assert np.array_equal(got, want)


def test_mesh_sharded_bulk_crush_equals_scalar_oracle():
    """The bulk PG->OSD sweep partitioned across the mesh: every row
    equal to the scalar interpreter (which is itself differential-tested
    against the compiled reference C)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ceph_tpu.crush import map as cmap_mod, mapper_ref
    from ceph_tpu.crush.batched import batched_do_rule
    from ceph_tpu.crush.map import CrushMap, Rule

    rng = np.random.default_rng(9)
    hosts, per = 6, 4
    ndev = hosts * per
    weights = rng.integers(1, 3 * 0x10000, size=ndev, dtype=np.uint32)
    m = CrushMap()
    m.type_names = {"osd": 0, "host": 1, "root": 2}
    host_ids, host_w = [], []
    for h in range(hosts):
        items = [h * per + i for i in range(per)]
        w = [int(weights[i]) for i in items]
        host_ids.append(m.add_bucket("straw2", 1, items, w, id=-2 - h))
        host_w.append(sum(w))
    m.add_bucket("straw2", 2, host_ids, host_w, id=-1, name="default")
    m.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                           (cmap_mod.RULE_CHOOSELEAF_INDEP, 5, 1),
                           (cmap_mod.RULE_EMIT,)]))
    reweight = np.full(ndev, 0x10000, dtype=np.int64)
    reweight[2] = 0
    mesh = pmesh.make_mesh(8, axis_names=("pg", "unused"))
    flat = jax.sharding.Mesh(
        np.array(jax.devices()).reshape(8), ("pg",))
    xs = np.arange(256)
    got = batched_do_rule(
        m, 0, xs, 5, reweight,
        xs_sharding=NamedSharding(flat, P("pg")))
    for x in xs:
        ref = mapper_ref.crush_do_rule(m, 0, int(x), 5, list(reweight))
        assert list(got[x]) == ref, x


# ---------------------------------------------------------------------------
# mesh-native cluster: placement, pinned pipelines, balancer, recovery


def _toy_osdmap(num_osds=6, pg_num=32):
    from ceph_tpu.crush.map import CrushMap, weight_fixed
    from ceph_tpu.osd.osd_map import OSDMap, PGPool
    m = OSDMap()
    m.set_max_osd(num_osds)
    cm = CrushMap()
    cm.type_names.update({"osd": 0, "host": 1, "root": 2})
    hosts = num_osds // 2
    for h in range(hosts):
        cm.add_bucket("straw2", 1, [2 * h, 2 * h + 1],
                      [weight_fixed(1.0)] * 2, name="host%d" % h)
    cm.add_bucket("straw2", 2, [-1 - h for h in range(hosts)],
                  [weight_fixed(2.0)] * hosts, name="default")
    cm.add_simple_rule("r", "default")
    m.crush = cm
    for o in range(num_osds):
        m.osd_exists[o] = True
        m.osd_up[o] = True
        m.osd_weight[o] = 0x10000
    m.pools[1] = PGPool(1, "p", size=3, pg_num=pg_num, crush_rule=0)
    m.pools[2] = PGPool(2, "q", size=2, pg_num=pg_num // 2,
                        crush_rule=0)
    return m


def test_placement_registry_round_robin():
    """One OSD per chip with zero per-daemon conf: the default
    osd_device_index=-1 round-robins by osd id over the fake mesh."""
    import jax

    from ceph_tpu.parallel.placement import (DevicePlacement,
                                             device_label)
    reg = DevicePlacement()
    devs = jax.devices()
    for osd in range(10):
        dev = reg.resolve(osd)
        assert dev is devs[osd % len(devs)]
    # explicit index wins (modulo the device count)
    assert reg.resolve(99, device_index=3) is devs[3]
    doc = reg.assignments()
    assert doc["num_devices"] == len(devs)
    assert doc["osds"]["0"]["device"] == device_label(devs[0])
    assert doc["osds"]["9"]["device"] == device_label(devs[9 % 8])


def test_pinned_dispatchers_concurrent_disjoint_buffers(codec, payload):
    """Two dispatchers pinned to distinct devices drive concurrently:
    results bit-equal to the host reference, and each pipeline's
    device buffers (the HBM-tier residents it adopts) live ONLY on
    its home device — no shared default-device staging."""
    import threading

    import jax

    from ceph_tpu.osd.hbm_tier import HbmChunkTier
    from ceph_tpu.osd.tpu_dispatch import TpuDispatcher

    dev_a, dev_b = jax.devices()[2], jax.devices()[5]
    ref = np.asarray(codec.encode_batch(payload))
    results = {}

    def drive(name, dev):
        disp = TpuDispatcher(max_delay=0.001, device=dev)
        tier = HbmChunkTier(capacity_objects=8, device=dev)
        try:
            for i in range(4):
                out = np.asarray(disp.encode(
                    codec, payload,
                    resident=(tier, ("pg", "%s-%d" % (name, i)))))
            results[name] = (out, tier)
        finally:
            disp.shutdown()

    t_a = threading.Thread(target=drive, args=("a", dev_a))
    t_b = threading.Thread(target=drive, args=("b", dev_b))
    t_a.start()
    t_b.start()
    t_a.join()
    t_b.join()
    out_a, tier_a = results["a"]
    out_b, tier_b = results["b"]
    assert np.array_equal(out_a, ref)
    assert np.array_equal(out_b, ref)
    # residency is disjoint per home device
    devs_a = {d for batch, _row in tier_a._objs.values()
              for d in batch.arr.devices()}
    devs_b = {d for batch, _row in tier_b._objs.values()
              for d in batch.arr.devices()}
    assert devs_a == {dev_a}, devs_a
    assert devs_b == {dev_b}, devs_b


def test_mesh_balancer_sweep_matches_native_exactly():
    """The sharded all-PG sweep (direction D / carried item 5) must be
    bit-identical to the native mapper — same PG -> OSD mapping for
    every PG of every pool, straight through OSDMapMapping.update."""
    from ceph_tpu.osd.balancer import _sweep
    from ceph_tpu.osd.osd_map import OSDMapMapping

    m = _toy_osdmap()
    native = _sweep(m, None, use_device=False)
    mesh = _sweep(m, None, use_device=False, use_mesh=True)
    assert mesh == native
    # and the full mapping document (up/acting/primaries) agrees too
    a, b = OSDMapMapping(), OSDMapMapping()
    a.update(m, batched=False)
    b.update(m, batched=True, mesh=True)
    assert a.by_pg == b.by_pg


def test_balancer_module_measures_mesh_backend():
    """pick_backend probes all three backends and records medians the
    operator can read back (`balancer status`)."""
    import types

    from ceph_tpu.mgr.modules import BalancerModule

    bal = BalancerModule(types.SimpleNamespace(metrics=None))
    bal.min_speed_samples = 1
    m = _toy_osdmap(pg_num=16)
    best = bal.pick_backend(m)
    assert best in ("native", "device", "mesh")
    for backend in ("native", "device", "mesh"):
        assert len(bal.sweep_samples[backend]) == 1
    meds = bal.sweep_medians()
    assert set(meds) == {"native", "device", "mesh"}


def test_cross_chip_recovery_byte_equality(codec):
    """recover_object's survivor fallback shape: reconstruct one
    missing shard via the mesh (sharded survivors + psum checksum),
    byte-identical to the host decode."""
    from ceph_tpu.osd import ec_util

    sinfo = ec_util.StripeInfo(K, K * 256)
    rng = np.random.default_rng(7)
    payload = rng.integers(0, 256, size=8 * K * 256,
                           dtype=np.uint8).tobytes()
    shards = ec_util.encode(sinfo, codec, payload)
    for target, lost2 in ((5, 2), (0, 4), (3, 1)):
        survivors = {s: v for s, v in shards.items()
                     if s not in (target, lost2)}
        use = tuple(sorted(survivors))[:K]
        survivors = {s: survivors[s] for s in use}
        got = ec_util.recover_cross_chip(sinfo, codec, survivors,
                                         target)
        want = np.asarray(
            ec_util.decode(sinfo, codec, survivors,
                           want={target})[target],
            dtype=np.uint8).tobytes()
        assert got == want, (target, lost2)


def test_cross_chip_recovery_checksum_trips_on_corruption(codec):
    """The psum checksum over the mesh must trip when the survivor
    bytes are corrupted after the host reference sum was taken —
    the device-resident inputs no longer match what was received."""
    from ceph_tpu.parallel.mesh import MeshChecksumError, \
        recover_sharded

    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, size=(8, K, 256), dtype=np.uint8)
    parity = np.asarray(codec.encode_batch(data))
    full = np.concatenate([data, parity], axis=1)
    avail = (0, 1, 3, 4)
    chunks = full[:, list(avail), :].copy()
    expected = int(chunks.astype(np.uint64).sum()) % (1 << 32)
    # clean run reconstructs row 2 exactly
    out = recover_sharded(codec, avail, chunks, 2,
                          expected_sum=expected)
    assert np.array_equal(out, full[:, 2, :])
    # inject corruption AFTER the expected checksum was computed
    chunks[3, 1, 17] ^= 0xFF
    with pytest.raises(MeshChecksumError):
        recover_sharded(codec, avail, chunks, 2,
                        expected_sum=expected)


def test_straggler_keeps_other_devices_within_spread(codec, payload):
    """Wedging ONE pinned pipeline's h2d hop must not stall the other
    devices (no cross-pipeline serialization).

    Deterministic formulation: the straggler's h2d blocks on an Event
    instead of a sleep, and the invariant is ORDERING — the three
    healthy pipelines' encodes complete while pipeline 3 is provably
    still stuck inside its h2d — so a loaded box slows the test down
    but can never flip its verdict (the old wall-clock-rate spread
    comparison flaked under scheduler noise)."""
    import threading

    import jax

    from ceph_tpu.osd.tpu_dispatch import TpuDispatcher

    devs = jax.devices()[:4]
    disps = [TpuDispatcher(max_delay=0.008, device=d) for d in devs]
    gate = threading.Event()
    entered = threading.Event()
    orig_h2d = disps[3]._devops.h2d

    def wedged_h2d(host):
        entered.set()
        assert gate.wait(60), "straggler gate never released"
        return orig_h2d(host)

    results: dict = {}

    def drive(i):
        results[i] = np.asarray(disps[i].encode(codec, payload))

    try:
        expect = np.asarray(disps[0].encode(codec, payload))  # warm
        for d in disps[1:]:
            np.asarray(d.encode(codec, payload))
        disps[3]._devops.h2d = wedged_h2d
        threads = [threading.Thread(target=drive, args=(i,))
                   for i in range(len(disps))]
        for t in threads:
            t.start()
        try:
            # the straggler is INSIDE its h2d hop...
            assert entered.wait(60), "straggler never reached h2d"
            # ...and the healthy pipelines complete while it is stuck
            for i in (0, 1, 2):
                threads[i].join(timeout=60)
                assert not threads[i].is_alive(), \
                    "pipeline %d stalled behind the straggler" % i
                assert np.array_equal(results[i], expect)
            assert threads[3].is_alive(), \
                "straggler finished while its h2d was gated"
        finally:
            gate.set()
        threads[3].join(timeout=60)
        assert not threads[3].is_alive()
        assert np.array_equal(results[3], expect)
    finally:
        gate.set()
        disps[3]._devops.h2d = orig_h2d
        for d in disps:
            d.shutdown()


# ---------------------------------------------------------------------------
# rateless work-stealing dispatch (parallel/rateless.py, direction J)
# ---------------------------------------------------------------------------

class _FakeClock:
    """Injectable monotonic clock: every deadline / blacklist decision
    in RatelessDispatcher reads this, so tests advance logical time
    explicitly instead of sleeping (PR-13 deterministic-clock
    precedent — wall-clock scheduling noise can slow a test down but
    never flip its verdict)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture
def rateless_pair():
    """(dispatcher, injector, fake clock) over 2 devices, torn down."""
    import jax

    from ceph_tpu.parallel.rateless import (DeviceFaultSet,
                                            RatelessDispatcher)
    clk = _FakeClock()
    inj = DeviceFaultSet(seed=3)
    rl = RatelessDispatcher(devices=jax.devices()[:2], clock=clk,
                            injector=inj, name="test-rl")
    yield rl, inj, clk
    rl.shutdown()


def _spin(check, timeout=30.0):
    """Poll a timing-independent predicate: generous wall deadline,
    verdict decided by the predicate alone."""
    import time as _time
    end = _time.monotonic() + timeout
    while _time.monotonic() < end:
        if check():
            return True
        _time.sleep(0.005)
    return check()


class TestRatelessWorkStealing:
    def test_bit_identical_to_fixed_shard_oracle_under_stalls(
            self, codec, payload):
        """Random per-device stalls reshuffle WHICH chip runs each
        micro-batch; the reassembled result must stay bit-identical to
        the oracle, and idle devices must actually steal (a stolen
        micro-batch = completed off its fixed-shard home)."""
        import jax

        from ceph_tpu.parallel.rateless import (DeviceFaultSet,
                                                RatelessDispatcher)
        rng = np.random.default_rng(17)
        inj = DeviceFaultSet(seed=17)
        rl = RatelessDispatcher(devices=jax.devices()[:4],
                                injector=inj, name="steal-rl")
        try:
            want = np.asarray(codec.encode_batch(payload))
            for trial in range(3):
                inj.clear_all()
                for idx in range(4):
                    if rng.random() < 0.5:
                        inj.stall_ms(idx, float(rng.integers(1, 15)))
                got = np.asarray(rl.encode(codec, payload))
                assert np.array_equal(got, want), trial
            assert rl.status()["stolen_total"] > 0
        finally:
            inj.clear_all()
            rl.shutdown()

    def test_lt_coded_decode_bit_identical(self, codec, payload):
        """LT-coded dispatch: coded micro-batches are XORs of seeded
        source subsets; the peeling decoder must reassemble the exact
        plain result from whichever subset lands first."""
        import jax

        from ceph_tpu.parallel.rateless import RatelessDispatcher
        rl = RatelessDispatcher(devices=jax.devices()[:4],
                                name="lt-rl")
        try:
            parity = np.asarray(codec.encode_batch(payload))
            full = np.concatenate([payload, parity], axis=1)
            avail = (0, 2, 3, 5)
            chunks = full[:, list(avail), :]
            want = np.asarray(codec.decode_batch(avail, chunks))
            for seed in (0, 1, 2):
                got = np.asarray(rl.decode(codec, avail, chunks,
                                           lt=True, seed=seed))
                assert np.array_equal(got, want), seed
        finally:
            rl.shutdown()

    def test_queue_path_equals_mesh_do_rule_oracle(self):
        """crush.mesh_do_rule adopts the work queue when no explicit
        mesh is passed: the bulk sweep must equal the scalar oracle."""
        from ceph_tpu.crush import map as cmap_mod, mapper_ref
        from ceph_tpu.crush.batched import mesh_do_rule
        from ceph_tpu.crush.map import CrushMap, Rule
        from ceph_tpu.parallel import rateless

        cm = CrushMap()
        cm.type_names = {"osd": 0, "host": 1, "root": 2}
        host_ids, host_w = [], []
        for h in range(3):
            items = [h * 2 + i for i in range(2)]
            w = [0x10000] * 2
            host_ids.append(cm.add_bucket("straw2", 1, items, w,
                                          id=-2 - h))
            host_w.append(sum(w))
        cm.add_bucket("straw2", 2, host_ids, host_w, id=-1,
                      name="default")
        cm.add_rule(Rule(steps=[(cmap_mod.RULE_TAKE, -1),
                                (cmap_mod.RULE_CHOOSELEAF_INDEP, 3, 1),
                                (cmap_mod.RULE_EMIT,)]))
        weight = np.full(6, 0x10000, dtype=np.int64)
        xs = list(range(48))
        assert rateless.get_dispatcher() is not None, \
            "queue dispatcher unavailable on the 8-device suite"
        got = mesh_do_rule(cm, 0, xs, 3, weight)
        for seed in xs:
            assert list(got[seed]) == mapper_ref.crush_do_rule(
                cm, 0, seed, 3, list(weight)), seed


class TestSpeculativeRedispatch:
    def test_first_result_wins_and_duplicate_discarded(
            self, codec, payload, rateless_pair):
        """Wedge one chip past its (fake-clock) deadline mid-encode:
        the overdue micro-batch is speculatively re-dispatched, the
        healthy chip's copy seals the job, and the straggler's late
        answer is discarded as a duplicate — result bit-identical."""
        import threading
        import time as _time

        from ceph_tpu.common.profiler import PROFILER
        rl, inj, clk = rateless_pair
        want = np.asarray(codec.encode_batch(payload))
        # prime the latency EWMA (deadline stays inf with no sample)
        assert np.array_equal(
            np.asarray(rl.encode(codec, payload)), want)
        prev_enabled, PROFILER.enabled = PROFILER.enabled, True
        inj.stall_ms(0, 400.0)
        stop = threading.Event()

        def tick():
            while not stop.is_set():
                clk.advance(0.05)
                _time.sleep(0.002)

        t = threading.Thread(target=tick, daemon=True)
        t.start()
        try:
            got = np.asarray(rl.encode(codec, payload))
            assert np.array_equal(got, want)
            st = rl.status()
            assert st["redispatch_total"] >= 1
            # the wedged chip's late answers surface as discarded
            # duplicates once it wakes (first-result-wins by seq)
            assert _spin(
                lambda: rl.status()["duplicate_total"] >= 1), \
                rl.status()
            # the duplicated buffers went through the device-memory
            # ledger and were released when their seq sealed
            mem = PROFILER.mem_dump().get("speculative_buffers")
            assert mem is not None and mem["high_watermark"] > 0
            assert _spin(lambda: PROFILER.mem_dump()
                         ["speculative_buffers"]["bytes"] == 0)
        finally:
            stop.set()
            t.join()
            inj.clear_all()
            PROFILER.enabled = prev_enabled


class TestBlacklistProbation:
    def test_strikeout_blacklists_then_probation_readmits(
            self, codec, payload, rateless_pair):
        """Three erroring pulls blacklist the chip; the encode still
        completes on the survivor; after the (fake-clock) backoff one
        canary micro-batch re-admits it to healthy."""
        rl, inj, clk = rateless_pair
        want = np.asarray(codec.encode_batch(payload))
        inj.fail_next(0, 3)
        # the 3 strikes normally land inside one encode (the failing
        # pulls are instant); extra rounds only guard the rare
        # schedule where the survivor drains the queue first
        for _ in range(5):
            assert np.array_equal(
                np.asarray(rl.encode(codec, payload)), want)
            if rl.health[0].state == "blacklisted":
                break
        assert _spin(lambda: rl.health[0].state == "blacklisted")
        assert rl.degraded() == 1
        assert rl.health[0].errors == 3
        # backoff not yet expired: the chip must NOT take work
        assert np.array_equal(
            np.asarray(rl.encode(codec, payload)), want)
        assert rl.health[0].state == "blacklisted"
        # expire the backoff: the next job hands it ONE canary, the
        # canary lands clean (fake clock: dt 0 <= deadline), re-admit
        clk.advance(60.0)
        assert np.array_equal(
            np.asarray(rl.encode(codec, payload)), want)
        assert _spin(lambda: rl.health[0].state == "healthy")
        assert rl.degraded() == 0
        assert rl.health[0].strikes == 0

    def test_failed_canary_doubles_backoff(self, codec, payload,
                                           rateless_pair):
        """A canary that errors goes straight back to the blacklist
        with a DOUBLED backoff (exponential probation)."""
        rl, inj, clk = rateless_pair
        want = np.asarray(codec.encode_batch(payload))
        inj.fail_next(0, 4)          # 3 strikes + 1 failed canary
        for _ in range(5):
            assert np.array_equal(
                np.asarray(rl.encode(codec, payload)), want)
            if rl.health[0].state == "blacklisted":
                break
        assert _spin(lambda: rl.health[0].state == "blacklisted")
        first_until = rl.health[0].blacklist_until
        clk.advance(60.0)
        assert np.array_equal(
            np.asarray(rl.encode(codec, payload)), want)
        assert _spin(lambda: rl.health[0].blacklist_total == 2)
        assert rl.health[0].state == "blacklisted"
        assert rl.health[0].backoffs == 2
        # doubled: the second backoff window is twice the first
        assert (rl.health[0].blacklist_until - clk()) \
            > (first_until - 0.0) * 1.5
        # and a clean canary after the doubled backoff still re-admits
        clk.advance(60.0)
        assert np.array_equal(
            np.asarray(rl.encode(codec, payload)), want)
        assert _spin(lambda: rl.health[0].state == "healthy")


class TestDeadChipDrain:
    def test_mid_batch_kill_drains_and_completes_on_survivor(
            self, codec, payload, rateless_pair):
        """Kill a chip WHILE it holds an in-flight micro-batch: the
        item drains back to the queue (zero lost), the job seals on
        the survivor bit-identically, and the mesh reports n-1."""
        import threading

        rl, inj, clk = rateless_pair
        want = np.asarray(codec.encode_batch(payload))
        # wedge chip 0 so it provably holds work when the kill lands
        inj.stall_ms(0, 250.0)
        got_box: dict = {}

        def drive():
            got_box["out"] = np.asarray(rl.encode(codec, payload))

        t = threading.Thread(target=drive, daemon=True)
        t.start()
        assert _spin(lambda: rl.health[0].inflight >= 1), \
            "chip 0 never pulled a micro-batch"
        inj.kill(0)
        t.join(timeout=60)
        assert not t.is_alive(), "encode hung after mid-batch kill"
        assert np.array_equal(got_box["out"], want)
        assert _spin(lambda: rl.degraded() == 1)
        assert rl.health[0].state == "blacklisted"
        # revive: the chip re-enters via probation, not straight in
        inj.clear_all()
        clk.advance(60.0)
        assert np.array_equal(
            np.asarray(rl.encode(codec, payload)), want)
        assert _spin(lambda: rl.health[0].state == "healthy")
        assert rl.degraded() == 0

    def test_all_chips_killed_falls_back_to_host(self, codec, payload,
                                                 rateless_pair):
        """Degenerate survival: with EVERY chip killed the caller
        thread runs the remaining micro-batches inline — degraded to
        the host, never failed, still bit-identical."""
        rl, inj, clk = rateless_pair
        want = np.asarray(codec.encode_batch(payload))
        inj.kill(0)
        inj.kill(1)
        got = np.asarray(rl.encode(codec, payload))
        assert np.array_equal(got, want)
        inj.clear_all()


class TestCoalesceWaitEwma:
    def test_take_group_wait_tracks_latency_ewma(self):
        """The dispatcher's straggler-wait satellite: _coalesce_wait
        follows the rolling dispatch-latency EWMA instead of pinning
        to the configured max_delay, floored at max_delay/8."""
        from ceph_tpu.osd.tpu_dispatch import TpuDispatcher
        d = TpuDispatcher(max_delay=0.016)
        try:
            # no samples yet: fall back to the configured window
            assert d._coalesce_wait() == d.max_delay
            # fast completions shrink the window (half the EWMA)...
            for _ in range(64):
                d._note_dispatch_wall(0.008)
            assert abs(d._coalesce_wait() - 0.004) < 4e-4
            # ...but never below max_delay/8
            for _ in range(64):
                d._note_dispatch_wall(1e-5)
            assert d._coalesce_wait() == d.max_delay / 8.0
            # slow completions are capped at the configured window
            for _ in range(64):
                d._note_dispatch_wall(1.0)
            assert d._coalesce_wait() == d.max_delay
            st = d.dispatch_status()
            assert st["lat_ewma_ms"] > 0
            assert st["coalesce_wait_ms"] == d.max_delay * 1e3
        finally:
            d.shutdown()


# ---------------------------------------------------------------------------
# DEVICE_DEGRADED health + observability + chaos (cluster level)
# ---------------------------------------------------------------------------

_FAST = {"osd_heartbeat_interval": 0.1, "osd_heartbeat_grace": 0.6,
         "mon_osd_down_out_interval": 1.0,
         "paxos_propose_interval": 0.02}


def _health_checks(client):
    res, _, data = client.mon_command({"prefix": "health"})
    assert res == 0
    return data["checks"]


class TestDeviceDegradedHealth:
    def test_blacklisted_chip_raises_and_clears_device_degraded(
            self, codec, payload):
        """An injector-killed chip blacklists out of the mesh queue;
        the OSD's MPGStats report carries the count, the mon raises
        DEVICE_DEGRADED, and the probation re-admit after revival
        clears it.  The mesh health also shows up in `mesh status`
        asok and in the mgr's Prometheus exposition."""
        import jax

        from ceph_tpu.mgr import MgrDaemon, PrometheusModule
        from ceph_tpu.parallel import rateless
        from ceph_tpu.parallel.rateless import (DeviceFaultSet,
                                                RatelessDispatcher)

        from .cluster_util import MiniCluster, wait_until

        cluster = MiniCluster(num_mons=1, num_osds=3,
                              conf_overrides=_FAST).start()
        inj = DeviceFaultSet(seed=5)
        rl = RatelessDispatcher(devices=jax.devices()[:2],
                                injector=inj, name="health-rl")
        old = rateless.get_dispatcher(create=False)
        rateless.set_dispatcher(rl)
        mgr = MgrDaemon(cluster.monmap)
        mgr.init()
        for osd in cluster.osds.values():
            osd.mgr_addr = mgr.addr
        try:
            client = cluster.client()
            inj.kill(0)
            assert wait_until(lambda: rl.degraded() >= 1, timeout=10)
            assert wait_until(
                lambda: "DEVICE_DEGRADED" in _health_checks(client),
                timeout=20)
            check = _health_checks(client)["DEVICE_DEGRADED"]
            assert check["severity"] == "warning"
            assert any("blacklisted" in d for d in check["detail"])
            # mesh status asok carries the per-device health table
            doc = cluster.osds[0]._mesh_status()["rateless"]
            states = {row["device"]: row["state"]
                      for row in doc["devices"]}
            assert "blacklisted" in states.values()
            assert {"ewma_ms", "inflight", "stolen", "redispatched",
                    "blacklisted", "probation"} <= set(
                        doc["devices"][0])
            # ...and the mgr exports the device-health series
            prom = mgr.register_module(PrometheusModule)
            assert wait_until(
                lambda: "ceph_tpu_device_health" in prom.render(),
                timeout=15)
            text = prom.render()
            assert "ceph_tpu_mesh_blacklist" in text
            assert "ceph_tpu_mesh_redispatch_total" in text
            # revive: the canary path re-admits the chip, the osd
            # re-reports zero, the mon clears the check
            inj.revive(0)

            def readmitted():
                np.asarray(rl.encode(codec, payload[:2]))
                return rl.degraded() == 0
            assert wait_until(readmitted, timeout=20)
            assert wait_until(
                lambda: "DEVICE_DEGRADED"
                not in _health_checks(client), timeout=20)
        finally:
            rateless.set_dispatcher(old)
            rl.shutdown()
            mgr.shutdown()
            cluster.stop()


@pytest.mark.slow
class TestChipKillChaos:
    def test_chip_chaos_under_io_reaches_health_ok(self, codec,
                                                   payload):
        """Long leg: the thrasher kills/revives mesh chips while
        client IO and rateless encodes run; when the dust settles
        every encode stayed bit-identical, the devices are all
        re-admitted, and the cluster reports HEALTH_OK."""
        import jax

        from ceph_tpu.parallel import rateless
        from ceph_tpu.parallel.rateless import (DEVICE_FAULTS,
                                                RatelessDispatcher)

        from .cluster_util import MiniCluster, wait_until
        from .thrasher import Thrasher

        cluster = MiniCluster(num_mons=1, num_osds=3,
                              conf_overrides=_FAST).start()
        rl = RatelessDispatcher(devices=jax.devices()[:4],
                                injector=DEVICE_FAULTS,
                                name="chaos-rl")
        old = rateless.get_dispatcher(create=False)
        rateless.set_dispatcher(rl)
        want = np.asarray(codec.encode_batch(payload))
        try:
            client = cluster.client()
            cluster.create_replicated_pool(client, "chaos", size=2,
                                           pg_num=4)
            ioctx = client.open_ioctx("chaos")
            thrasher = Thrasher(cluster, seed=11, min_in=3,
                                device_thrash_prob=0.9,
                                interval=0.2)
            thrasher.start()
            try:
                for i in range(30):
                    ioctx.write_full("c%d" % i, b"%d" % i * 64)
                    got = np.asarray(rl.encode(codec, payload))
                    assert np.array_equal(got, want), i
            finally:
                thrasher.stop_and_heal()
            assert thrasher.log, "thrasher never acted"
            assert any(a[0] == "device_kill" for a in thrasher.log), \
                "no chip was ever killed: %s" % (thrasher.log[:8],)
            # every chip re-admits through probation once work flows
            def all_healthy():
                np.asarray(rl.encode(codec, payload[:2]))
                return rl.degraded() == 0
            assert wait_until(all_healthy, timeout=30)

            def healthy():
                _, _, data = client.mon_command({"prefix": "health"})
                return bool(data) and data.get("status") == "HEALTH_OK"
            assert wait_until(healthy, timeout=40), \
                client.mon_command({"prefix": "health"})[1]
            for i in range(30):
                assert ioctx.read("c%d" % i) == b"%d" % i * 64, i
        finally:
            DEVICE_FAULTS.clear_all()
            rateless.set_dispatcher(old)
            rl.shutdown()
            cluster.stop()

"""Map-churn survival (ISSUE 19): incremental OSDMap pipeline,
trim/full-map fallback, peering storm control, huge-map balancer
convergence and the map-churn thrash riders.

Mirrors the reference's OSDMap/MOSDMap machinery
(OSDMonitor::build_incremental + send_incremental, osd_map_message_max
batching, mon_min_osdmap_epochs trimming, OSD::osd_map_max_advance) at
in-process scale: a subscriber behind the trim floor gets exactly one
full map, everyone else catches up through bounded incremental frames,
and a daemon applies at most osd_map_max_advance epochs per tick.
"""

from __future__ import annotations

import random
import threading
import time

import pytest

from ceph_tpu import encoding
from ceph_tpu.osd.osd_map import (CRUSH_ITEM_NONE, Incremental, OSDMap,
                                  OSDMapMapping, PGID)
from ceph_tpu.tools import osdmaptool

from .cluster_util import MiniCluster, wait_until
from .thrasher import Thrasher

FAST = {"osd_heartbeat_interval": 0.1, "osd_heartbeat_grace": 0.6,
        "mon_osd_down_out_interval": 1.0, "paxos_propose_interval": 0.02}


def _churn_epochs(client, cluster, n: int, seed: int = 0) -> None:
    """Drive at least n committed osdmap epochs via reweight churn
    (every accepted reweight is one epoch)."""
    rng = random.Random(seed)
    start = cluster.osdmap_epoch()
    osds = sorted(cluster.osds)
    i = 0
    while cluster.osdmap_epoch() < start + n:
        osd = osds[i % len(osds)]
        w = rng.uniform(0.7, 0.99)
        # reweights pend until the next paxos propose: capture the
        # target epoch BEFORE the command (the pend can commit before
        # mon_command returns) and wait for the commit so each round
        # lands its own epoch instead of merging
        want = cluster.osdmap_epoch() + 1
        res, outs, _ = client.mon_command(
            {"prefix": "osd reweight", "id": osd, "weight": w})
        assert res == 0, outs
        assert wait_until(
            lambda: cluster.osdmap_epoch() >= want, timeout=30), \
            "reweight never committed (epoch %d)" \
            % cluster.osdmap_epoch()
        i += 1
        assert i < n * 8, "churn stalled at epoch %d (want %d)" \
            % (cluster.osdmap_epoch(), start + n)


# ---------------------------------------------------------------------------
# property test: incremental fold == mon full map, bit-equal encoded


def _random_inc(rng: random.Random, m: OSDMap) -> Incremental:
    """One random churn inc drawn from the steady-state classes:
    up/down flaps, reweights, pg_temp/primary_temp overlays, upmap
    edits."""
    inc = Incremental(m.epoch + 1)
    pool = m.pools[0]
    roll = rng.random()
    pgid = PGID(0, rng.randrange(pool.pg_num))
    if roll < 0.25:
        osd = rng.randrange(m.max_osd)
        if m.is_up(osd):
            inc.new_down.append(osd)
        else:
            inc.new_up[osd] = ("127.0.0.1", 6800 + osd)
    elif roll < 0.45:
        inc.new_weight[rng.randrange(m.max_osd)] = \
            rng.choice([0x8000, 0xc000, 0xffff, 0x10000])
    elif roll < 0.65:
        if pgid in m.pg_temp and rng.random() < 0.5:
            inc.new_pg_temp[pgid] = []          # clear
        else:
            inc.new_pg_temp[pgid] = sorted(
                rng.sample(range(m.max_osd), pool.size))
    elif roll < 0.8:
        if pgid in m.primary_temp and rng.random() < 0.5:
            inc.new_primary_temp[pgid] = -1     # clear
        else:
            inc.new_primary_temp[pgid] = rng.randrange(m.max_osd)
    else:
        if pgid in m.pg_upmap_items and rng.random() < 0.5:
            inc.old_pg_upmap_items.append(pgid)
        else:
            a, b = rng.sample(range(m.max_osd), 2)
            inc.new_pg_upmap_items[pgid] = [(a, b)]
    return inc


class TestIncrementalProperty:
    def test_random_inc_folds_bit_equal_to_full_map(self):
        """Fold 60 random Incrementals through a wire round-trip
        (encode/decode each inc) into a follower map; at EVERY epoch
        the follower must encode bit-identical to the authoritative
        map.  Mid-sequence, simulate trim-floor fallbacks: replace the
        follower with a decoded full-map snapshot and keep folding."""
        rng = random.Random(1234)
        mon = osdmaptool.create_simple(12, pg_num=64, pool_size=3,
                                       hosts=6)
        follower = encoding.decode_any(encoding.encode_any(mon))
        assert encoding.encode_any(follower) == \
            encoding.encode_any(mon)
        for step in range(60):
            inc = _random_inc(rng, mon)
            mon.apply_incremental(inc)
            wire_inc = encoding.decode_any(encoding.encode_any(inc))
            follower.apply_incremental(wire_inc)
            assert encoding.encode_any(follower) == \
                encoding.encode_any(mon), \
                "divergence at epoch %d (step %d)" % (mon.epoch, step)
            if step % 17 == 16:
                # trim-floor fallback boundary: the follower is thrown
                # away and re-seeded from one full wire map
                follower = encoding.decode_any(
                    encoding.encode_any(mon))
                assert follower.epoch == mon.epoch
                assert encoding.encode_any(follower) == \
                    encoding.encode_any(mon)

    def test_mapping_incremental_matches_full_rebuild(self):
        """OSDMapMapping.apply_incremental on overlay-only incs must
        land on exactly the state a full rebuild computes — while
        touching only the affected PGs."""
        rng = random.Random(77)
        m = osdmaptool.create_simple(16, pg_num=128, pool_size=3,
                                     hosts=8)
        mapping = OSDMapMapping()
        mapping.update(m, batched=False)
        pool = m.pools[0]
        total = pool.pg_num
        saw_incremental = False
        for step in range(30):
            inc = Incremental(m.epoch + 1)
            pgid = PGID(0, rng.randrange(pool.pg_num))
            roll = rng.random()
            if roll < 0.3:
                inc.new_pg_temp[pgid] = sorted(
                    rng.sample(range(m.max_osd), pool.size))
            elif roll < 0.5:
                inc.new_primary_temp[pgid] = rng.randrange(m.max_osd)
            elif roll < 0.7:
                a, b = rng.sample(range(m.max_osd), 2)
                inc.new_pg_upmap_items[pgid] = [(a, b)]
            elif roll < 0.85:
                up = [o for o in range(m.max_osd) if m.is_up(o)]
                if len(up) <= 3:
                    continue
                inc.new_down.append(rng.choice(up))
            else:
                if not m.pg_upmap_items:
                    continue
                inc.old_pg_upmap_items.append(
                    rng.choice(sorted(m.pg_upmap_items, key=str)))
            m.apply_incremental(inc)
            info = mapping.apply_incremental(m, inc, batched=False)
            assert info["mode"] == "incremental", (step, info)
            assert info["recomputed"] < total, \
                "incremental apply recomputed the whole pool"
            saw_incremental = True
            ref = OSDMapMapping()
            ref.update(m, batched=False)
            assert mapping.by_pg == ref.by_pg, "step %d" % step
            assert {o: sorted(pgs, key=str)
                    for o, pgs in mapping.by_osd.items() if pgs} == \
                   {o: sorted(pgs, key=str)
                    for o, pgs in ref.by_osd.items() if pgs}, \
                "by_osd divergence at step %d" % step
        assert saw_incremental

    def test_mapping_falls_back_on_weight_change(self):
        """A reweight moves raw placements: the mapping must take the
        full-rebuild path, not pretend the overlay math covers it."""
        m = osdmaptool.create_simple(8, pg_num=32, hosts=4)
        mapping = OSDMapMapping()
        mapping.update(m, batched=False)
        inc = Incremental(m.epoch + 1)
        inc.new_weight[0] = 0x8000
        m.apply_incremental(inc)
        info = mapping.apply_incremental(m, inc, batched=False)
        assert info["mode"] == "full"
        ref = OSDMapMapping()
        ref.update(m, batched=False)
        assert mapping.by_pg == ref.by_pg


# ---------------------------------------------------------------------------
# mon-side: inc ring, batching, trim-floor fallback, re-push, status


class TestMonMapPipeline:
    def test_batched_catchup_and_wire_accounting(self):
        """A subscriber N epochs behind catches up through frames of
        at most osd_map_message_max incrementals each, and the inc
        path ships far fewer bytes than re-sending full maps."""
        conf = dict(FAST)
        conf["osd_map_message_max"] = 4
        cluster = MiniCluster(num_mons=1, num_osds=3,
                              conf_overrides=conf).start()
        try:
            client = cluster.client()
            mon = cluster.leader()
            # a stale follower snapshotted before the churn
            stale = encoding.decode_any(
                encoding.encode_any(mon.osdmon.osdmap))
            _churn_epochs(client, cluster, 10)
            full_size = len(encoding.encode_any(mon.osdmon.osdmap))
            frames = 0
            inc_bytes = 0
            while True:
                m = mon.osdmon.build_map_message(stale.epoch)
                if m is None:
                    break
                frames += 1
                assert m.full_map is None, \
                    "above the trim floor yet got a full map"
                assert 1 <= len(m.incrementals) <= 4
                for inc in m.incrementals:
                    inc_bytes += len(encoding.encode_any(inc))
                    stale.apply_incremental(inc)
                assert frames < 50
            lag = mon.osdmon.osdmap.epoch - stale.epoch
            assert lag == 0
            assert encoding.encode_any(stale) == \
                encoding.encode_any(mon.osdmon.osdmap)
            assert frames >= 3, "10+ epochs should need >=3 frames of 4"
            # sub-linear wire claim at test scale: shipping the incs
            # must beat shipping one full map per frame
            assert inc_bytes < frames * full_size, \
                "incs (%d B over %d frames) not cheaper than full " \
                "maps (%d B each)" % (inc_bytes, frames, full_size)
        finally:
            cluster.stop()

    def test_trim_floor_fallback_ships_one_full_map(self):
        """A subscriber below mon_min_osdmap_epochs' trim floor gets
        EXACTLY one full map, never an unbounded inc chain."""
        conf = dict(FAST)
        conf["mon_min_osdmap_epochs"] = 4
        cluster = MiniCluster(num_mons=1, num_osds=3,
                              conf_overrides=conf).start()
        try:
            client = cluster.client()
            mon = cluster.leader()
            behind_epoch = cluster.osdmap_epoch()
            _churn_epochs(client, cluster, 12)
            assert mon.osdmon.first_committed() > behind_epoch + 1, \
                "ring never trimmed past the stale epoch"
            m = mon.osdmon.build_map_message(behind_epoch)
            assert m is not None and m.full_map is not None
            assert not m.incrementals
            caught = encoding.decode_any(m.full_map)
            assert caught.epoch == m.epoch
            # exactly one frame: at the shipped epoch there is nothing
            # further to send
            assert mon.osdmon.build_map_message(caught.epoch) is None \
                or caught.epoch < mon.osdmon.osdmap.epoch
        finally:
            cluster.stop()

    def test_repush_is_bounded_per_subscriber(self):
        """The mon tick re-pushes catch-up frames to a lagging
        subscriber, but a subscriber that never renews (dead client)
        stops getting frames after 8 strikes."""
        cluster = MiniCluster(num_mons=1, num_osds=3,
                              conf_overrides=FAST).start()
        try:
            client = cluster.client()
            mon = cluster.leader()
            _churn_epochs(client, cluster, 4)
            fake = ("127.0.0.1", 65001)
            sent = []
            orig = mon.msgr.send_message

            def spy(msg, addr):
                if tuple(addr) == fake:
                    sent.append(msg)
                    return
                return orig(msg, addr)

            mon.msgr.send_message = spy
            try:
                with mon._lock:
                    mon._subscribers[fake] = 1
                for _ in range(12):
                    mon._repush_lagging_subs()
                    state = mon._sub_repush.get(fake)
                    if state is not None:
                        state[0] = 0.0     # defeat the 1/s rate limit
                assert len(sent) == 8, \
                    "re-push not strike-bounded: %d frames" % len(sent)
                for m in sent:
                    assert m.get_type() == "MOSDMap"
                # progress rearms the strikes: the subscriber reports
                # a newer (still lagging) epoch and gets frames again
                with mon._lock:
                    mon._subscribers[fake] = 2
                mon._repush_lagging_subs()
                assert len(sent) == 9
            finally:
                mon.msgr.send_message = orig
        finally:
            cluster.stop()

    def test_osdmap_status_surfaces(self):
        """'osdmap status' (asok) and 'osd map status' (mon command)
        dump ring span, trim floor and the laggiest subscriber."""
        cluster = MiniCluster(num_mons=1, num_osds=3,
                              conf_overrides=FAST).start()
        try:
            client = cluster.client()
            mon = cluster.leader()
            _churn_epochs(client, cluster, 5)
            res, outs, doc = client.mon_command(
                {"prefix": "osd map status"})
            assert res == 0, outs
            assert doc["epoch"] == mon.osdmon.osdmap.epoch
            assert doc["ring_epochs"] >= 5
            assert doc["ring_span"][0] == doc["trim_floor"]
            assert doc["ring_span"][1] == doc["epoch"]
            assert doc["ring_bytes"] > 0
            assert doc["subscribers"] >= 1
            lag = doc["laggiest_subscriber"]
            assert lag is None or lag["lag_epochs"] >= 0
            # asok lane: register against a real admin socket
            import os
            import tempfile
            if mon.ctx.admin_socket is None:
                path = os.path.join(tempfile.mkdtemp(), "mon.asok")
                mon.ctx.init_admin_socket(path)
            mon.register_admin_commands()
            mon.register_admin_commands()   # idempotent
            out = mon.ctx.admin_socket.execute("osdmap status", {})
            assert out["trim_floor"] == doc["trim_floor"]
        finally:
            cluster.stop()


# ---------------------------------------------------------------------------
# client-side: map-advance throttle


class _FakeMsgr:
    def __init__(self):
        self.sent = []
        self.my_addr = ("127.0.0.1", 59999)

    def add_dispatcher_tail(self, d):
        pass

    def send_message(self, msg, addr):
        self.sent.append((msg, tuple(addr)))


class TestMapAdvanceThrottle:
    def _mk(self, max_advance: int):
        from ceph_tpu.mon.mon_client import MonClient
        mc = MonClient({0: ("127.0.0.1", 1)}, _FakeMsgr(), "osd.0")
        mc.map_max_advance = max_advance
        return mc

    def test_advance_slices_respect_budget(self):
        from ceph_tpu.msg.message import MOSDMap
        mc = self._mk(3)
        advances = []
        mc.map_callbacks.append(lambda m: advances.append(m.epoch))
        base = osdmaptool.create_simple(4, pg_num=8)
        mon = base.clone()
        incs = []
        for _ in range(11):
            inc = Incremental(mon.epoch + 1)
            inc.new_weight[0] = 0x10000
            mon.apply_incremental(inc)
            incs.append(encoding.decode_any(encoding.encode_any(inc)))
        mc._handle_osdmap(MOSDMap(
            full_map=encoding.encode_any(base),
            incrementals=incs, epoch=mon.epoch))
        # first drain: full map + 3 incs
        assert mc.osdmap.epoch == base.epoch + 3
        assert mc.map_lag_epochs() == mon.epoch - mc.osdmap.epoch
        epochs = [mc.osdmap.epoch]
        for _ in range(4):
            mc.renew_subs(min_interval=0.0)
            epochs.append(mc.osdmap.epoch)
        assert epochs == [base.epoch + 3, base.epoch + 6,
                          base.epoch + 9, mon.epoch, mon.epoch]
        assert mc.map_lag_epochs() == 0
        assert not mc._inc_backlog
        assert advances, "map callbacks never fired"
        assert encoding.encode_any(mc.osdmap) == \
            encoding.encode_any(mon)

    def test_gap_triggers_resubscribe(self):
        """A dropped frame leaves a hole: the client must re-subscribe
        at its current epoch instead of wedging on the backlog."""
        from ceph_tpu.msg.message import MOSDMap
        mc = self._mk(150)
        base = osdmaptool.create_simple(4, pg_num=8)
        mon = base.clone()
        incs = []
        for _ in range(4):
            inc = Incremental(mon.epoch + 1)
            inc.new_weight[1] = 0x10000
            mon.apply_incremental(inc)
            incs.append(inc)
        # deliver the full map, then ONLY the last two incs (the first
        # two frames were "dropped")
        mc._handle_osdmap(MOSDMap(full_map=encoding.encode_any(base),
                                  incrementals=[], epoch=base.epoch))
        mc.msgr.sent.clear()
        mc._handle_osdmap(MOSDMap(incrementals=incs[2:],
                                  epoch=mon.epoch))
        assert mc.osdmap.epoch == base.epoch   # cannot apply past gap
        assert mc.map_lag_epochs() == 4
        subs = [m for m, _ in mc.msgr.sent
                if m.get_type() == "MMonSubscribe"]
        assert subs and subs[-1].start_epoch == base.epoch
        # the mon answers with the missing span: now it all applies
        mc._handle_osdmap(MOSDMap(incrementals=incs[:2],
                                  epoch=mon.epoch))
        assert mc.osdmap.epoch == mon.epoch
        assert mc.map_lag_epochs() == 0


# ---------------------------------------------------------------------------
# long-offline OSD: rejoin through the trim-floor full-map path


class TestTrimFloorRejoin:
    def test_long_offline_osd_rejoins_past_trim_floor(self):
        """An OSD that slept through more epochs than the mon retains
        incrementals for must rejoin via the one-full-map fallback and
        serve data again."""
        conf = dict(FAST)
        conf["mon_min_osdmap_epochs"] = 4
        cluster = MiniCluster(num_mons=1, num_osds=3,
                              conf_overrides=conf).start()
        try:
            client = cluster.client()
            cluster.create_replicated_pool(client, "sleepy", size=2,
                                           pg_num=4)
            ioctx = client.open_ioctx("sleepy")
            for i in range(6):
                ioctx.write_full("s%d" % i, b"payload-%d" % i * 64)
            victim = 2
            sleep_epoch = cluster.osdmap_epoch()
            store = cluster.stop_osd(victim)
            assert wait_until(
                lambda: cluster.leader().osdmon.osdmap.is_down(victim),
                15)
            _churn_epochs(client, cluster, 10, seed=3)
            mon = cluster.leader()
            assert mon.osdmon.first_committed() > sleep_epoch + 1, \
                "churn never pushed the trim floor past the sleeper"
            cluster.revive_osd(victim, store=store)
            client.mon_command({"prefix": "osd in", "id": victim})
            assert wait_until(cluster.all_osds_up, timeout=30)
            osd = cluster.osds[victim]
            assert wait_until(
                lambda: osd.osdmap.epoch >= mon.osdmon.osdmap.epoch
                - 1, timeout=30), \
                "revived osd stuck at epoch %d (mon at %d)" \
                % (osd.osdmap.epoch, mon.osdmon.osdmap.epoch)
            for i in range(6):
                assert ioctx.read("s%d" % i) == b"payload-%d" % i * 64
        finally:
            cluster.stop()


# ---------------------------------------------------------------------------
# map-churn thrash riders under live traffic


class TestMapChurnRiders:
    def test_riders_drive_epochs_and_heal(self):
        """Deterministic rider pass: out/in storm, reweight sweep and
        a churn-pool resize under a live writer — epochs advance, the
        resize instantiates new PGs, and the cluster heals to every
        acked object intact."""
        cluster = MiniCluster(num_mons=1, num_osds=4,
                              conf_overrides=FAST).start()
        try:
            client = cluster.client()
            cluster.create_replicated_pool(client, "riderdata",
                                           size=2, pg_num=8)
            cluster.create_replicated_pool(client, "riderchurn",
                                           size=2, pg_num=4)
            ioctx = client.open_ioctx("riderdata")
            stop_evt = threading.Event()
            acked = []

            def writer():
                i = 0
                while not stop_evt.is_set():
                    try:
                        ioctx.write_full("r%d" % i, b"x%d" % i * 128)
                        acked.append(i)
                    except Exception:
                        pass
                    i += 1
                    time.sleep(0.02)

            wt = threading.Thread(target=writer, daemon=True)
            wt.start()
            th = Thrasher(cluster, seed=9, min_in=2, interval=0.2,
                          churn_pool="riderchurn")
            # riders pend into paxos proposes and coalesce freely
            # under load — on a starved box ALL of them can merge
            # into one commit, so wait for a commit between riders
            # instead of demanding a fixed total afterwards
            e0 = cluster.osdmap_epoch()
            assert th.out_in_storm(count=2)
            assert wait_until(
                lambda: cluster.osdmap_epoch() >= e0 + 1, timeout=30)
            e1 = cluster.osdmap_epoch()
            assert th.reweight_sweep(count=2)
            assert wait_until(
                lambda: cluster.osdmap_epoch() >= e1 + 1, timeout=30)
            e2 = cluster.osdmap_epoch()
            assert th.pool_resize(grow_by=4) == 8
            assert wait_until(
                lambda: cluster.osdmap_epoch() >= e2 + 1, timeout=30)
            assert cluster.osdmap_epoch() >= e0 + 3
            # the split instantiated PGs: some OSD holds a riderchurn
            # PG with ps >= 4
            pool_id = client.pool_id("riderchurn")

            def split_pgs_exist():
                return any(k.pool == pool_id and k.ps >= 4
                           for osd in cluster.osds.values()
                           for k in list(osd.pgs))
            assert wait_until(split_pgs_exist, timeout=30), \
                "pool resize never instantiated the new PGs"
            th.stop_and_heal(timeout=60)

            # weights restored: no lingering override (the restore
            # pends until the next paxos propose)
            def weights_restored():
                m = cluster.leader().osdmon.osdmap
                return all(m.osd_weight[o] == 0x10000
                           for o in cluster.osds)
            assert wait_until(weights_restored, timeout=30)

            def healthy():
                _, _, data = client.mon_command({"prefix": "health"})
                return bool(data) and \
                    data.get("status") == "HEALTH_OK"
            assert wait_until(healthy, timeout=60)
            # churn may block (not fail) in-flight writes; once healed
            # the writer must make progress again
            n_heal = len(acked)
            assert wait_until(lambda: len(acked) > n_heal + 5,
                              timeout=30), \
                "IO never resumed after heal (%d acked)" % len(acked)
            stop_evt.set()
            wt.join(timeout=10)
            for i in list(acked):
                assert ioctx.read("r%d" % i) == b"x%d" % i * 128, i
        finally:
            cluster.stop()

    def test_peering_gate_dump_reaches_asok(self):
        """The peering reserver rides dump_reservations and 'osdmap
        status' on the OSD asok."""
        import os
        import tempfile
        cluster = MiniCluster(num_mons=1, num_osds=3,
                              conf_overrides=FAST).start()
        try:
            client = cluster.client()
            cluster.create_replicated_pool(client, "gated", size=2,
                                           pg_num=8)
            osd = cluster.osds[0]
            assert "peering" in osd.reservations
            assert osd.peering_gate
            doc = osd._osdmap_status()
            assert doc["epoch"] == osd.osdmap.epoch
            assert doc["map_max_advance"] == 150
            assert doc["peering_gate"] is True
            assert doc["lag_epochs"] >= 0
            # all slots drain back once the fresh pool finishes peering
            assert wait_until(
                lambda: osd._osdmap_status()["peering_active"] == 0,
                timeout=30), osd._osdmap_status()
            # p99 lane has samples once any PG peered
            assert wait_until(
                lambda: any(o.peering_p99() >= 0.0
                            and len(o._peering_durations) > 0
                            for o in cluster.osds.values()),
                timeout=20), "no peering durations recorded"
        finally:
            cluster.stop()


# ---------------------------------------------------------------------------
# huge-map balancer convergence (tier-1 64-OSD variant; 1000-OSD slow)


def _converge(m: OSDMap, changes_per_sweep: int, max_changes: int,
              rounds: int):
    from ceph_tpu.osd.balancer import calc_pg_upmaps, eval_distribution
    before = eval_distribution(m, use_device=True)
    res = calc_pg_upmaps(m, max_deviation_ratio=0.05,
                         max_changes=max_changes, use_device=True,
                         changes_per_sweep=changes_per_sweep)
    assert res.sweeps <= rounds, \
        "needed %d sweeps (cap %d)" % (res.sweeps, rounds)
    inc = Incremental(m.epoch + 1)
    res.apply_to(inc)
    m.apply_incremental(inc)
    after = eval_distribution(m, use_device=True)
    return before, res, after


class TestHugeMapConvergence:
    def test_64osd_batched_sweep_converges(self):
        from .test_balancer import assert_failure_domains_intact
        m = osdmaptool.create_simple(64, pg_num=1024, pool_size=3,
                                     hosts=16)
        before, res, after = _converge(m, changes_per_sweep=16,
                                       max_changes=400, rounds=60)
        assert after.total_deviation <= before.total_deviation
        worst = max(abs(after.deviation(o)) / t
                    for o, t in after.targets.items() if t > 0)
        assert worst <= 0.15, (worst, res.num_changed)
        # the batch amortization actually batched: far fewer sweeps
        # than accepted changes
        if res.num_changed > 32:
            assert res.sweeps < res.num_changed
        assert_failure_domains_intact(m)

    @pytest.mark.slow
    def test_1000osd_map_converges_via_mesh_sweep(self):
        """Scale leg: a 1000-OSD map balances within a bounded round
        count, never violating the rule's failure-domain separation
        (sampled).  The bulk sweeps run the compiled host mapper (the
        honest comparator on a CPU-only host); a sampled mesh_do_rule pass gates that the mesh-sharded
        device sweep is bit-identical on the SAME balanced map, so on
        real hardware the full-width sweep is interchangeable."""
        from ceph_tpu.crush.batched import mesh_do_rule
        from ceph_tpu.osd.balancer import (calc_pg_upmaps,
                                           eval_distribution,
                                           parent_index,
                                           parent_of_type,
                                           rule_failure_domain)
        m = osdmaptool.create_simple(1000, pg_num=32768, pool_size=3,
                                     hosts=250)
        before = eval_distribution(m, use_native=True)
        res = calc_pg_upmaps(m, max_deviation_ratio=0.1,
                             max_changes=3000, use_native=True,
                             changes_per_sweep=128)
        assert res.sweeps <= 40, res.sweeps
        inc = Incremental(m.epoch + 1)
        res.apply_to(inc)
        m.apply_incremental(inc)
        after = eval_distribution(m, use_native=True)
        assert after.total_deviation <= before.total_deviation
        worst = max(abs(after.deviation(o)) / t
                    for o, t in after.targets.items() if t > 0)
        assert worst <= 0.25, (worst, res.num_changed, res.sweeps)
        # sampled CRUSH-constraint validation over the remapped PGs
        fd = rule_failure_domain(m.crush, 0)
        pindex = parent_index(m.crush)
        rng = random.Random(5)
        check = rng.sample(sorted(m.pg_upmap_items, key=str),
                           min(200, len(m.pg_upmap_items)))
        for pgid in check:
            up, _, _, _ = m.pg_to_up_acting_osds(pgid)
            osds = [o for o in up if o != CRUSH_ITEM_NONE]
            assert len(set(osds)) == len(osds), (pgid, up)
            parents = [parent_of_type(m.crush, o, fd, pindex)
                       for o in osds]
            assert len(set(parents)) == len(parents), (pgid, up)
        # mesh-sweep parity on the balanced map: 256 sampled seeds
        # through the mesh-sharded device kernel vs the native rows
        from ceph_tpu.native import crush_do_rule_batch_native
        pool = m.pools[0]
        import numpy as np
        sample_ps = rng.sample(range(pool.pg_num), 256)
        seeds = np.array([pool.raw_pg_to_pps(PGID(0, ps))
                          for ps in sample_ps], dtype=np.int64)
        w = m._weight_vector()
        mesh_rows = mesh_do_rule(m.crush, pool.crush_rule, seeds,
                                 pool.size, w, choose_args=0)
        nat_rows = crush_do_rule_batch_native(
            m.crush, pool.crush_rule, seeds, pool.size, w,
            choose_args=0)
        for i in range(len(seeds)):
            dev = [int(v) for v in mesh_rows[i]
                   if int(v) != CRUSH_ITEM_NONE]
            assert dev == nat_rows[i], \
                "mesh/native divergence at seed %d" % seeds[i]

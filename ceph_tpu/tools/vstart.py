"""vstart: boot a development cluster in one process.

Counterpart of the reference's src/vstart.sh (and the
qa/standalone/ceph-helpers.sh run_mon/run_osd pattern): start N
monitors, N OSDs and optionally an mgr on localhost, write a monmap
file other tools (rados, ceph CLI) can point at, then serve until
interrupted. Stores are MemStore by default or FileStore under
--data DIR for durability across restarts.

  vstart --mons 1 --osds 3 --monmap /tmp/monmap [--data /tmp/cstore]
  rados --monmap /tmp/monmap mkpool data
  rados --monmap /tmp/monmap -p data bench 10 write
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import sys
import time

from ..common.compile_cache import configure_compile_cache
from ..common.context import Context
from ..mgr.mgr_daemon import MgrDaemon
from ..mon.monitor import Monitor
from ..osd.osd_daemon import OSDDaemon


def free_ports(n: int) -> list[int]:
    socks = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="vstart", description="run a dev cluster in one process")
    p.add_argument("--mons", type=int, default=1)
    p.add_argument("--osds", type=int, default=3)
    p.add_argument("--mgr", action="store_true",
                   help="also run a manager daemon")
    p.add_argument("--monmap", required=True,
                   help="write the monmap here for client tools")
    p.add_argument("--data",
                   help="directory for FileStore-backed OSDs "
                        "(default: in-memory stores)")
    p.add_argument("--asok-dir",
                   help="create per-daemon admin sockets here "
                        "(drive with: ceph daemon <dir>/osd.N.asok "
                        "perf dump)")
    p.add_argument("--conf", action="append", default=[],
                   metavar="KEY=VALUE", help="config override")
    p.add_argument("--run-seconds", type=float, default=0,
                   help="exit after N seconds (0 = until SIGINT)")
    return p


def parse_overrides(pairs) -> dict:
    """--conf KEY=VALUE pairs -> typed config overrides."""
    overrides = {}
    for kv in pairs:
        k, _, v = kv.partition("=")
        try:
            overrides[k] = float(v) if "." in v else int(v)
        except ValueError:
            overrides[k] = v
    return overrides


class DevCluster:
    """The daemons one vstart boot runs in this process: monitors,
    OSDs and an optional mgr, plus the stop/start of single OSDs that
    a failure drill needs (a stopped OSD keeps its store, so a restart
    keeps its data)."""

    def __init__(self, monmap: dict, overrides: dict,
                 data: str | None = None, asok_dir: str | None = None):
        self.monmap = monmap
        self.overrides = dict(overrides)
        self.data = data
        self.asok_dir = asok_dir
        self.mons: list = []
        self.osds: dict = {}
        self.mgr = None

    def leader(self):
        return next((m for m in self.mons if m.is_leader()), None)

    def _new_store(self, osd_id: int):
        if not self.data:
            return None
        path = os.path.join(self.data, "osd.%d" % osd_id)
        os.makedirs(path, exist_ok=True)
        # osd_objectstore picks the durable backend, like the
        # reference's bluestore/filestore choice
        kind = str(self.overrides.get("osd_objectstore", "filestore"))
        if kind == "bluestore":
            from ..store.block_store import BlockStore
            return BlockStore(
                path,
                compression=str(self.overrides.get(
                    "bluestore_compression", "none")))
        from ..store.file_store import FileStore
        return FileStore(
            path,
            compression=str(self.overrides.get(
                "filestore_compression", "none")),
            compression_required_ratio=float(self.overrides.get(
                "filestore_compression_required_ratio", 0.875)))

    def start_osd(self, osd_id: int, store=None) -> OSDDaemon:
        """Boot osd.N (on `store` when restarting a stopped one)."""
        if store is None:
            store = self._new_store(osd_id)
        ctx = Context(self.overrides, name="osd.%d" % osd_id)
        if self.asok_dir:
            # per-daemon unix command socket ('ceph daemon' surface):
            # must exist before the OSD constructor so the op tracker
            # registers its dump commands on it
            ctx.init_admin_socket(
                os.path.join(self.asok_dir, "osd.%d.asok" % osd_id))
        osd = OSDDaemon(osd_id, self.monmap, ctx, store=store)
        osd.init()
        if self.mgr is not None:
            osd.mgr_addr = self.mgr.addr
        self.osds[osd_id] = osd
        return osd

    def stop_osd(self, osd_id: int):
        """Shut osd.N down; returns its store for a later start_osd."""
        osd = self.osds.pop(osd_id)
        store = osd.store
        osd.shutdown()
        return store

    def wait_osds_up(self, timeout: float = 30.0) -> None:
        deadline = time.monotonic() + timeout
        while True:
            leader = self.leader()
            if leader is not None and all(
                    leader.osdmon.osdmap.is_up(o) for o in self.osds):
                return
            if time.monotonic() > deadline:
                raise RuntimeError("vstart: osds never came up")
            time.sleep(0.05)

    def shutdown(self) -> None:
        if self.mgr is not None:
            self.mgr.shutdown()
            self.mgr = None
        for osd in list(self.osds.values()):
            osd.shutdown()
        self.osds.clear()
        for mon in self.mons:
            mon.shutdown()
        self.mons.clear()


def boot(mons: int = 1, osds: int = 3, mgr: bool = False,
         overrides: dict | None = None, monmap_path: str | None = None,
         data: str | None = None, asok_dir: str | None = None,
         out=sys.stdout) -> DevCluster:
    """Start `mons` monitors, `osds` OSDs and optionally the mgr on
    localhost and wait until a leader is elected and every OSD is up.
    Writes the monmap to `monmap_path` for client tools when given.
    Raises RuntimeError when the cluster does not form; the daemons
    already started are shut down first."""
    monmap = {r: ("127.0.0.1", p) for r, p in enumerate(free_ports(mons))}
    if monmap_path:
        with open(monmap_path, "w") as f:
            for rank, (host, port) in monmap.items():
                f.write("%d %s:%d\n" % (rank, host, port))
    cluster = DevCluster(monmap, overrides or {}, data=data,
                         asok_dir=asok_dir)
    try:
        for rank in monmap:
            mon = Monitor(rank, monmap,
                          Context(cluster.overrides, name="mon.%d" % rank))
            mon.init()
            cluster.mons.append(mon)
        deadline = time.monotonic() + 15
        while cluster.leader() is None:
            if time.monotonic() > deadline:
                raise RuntimeError("vstart: no mon leader")
            time.sleep(0.05)
        out.write("vstart: %d mon(s) up, leader elected\n" % mons)

        if asok_dir:
            os.makedirs(asok_dir, exist_ok=True)
        for osd_id in range(osds):
            cluster.start_osd(osd_id)
        cluster.wait_osds_up()
        out.write("vstart: %d osd(s) up\n" % osds)

        if mgr:
            mgr_ctx = Context(cluster.overrides, name="mgr.x")
            if asok_dir:
                # the mgr asok is the `ceph df` / `osd perf` / `iostat` /
                # `counter dump` operator surface
                mgr_ctx.init_admin_socket(
                    os.path.join(asok_dir, "mgr.asok"))
            cluster.mgr = MgrDaemon(monmap, mgr_ctx)
            cluster.mgr.init()
            for osd in cluster.osds.values():
                osd.mgr_addr = cluster.mgr.addr
            for mon in cluster.mons:
                mon.mgr_addr = cluster.mgr.addr
            out.write("vstart: mgr up at %s\n" % (cluster.mgr.addr,))
    except BaseException:
        cluster.shutdown()
        raise
    return cluster


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    configure_compile_cache()
    try:
        cluster = boot(mons=args.mons, osds=args.osds, mgr=args.mgr,
                       overrides=parse_overrides(args.conf),
                       monmap_path=args.monmap, data=args.data,
                       asok_dir=args.asok_dir)
    except RuntimeError as e:
        sys.stderr.write("%s\n" % e)
        return 1
    sys.stdout.write("vstart: cluster ready (monmap: %s)\n"
                     % args.monmap)
    sys.stdout.flush()

    stop = []
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    t0 = time.monotonic()
    while not stop:
        if args.run_seconds and time.monotonic() - t0 > args.run_seconds:
            break
        time.sleep(0.2)

    sys.stdout.write("vstart: shutting down\n")
    cluster.shutdown()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Daemon entrypoints: ceph-mon / ceph-osd process mains.

Reference parity: src/ceph_mon.cc, src/ceph_osd.cc — global_init, store
open/mkfs, daemon construction, run forever.  Launched by vstart.py as
real subprocesses (multi-node-without-a-cluster, qa/ceph-helpers.sh
run_mon/run_osd role).

    python -m ceph_tpu.tools.daemons mon --id a --dir DIR
    python -m ceph_tpu.tools.daemons osd --id 0 --dir DIR

DIR must contain monmap.bin (written by vstart/`ceph-tpu mon mkmap`).
"""

from __future__ import annotations

import argparse
import asyncio
import os
import signal
import sys

from ceph_tpu.common.context import Context
from ceph_tpu.mon.monmap import MonMap
from ceph_tpu.msg.messenger import Messenger
from ceph_tpu.msg.types import EntityName


def load_monmap(cluster_dir: str) -> MonMap:
    with open(os.path.join(cluster_dir, "monmap.bin"), "rb") as f:
        return MonMap.from_bytes(f.read())


def apply_conf(ctx: Context, cluster_dir: str) -> None:
    conf = os.path.join(cluster_dir, "ceph.conf")
    if os.path.exists(conf):
        ctx.config.parse_file(conf)


async def run_mon(args) -> None:
    from ceph_tpu.mon.monitor import Monitor
    from ceph_tpu.store.kv import FileDB
    ctx = Context(f"mon.{args.id}")
    apply_conf(ctx, args.dir)
    monmap = load_monmap(args.dir)
    store = FileDB(os.path.join(args.dir, f"mon.{args.id}"))
    msgr = Messenger(ctx, EntityName("mon", args.id))
    mon = Monitor(ctx, args.id, monmap, store, msgr)
    await mon.start()
    await _run_until_signal()
    await mon.shutdown()


async def run_osd(args) -> None:
    from ceph_tpu.osd.daemon import OSD
    from ceph_tpu.store.objectstore import ObjectStore
    ctx = Context(f"osd.{args.id}")
    apply_conf(ctx, args.dir)
    monmap = load_monmap(args.dir)
    # durable: memstore can't back a daemon restart
    store = ObjectStore.for_osd(ctx.config, args.dir, args.id,
                                durable=True)
    if not store.made():
        store.mkfs()
    msgr = Messenger(ctx, EntityName("osd", args.id))
    osd = OSD(ctx, int(args.id), store, msgr, monmap)
    await osd.start()
    await _run_until_signal()
    await osd.shutdown()


async def run_mds(args) -> None:
    """MDS daemon: metadata service over the cephfs metadata pool
    (creates both cephfs pools if absent)."""
    from ceph_tpu.client.rados import Rados
    from ceph_tpu.services.mds import MDS
    ctx = Context(f"mds.{args.id}")
    apply_conf(ctx, args.dir)
    monmap = load_monmap(args.dir)
    r = Rados(ctx, monmap)
    await r.connect()
    for pool in ("cephfs_metadata", "cephfs_data"):
        if r.monc.osdmap.lookup_pool(pool) < 0:
            await r.pool_create(pool, pg_num=8)
    msgr = Messenger(ctx, EntityName("mds", args.id))
    addr = await msgr.bind()
    rank, nranks = getattr(args, "rank", 0), getattr(args, "nranks", 1)
    mds = MDS(ctx, msgr, r, "cephfs_metadata",
              rank=rank, nranks=nranks)
    if rank == 0:
        await mds.create_fs()
    await mds.start()          # MDLog recovery + write-back flusher
    # register with the mon (FSMonitor beacon) + a file fallback for
    # offline inspection; a transient registration failure must not
    # kill the daemon — clients fall back to the file
    with open(os.path.join(args.dir, f"mds.{args.id}.addr"), "w") as f:
        f.write(f"{addr.host}:{addr.port}:{addr.nonce}")
    try:
        await r.mon_command(
            {"prefix": "mds boot", "name": f"mds.{args.id}",
             "addr": f"{addr.host}:{addr.port}:{addr.nonce}",
             "rank": rank})
    except Exception as e:
        ctx.logger("mds").warning(f"mds boot registration failed: {e}")
    if nranks > 1:
        # resolve peer ranks from the committed fsmap (poll: the other
        # daemons register on their own schedule)
        import json as _json
        from ceph_tpu.msg.types import EntityAddr
        deadline = asyncio.get_running_loop().time() + 60.0
        while len(mds.peers) < nranks:
            try:
                ack = await r.mon_command({"prefix": "mds dump"})
                fsmap = _json.loads(ack.outs)
            except Exception:
                fsmap = {}
            peers = {}
            for rec in fsmap.values():
                h, p, n = rec["addr"].rsplit(":", 2)
                peers[rec.get("rank", 0)] = EntityAddr(
                    h, int(p), int(n))
            mds.peers = peers          # partial map beats none: local
            #                            ops keep working meanwhile
            if len(peers) >= nranks:
                break
            if asyncio.get_running_loop().time() > deadline:
                ctx.logger("mds").warning(
                    f"only {sorted(peers)} of {nranks} ranks "
                    "registered after 60s; cross-rank ops to missing "
                    "ranks will fail until they boot")
                break
            await asyncio.sleep(0.5)
    await _run_until_signal()
    await msgr.shutdown()
    await r.shutdown()


async def _run_until_signal() -> None:
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()


def daemonize(pidfile: str, logfile: str) -> None:
    """Classic double-fork daemonization (global/global_init.cc
    global_init_daemonize role): detach from the controlling terminal,
    write a pidfile, point stdio at the log."""
    # resolve BEFORE the chdir below — relative --dir/--pid-file would
    # silently resolve against / in the detached child
    pidfile = os.path.abspath(pidfile)
    logfile = os.path.abspath(logfile)
    if os.fork() > 0:
        os._exit(0)                      # parent returns to the shell
    os.setsid()
    if os.fork() > 0:
        os._exit(0)                      # session leader exits
    os.chdir("/")
    fd = os.open(logfile, os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                 0o644)
    null = os.open(os.devnull, os.O_RDONLY)
    os.dup2(null, 0)
    os.dup2(fd, 1)
    os.dup2(fd, 2)
    os.close(null)
    if fd > 2:
        os.close(fd)
    with open(pidfile, "w") as f:
        f.write(str(os.getpid()))
    import atexit
    atexit.register(lambda: os.path.exists(pidfile)
                    and os.unlink(pidfile))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="ceph-tpu-daemon")
    ap.add_argument("kind", choices=["mon", "osd", "mds"])
    ap.add_argument("--id", required=True)
    ap.add_argument("--dir", required=True, help="cluster directory")
    ap.add_argument("-d", "--daemonize", action="store_true",
                    help="double-fork into the background with a "
                         "pidfile + log redirect (global_init role)")
    ap.add_argument("--pid-file", default="",
                    help="pidfile path (default: "
                         "<dir>/<kind>.<id>.pid)")
    ap.add_argument("--rank", type=int, default=0,
                    help="mds only: this daemon's rank")
    ap.add_argument("--nranks", type=int, default=1,
                    help="mds only: total active ranks")
    args = ap.parse_args(argv)
    if args.daemonize:
        pidfile = args.pid_file or os.path.join(
            args.dir, f"{args.kind}.{args.id}.pid")
        logfile = os.path.join(args.dir,
                               f"{args.kind}.{args.id}.daemon.log")
        daemonize(pidfile, logfile)
    runner = {"mon": run_mon, "osd": run_osd,
              "mds": run_mds}[args.kind]
    asyncio.run(runner(args))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""vstart: boot a dev cluster of real mon/osd subprocesses.

Reference parity: src/vstart.sh (:111-120 — N mons/osds as local
processes) and qa/workunits/ceph-helpers.sh (setup/run_mon/run_osd/
kill_daemon/wait_for_clean) — the multi-node-without-a-cluster test
strategy (SURVEY §4).  Usable as a CLI and as a library (fault tests
import VCluster to kill/restart daemons).

    python -m ceph_tpu.tools.vstart --dir /tmp/cl -n 3 --mons 1 \
        [--osds-per-host 1] [--conf k=v ...] [--keep-running]
"""

from __future__ import annotations

import argparse
import asyncio
import os
import shutil
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional

from ceph_tpu.common.context import Context
from ceph_tpu.mon.monmap import MonMap
from ceph_tpu.msg.types import EntityAddr


def _free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class VCluster:
    """Launcher handle: daemon subprocess management + admin client."""

    def __init__(self, directory: str, n_osds: int = 3, n_mons: int = 1,
                 osds_per_host: int = 1,
                 conf: Optional[Dict[str, str]] = None,
                 cephx: bool = False, mds: int = 0):
        self.dir = os.path.abspath(directory)
        self.n_osds = n_osds
        self.n_mons = n_mons
        self.osds_per_host = osds_per_host
        self.conf = conf or {}
        self.cephx = cephx
        self.mds = int(mds)          # number of MDS ranks (0 = none)
        self.procs: Dict[str, subprocess.Popen] = {}
        self.monmap = MonMap()

    # ------------------------------------------------------------ lifecycle
    def write_configs(self) -> None:
        os.makedirs(self.dir, exist_ok=True)
        self.monmap.fsid = f"vstart-{os.path.basename(self.dir)}"
        for i in range(self.n_mons):
            name = chr(ord("a") + i)
            self.monmap.add(name,
                            EntityAddr("127.0.0.1", _free_port(), 0))
        with open(os.path.join(self.dir, "monmap.bin"), "wb") as f:
            f.write(self.monmap.to_bytes())
        conf = dict(self.conf)
        # every daemon gets an admin socket under the cluster dir
        # ($name expands per daemon: mon.a.asok, osd.0.asok, ...)
        conf.setdefault("admin_socket",
                        os.path.join(self.dir, "$name.asok"))
        conf.setdefault("mon_cluster_log_file",
                        os.path.join(self.dir, "cluster.log"))
        if self.cephx:
            # one shared keyring (vstart.sh writes keyring + caps the
            # same way: mon. master, client.admin allow *, per-osd keys)
            from ceph_tpu.auth.keyring import Keyring
            kr = Keyring()
            kr.add("mon.")
            kr.add("client.admin",
                   caps={"mon": "allow *", "osd": "allow *"})
            for i in range(self.n_osds):
                kr.add(f"osd.{i}", caps={"mon": "allow profile osd",
                                         "osd": "allow *"})
            for i in range(max(1, self.mds)):
                kr.add(f"mds.{chr(ord('a') + i)}",
                       caps={"mon": "allow *", "osd": "allow *"})
            kr.save(os.path.join(self.dir, "keyring"))
            conf["auth_supported"] = "cephx"
            conf["keyring"] = os.path.join(self.dir, "keyring")
        with open(os.path.join(self.dir, "ceph.conf"), "w") as f:
            for k, v in conf.items():
                f.write(f"{k} = {v}\n")

    def _spawn(self, kind: str, id_: str, extra=()) -> None:
        # Daemons run jax on the CPU backend because a chip belongs
        # to ONE process: N daemon processes cannot share it, and a
        # child that asked for it while another held it would fail or
        # hang.  The on-chip deployment shape is the single-process
        # cluster (qa/cluster.py, driven by chip_smoke.py).
        from ceph_tpu.common.envutil import cpu_child_env
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        with open(os.path.join(self.dir, f"{kind}.{id_}.log"), "ab") as logf:
            p = subprocess.Popen(
                [sys.executable, "-m", "ceph_tpu.tools.daemons", kind,
                 "--id", id_, "--dir", self.dir, *extra],
                stdout=logf, stderr=subprocess.STDOUT,
                env=cpu_child_env(pythonpath_first=repo_root))
        self.procs[f"{kind}.{id_}"] = p

    def start_daemons(self) -> None:
        for i in range(self.n_mons):
            self._spawn("mon", chr(ord("a") + i))
        for i in range(self.n_osds):
            self._spawn("osd", str(i))

    def start_mds(self) -> None:
        """After bootstrap (the mds needs pools + a served osdmap).
        Multi-rank: rank i = mds.<a+i>, each told the rank count so
        dirfrag ownership (services/mds.py owner_rank) agrees."""
        n = max(1, self.mds)
        for i in range(n):
            self._spawn("mds", chr(ord("a") + i),
                        extra=["--rank", str(i), "--nranks", str(n)])

    def kill_daemon(self, name: str, sig=signal.SIGKILL) -> None:
        """qa/ceph-helpers.sh kill_daemon."""
        p = self.procs.pop(name, None)
        if p is not None:
            p.send_signal(sig)
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                # daemon ignored the signal: escalate
                p.kill()
                p.wait(timeout=10)

    def restart_daemon(self, name: str) -> None:
        kind, id_ = name.split(".", 1)
        self._spawn(kind, id_)

    def stop(self) -> None:
        for name in list(self.procs):
            self.kill_daemon(name, signal.SIGTERM)

    # ------------------------------------------------------------ admin ops
    async def admin(self):
        from ceph_tpu.client.rados import Rados
        ctx = Context("client.admin")
        for k, v in self.conf.items():
            try:
                ctx.config.set(k, v)
            except KeyError:
                pass
        if self.cephx:
            ctx.config.set("auth_supported", "cephx")
            ctx.config.set("keyring", os.path.join(self.dir, "keyring"))
        r = Rados(ctx, self.monmap)
        await r.connect()
        return r

    async def wait_healthy(self, timeout: float = 120.0) -> None:
        """Wait until every osd is up/in (wait_for_clean role)."""
        admin = await self.admin()
        try:
            deadline = time.monotonic() + timeout
            while True:
                m = admin.monc.osdmap
                if m is not None and m.count_up() == self.n_osds:
                    return
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"cluster not healthy: {m and m.summary()}")
                await asyncio.sleep(0.2)
        finally:
            await admin.shutdown()

    async def bootstrap(self) -> None:
        """Full bring-up: crush map + wait for osds."""
        admin = await self.admin()
        try:
            await admin.mon_command(
                {"prefix": "osd crush build-simple",
                 "num_osds": self.n_osds,
                 "osds_per_host": self.osds_per_host}, timeout=60)
        finally:
            await admin.shutdown()
        await self.wait_healthy()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="vstart")
    ap.add_argument("--dir", default="./vcluster")
    ap.add_argument("-n", "--osds", type=int, default=3)
    ap.add_argument("--mons", type=int, default=1)
    ap.add_argument("--osds-per-host", type=int, default=1)
    ap.add_argument("--conf", nargs="*", default=[],
                    help="extra k=v config entries")
    ap.add_argument("--new", action="store_true",
                    help="wipe the cluster dir first (vstart -n)")
    ap.add_argument("--cephx", action="store_true",
                    help="enable cephx auth (generates a keyring)")
    ap.add_argument("--mds", nargs="?", const=1, default=0, type=int,
                    help="start N mds ranks (CephFS) after bootstrap "
                         "(bare --mds = 1)")
    ap.add_argument("--keep-running", action="store_true",
                    help="stay attached until ^C")
    args = ap.parse_args(argv)

    if args.new and os.path.exists(args.dir):
        shutil.rmtree(args.dir)
    conf = dict(kv.split("=", 1) for kv in args.conf)
    cl = VCluster(args.dir, args.osds, args.mons, args.osds_per_host,
                  conf, cephx=args.cephx, mds=args.mds)
    cl.write_configs()
    cl.start_daemons()
    asyncio.run(cl.bootstrap())
    if args.mds:
        cl.start_mds()
    print(f"cluster up: dir={cl.dir} mons={args.mons} osds={args.osds}"
          + (" +mds" if args.mds else ""))
    print(f"  use: python -m ceph_tpu.tools.ceph --dir {cl.dir} status")
    if args.keep_running:
        try:
            while True:
                time.sleep(1)
        except KeyboardInterrupt:
            pass
        cl.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())

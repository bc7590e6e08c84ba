"""Traffic kind `closed_loop_restart`: `closed_loop`'s load, parameter
for parameter, on a deployment whose OSDs keep their objects in a
durable store; the timed loop is the parent class's.  Two additions,
both outside the window:

  the deployment is checked   at construction every OSD's store has to
      be of the kind, at the directory and with the barriers the
      configuration's file states, or the run ends there with an error
      and no result line.  A program that ignores the `objectstore`
      option would otherwise measure a RAM store under this cell's
      name.  The directory is the run's own: the configuration's
      relative `objectstore_path` under this process's temp directory
      (TMPDIR), `<tmp>/<path>.<pid>`, which the cluster removes when it
      stops, so two runs on one machine never meet in it.  The
      filesystem found under it goes to stderr (`deployment: ...`) and,
      as `store_fs_ram` (1: tmpfs or ramfs, a barrier with no device
      behind it), into `compared`.

  the crash image   at the entry of `stop()`, the instant the window
      has closed and in ONE synchronous stretch of the loop (nothing is
      applied, allocated or freed meanwhile; the stores' kv-sync
      threads run on, as they would at a crash): which ring objects
      have a write in flight (unknown: left out) and what every other
      one holds by its last ack; a copy of every OSD's metadata files
      as they are now (FileDB.copy_files); a second, read-only store
      mounted on that copy and the OSD's own block file; EVERY shard,
      data and parity, of every known ring object read from those
      second instances and compared with the plain reference's encode
      of the last acked write.  This is the configuration's guarantee
      "a write acked before an instant is readable from what the files
      held at that instant", as far as a run can show it: the page
      cache stands between fsync and the platters, so power loss is not
      tested, only that every barrier was issued before its ack and
      that the files alone carry every acked write.

One image is one instant, and an ack that runs ahead of its barriers by
less than its way back to the client is behind them again at every
instant a client can name.  So the ORDER is counted where it happens,
in the program's commit thread (`commit_counters()`, over the load):
`acks_before_commit` (completion records posted for transactions whose
group's barriers had not returned; limit 0), `groups_without_data_sync`
(groups that held a data-writing transaction and issued no data
barrier; limit 0), `groups_without_kv_sync` (limit 0) and
`osds_without_data_fsync` (limit 0), beside `data_fsyncs`, `kv_syncs`,
`commit_groups`.  `compared` also gains `crash_image_shards`,
`crash_image_mismatch` (limit 0) and, with no limit, the block files'
bytes when the load started and when it closed and the milliseconds
the stretch held the loop."""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Dict

import numpy as np

from benchmark import reference
from benchmark.manifest import Manifest

_base = Manifest().kind("closed_loop")

COUNTERS = ("data_fsyncs", "kv_syncs", "commit_batches", "data_groups",
            "acks_before_commit")
RAM_FILESYSTEMS = ("tmpfs", "ramfs")


def store_dir(path: str) -> str:
    """The directory a configuration's `objectstore_path` names in this
    run: an absolute path as it stands, a relative one under the run's
    own temp directory, one per process."""
    if os.path.isabs(path):
        return path
    return os.path.join(tempfile.gettempdir(), f"{path}.{os.getpid()}")


def filesystem_under(path: str) -> tuple:
    """(mount point, type, options) of the mount that holds `path`, by
    /proc/mounts; ("", "unknown", "") where that cannot be read."""
    best = ("", "unknown", "")
    try:
        with open("/proc/mounts") as f:
            for line in f:
                mnt, fstype, opts = line.split()[1:4]
                if (path == mnt or path.startswith(
                        mnt.rstrip("/") + "/")) and len(mnt) > len(best[0]):
                    best = (mnt, fstype, opts)
    except OSError:
        pass
    return best


class _TrackedIo:
    """The workers' pool handle, keeping the names whose write_full is
    in flight: a name leaves the set in the same step of its worker's
    task that records the ack, so seen from outside a step the set and
    the reference's `write_holds` always agree."""

    def __init__(self, io):
        self._io = io
        self.writing: set = set()

    def __getattr__(self, name):
        return getattr(self._io, name)

    async def write_full(self, name, data):
        self.writing.add(name)
        try:
            return await self._io.write_full(name, data)
        finally:
            self.writing.discard(name)


class Load(_base.Load):
    def __init__(self, env):
        super().__init__(SimpleNamespace(**{
            **vars(env), "io": _TrackedIo(env.io)}))
        self.base_dir = store_dir(
            env.config["options"]["objectstore_path"])
        self.stores = {i: osd.store
                       for i, osd in env.cluster.osds.items()}
        self._check_deployment(env.config)
        mnt, self.fs_type, opts = filesystem_under(
            os.path.realpath(self.base_dir))
        print(f"deployment: {len(self.stores)} x "
              f"{env.config['objectstore']} under {self.base_dir} on "
              f"{self.fs_type} (mounted at {mnt}: {opts})",
              file=sys.stderr, flush=True)
        self._c_start: Dict[int, dict] = {}
        self.block_bytes = [0, 0]
        self.image = {"shards": 0, "mismatch": 0, "ms": 0.0}

    def _check_deployment(self, config: dict) -> None:
        from ceph_tpu.store.objectstore import ObjectStore
        kind = config["objectstore"]
        want = type(ObjectStore.create(kind, self.base_dir))
        for i, store in self.stores.items():
            path = os.path.join(self.base_dir, f"osd.{i}")
            barriers = tuple(getattr(store, "barriers", ()))
            if type(store) is not want or store.path != path \
                    or barriers != ("data", "kv"):
                raise RuntimeError(
                    f"osd.{i} stores through {type(store).__name__} at "
                    f"{store.path!r} with barriers {barriers}; the "
                    f"configuration states {kind} at {path!r} with a "
                    f"data barrier and a kv sync: refusing to measure "
                    f"another deployment under this cell's name")

    # --------------------------------------------------------------- load
    def _counters(self) -> Dict[int, dict]:
        return {i: {key: int(s.commit_counters().get(key, 0))
                    for key in COUNTERS}
                for i, s in self.stores.items()}

    def _block_bytes(self) -> int:
        return sum(os.stat(os.path.join(s.path, "block")).st_size
                   for s in self.stores.values())

    def start(self) -> None:
        self._c_start = self._counters()
        self.block_bytes[0] = self._block_bytes()
        super().start()

    async def stop(self) -> None:
        # no await before this returns: the harness calls stop() the
        # instant the window has closed
        self._crash_image()
        self.block_bytes[1] = self._block_bytes()
        await super().stop()

    # -------------------------------------------------------- crash image
    def _crash_image(self) -> None:
        from ceph_tpu.client.objecter import ObjectLocator
        from ceph_tpu.store.types import CollectionId, ObjectId
        t0 = time.monotonic()
        env = self.env
        writing = env.io.writing
        by_payload: Dict[int, list] = {}
        for i, name in enumerate(self.write_names):
            if i not in self.write_unknown and name not in writing:
                by_payload.setdefault(
                    int(self.write_holds[i]), []).append(name)
        images, copies = {}, []
        try:
            for i, store in self.stores.items():
                copy = os.path.join(self.base_dir, f"crash_image.osd.{i}")
                shutil.rmtree(copy, ignore_errors=True)
                copies.append(copy)
                store.db.copy_files(copy)
                images[i] = type(store)(store.path)
                images[i].mount_read_only(db_path=copy)
            omap = env.admin.monc.osdmap
            loc = ObjectLocator(env.pool_id)
            seen = bad = 0
            for p, names in by_payload.items():
                want = reference.shards(self.payloads[p], env.k, env.m)
                for name in names:
                    pgid, acting = omap.object_to_acting(name, loc)[:2]
                    oid = ObjectId(name, pool=env.pool_id)
                    for j, osd_id in enumerate(acting):
                        seen += 1
                        try:
                            raw = images[osd_id].read(CollectionId.pg(
                                env.pool_id, pgid.seed, j), oid)
                        except Exception:
                            bad += 1    # missing, or its checksum fails
                            continue
                        if not np.array_equal(
                                np.frombuffer(raw, np.uint8), want[j]):
                            bad += 1
        finally:
            for image in images.values():
                image.umount()
            for copy in copies:
                shutil.rmtree(copy, ignore_errors=True)
        self.image = {"shards": seen, "mismatch": bad,
                      "ms": round((time.monotonic() - t0) * 1e3, 1)}

    # ------------------------------------------------------------- result
    async def verify(self) -> Dict[str, tuple]:
        out = await super().verify()
        now = self._counters()
        d = {i: {key: now[i][key] - self._c_start[i][key]
                 for key in COUNTERS} for i in now}
        out["crash_image_shards"] = (self.image["shards"], None)
        out["crash_image_mismatch"] = (self.image["mismatch"], 0)
        out["data_fsyncs"] = (sum(c["data_fsyncs"] for c in d.values()),
                              None)
        out["kv_syncs"] = (sum(c["kv_syncs"] for c in d.values()), None)
        out["commit_groups"] = (
            sum(c["commit_batches"] for c in d.values()), None)
        out["groups_without_kv_sync"] = (sum(
            max(0, c["commit_batches"] - c["kv_syncs"])
            for c in d.values()), 0)
        out["groups_without_data_sync"] = (sum(
            max(0, c["data_groups"] - c["data_fsyncs"])
            for c in d.values()), 0)
        out["acks_before_commit"] = (
            sum(c["acks_before_commit"] for c in d.values()), 0)
        out["osds_without_data_fsync"] = (
            sum(1 for c in d.values() if c["data_fsyncs"] <= 0), 0)
        out["block_bytes_start"] = (self.block_bytes[0], None)
        out["block_bytes_close"] = (self.block_bytes[1], None)
        out["crash_image_ms"] = (self.image["ms"], None)
        out["store_fs_ram"] = (
            int(self.fs_type in RAM_FILESYSTEMS), None)
        return out

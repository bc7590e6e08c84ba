"""Traffic kind `closed_loop`: `depth` long-lived workers (obj_bencher's
slots, COSBench's workers), each waiting for its reply before its next
op.  One general generator; a mix is a data file of its parameters:

    depth          workers
    object_size    bytes of every object and every op
    read_ratio     share of ops that are reads (0, 1 or between)
    read_objects   objects prepared in set-up for reads (0: none)
    read_select    "seq" (one cursor over the range, wrapping: rados
                   bench seq) or "uniform" (COSBench u())
    write_objects  ring of names that writes overwrite (0: none)
    write_select   "ring" (each worker walks its own slice in order:
                   rados bench write) or "uniform" (within its slice)
    payloads       distinct payloads made from the seed and cycled
    kill_osds      OSDs killed in set-up, highest first, marked down
                   and not out (0: healthy cluster)
    ramp_s         load before the window opens
    keep_reads     at most this many window reads have their bytes kept
                   for comparison, each drawn with probability keep_prob
    check_shards   objects whose stored shards are compared

Object NAMES do not depend on the seed; payload bytes, op kinds and
object choices do.  Every worker writes only its own slice of the write
ring (slot % depth == worker), so no two writes to one object are ever
in flight together and "the last acked write" of every object is
defined."""

from __future__ import annotations

import asyncio
import time
from typing import Dict, List

import numpy as np

from benchmark import reference, verify

PLAN_OPS = 1 << 15          # ops drawn per worker; the plan wraps


class Load:
    def __init__(self, env):
        self.env = env
        t = env.traffic
        self.depth = int(t["depth"])
        self.size = int(t["object_size"])
        self.read_ratio = float(t["read_ratio"])
        self.n_read = int(t.get("read_objects", 0))
        self.n_write = int(t.get("write_objects", 0))
        self.read_select = t.get("read_select", "uniform")
        self.write_select = t.get("write_select", "ring")
        self.ramp_s = float(t.get("ramp_s", 3.0))
        self.keep_reads = int(t.get("keep_reads", 0))
        self.keep_prob = float(t.get("keep_prob", 0.0))
        self.check_shards = int(t.get("check_shards", 0))
        self.kill_osds = int(t.get("kill_osds", 0))
        if self.read_ratio > 0 and not self.n_read:
            raise ValueError("reads need read_objects")
        if self.read_ratio < 1 and self.n_write < self.depth:
            raise ValueError("writes need a ring of at least depth names")
        prefix = f"benchmark_data_{env.cell}_object"
        self.read_names = [f"{prefix}{i}" for i in range(self.n_read)]
        self.write_names = [f"{prefix}w{i}" for i in range(self.n_write)] \
            if self.n_read else [f"{prefix}{i}" for i in range(self.n_write)]
        self.payloads = reference.payloads(env.seed, int(t["payloads"]),
                                           self.size)
        npay = len(self.payloads)
        # the reference's state: which payload each object holds.  Read
        # range: fixed in set-up.  Write ring: the last acked write.
        self.read_holds = np.arange(self.n_read) % npay
        self.write_holds = np.arange(self.n_write) % npay
        self.write_unknown: set = set()
        rng = np.random.default_rng([int(env.seed), 0xC105ED])
        self.plan = [self._plan_worker(rng, w) for w in range(self.depth)]
        self._seq = 0                       # the shared "seq" cursor
        self.stop_flag = False
        self.tasks: List[asyncio.Task] = []
        # records, one entry per completed op
        self.t_end: List[float] = []
        self.lat: List[float] = []
        self.is_read: List[bool] = []
        self.failed: List[str] = []
        self.attempted = 0
        self.kept: List[tuple] = []         # (read object, bytes)
        self.keep_armed = False

    # -------------------------------------------------------------- plan
    def _plan_worker(self, rng, w: int) -> Dict[str, np.ndarray]:
        n = PLAN_OPS
        is_read = rng.random(n) < self.read_ratio
        rd = rng.integers(0, max(1, self.n_read), n)
        mine = np.arange(w, self.n_write, self.depth)   # this worker's slice
        if not len(mine):
            wr = np.zeros(n, np.int64)                   # a read-only mix
        elif self.write_select == "ring":
            wr = mine[np.arange(n) % len(mine)]
        else:
            wr = mine[rng.integers(0, len(mine), n)]
        pay = rng.integers(0, len(self.payloads), n)
        return {"is_read": is_read, "rd": rd, "wr": wr, "pay": pay,
                "keep": rng.random(n) < self.keep_prob}

    # ------------------------------------------------------------ set-up
    async def prepare(self) -> None:
        """Write every object once (bounded working set: the window
        overwrites, it never grows the store)."""
        io, sem = self.env.io, asyncio.Semaphore(64)

        async def put(name, data):
            async with sem:
                await io.write_full(name, data)
        jobs = [put(n, self.payloads[self.read_holds[i]])
                for i, n in enumerate(self.read_names)]
        jobs += [put(n, self.payloads[self.write_holds[i]])
                 for i, n in enumerate(self.write_names)]
        await asyncio.gather(*jobs)

    def seam_shapes(self) -> dict:
        """What this mix presents to the device seam: requests of
        `lanes` lanes, up to `depth` pending at once, through the
        encode matrix (writes) and, on a degraded cluster, the decode
        matrices (reads)."""
        k = self.env.k
        return {"lanes": self.size // k, "depth": self.depth,
                "encode": self.read_ratio < 1,
                "decode": self.read_ratio > 0 and self.kill_osds > 0}

    def seam_rows(self) -> int:
        """Output rows r of this mix's requests, for the kernel's least
        bytes: m parity rows for an encode, one lost chunk for a
        degraded read of a cluster that lost one OSD."""
        return self.env.m if self.read_ratio < 1 else 1

    # -------------------------------------------------------------- load
    def start(self) -> None:
        loop = asyncio.get_running_loop()
        self.tasks = [loop.create_task(self._worker(w))
                      for w in range(self.depth)]

    async def _worker(self, w: int) -> None:
        io, plan = self.env.io, self.plan[w]
        is_read, rd, wr, pay, keep = (plan["is_read"], plan["rd"],
                                      plan["wr"], plan["pay"], plan["keep"])
        seq = self.read_select == "seq"
        clock = time.monotonic
        i = 0
        while not self.stop_flag:
            j = i % PLAN_OPS
            i += 1
            self.attempted += 1
            if is_read[j]:
                if seq:
                    obj = self._seq % self.n_read
                    self._seq += 1
                else:
                    obj = int(rd[j])
                t0 = clock()
                try:
                    got = await io.read(self.read_names[obj],
                                        length=self.size)
                except Exception as e:              # counted, not hidden
                    self.failed.append(f"read {obj}: {e!r}")
                    continue
                t1 = clock()
                self.t_end.append(t1)
                self.lat.append(t1 - t0)
                self.is_read.append(True)
                if len(got) != self.size:
                    self.failed.append(f"read {obj}: {len(got)} bytes")
                elif self.keep_armed and keep[j] \
                        and len(self.kept) < self.keep_reads:
                    self.kept.append((obj, got))
            else:
                obj, p = int(wr[j]), int(pay[j])
                t0 = clock()
                try:
                    await io.write_full(self.write_names[obj],
                                        self.payloads[p])
                except Exception as e:
                    self.failed.append(f"write {obj}: {e!r}")
                    self.write_unknown.add(obj)
                    continue
                t1 = clock()
                self.write_holds[obj] = p
                self.t_end.append(t1)
                self.lat.append(t1 - t0)
                self.is_read.append(False)

    async def stop(self) -> None:
        """Close the load: every worker finishes the op it is in (its
        answer is waited for, a minute if need be) and exits."""
        self.stop_flag = True
        done, pending = await asyncio.wait(self.tasks, timeout=60.0)
        for t in pending:
            t.cancel()
            self.failed.append("op never answered within 60 s of the close")
        for t in done:
            if t.exception() is not None:
                self.failed.append(f"worker died: {t.exception()!r}")

    # ------------------------------------------------------------ result
    def window(self, t0: float, seconds: float) -> dict:
        """The ops that COMPLETED inside [t0, t0 + seconds]."""
        t1 = t0 + seconds
        rl, wl, tt, amt = [], [], [], []
        for te, la, rd in zip(self.t_end, self.lat, self.is_read):
            if t0 <= te <= t1:
                (rl if rd else wl).append(la * 1e3)
                tt.append(te)
                amt.append(self.size)
        return {"read_ms": rl, "write_ms": wl, "t_end": tt, "bytes": amt,
                "ops": len(tt), "user_bytes": len(tt) * self.size}

    async def verify(self) -> Dict[str, tuple]:
        """The numbers compared, each (value, limit), after the window."""
        env = self.env
        out = {}
        bad = sum(1 for obj, got in self.kept
                  if got != self.payloads[self.read_holds[obj]])
        if self.read_ratio > 0:
            out["window_reads_kept"] = (len(self.kept), None)
            out["window_read_mismatch"] = (bad, 0)
        if self.read_ratio < 1:
            known = [i for i in range(self.n_write)
                     if i not in self.write_unknown]
            want = {self.write_names[i]: self.payloads[self.write_holds[i]]
                    for i in known}
            out["readback_objects"] = (len(want), None)
            out["readback_mismatch"] = (
                await verify.readback_mismatch(env.io, want), 0)
            names, holds = self.write_names, self.write_holds
        else:
            known = list(range(self.n_read))
            names, holds = self.read_names, self.read_holds
        rng = np.random.default_rng([int(env.seed), 0x5AA7D5])
        picks = rng.choice(known, size=min(self.check_shards, len(known)),
                           replace=False) if known else []
        sample = {names[i]: self.payloads[holds[i]] for i in picks}
        seen, differ = verify.shard_mismatch(env, sample)
        out["shards_checked"] = (seen, None)
        out["shard_mismatch"] = (differ, 0)
        return out

"""Traffic kind `closed_loop_sizes`: `closed_loop`'s workers, each
waiting for its reply before its next op, over several object sizes in
one stage (COSBench's histogram size selector, `sizes=h(min|max|weight,
...)`).  A mix is a data file of its parameters:

    depth          workers
    read_ratio     share of ops that are reads; the rest are writes
    block_ops      ops in one block of a worker's plan
    classes        the size classes, each with
                     name           a tag: a name's part, a column name
                     object_size    bytes of every object and op of it
                     per_block      ops of the class in every block
                     read_objects   objects written in set-up for reads
                     write_objects  ring of names that writes overwrite;
                                    each worker writes its own slice
                     payloads       distinct payloads from the seed
                     keep_reads     at most this many window reads kept
                                    for comparison
                     check_shards   ring objects whose stored shards
                                    are compared
    keep_prob      chance that a window read is kept (up to its quota)
    ramp_s         load before the window opens

A worker's plan is blocks of `block_ops` ops.  Every block holds each
class's `per_block` ops exactly, shuffled from the seed, and exactly
`read_ratio` of its ops are reads; where a class's writes in a block
are not a whole number, they are dealt over a group of blocks so that
every class is read and written in `read_ratio` over the group.  So the
window's mix of sizes and of bytes is the same in every run, whatever
the seed.  Within a class an op's object is uniform (COSBench u()): a
read over the class's read range, a write over the worker's own slice
of the class's ring, so no two writes to one object are ever in flight
together and "the last acked write" of every object is defined.

Object NAMES do not depend on the seed; payload bytes, the order of a
block and object choices do.

The comparison (`verify`), against `benchmark/reference.py`: the kept
window reads of every class (`window_read_mismatch`), every ring object
whose last write was acked, read back through the served path
(`readback_mismatch`), and the stored shards, data and parity, of a
seeded sample of ring objects of every class (`shard_mismatch`; parity
by the reference a block of lanes at a time, so that a 64 MiB object's
fits), limit 0 each.  The harness holds the run to `host_bytes` and
`device_fallbacks` 0; it counts the bytes written as writes times ONE
object size, so this kind says it has none (`size` 0) and sums the
bytes of its own acked writes against the seam's `device_bytes` over
the load (`device_bytes_short`, limit 0).  Printed without limit: the
client's budget (`throttle_waits`, `inflight_bytes_peak`,
`inflight_ops_peak`), the seam's `device_lanes_launched` and
`device_pad_lanes` over the load (a program without the counter prints
none), and each class's p50 / p95 of reads and of writes in the
window."""

from __future__ import annotations

import asyncio
import time
from typing import Dict, List, Tuple

import numpy as np

from benchmark import diag, reference, stats, verify, warm

PLAN_BLOCKS = 256           # blocks drawn per worker; the plan wraps
#: lanes of the reference's parity in one go, in the shard comparison
PARITY_BLOCK_LANES = 1 << 22
#: the program's own counters over the load, where it keeps them
PROGRAM_COUNTERS = ("device_lanes_launched", "device_pad_lanes")


def blocks_per_group(block_ops: int, per_block: List[int],
                     write_share: float) -> int:
    """The fewest blocks over which every class's writes, and a block's,
    come to whole numbers."""
    for g in range(1, 101):
        if all(abs(n * g * write_share - round(n * g * write_share))
               < 1e-9 for n in per_block + [block_ops]):
            return g
    raise ValueError("the classes' writes come to no whole number "
                     "over 100 blocks")


def writes_per_block(rng, block_ops: int, per_block: List[int],
                     write_share: float) -> np.ndarray:
    """[blocks of a group, classes]: each class's writes in each block
    of one group.  Each class is written `write_share` of its ops over
    the group, each block `write_share` of its ops; what a class's share
    leaves over a whole number is dealt over the group's blocks."""
    g = blocks_per_group(block_ops, per_block, write_share)
    total = [round(n * g * write_share) for n in per_block]
    out = np.array([[t // g for t in total]] * g, np.int64)
    extra = [c for c, t in enumerate(total) for _ in range(t % g)]
    per = round(block_ops * write_share) - int(out[0].sum())
    deal = rng.permutation(np.array(extra, np.int64))
    for b in range(g):
        for c in deal[b * per:(b + 1) * per]:
            out[b, c] += 1
    if (out > np.array(per_block)).any():
        raise ValueError("a block would write more of a class than it "
                         "has ops of it")
    return out


class Class:
    """One size class: its objects, payloads and what each holds."""

    def __init__(self, spec: dict, index: int, of: int, cell: str,
                 seed: int, depth: int):
        self.name = str(spec["name"])
        self.size = int(spec["object_size"])
        self.per_block = int(spec["per_block"])
        self.n_read = int(spec["read_objects"])
        self.n_write = int(spec["write_objects"])
        self.keep_reads = int(spec.get("keep_reads", 0))
        self.check_shards = int(spec.get("check_shards", 0))
        if self.n_write < depth:
            raise ValueError(f"class {self.name}: every worker writes "
                             f"its own slice: at least {depth} names")
        prefix = f"benchmark_data_{cell}_{self.name}_object"
        self.read_names = [f"{prefix}{i}" for i in range(self.n_read)]
        self.write_names = [f"{prefix}w{i}" for i in range(self.n_write)]
        # one seed per class, so no class's payloads begin another's
        self.payloads = reference.payloads(
            int(seed) * of + index, int(spec["payloads"]), self.size)
        npay = len(self.payloads)
        # the reference's state: which payload each object holds.  Read
        # range: fixed in set-up.  Write ring: the last acked write.
        self.read_holds = np.arange(self.n_read) % npay
        self.write_holds = np.arange(self.n_write) % npay
        self.write_unknown: set = set()
        self.kept: List[Tuple[int, bytes]] = []


class Load:
    def __init__(self, env):
        self.env = env
        t = env.traffic
        self.depth = int(t["depth"])
        self.read_ratio = float(t["read_ratio"])
        self.block_ops = int(t["block_ops"])
        self.keep_prob = float(t.get("keep_prob", 0.0))
        self.ramp_s = float(t.get("ramp_s", 3.0))
        self.kill_osds = 0
        # the harness counts bytes written as writes x one object size;
        # with many sizes this kind counts its own (verify)
        self.size = 0
        self.classes = [Class(spec, i, len(t["classes"]), env.cell,
                              env.seed, self.depth)
                        for i, spec in enumerate(t["classes"])]
        per_block = [c.per_block for c in self.classes]
        if sum(per_block) != self.block_ops:
            raise ValueError("a block holds every class's per_block ops: "
                             f"{sum(per_block)} is not {self.block_ops}")
        if not 0.0 < self.read_ratio < 1.0:
            raise ValueError("read_ratio lies between 0 and 1")
        rng = np.random.default_rng([int(env.seed), 0x512E5])
        self.plan = [self._plan_worker(rng, w) for w in range(self.depth)]
        # the client's op budget, where the program's Objecter keeps one
        self.budget_stats = getattr(
            getattr(getattr(env, "admin", None), "objecter", None),
            "budget_stats", None)
        self.stop_flag = False
        self.tasks: List[asyncio.Task] = []
        # records, one entry per completed op
        self.t_end: List[float] = []
        self.lat: List[float] = []
        self.is_read: List[bool] = []
        self.op_class: List[int] = []
        self.failed: List[str] = []
        self.attempted = 0
        self.keep_armed = False
        self.written_bytes = 0      # acked writes of the load
        self._at_start: dict = {}
        self._at_stop: dict = {}
        self._class_tails: Dict[str, tuple] = {}

    # -------------------------------------------------------------- plan
    def _plan_worker(self, rng, w: int) -> Dict[str, np.ndarray]:
        """At least PLAN_BLOCKS blocks, in whole groups of blocks, each
        block every class's per_block ops with its writes, in an order
        shuffled from the seed."""
        per_block = [c.per_block for c in self.classes]
        writes: list = []
        while len(writes) < PLAN_BLOCKS:
            writes.extend(writes_per_block(rng, self.block_ops, per_block,
                                           1.0 - self.read_ratio))
        c_of = np.repeat(np.arange(len(per_block)), per_block)
        first = np.cumsum([0] + per_block[:-1])
        cls, is_read = [], []
        for wb in writes:
            rd = np.ones(self.block_ops, bool)
            for at, n in zip(first, wb):
                rd[at:at + n] = False
            order = rng.permutation(self.block_ops)
            cls.append(c_of[order])
            is_read.append(rd[order])
        cls = np.concatenate(cls)
        is_read = np.concatenate(is_read)
        n = len(cls)
        obj = np.zeros(n, np.int64)
        pay = np.zeros(n, np.int64)
        for c, k in enumerate(self.classes):
            at = cls == c
            mine = np.arange(w, k.n_write, self.depth)
            reads, writes_ = at & is_read, at & ~is_read
            obj[reads] = rng.integers(0, k.n_read, int(reads.sum()))
            obj[writes_] = mine[rng.integers(0, len(mine),
                                             int(writes_.sum()))]
            pay[at] = rng.integers(0, len(k.payloads), int(at.sum()))
        return {"cls": cls, "is_read": is_read, "obj": obj, "pay": pay,
                "keep": rng.random(n) < self.keep_prob}

    # ------------------------------------------------------------ set-up
    async def prepare(self) -> None:
        """Write every object once (a bounded working set: the window
        overwrites, it never grows the store), then launch each lane
        bucket and each class's width once through the encode matrix,
        so that nothing the mix reaches compiles in the window."""
        io, sem = self.env.io, asyncio.Semaphore(64)

        async def put(name, data):
            async with sem:
                await io.write_full(name, data)
        jobs = []
        for k in self.classes:
            jobs += [put(n, k.payloads[k.read_holds[i]])
                     for i, n in enumerate(k.read_names)]
            jobs += [put(n, k.payloads[k.write_holds[i]])
                     for i, n in enumerate(k.write_names)]
        await asyncio.gather(*jobs)
        from ceph_tpu.osd.ec_queue import LANE_BUCKETS
        env = self.env
        widths = {c.size // env.k for c in self.classes}
        # a window is at most the bucket of the widest group: every
        # worker's write pending at once, of the largest class
        top = next((b for b in LANE_BUCKETS
                    if b >= self.depth * max(widths)), LANE_BUCKETS[-1])
        reach = {b for b in LANE_BUCKETS if b <= top}
        queue = next(iter(env.cluster.osds.values())).ec_queue
        mats = warm.seam_matrices(env.k, env.m, self.seam_shapes(), [])
        for lanes in sorted(reach | widths):
            await warm.enumerate_seam(queue, env.k, mats, lanes, 1)

    def seam_shapes(self) -> dict:
        """What the harness walks after `prepare()`: the smallest class's
        requests, up to `depth` pending at once, encodes only (the other
        widths `prepare()` has launched)."""
        return {"lanes": min(c.size for c in self.classes) // self.env.k,
                "depth": self.depth, "encode": True, "decode": False}

    def seam_rows(self) -> int:
        """Output rows of this mix's device requests: m parity rows."""
        return self.env.m

    # -------------------------------------------------------------- load
    def counters(self) -> dict:
        """The seam's and the program's counters as they stand, summed
        over every daemon; a counter this program does not keep is
        left out."""
        env = self.env
        osds = list(env.cluster.osds.values())
        out = diag.cluster_counters(osds, env.admin, env.cluster.mons)
        dumps = [osd.ctx.perf.dump().get("ec_batch_queue", {})
                 for osd in osds]
        for key in PROGRAM_COUNTERS:
            vals = [int(d[key]) for d in dumps if key in d]
            if vals:
                out[key] = sum(vals)
        if self.budget_stats is not None:
            out["throttle_waits"] = self.budget_stats()["throttle_waits"]
        return out

    def start(self) -> None:
        self._at_start = self.counters()
        loop = asyncio.get_running_loop()
        self.tasks = [loop.create_task(self._worker(w))
                      for w in range(self.depth)]

    async def _worker(self, w: int) -> None:
        io, plan, classes = self.env.io, self.plan[w], self.classes
        cls, is_read, obj, pay, keep = (plan["cls"], plan["is_read"],
                                        plan["obj"], plan["pay"],
                                        plan["keep"])
        clock = time.monotonic
        i = 0
        while not self.stop_flag:
            j = i % len(cls)
            i += 1
            self.attempted += 1
            c = int(cls[j])
            k = classes[c]
            o = int(obj[j])
            if is_read[j]:
                t0 = clock()
                try:
                    got = await io.read(k.read_names[o], length=k.size)
                except Exception as e:              # counted, not hidden
                    self.failed.append(f"read {k.name} {o}: {e!r}")
                    continue
                t1 = clock()
                if len(got) != k.size:
                    self.failed.append(f"read {k.name} {o}: "
                                       f"{len(got)} bytes")
                    continue
                if self.keep_armed and keep[j] \
                        and len(k.kept) < k.keep_reads:
                    k.kept.append((o, got))
            else:
                p = int(pay[j])
                t0 = clock()
                try:
                    await io.write_full(k.write_names[o], k.payloads[p])
                except Exception as e:
                    self.failed.append(f"write {k.name} {o}: {e!r}")
                    k.write_unknown.add(o)
                    continue
                t1 = clock()
                k.write_holds[o] = p
                self.written_bytes += k.size
            self.t_end.append(t1)
            self.lat.append(t1 - t0)
            self.is_read.append(bool(is_read[j]))
            self.op_class.append(c)

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
        self._at_stop = self.counters()

    # ------------------------------------------------------------ result
    def window(self, t0: float, seconds: float) -> dict:
        """The ops that COMPLETED inside [t0, t0 + seconds], each with
        its own bytes."""
        t1 = t0 + seconds
        rl, wl, tt, amt = [], [], [], []
        tails = {(c, rd): [] for c in range(len(self.classes))
                 for rd in (True, False)}
        for te, la, rd, c in zip(self.t_end, self.lat, self.is_read,
                                 self.op_class):
            if t0 <= te <= t1:
                (rl if rd else wl).append(la * 1e3)
                tt.append(te)
                amt.append(self.classes[c].size)
                tails[c, rd].append(la * 1e3)
        self._class_tails = {}
        for (c, rd), ms in tails.items():
            tag = f"{'read' if rd else 'write'}_{self.classes[c].name}"
            self._class_tails[tag] = (len(ms), stats.percentile(ms, 50),
                                      stats.percentile(ms, 95))
        return {"read_ms": rl, "write_ms": wl, "t_end": tt, "bytes": amt,
                "ops": len(tt), "user_bytes": sum(amt)}

    async def verify(self) -> Dict[str, tuple]:
        """The numbers compared, each (value, limit), after the window;
        limit None: printed for the reader of a run."""
        env = self.env
        out = {}
        bad = 0
        for k in self.classes:
            bad += sum(1 for o, got in k.kept
                       if got != k.payloads[k.read_holds[o]])
            out[f"window_reads_kept_{k.name}"] = (len(k.kept), None)
        out["window_read_mismatch"] = (bad, 0)
        want = {}
        sample = {}
        rng = np.random.default_rng([int(env.seed), 0x5AA7D5])
        for k in self.classes:
            known = [i for i in range(k.n_write)
                     if i not in k.write_unknown]
            want.update({k.write_names[i]: k.payloads[k.write_holds[i]]
                         for i in known})
            picks = rng.choice(known, size=min(k.check_shards, len(known)),
                               replace=False) if known else []
            sample.update({k.write_names[i]: k.payloads[k.write_holds[i]]
                           for i in picks})
        out["readback_objects"] = (len(want), None)
        out["readback_mismatch"] = (
            await verify.readback_mismatch(env.io, want), 0)
        seen, differ = stored_shard_mismatch(env, sample)
        out["shards_checked"] = (seen, None)
        out["shard_mismatch"] = (differ, 0)
        # the seam's bytes over the load against this kind's own writes
        moved = self._at_stop["device_bytes"] \
            - self._at_start["device_bytes"]
        out["bytes_written"] = (self.written_bytes, None)
        out["device_bytes_short"] = (
            max(0, self.written_bytes - moved), 0)
        for key in PROGRAM_COUNTERS + ("throttle_waits",):
            if key in self._at_stop:
                out[key] = (self._at_stop[key] - self._at_start[key], None)
        if self.budget_stats is not None:
            ob = self.budget_stats()
            out["inflight_bytes_peak"] = (ob["inflight_bytes_peak"], None)
            out["inflight_ops_peak"] = (ob["inflight_ops_peak"], None)
        for tag, (n, p50, p95) in self._class_tails.items():
            out[f"{tag}_ops"] = (n, None)
            if n:
                out[f"{tag}_p50_ms"] = (round(p50, 3), None)
                out[f"{tag}_p95_ms"] = (round(p95, 3), None)
        return out


def stored_shard_mismatch(env, sample: Dict[str, bytes]) -> Tuple[int, int]:
    """(shards compared, shards that differ) over the sample: what each
    up OSD of the object's acting set stored, against the reference:
    the data chunks themselves, and the parity the reference computes
    a block of `PARITY_BLOCK_LANES` lanes at a time."""
    from ceph_tpu.client.objecter import ObjectLocator
    from ceph_tpu.store.types import CollectionId, ObjectId
    omap = env.admin.monc.osdmap
    loc = ObjectLocator(env.pool_id)
    gen = reference.generator(env.k, env.m)
    seen = bad = 0
    for name, data in sample.items():
        pgid, acting = omap.object_to_acting(name, loc)[:2]
        if len(data) % env.k:
            raise ValueError(f"{name}: {len(data)} bytes do not split "
                             f"into {env.k} equal chunks")
        chunks = np.frombuffer(data, np.uint8).reshape(env.k, -1)
        for j, osd_id in enumerate(acting):
            osd = env.cluster.osds.get(osd_id)
            if osd is None:
                continue                    # a killed OSD holds nothing
            seen += 1
            try:
                raw = osd.store.read(
                    CollectionId.pg(env.pool_id, pgid.seed, j),
                    ObjectId(name, pool=env.pool_id))
            except Exception:
                bad += 1
                continue
            got = np.frombuffer(raw, np.uint8)
            if not _same_shard(got, chunks, gen, j, env.k):
                bad += 1
    return seen, bad


def _same_shard(got: np.ndarray, chunks: np.ndarray, gen: np.ndarray,
                j: int, k: int) -> bool:
    lanes = chunks.shape[1]
    if len(got) != lanes:
        return False
    if j < k:
        return bool(np.array_equal(got, chunks[j]))
    row = gen[j:j + 1]
    for a in range(0, lanes, PARITY_BLOCK_LANES):
        b = min(lanes, a + PARITY_BLOCK_LANES)
        if not np.array_equal(got[a:b],
                              reference.apply(row, chunks[:, a:b])[0]):
            return False
    return True

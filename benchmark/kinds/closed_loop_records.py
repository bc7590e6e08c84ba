"""Traffic kind `closed_loop_records`: `depth` long-lived workers, each
waiting for its reply before its next op, over ONE range of records
that reads and updates share, drawn with skew (YCSB's core workloads:
`-threads N` client threads, `requestdistribution=zipfian`, one key
space).  A mix is a data file of its parameters:

    depth          workers (YCSB's threads)
    object_size    bytes of every record, every update and every read
    read_ratio     share of ops that are reads; the rest are updates
                   that rewrite the whole record (`write_full`)
    records        the ONE range of names, written once in set-up
    select         "zipfian": plain Zipf(`zipf_constant`) over ranks
                   0..records-1, P(rank r) ~ 1 / (r + 1) ** constant
    zipf_constant  YCSB's 0.99
    payloads       distinct payloads made from the seed
    ramp_s         load before the window opens
    check_shards   seeded records whose stored shards are compared
    check_hottest  and this many of the hottest ranks besides
    prepare_depth  writes in flight in set-up (it is `setup_s`, not
                   traffic)

Object NAMES do not depend on the seed, and neither does which name is
hot: ranks are scattered over the names by a FIXED permutation (YCSB's
scrambled zipfian hashes ranks over the key space), so placement and
the hot PGs are the same in every run.  Op kinds, ranks and payloads
are drawn from the seed.

No worker owns a record: two workers meet on the hot ones all the time,
so "the last acked write" of an object is not defined and the object
model is by ORDER (`RecordOrder`, plain, below; `open_loop.WriteOrder`
is its write half).  Every op takes a sequence number at the instant
its worker calls `io.read` / `io.write_full`, before any await: that is
the order in which one client SUBMITTED them, and RADOS applies the ops
on one object from one client in that order.  So

  * a write's ack must not overtake an earlier-submitted write to the
    same object still in flight (`write_order_violations`, limit 0);
  * EVERY read of the load returns exactly the payload of the last write
    submitted before it to that object, the set-up's if there was none
    (`read_order_violations`, limit 0; compared at the read's reply; a
    read whose deciding write failed or was never answered is unknown,
    left out and counted as `unknown_reads`);
  * a read returns the record's `object_size` bytes, not its padded
    stripe (`read_length_mismatch`, limit 0);
  * after the close every record reads back as the last SUBMITTED write
    among those acked (`readback_mismatch`, limit 0; `unknown_objects`
    counted), and the stored shards are the padded reference's
    (`benchmark/reference_padded.py`; `shard_mismatch`, limit 0).

Printed without limit: how often the order was put to the test
(`overlapping_writes`, `reads_behind_a_write`, `order_tested`: 0 when
either is 0, and the limits above then held the run to nothing), where
the skew fell (`hottest_object_ops`, `hottest_pg_share`), the client's
budget, and what the program counts of its own window and seam over the
load (`same_object_waits`, `window_full_waits`, `chain_peak`,
`device_lanes_launched`: the seam's pad share is 1 - (`device_bytes` /
k) / `device_lanes_launched`), read from the daemons' public perf
dumps; a program without such a counter prints none."""

from __future__ import annotations

import asyncio
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmark import reference, reference_padded, verify
from benchmark.manifest import Manifest

_closed = Manifest().kind("closed_loop")
_open = Manifest().kind("open_loop")

PLAN_OPS = 1 << 12          # ops drawn per worker; the plan wraps
PERMUTATION_SEED = 0x59C5BA     # rank -> name: one for every run
SETUP = -1                  # the "sequence number" of the set-up's write

#: the program's own counters, (perf group, key, how it adds up over
#: the daemons): sums are taken over the load, a peak as it stands
PROGRAM_COUNTERS = (("osd_op_window", "same_object_waits", sum),
                    ("osd_op_window", "window_full_waits", sum),
                    ("osd_op_window", "chain_peak", max),
                    ("ec_batch_queue", "device_lanes_launched", sum))


def zipf_cdf(n: int, constant: float) -> np.ndarray:
    """Cumulative shares of ranks 0..n-1 under plain Zipf."""
    weight = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** constant
    return np.cumsum(weight) / weight.sum()


def draw_ranks(rng, cdf: np.ndarray, count: int) -> np.ndarray:
    """`count` ranks by inversion of the cumulative shares."""
    ranks = np.searchsorted(cdf, rng.random(count), side="right")
    return np.minimum(ranks, len(cdf) - 1)


def rank_to_record(n: int) -> np.ndarray:
    """The record each rank names: fixed, whatever the seed."""
    return np.random.default_rng(PERMUTATION_SEED).permutation(n)


class RecordOrder(_open.WriteOrder):
    """What each record holds and what each read had to return, by the
    order in which one client SUBMITTED its ops.  The write half
    (`submit`, `ack`, `fail`, `close`, `unknown`, `holds`) is
    `open_loop.WriteOrder`'s; this adds the reads."""

    def __init__(self, holds):
        super().__init__(holds)
        self.initial = holds.copy()         # what the set-up wrote
        self.last_write: Dict[int, Tuple[int, int]] = {}
        self.lost: set = set()              # write seqs failed / unanswered
        self.reads: List[Tuple[int, bool]] = []  # (deciding seq, matched)
        self.reads_behind = 0   # reads submitted behind a flying write

    def submit_write(self, obj: int, seq: int, payload: int) -> None:
        self.submit(obj, seq)
        self.last_write[obj] = (seq, payload)

    def submit_read(self, obj: int) -> Tuple[int, int]:
        """(sequence number, payload) of the write that decides what
        this read returns: the last one submitted to `obj` so far."""
        if obj in self.flying:
            self.reads_behind += 1
        return self.last_write.get(obj, (SETUP, int(self.initial[obj])))

    def read_reply(self, deciding: int, matched: bool) -> None:
        self.reads.append((deciding, matched))

    def fail(self, obj: int, seq: int) -> None:
        super().fail(obj, seq)
        self.lost.add(seq)

    def read_verdict(self) -> Tuple[int, int]:
        """After `close()`: (reads that did not return their deciding
        write's payload, reads whose deciding write is unknown)."""
        bad = unknown = 0
        for deciding, matched in self.reads:
            if deciding in self.lost:
                unknown += 1
            elif not matched:
                bad += 1
        return bad, unknown


class Load(_closed.Load):
    """`closed_loop`'s start, close, window and result line (its
    `start`, `stop`, `window` and `seam_rows` run here on the attributes
    they read there); the plan, the workers, the object model and the
    comparison are this kind's own."""

    def __init__(self, env):
        self.env = env
        t = env.traffic
        self.depth = int(t["depth"])
        self.size = int(t["object_size"])
        self.read_ratio = float(t["read_ratio"])
        self.n_records = int(t["records"])
        self.select = t.get("select", "zipfian")
        self.zipf_constant = float(t.get("zipf_constant", 0.99))
        self.ramp_s = float(t.get("ramp_s", 3.0))
        self.check_shards = int(t.get("check_shards", 0))
        self.check_hottest = int(t.get("check_hottest", 0))
        self.prepare_depth = int(t.get("prepare_depth", 64))
        self.kill_osds = 0
        if not 0.0 < self.read_ratio < 1.0:
            raise ValueError("reads and updates share the records: "
                             "read_ratio lies between 0 and 1")
        if self.select != "zipfian":
            raise ValueError(f"no selector {self.select!r}")
        self.names = [f"benchmark_data_{env.cell}_object{i}"
                      for i in range(self.n_records)]
        self.record_of = rank_to_record(self.n_records)
        self.payloads = reference.payloads(env.seed, int(t["payloads"]),
                                           self.size)
        npay = len(self.payloads)
        self.order = RecordOrder(np.arange(self.n_records) % npay)
        # the plan, all of it drawn before the load starts
        rng = np.random.default_rng([int(env.seed), 0x5EC02D5])
        shape = (self.depth, PLAN_OPS)
        self.plan_read = rng.random(shape) < self.read_ratio
        cdf = zipf_cdf(self.n_records, self.zipf_constant)
        self.plan_rank = draw_ranks(rng, cdf, self.depth * PLAN_OPS
                                    ).reshape(shape)
        self.plan_pay = rng.integers(0, npay, shape)
        # the client's op budget, where the program's Objecter keeps one
        self.budget_stats = getattr(
            getattr(getattr(env, "admin", None), "objecter", None),
            "budget_stats", None)
        self._seq = 0                       # the client's submit order
        self.ops_on = np.zeros(self.n_records, np.int64)
        self.read_length_mismatch = 0
        self.stop_flag = False
        self.tasks: List[asyncio.Task] = []
        # records, one entry per completed op
        self.t_end: List[float] = []
        self.lat: List[float] = []
        self.is_read: List[bool] = []
        self.failed: List[str] = []
        self.attempted = 0
        self.keep_armed = False
        self._at_start: Dict[str, Optional[int]] = {}
        self._waits0 = 0

    # ------------------------------------------------------------ set-up
    async def prepare(self) -> None:
        """Write every record once."""
        io, sem = self.env.io, asyncio.Semaphore(self.prepare_depth)
        initial = self.order.initial

        async def put(i):
            async with sem:
                await io.write_full(self.names[i], self.payloads[initial[i]])
        await asyncio.gather(*[put(i) for i in range(self.n_records)])

    def seam_shapes(self) -> dict:
        """Requests of one CHUNK of lanes (the padded geometry, not
        `size // k`), up to `depth` pending at once, encodes only."""
        return {"lanes": reference_padded.chunk_size(self.size, self.env.k),
                "depth": self.depth, "encode": True, "decode": False}

    # -------------------------------------------------------------- load
    def start(self) -> None:
        self._at_start = self.program_counters()
        if self.budget_stats is not None:
            self._waits0 = self.budget_stats()["throttle_waits"]
        super().start()

    async def _worker(self, w: int) -> None:
        io, order, names = self.env.io, self.order, self.names
        is_read, ranks, pay = (self.plan_read[w], self.plan_rank[w],
                               self.plan_pay[w])
        record_of, payloads, size = self.record_of, self.payloads, self.size
        clock = time.monotonic
        i = 0
        while not self.stop_flag:
            j = i % PLAN_OPS
            i += 1
            self.attempted += 1
            obj = int(record_of[ranks[j]])
            self.ops_on[obj] += 1
            # the op's place in the client's order: taken here, with
            # no await between this and the call that submits it
            seq = self._seq
            self._seq += 1
            if is_read[j]:
                deciding, want = order.submit_read(obj)
                t0 = clock()
                try:
                    got = await io.read(names[obj], length=size)
                except Exception as e:              # counted, not hidden
                    self.failed.append(f"read {obj}: {e!r}")
                    continue
                t1 = clock()
                if len(got) != size:
                    self.read_length_mismatch += 1
                order.read_reply(deciding, got == payloads[want])
                self.is_read.append(True)
            else:
                p = int(pay[j])
                order.submit_write(obj, seq, p)
                t0 = clock()
                try:
                    await io.write_full(names[obj], payloads[p])
                except Exception as e:
                    order.fail(obj, seq)
                    self.failed.append(f"write {obj}: {e!r}")
                    continue
                t1 = clock()
                order.ack(obj, seq, p)
                self.is_read.append(False)
            self.t_end.append(t1)
            self.lat.append(t1 - t0)

    # ------------------------------------------------------------ result
    def program_counters(self) -> Dict[str, Optional[int]]:
        """The program's own counters as they stand, over every daemon
        (a killed one included), from the public perf dumps; None for a
        counter this program does not keep."""
        dumps = [osd.ctx.perf.dump()
                 for osd in getattr(self.env.cluster, "osds", {}).values()]
        out: Dict[str, Optional[int]] = {}
        for group, key, fold in PROGRAM_COUNTERS:
            vals = [int(d[group][key]) for d in dumps
                    if key in d.get(group, {})]
            out[key] = fold(vals) if vals else None
        return out

    def hottest_pg_share(self) -> float:
        """Share of the load's ops that went to the one PG that drew
        most (names, and so this PG, are the same in every run)."""
        from ceph_tpu.client.objecter import ObjectLocator
        omap = self.env.admin.monc.osdmap
        loc = ObjectLocator(self.env.pool_id)
        per_pg: Dict[int, int] = {}
        for obj in np.nonzero(self.ops_on)[0]:
            pgid = omap.object_to_acting(self.names[obj], loc)[0]
            per_pg[pgid.seed] = per_pg.get(pgid.seed, 0) \
                + int(self.ops_on[obj])
        return max(per_pg.values()) / max(1, int(self.ops_on.sum()))

    async def verify(self) -> Dict[str, tuple]:
        """The numbers compared, each (value, limit), after the window;
        limit None: printed for the reader of a run."""
        env, order = self.env, self.order
        order.close()
        unknown = order.unknown()
        bad_reads, unknown_reads = order.read_verdict()
        out = {
            "write_order_violations": (order.violations, 0),
            "reads_checked": (len(order.reads) - unknown_reads, None),
            "read_order_violations": (bad_reads, 0),
            "unknown_reads": (unknown_reads, None),
            "read_length_mismatch": (self.read_length_mismatch, 0),
        }
        known = [i for i in range(self.n_records) if i not in unknown]
        holds = order.holds
        want = {self.names[i]: self.payloads[holds[i]] for i in known}
        out["readback_objects"] = (len(want), None)
        out["readback_mismatch"] = (
            await verify.readback_mismatch(env.io, want, depth=64), 0)
        out["unknown_objects"] = (len(unknown), None)
        rng = np.random.default_rng([int(env.seed), 0x5AA7D5])
        picks = set(rng.choice(known, size=min(self.check_shards,
                                               len(known)),
                               replace=False).tolist()) if known else set()
        picks |= {int(r) for r in self.record_of[:self.check_hottest]
                  if int(r) not in unknown}
        seen, differ = stored_shard_mismatch(
            env, {self.names[i]: self.payloads[holds[i]]
                  for i in sorted(picks)})
        out["shards_checked"] = (seen, None)
        out["shard_mismatch"] = (differ, 0)
        # how often the order was put to the test, and where the skew fell
        out["overlapping_writes"] = (order.overlapping, None)
        out["reads_behind_a_write"] = (order.reads_behind, None)
        out["order_tested"] = (int(order.overlapping > 0
                                   and order.reads_behind > 0), None)
        out["hottest_object_ops"] = (int(self.ops_on.max()), None)
        out["hottest_pg_share"] = (round(self.hottest_pg_share(), 4), None)
        if self.budget_stats is not None:
            ob = self.budget_stats()
            out["throttle_waits"] = (ob["throttle_waits"] - self._waits0,
                                     None)
            out["inflight_ops_peak"] = (ob["inflight_ops_peak"], None)
        # the program's own counters over the load (a peak: as it stands)
        now, then = self.program_counters(), self._at_start
        for _group, key, fold in PROGRAM_COUNTERS:
            if now[key] is not None:
                out[key] = (now[key] if fold is max
                            else now[key] - (then.get(key) or 0), None)
        return out


def stored_shard_mismatch(env, sample: Dict[str, bytes]) -> Tuple[int, int]:
    """(shards compared, shards that differ) over the sample: what each
    up OSD of the object's acting set stored against the PADDED
    reference's encode of what the object should hold
    (`verify.shard_mismatch` with `reference_padded.shards`)."""
    from ceph_tpu.client.objecter import ObjectLocator
    from ceph_tpu.store.types import CollectionId, ObjectId
    omap = env.admin.monc.osdmap
    loc = ObjectLocator(env.pool_id)
    seen = bad = 0
    for name, data in sample.items():
        pgid, acting = omap.object_to_acting(name, loc)[:2]
        want = reference_padded.shards(data, env.k, env.m)
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
            if not np.array_equal(np.frombuffer(raw, np.uint8), want[j]):
                bad += 1
    return seen, bad

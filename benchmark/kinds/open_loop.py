"""Traffic kind `open_loop`: arrivals that do not wait.  A schedule of
due times is made from the seed before the load starts; at each due
time the op is submitted in a task of its own, whatever is in flight
(independent users: an application fleet on librados, a gateway's front
end; YCSB's `-target` with `measurement.interval=intended`).  The
generator never skips and never thins: when it wakes late it submits
everything that is due, and an op's latency is its reply minus its DUE
time, so the generator's own lateness and a wait for the client's op
budget both count.  A mix is a data file of its parameters:

    rate_ops_s     offered rate, ops per second (a number fixed in the
                   file: found once by a sweep, PERF.md section 4)
    arrivals       "poisson" (exponential gaps from the seed) or "even"
    object_size, read_ratio, read_objects, write_objects, payloads,
    ramp_s, keep_reads, keep_prob, check_shards, kill_osds
                   as in `closed_loop`
    read_select, write_select   "uniform" only: COSBench's u() over the
                   WHOLE range (no worker owns a slice of the ring)
    warm_depth     widths the seam's warm-up walks (an open loop has no
                   depth of its own: `seam_shapes()["depth"]`)

Object NAMES do not depend on the seed; due times, op kinds, objects
and payloads do.  Reads go to the prepared range and writes to the
separate ring, so a read never races a write.  Two writes to one object
CAN be in flight together, so the cell holds the system to RADOS's
per-object order: writes to one object from one client apply, and are
acked, in the order submitted.  `WriteOrder` is that object model,
written plainly: an object holds the payload of the LAST SUBMITTED
write among those acked; an object with a failed or unanswered write
submitted after that one is unknown, left out of the read-back and
counted.  `write_order_violations` counts acks that overtook an
earlier-submitted write to the same object still in flight (limit 0).

The kind refuses to measure under a program whose Objecter keeps no op
budget: the configuration states `objecter_inflight_ops` and
`objecter_inflight_op_bytes`, and a client that ignores them would put
an unbounded backlog on the wire after a stall under this cell's name.
"""

from __future__ import annotations

import asyncio
import time
from typing import Dict, List

import numpy as np

from benchmark import reference, stats
from benchmark.manifest import Manifest

_closed = Manifest().kind("closed_loop")

PLAN_OPS = 1 << 16          # ops drawn; the plan wraps, the clock goes on


class WriteOrder:
    """What each object of the write ring holds, by the order in which
    its writes were SUBMITTED and which of them were acked."""

    def __init__(self, holds):
        self.holds = holds                  # object -> payload index
        self.acked_seq: Dict[int, int] = {}     # last submitted, acked
        self.lost_seq: Dict[int, int] = {}      # last failed / unanswered
        self.flying: Dict[int, List[int]] = {}  # object -> seqs in flight
        self.overlapping = 0    # submits that met a write in flight
        self.violations = 0     # acks that overtook an earlier submit

    def submit(self, obj: int, seq: int) -> None:
        fly = self.flying.setdefault(obj, [])
        if fly:
            self.overlapping += 1
        fly.append(seq)

    def _landed(self, obj: int, seq: int) -> List[int]:
        fly = self.flying[obj]
        fly.remove(seq)
        if not fly:
            del self.flying[obj]
        return fly

    def ack(self, obj: int, seq: int, payload: int) -> None:
        if any(s < seq for s in self._landed(obj, seq)):
            self.violations += 1
        if seq > self.acked_seq.get(obj, -1):
            self.acked_seq[obj] = seq
            self.holds[obj] = payload

    def fail(self, obj: int, seq: int) -> None:
        self._landed(obj, seq)
        self.lost_seq[obj] = max(seq, self.lost_seq.get(obj, -1))

    def close(self) -> None:
        """Whatever is still in flight was never answered."""
        for obj, fly in list(self.flying.items()):
            for seq in list(fly):
                self.fail(obj, seq)

    def unknown(self) -> set:
        return {obj for obj, seq in self.lost_seq.items()
                if seq > self.acked_seq.get(obj, -1)}


def schedule(seed: int, rate: float, arrivals: str, n: int) -> np.ndarray:
    """Seconds from the load's start at which op 0..n-1 is due."""
    if rate <= 0:
        raise ValueError("an open loop needs rate_ops_s > 0")
    if arrivals == "poisson":
        rng = np.random.default_rng([int(seed), 0x0BE21009])
        gaps = rng.exponential(1.0 / rate, n)
    elif arrivals == "even":
        gaps = np.full(n, 1.0 / rate)
    else:
        raise ValueError(f"no arrival process {arrivals!r}")
    return np.cumsum(gaps)


class Load(_closed.Load):
    """`closed_loop`'s set-up, window and comparison (its `prepare`,
    `seam_shapes`, `seam_rows`, `window` and `verify` run here on the
    attributes they read there); the plan, the generator and the object
    model are this kind's own: nothing of its workers is constructed."""

    def __init__(self, env):
        self.env = env
        t = env.traffic
        self.rate = float(t["rate_ops_s"])
        self.arrivals = t.get("arrivals", "poisson")
        self.size = int(t["object_size"])
        self.read_ratio = float(t["read_ratio"])
        self.n_read = int(t.get("read_objects", 0))
        self.n_write = int(t.get("write_objects", 0))
        self.ramp_s = float(t.get("ramp_s", 3.0))
        self.keep_reads = int(t.get("keep_reads", 0))
        self.keep_prob = float(t.get("keep_prob", 0.0))
        self.check_shards = int(t.get("check_shards", 0))
        self.kill_osds = int(t.get("kill_osds", 0))
        self.depth = int(t["warm_depth"])      # what the seam walk walks
        for key in ("read_select", "write_select"):
            if t.get(key, "uniform") != "uniform":
                raise ValueError(f"open_loop draws uniformly over the "
                                 f"whole range; {key}={t[key]!r}")
        if self.read_ratio > 0 and not self.n_read:
            raise ValueError("reads need read_objects")
        if self.read_ratio < 1 and not self.n_write:
            raise ValueError("writes need write_objects")
        self.objecter = getattr(getattr(env, "admin", None), "objecter",
                                None)
        if self.objecter is not None and not hasattr(
                self.objecter, "budget_stats"):
            raise RuntimeError(
                "refusing to measure: the configuration states the "
                "Objecter's op budget (objecter_inflight_ops, "
                "objecter_inflight_op_bytes) and this program's "
                "Objecter keeps none")
        prefix = f"benchmark_data_{env.cell}_object"
        self.read_names = [f"{prefix}{i}" for i in range(self.n_read)]
        self.write_names = [f"{prefix}w{i}" for i in range(self.n_write)] \
            if self.n_read else [f"{prefix}{i}" for i in range(self.n_write)]
        self.payloads = reference.payloads(env.seed, int(t["payloads"]),
                                           self.size)
        npay = len(self.payloads)
        # the reference's state: which payload each object holds
        self.read_holds = np.arange(self.n_read) % npay
        self.write_holds = np.arange(self.n_write) % npay
        self.write_unknown: set = set()
        self.order = WriteOrder(self.write_holds)
        # the plan, all of it drawn before the load starts
        n = PLAN_OPS
        self.due_at = schedule(env.seed, self.rate, self.arrivals, n)
        rng = np.random.default_rng([int(env.seed), 0x09E2100B])
        self.plan_read = rng.random(n) < self.read_ratio
        self.plan_rd = rng.integers(0, max(1, self.n_read), n)
        self.plan_wr = rng.integers(0, max(1, self.n_write), n)
        self.plan_pay = rng.integers(0, npay, n)
        self.plan_keep = rng.random(n) < self.keep_prob
        self.stop_flag = False
        self._timer = None
        self._live: set = set()             # op tasks not yet finished
        self._next = 0                      # the next op to submit
        self.t_start = 0.0
        # records: per completed op, and per submitted op
        self.t_end: List[float] = []
        self.lat: List[float] = []
        self.is_read: List[bool] = []
        self.due: List[float] = []
        self.late: List[float] = []         # submit - due
        self.failed: List[str] = []
        self.attempted = 0
        self.kept: List[tuple] = []         # (read object, bytes)
        self.keep_armed = False
        self.unanswered_at_close = 0
        self.outstanding_peak = 0           # most op tasks alive at once
        self._waits0 = 0
        self._win = (0.0, 0.0)

    @property
    def tasks(self) -> list:
        return list(self._live)

    # -------------------------------------------------------------- load
    def due_time(self, i: int) -> float:
        """When op i is due: the plan wraps, the clock goes on."""
        lap, j = divmod(i, PLAN_OPS)
        return self.t_start + lap * float(self.due_at[-1]) \
            + float(self.due_at[j])

    def start(self) -> None:
        if self.objecter is not None:   # read, never written
            self._waits0 = self.objecter.budget_stats()["throttle_waits"]
        self._loop = asyncio.get_running_loop()
        self.t_start = time.monotonic()
        self._fire()

    def _fire(self) -> None:
        """Submit everything that is due, then sleep to the next due
        time.  One timer, re-armed: no task of its own to fall behind."""
        if self.stop_flag:
            return
        loop, now = self._loop, time.monotonic()
        while True:
            i = self._next
            due = self.due_time(i)
            if due > now:
                break
            self._next = i + 1
            self.attempted += 1
            self.due.append(due)
            self.late.append(now - due)
            task = loop.create_task(self._op(i, due))
            self._live.add(task)
            task.add_done_callback(self._live.discard)
        if len(self._live) > self.outstanding_peak:
            self.outstanding_peak = len(self._live)
        self._timer = loop.call_later(due - now, self._fire)

    async def _op(self, i: int, due: float) -> None:
        io, j = self.env.io, i % PLAN_OPS
        if self.plan_read[j]:
            obj = int(self.plan_rd[j])
            try:
                got = await io.read(self.read_names[obj], length=self.size)
            except Exception as e:                  # counted, not hidden
                self.failed.append(f"read {obj}: {e!r}")
                return
            t1 = time.monotonic()
            self.t_end.append(t1)
            self.lat.append(t1 - due)
            self.is_read.append(True)
            if len(got) != self.size:
                self.failed.append(f"read {obj}: {len(got)} bytes")
            elif self.keep_armed and self.plan_keep[j] \
                    and len(self.kept) < self.keep_reads:
                self.kept.append((obj, got))
        else:
            obj, p = int(self.plan_wr[j]), int(self.plan_pay[j])
            self.order.submit(obj, i)
            try:
                await io.write_full(self.write_names[obj], self.payloads[p])
            except Exception as e:
                self.order.fail(obj, i)
                self.failed.append(f"write {obj}: {e!r}")
                return
            t1 = time.monotonic()
            self.order.ack(obj, i, p)
            self.t_end.append(t1)
            self.lat.append(t1 - due)
            self.is_read.append(False)

    async def stop(self) -> None:
        """End the schedule; every op in flight is waited for (a minute
        if need be), as `closed_loop.stop()` does."""
        self.stop_flag = True
        if self._timer is not None:
            self._timer.cancel()
        live = list(self._live)
        self.unanswered_at_close = len(live)
        if not live:
            return
        _done, pending = await asyncio.wait(live, timeout=60.0)
        for t in pending:
            t.cancel()
            self.failed.append("op never answered within 60 s of the close")
        if pending:
            await asyncio.wait(pending, timeout=5.0)

    # ------------------------------------------------------------ result
    def window(self, t0: float, seconds: float) -> dict:
        """The ops that COMPLETED inside [t0, t0 + seconds], as the
        other kinds count them; `lat` here is reply minus DUE time."""
        self._win = (t0, t0 + seconds)
        return super().window(t0, seconds)

    async def verify(self) -> Dict[str, tuple]:
        """The numbers compared, each (value, limit), after the window:
        `closed_loop`'s, with the ring's contents by the object model's
        order; limit None: printed for the reader of a run."""
        order = self.order
        order.close()
        self.write_unknown = order.unknown()
        out = await super().verify()
        if self.read_ratio < 1:
            out["unknown_objects"] = (len(self.write_unknown), None)
            out["write_order_violations"] = (order.violations, 0)
            out["overlapping_writes"] = (order.overlapping, None)
            # 0: no two writes to one object met in this run, so the
            # limit above held it to nothing
            out["write_order_tested"] = (int(order.overlapping > 0), None)
        # the generator and the client's budget
        t0, t1 = self._win
        late = [la * 1e3 for du, la in zip(self.due, self.late)
                if t0 <= du <= t1]
        out["due_in_window"] = (len(late), None)
        out["completed_in_window"] = (
            sum(1 for te in self.t_end if t0 <= te <= t1), None)
        out["sched_late_p99_ms"] = (stats.percentile(late, 99) or 0.0, None)
        out["unanswered_at_close"] = (self.unanswered_at_close, None)
        # the generator's own count: ops due and not yet answered, in
        # flight or waiting for budget, at most at once over the load
        out["outstanding_peak"] = (self.outstanding_peak, None)
        if self.objecter is not None:
            # the client's counters; its peaks are since it started, so
            # the set-up's writes are in them
            ob = self.objecter.budget_stats()
            out["inflight_ops_peak"] = (ob["inflight_ops_peak"], None)
            out["inflight_bytes_peak"] = (ob["inflight_bytes_peak"], None)
            out["throttle_waits"] = (ob["throttle_waits"] - self._waits0,
                                     None)
        return out

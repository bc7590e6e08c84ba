"""Sharded OSD data plane (ISSUE 10): osd/shards.py.

Coverage map:
  * shard_index — stable pgid->shard hash (process-stable, shard-less
    identity, full coverage of the shard range);
  * Courier — FIFO order, batched wakeups (one drain per burst), and
    cross-thread posting;
  * e2e inline lanes — a 4-shard EC cluster serves writes+reads with
    zero local-path encodes, PG work pinned to home shards, handoff
    wakeups batched (wakeups < ops), and sub-op inline applies
    engaged;
  * e2e threaded — the same cluster with real per-shard event-loop
    threads (the msgr-worker split) stays correct through teardown;
  * objecter corked batching — N concurrent submits to one OSD ride
    one MOSDOpBatch (one frame / one local handoff), each earning its
    own reply; single submits stay unbatched on the wire;
  * backward compat — osd_op_num_shards=1 leaves the plane disabled:
    no shard router on the messenger, route() is an inline call
    (today's dispatch, bit-for-bit — the pin the rest of tier-1 runs
    under via FAST_CFG).
"""

import asyncio

import pytest
import threading

from ceph_tpu.osd.shards import Courier, shard_index
from ceph_tpu.osd.types import PGId
from ceph_tpu.qa.cluster import Cluster, make_ctx


# ------------------------------------------------------------- unit: hash

def test_shard_index_stable_and_covering():
    n = 4
    seen = set()
    for pool in range(4):
        for seed in range(64):
            pgid = PGId(pool, seed)
            i = shard_index(pgid, n)
            assert 0 <= i < n
            seen.add(i)
            # stable across calls and shard-qualified ids (EC shard
            # members of one PG share the home shard)
            assert shard_index(pgid, n) == i
            assert shard_index(pgid.with_shard(2), n) == i
    assert seen == set(range(n))        # every shard gets PGs
    assert shard_index(PGId(1, 2), 1) == 0


# ---------------------------------------------------------- unit: courier

def test_courier_fifo_and_batched_wakeups():
    async def run():
        loop = asyncio.get_running_loop()
        c = Courier(loop, "t")
        flushes = []
        c.on_flush = flushes.append
        got = []
        for i in range(10):
            c.post(got.append, i)
        assert got == []                # nothing ran synchronously
        await asyncio.sleep(0)
        assert got == list(range(10))   # FIFO
        assert flushes == [10]          # ONE drain for the burst
    asyncio.run(run())


def test_courier_cross_thread_post():
    async def run():
        loop = asyncio.get_running_loop()
        c = Courier(loop, "x")
        got = []
        done = threading.Event()

        def producer():
            for i in range(50):
                c.post(got.append, i)
            done.set()

        t = threading.Thread(target=producer)
        t.start()
        for _ in range(2000):
            await asyncio.sleep(0.001)
            if done.is_set() and len(got) == 50:
                break
        t.join()
        assert got == list(range(50))
    asyncio.run(run())


# ------------------------------------------------------------ e2e helpers

def _ctx_factory(shards, threads=False, tracing=False):
    def f(name):
        c = make_ctx(name)
        c.config.set("osd_op_num_shards", shards)
        c.config.set("osd_shard_threads", threads)
        c.config.set("ms_local_delivery", True)
        if tracing:
            c.config.set("op_tracing", True)
        return c
    return f


def _sum_shard_counters(cl):
    out = {}
    for osd in cl.osds.values():
        for k, v in osd.shards.counters().items():
            if isinstance(v, (int, float)):
                out[k] = out.get(k, 0) + v
    return out


async def _rw_burst(cl, admin, pool="shpool", n=24, ec=True):
    if ec:
        await admin.pool_create(pool, pg_num=4, pool_type="erasure",
                                k=2, m=2)
    else:
        await admin.pool_create(pool, pg_num=4)
    io = admin.open_ioctx(pool)
    blobs = {f"s{i:03d}": bytes([i]) * (4096 + i) for i in range(n)}
    await cl.write_burst(io, blobs, iodepth=12)
    for k, v in blobs.items():
        assert await io.read(k) == v
    return io


# ------------------------------------------------------- e2e inline lanes

def test_sharded_inline_cluster_rw_and_home_shard_pinning():
    from ceph_tpu.msg import payload as payload_mod
    from ceph_tpu.osd.shards import shard_index as sidx

    async def run():
        cl = Cluster(ctx_factory=_ctx_factory(4))
        admin = await cl.start(4)
        payload_mod.reset_counters()
        await _rw_burst(cl, admin)
        enc = payload_mod.counters()
        # zero-encode invariant holds through the classify seam
        assert enc["msg_encode_calls"] == 0, enc
        sc = _sum_shard_counters(cl)
        assert sc["handoff_ops"] > 0
        # batched wakeups: strictly fewer pump wakeups than items
        assert sc["handoff_wakeups"] < sc["handoff_ops"], sc
        # replica write sub-ops applied inline off the ring
        assert sc["subop_inline"] > 0, sc
        # and the reads' sub-reads were served off the ring
        assert sc["subread_inline"] > 0, sc
        # home-shard pinning: every PG's worker task lives on the loop
        # of shard_index(pgid) — the SHARD11 property, checked live
        for osd in cl.osds.values():
            assert osd.shards.enabled and osd.messenger.shard_router
            for pgid, pg in osd.pgs.items():
                home = osd.shards.shards[sidx(pgid, 4)]
                if pg._worker_task is not None:
                    assert pg._worker_task.get_loop() is home.loop
        await cl.stop()

    asyncio.run(run())


# ------------------------------------- sub-reads off the ring (ISSUE 35)

def _pool_pgs(cl, io):
    return [(o, p) for o in cl.osds.values() for p in o.pgs.values()
            if p.pool_id == io.pool_id]


async def _until(cond, tries=400):
    for _ in range(tries):
        if cond():
            return
        await asyncio.sleep(0.005)
    assert cond()


async def _sub_read_cluster_case(shards):
    """A burst of EC writes and reads: (shard counters, sub-reads that
    took a PG queue)."""
    from ceph_tpu.osd.messages import MOSDECSubOpRead
    from ceph_tpu.osd.pg import PG
    queued = []
    real = PG.queue_op

    def queue_op(self, m):
        if isinstance(m, MOSDECSubOpRead):
            queued.append(m)
        real(self, m)

    PG.queue_op = queue_op
    try:
        cl = Cluster(ctx_factory=_ctx_factory(shards))
        admin = await cl.start(4)
        await _rw_burst(cl, admin)
        sc = _sum_shard_counters(cl)
        await cl.stop()
    finally:
        PG.queue_op = real
    return sc, len(queued)


async def _sub_read_behind_a_sub_write(case):
    """One shard's sub-WRITE of an overwrite and a sub-READ of the same
    object, held at the shard OSD's dispatch and then dispatched in one
    step as `case` says.  Whatever path the read takes it answers
    AFTER the write applied: the new shard bytes and version."""
    from ceph_tpu.osd.backend import VERSION_XATTR
    from ceph_tpu.osd.messages import (EVersion, MOSDECSubOpRead,
                                       MOSDECSubOpWrite)
    cl = Cluster(ctx_factory=_ctx_factory(4))
    admin = await cl.start(3)
    await admin.pool_create("sr", pg_num=1, pool_type="erasure",
                            k=2, m=1)
    io = admin.open_ioctx("sr")
    old, new = b"a" * 5000, b"b" * 7000
    await io.write_full("o", old)
    posd, ppg = next((o, p) for o, p in _pool_pgs(cl, io)
                     if p.is_primary())
    sosd, spg = next((o, p) for o, p in _pool_pgs(cl, io)
                     if not p.is_primary())
    held, dispatch = [], sosd._dispatch_pg_msg

    def hold(m):
        if isinstance(m, (MOSDECSubOpWrite, MOSDECSubOpRead)) \
                and m.pgid == spg.pgid:
            held.append(m)
        else:
            dispatch(m)

    sosd._dispatch_pg_msg = hold
    write = asyncio.ensure_future(io.write_full("o", new))
    await _until(lambda: len(held) == 1)
    tid = posd.next_tid()
    answer = asyncio.get_running_loop().create_future()
    ppg.backend._inflight[tid] = ({sosd.whoami}, answer)
    posd.send_osd(sosd.whoami,
                  MOSDECSubOpRead(spg.pgid, tid, [("o", 0, -1)]))
    await _until(lambda: len(held) == 2)
    del sosd._dispatch_pg_msg
    sub_write, sub_read = held
    assert isinstance(sub_write, MOSDECSubOpWrite)
    before = dict(sosd.shards.counters())
    gate = asyncio.Event()
    if case == "idle":
        # nothing ahead: the write applies inline, the read is served
        # off the ring behind it
        dispatch(sub_write)
    elif case == "queue_nonempty":
        spg.queue_op(sub_write)     # as a refused fast path leaves it
        assert not spg._op_queue.empty()
    elif case == "worker_busy":
        spg.queue_op(gate.wait)     # a work item the worker sits in
        await _until(lambda: spg._worker_busy
                     and spg._op_queue.empty())
        dispatch(sub_write)
    dispatch(sub_read)
    after = dict(sosd.shards.counters())
    gate.set()
    reply = await asyncio.wait_for(answer, 10.0)
    await asyncio.wait_for(write, 10.0)
    assert reply.result == 0
    assert bytes(reply.data[0]) == bytes(spg.backend.codec.encode(
        set(range(spg.backend.n)), new)[spg.pgid.shard])
    assert EVersion.from_bytes(reply.attrs[VERSION_XATTR]) == \
        ppg.info.last_update == spg.info.last_update
    assert await io.read("o") == new
    await cl.stop()
    return {k: after[k] - before[k] for k in
            ("subread_inline", "subread_queued", "subop_inline")}


@pytest.mark.parametrize("case", ["sharded_cluster", "one_shard", "idle",
                                  "queue_nonempty", "worker_busy"])
def test_sub_read_is_served_from_the_ring_or_takes_the_queue(case):
    """The sharded plane serves an EC sub-read straight off the ring
    under the sub-write's own rule (queue empty, worker idle);
    osd_op_num_shards=1 keeps the classic queue path; a sub-read that
    finds the queue non-empty or the worker busy is QUEUED, behind the
    sub-write ahead of it."""
    if case == "sharded_cluster":
        sc, queued = asyncio.run(_sub_read_cluster_case(4))
        assert sc["subread_inline"] > 0, sc
        assert sc["subread_queued"] == queued, (sc, queued)
        # 24 reads of k=2 m=2 objects: one remote shard asked each
        assert sc["subread_inline"] + queued >= 24, (sc, queued)
    elif case == "one_shard":
        sc, queued = asyncio.run(_sub_read_cluster_case(1))
        assert sc.get("subread_inline", 0) == 0, sc
        assert sc.get("subread_queued", 0) == 0, sc
        assert queued >= 24
    else:
        d = asyncio.run(_sub_read_behind_a_sub_write(case))
        want = {"idle": (1, 0, 1), "queue_nonempty": (0, 1, 0),
                "worker_busy": (0, 1, 0)}[case]
        assert (d["subread_inline"], d["subread_queued"],
                d["subop_inline"]) == want, d


# ---------------------------------------------------------- e2e threaded

def test_sharded_threaded_cluster_rw_and_teardown():
    """The msgr-worker split for real: per-shard event-loop THREADS.
    Writes+reads land correctly (cross-thread handoffs both ways:
    intake->shard ring, shard->messenger courier), PG workers run on
    their shard threads, and teardown joins every thread cleanly."""
    async def run():
        cl = Cluster(ctx_factory=_ctx_factory(2, threads=True))
        admin = await cl.start(3)
        await _rw_burst(cl, admin, n=16)
        threads = []
        for osd in cl.osds.values():
            assert osd.shards.threaded
            for s in osd.shards.shards:
                assert s._thread is not None and s._thread.is_alive()
                assert s.loop is not asyncio.get_running_loop()
                threads.append(s._thread)
            # shard->intake marshalling engaged (sends from shard
            # threads ride the batched courier)
            assert osd.messenger._xthread_msgs > 0
        await cl.stop()
        return threads

    threads = asyncio.run(run())
    for t in threads:
        assert not t.is_alive()         # joined at shutdown


# ------------------------------------------------- objecter corked batching

def test_objecter_corked_batching_one_handoff_many_replies():
    async def run():
        cl = Cluster(ctx_factory=_ctx_factory(4))
        admin = await cl.start(3)
        await admin.pool_create("bat", pg_num=1)   # one PG = one OSD
        io = admin.open_ioctx("bat")
        obj = admin.objecter
        base_b, base_o = obj.batches_sent, obj.ops_batched
        # same loop pass: all submits cork into one frame per target
        blobs = {f"b{i:02d}": bytes([i]) * 512 for i in range(8)}
        await asyncio.gather(*[io.write_full(k, v)
                               for k, v in blobs.items()])
        assert obj.batches_sent > base_b
        assert obj.ops_batched - base_o >= 4
        for k, v in blobs.items():
            assert await io.read(k) == v
        await cl.stop()

    asyncio.run(run())


def test_objecter_batching_off_is_unbatched():
    def ctx(name):
        c = _ctx_factory(1)(name)
        c.config.set("objecter_op_batching", False)
        return c

    async def run():
        cl = Cluster(ctx_factory=ctx)
        admin = await cl.start(3)
        await admin.pool_create("nb", pg_num=1)
        io = admin.open_ioctx("nb")
        blobs = {f"n{i:02d}": bytes([i]) * 512 for i in range(6)}
        await asyncio.gather(*[io.write_full(k, v)
                               for k, v in blobs.items()])
        assert admin.objecter.batches_sent == 0
        for k, v in blobs.items():
            assert await io.read(k) == v
        await cl.stop()

    asyncio.run(run())


# ------------------------------------------------------ process lanes

def _proc_ctx_factory(shards):
    def f(name):
        c = make_ctx(name)
        c.config.set("osd_op_num_shards", shards)
        c.config.set("osd_shard_lanes", "process")
        c.config.set("ms_local_delivery", True)
        return c
    return f


def test_process_lanes_forced_inline_under_sim_loop():
    """The schedule explorer still covers the plane: under a
    deterministic loop, osd_shard_lanes=process degrades to inline
    pumps the seeded scheduler permutes — a worker process would be
    the one wakeup source the explorer cannot replay."""
    from ceph_tpu.common.context import Context
    from ceph_tpu.osd.shards import ShardedDataPlane

    class _OSD:
        def __init__(self):
            self.ctx = Context("osd.9")
            self.cfg = self.ctx.config
            self.cfg.set("osd_op_num_shards", 2)
            self.cfg.set("osd_shard_lanes", "process")
            self.whoami = 9

    async def run():
        loop = asyncio.get_running_loop()
        loop.deterministic = True       # what DeterministicLoop sets
        try:
            plane = ShardedDataPlane(_OSD())
            assert plane.lane_backend == "process"
            plane.start()
            assert plane.active_backend == "inline"
            assert plane.process_lanes is None
            assert not plane.threaded
            await plane.stop()
        finally:
            del loop.deterministic

    asyncio.run(run())


def test_lane_backend_auto_resolves_from_thread_knob():
    from ceph_tpu.common.context import Context
    from ceph_tpu.osd.shards import ShardedDataPlane

    class _OSD:
        def __init__(self, threads):
            self.ctx = Context("osd.8")
            self.cfg = self.ctx.config
            self.cfg.set("osd_op_num_shards", 2)
            self.cfg.set("osd_shard_threads", threads)
            self.whoami = 8

    assert ShardedDataPlane(_OSD(True)).lane_backend == "thread"
    assert ShardedDataPlane(_OSD(False)).lane_backend == "inline"


@pytest.mark.slow
def test_process_lane_minicluster_replicated_rw():
    """Real parallelism: 2 worker processes per OSD, every PG hosted
    lane-side, all traffic crossing the shared-memory rings as wire
    frames.  Writes + reads land correctly; per-lane courier counters
    show the frames; teardown joins every worker."""
    async def run():
        cl = Cluster(ctx_factory=_proc_ctx_factory(2))
        admin = await cl.start(3)
        for osd in cl.osds.values():
            assert osd.shards.active_backend == "process"
            assert osd.shards.process_lanes is not None
            assert not osd.pgs       # the parent hosts NO PGs
        await _rw_burst(cl, admin, n=12, ec=False)
        procs = []
        for osd in cl.osds.values():
            lanes = osd.shards.counters()["lanes"]
            assert sum(c["to_lane_frames"]
                       for c in lanes.values()) > 0
            assert not any(c["dead"] for c in lanes.values())
            for lane in osd.shards.process_lanes:
                procs.append(lane.proc)
        await cl.stop()
        return procs

    procs = asyncio.run(run())
    for p in procs:
        assert not p.is_alive()       # workers joined at shutdown


@pytest.mark.slow
def test_process_lane_observability_attribution_and_cluster_scrape():
    """ISSUE 15 acceptance: a PROCESS-lane cluster run attributes
    >=90% of measured e2e wall time to named chain stages — including
    the new lane-hop cuts (ring_wait / lane_codec) and the cause-split
    queue-wait stages — because each lane worker's stage histograms
    ship to the parent over the metrics plane and merge bit-for-bit.
    The same run proves the cluster scrape: one merged perf snapshot
    covering parent + all lanes with devstats and device_byte_fraction
    included, lane-merged dump_op_stages, and a LOUD lane_dead marker
    once a worker is killed."""
    import time as _time

    def ctx_f(name):
        c = make_ctx(name)
        c.config.set("osd_op_num_shards", 2)
        c.config.set("osd_shard_lanes", "process")
        c.config.set("ms_local_delivery", True)
        c.config.set("op_tracing", True)
        return c

    async def run():
        cl = Cluster(ctx_factory=ctx_f)
        admin = await cl.start(3)
        await admin.pool_create("obspool", pg_num=4)
        io = admin.open_ioctx("obspool")
        lats = []
        sem = asyncio.Semaphore(8)

        async def one(name, data):
            async with sem:
                t0 = _time.perf_counter()
                await io.write_full(name, data)
                lats.append(_time.perf_counter() - t0)

        blobs = {f"ob{i:03d}": bytes([i]) * 8192 for i in range(24)}
        await asyncio.gather(*[one(n, d) for n, d in blobs.items()])
        # fresh lane scrape (FRAME_RPC), then the merged views
        dead = await cl.refresh_lane_metrics()
        assert dead == [], dead
        bd = cl.stage_breakdown(measured_e2e_s=sum(lats))
        merged = cl.stage_histograms()
        scrape = cl.cluster_perf_dump()
        # lane-merged admin dump straight off one OSD
        osd = next(iter(cl.osds.values()))
        table = await osd._dump_op_stages()
        slow = await osd._dump_historic_slow_ops()
        # kill one worker: the dump must MARK the lane dead, not
        # silently omit it
        victim = osd.shards.process_lanes[0]
        victim.proc.terminate()
        victim.proc.join(timeout=10.0)
        for _ in range(100):
            if victim.dead:
                break
            await asyncio.sleep(0.05)
        table_dead = await osd._dump_op_stages()
        scrape_dead = await osd._perf_dump_full()
        await cl.stop()
        return (bd, merged, scrape, table, slow, victim.idx,
                table_dead, scrape_dead)

    (bd, merged, scrape, table, slow, victim_idx, table_dead,
     scrape_dead) = asyncio.run(run())
    # (a) the acceptance bar: >=90% attribution WITH process lanes
    assert bd["measured_s"] > 0
    assert bd["attributed_s"] >= 0.9 * bd["measured_s"], bd
    assert bd["unattributed_frac"] < 0.10, bd
    # (b) the lane-hop chain stages recorded real samples
    for stage in ("ring_wait", "lane_codec", "queue_wait_pump",
                  "prepare", "store_apply", "replica_rtt",
                  "ack_delivery"):
        assert stage in merged and merged[stage].count > 0, stage
    # (c) lane-merged dump_op_stages saw the lane-side pipeline
    assert table["lanes_merged"] >= 1 and table["lane_dead"] == []
    assert "ring_wait" in table["stages"], table["stages"].keys()
    assert "prepare" in table["stages"]
    assert slow["lane_dead"] == []
    # (d) one merged cluster snapshot covers parent + lanes + devstats
    assert any("/lane" in s for s in scrape["sources"]), scrape["sources"]
    assert "devstats" in scrape and "device_byte_fraction" in scrape
    assert "op_stages" in scrape["groups"]
    # (e) a dead lane is LOUD, never silence
    assert victim_idx in table_dead["lane_dead"], table_dead
    assert any(str(victim_idx) in d for d in scrape_dead["lane_dead"])


@pytest.mark.slow
def test_process_lane_minicluster_ec_write_burst():
    """The tier-1 smoke the ISSUE names: a 2-lane process plane
    serving one EC (k=2,m=2) write burst end to end — sub-op fan-out,
    shard applies, acks and client replies all crossing process
    boundaries.  slow-marked: the seed tier-1 run already saturates
    the suite budget on this container."""
    async def run():
        cl = Cluster(ctx_factory=_proc_ctx_factory(2))
        admin = await cl.start(4)
        await _rw_burst(cl, admin, n=12, ec=True)
        await cl.stop()

    asyncio.run(run())


# ------------------------------------------------------- backward compat

def test_single_shard_plane_is_disabled_legacy_dispatch():
    async def run():
        cl = Cluster()          # FAST_CFG pins osd_op_num_shards=1
        admin = await cl.start(3)
        await _rw_burst(cl, admin, n=8, ec=False)
        for osd in cl.osds.values():
            assert not osd.shards.enabled
            assert osd.messenger.shard_router is None
            assert osd.shards.num_shards == 1
            # ack-on-apply is plane-gated: shards=1 keeps the commit
            # thread (today's behavior, bit-for-bit)
            assert not osd.store._committer._inline
        await cl.stop()

    asyncio.run(run())

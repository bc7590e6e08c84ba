"""Perf smoke: the group-committed write path must actually engage.

A miniature in-process cluster takes a 32-way concurrent write burst on
OSDs backed by file-backed BlockStores whose data barrier costs ~1ms
and whose commit thread gathers for 8ms (emulating a real device — a
tmpfs fsync is free, so without the simulated cost the commit thread
drains groups of one and the test proves nothing).  The store commit
counters over the burst must show group commit working: strictly fewer
fsyncs than transactions and more than one transaction per commit
batch.  This is the tier-1 regression guard for ISSUE 1's async commit
pipeline — a reversion to per-txn synchronous fsync fails here instead
of only showing up in bench runs.
"""

import asyncio
import time

from ceph_tpu.osd.pg import STATE_ACTIVE
from ceph_tpu.qa.cluster import Cluster
from ceph_tpu.store.blockstore import BlockStore

N_OBJS = 64
OBJ_SIZE = 8 * 1024
CONC = 32
N_PGS = 16


class SlowBarrierBlockStore(BlockStore):
    """BlockStore with ~1ms data barriers and an 8ms commit gather
    window — the shape of a real disk, where the barrier dominates and
    batching behind it is what group commit exists for."""

    def mount(self):
        super().mount()
        self._committer.gather_window = 0.008
        # pin the window: this store EMULATES a device with a fixed
        # gather; the auto-tuner (tracks real barrier cost) would
        # shrink it toward the 1ms fake barrier and the test would
        # measure the tuner, not the group-commit machinery
        self._committer.auto_tune = False

    def _fsync_block(self):
        time.sleep(0.001)
        super()._fsync_block()


def _counters(cl):
    txns = fsyncs = batches = 0
    for osd in cl.osds.values():
        c = osd.store.commit_counters()
        txns += int(c.get("txns", 0))
        fsyncs += int(c.get("fsyncs", 0))
        batches += int(c.get("commit_batches", 0))
    return txns, fsyncs, batches


async def _settle(cl, n_pg_instances):
    """Wait for every PG instance to reach active so peering meta txns
    (sequential, batches-of-one by nature) stay out of the burst
    window."""
    for _ in range(300):
        pgs = [pg for osd in cl.osds.values() for pg in osd.pgs.values()]
        active = {pg.pgid for pg in pgs if pg.state == STATE_ACTIVE}
        if len(pgs) >= n_pg_instances and \
                len(active) == len({pg.pgid for pg in pgs}):
            break
        await asyncio.sleep(0.05)
    await asyncio.sleep(0.3)


def test_cluster_write_burst_engages_group_commit(tmp_path):
    async def run():
        cl = Cluster(store_factory=lambda i: SlowBarrierBlockStore(
            str(tmp_path / f"osd{i}")))
        admin = await cl.start(3)
        await admin.pool_create("smoke", pg_num=N_PGS)
        await _settle(cl, N_PGS * 3)
        io = admin.open_ioctx("smoke")
        data = bytes(range(256)) * (OBJ_SIZE // 256)
        sem = asyncio.Semaphore(CONC)

        async def one(i):
            async with sem:
                await io.write_full(f"smoke{i:04d}", data)

        t0, f0, b0 = _counters(cl)
        await asyncio.gather(*[one(i) for i in range(N_OBJS)])
        t1, f1, b1 = _counters(cl)   # read BEFORE stop: umount drops thread
        # spot-check durability through the async path
        assert await io.read("smoke0000") == data
        await cl.stop()
        return t1 - t0, f1 - f0, b1 - b0

    txns, fsyncs, batches = asyncio.run(run())
    # every replica write is a transaction (one per OSD per object); the
    # burst must share commit batches instead of one fsync pair each
    assert txns >= N_OBJS, txns
    assert fsyncs < txns, (fsyncs, txns)
    assert batches < txns and txns / batches > 1.0, (batches, txns)


def test_pg_op_window_depth_engages():
    """Regression guard for ISSUE 5's per-PG op pipelining (the twin
    of the zero-encode guard): a concurrent write burst against a
    single-PG pool must reach a counter-proven mean in-flight depth
    > 1 — a reversion to the serial one-op-per-PG worker pins the
    sampled depth at exactly 1.0 and fails here instead of only
    showing up as flat bench numbers."""
    from ceph_tpu.qa.cluster import Cluster

    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        # ONE pg: every write lands in the same window, so the client
        # iodepth (24) translates directly into window depth
        await admin.pool_create("winpool", pg_num=1)
        io = admin.open_ioctx("winpool")
        blobs = {f"w{i:03d}": bytes([i]) * 4096 for i in range(24)}
        await cl.write_burst(io, blobs, iodepth=24)
        win = cl.window_counters()
        for k, v in blobs.items():
            assert await io.read(k) == v
        await cl.stop()
        return win

    win = asyncio.run(run())
    assert win["ops_admitted"] >= 24, win
    assert win["mean_inflight_depth"] > 1.0, win
    assert win["max_inflight_depth"] > 1, win


def test_tracing_stage_coverage_and_zero_encode():
    """ISSUE 6 regression guard for the op tracer, twin of the
    zero-encode guard: on an EC mini-cluster with op_tracing on,
    (a) the chain stages must attribute >= 90% of the independently
    measured e2e op latency — a dropped cut or broken span propagation
    silently un-names the write path and fails here, and (b) tracing
    must add ZERO message-body encodes on the local path (the live
    span rides local_view; the trace header only encodes on TCP)."""
    import time as _time

    from ceph_tpu.msg import payload as payload_mod
    from ceph_tpu.qa.cluster import Cluster, make_ctx

    def ctx_f(name):
        c = make_ctx(name)
        c.config.set("ms_local_delivery", True)
        c.config.set("op_tracing", True)
        return c

    async def run():
        cl = Cluster(ctx_factory=ctx_f)
        admin = await cl.start(4)
        await admin.pool_create("trpool", pg_num=4,
                                pool_type="erasure", k=2, m=2)
        await _settle(cl, 4 * 4)
        io = admin.open_ioctx("trpool")
        payload_mod.reset_counters()
        blobs = {f"tr{i:03d}": bytes([i]) * 8192 for i in range(24)}
        lats = []
        sem = asyncio.Semaphore(8)

        async def one(name, data):
            async with sem:
                t0 = _time.perf_counter()
                await io.write_full(name, data)
                lats.append(_time.perf_counter() - t0)

        await asyncio.gather(*[one(n, d) for n, d in blobs.items()])
        bd = cl.stage_breakdown(measured_e2e_s=sum(lats))
        # the metrics plane on the same run (ISSUE 15): a full
        # cluster-wide scrape must be pure counter arithmetic —
        # zero message encodes at inline lanes
        scrape = cl.cluster_perf_dump()
        enc = payload_mod.counters()
        merged = cl.stage_histograms()
        for k, v in blobs.items():
            assert await io.read(k) == v
        await cl.stop()
        return bd, enc, merged, scrape

    bd, enc, merged, scrape = asyncio.run(run())
    # (b) tracing AND the metrics-plane scrape must not reintroduce
    # encodes on the pure-local path
    assert enc["msg_encode_calls"] == 0, enc
    assert enc["msg_encode_bytes"] == 0, enc
    assert "op_stages" in scrape["groups"] and scrape["sources"]
    # every write produced a finished span
    assert merged["op_total"].count >= 24, merged["op_total"].count
    # the EC write path stages all recorded samples
    for stage in ("client_submit", "prepare", "ec_encode", "store_apply",
                  "submit", "replica_rtt", "ack_delivery", "repl_apply"):
        assert stage in merged and merged[stage].count > 0, stage
    # (a) no silent unattributed gap: named stages cover >= 90% of the
    # measured e2e latency
    assert bd["measured_s"] > 0
    assert bd["attributed_s"] >= 0.9 * bd["measured_s"], bd
    assert bd["unattributed_frac"] < 0.10, bd


def test_cluster_rw_over_local_delivery(tmp_path):
    """E2E guard for the messenger's same-process fast path: a cluster
    with ms_local_delivery on serves writes+reads correctly (EC pool,
    so sub-op fan-out and acks all ride local), with the client's data
    ops actually taking the local path — and, since ISSUE 4's lazy
    payloads, performing ZERO message body encodes: every hop hands
    over the live object graph, so any encode call on this path is a
    regression (the counter is the guard that keeps the encode->decode
    round trip removed)."""
    from ceph_tpu.msg import payload as payload_mod
    from ceph_tpu.qa.cluster import Cluster, make_ctx

    def ctx_f(name):
        c = make_ctx(name)
        c.config.set("ms_local_delivery", True)
        return c

    async def run():
        cl = Cluster(ctx_factory=ctx_f)
        admin = await cl.start(4)
        await admin.pool_create("lp", pg_num=4,
                                pool_type="erasure", k=2, m=2)
        io = admin.open_ioctx("lp")
        payload_mod.reset_counters()
        blobs = {f"lo{i:03d}": bytes([i]) * (4096 + i) for i in range(24)}
        await asyncio.gather(*[io.write_full(k, v)
                               for k, v in blobs.items()])
        for k, v in blobs.items():
            assert await io.read(k) == v
        local = sum(o.messenger._local_msgs for o in cl.osds.values())
        local += admin.messenger._local_msgs
        enc = payload_mod.counters()
        assert local > 0, "fast path never engaged"
        # lazy-payload invariant: the pure-local I/O burst (client ops,
        # EC sub-op fan-out, acks, replies) encoded NOTHING
        assert enc["msg_encode_calls"] == 0, enc
        assert enc["msg_encode_bytes"] == 0, enc
        await cl.stop()

    asyncio.run(run())


def test_sharded_plane_perf_guards():
    """ISSUE 10 regression guards for the sharded data plane, with a
    shards=1 run in the same test pinning backward compatibility:

      * shards=4 on the local path keeps ``msg_encode_calls`` at 0
        (the classify seam hands over live object graphs, never
        bytes);
      * per-PG window depth still engages (> 1) through the shard
        rings;
      * the ``osd_shard_handoff`` counters prove cross-shard handoffs
        are BATCHED: pump wakeups < handed-off ops under burst, and
        replica write sub-ops apply inline off the ring;
      * shards=1 (the FAST_CFG default the whole suite runs under)
        leaves the plane disabled — no shard router, no handoff
        group, the commit thread intact — i.e. today's path."""
    from ceph_tpu.msg import payload as payload_mod
    from ceph_tpu.qa.cluster import Cluster, make_ctx

    def ctx_f(shards):
        def f(name):
            c = make_ctx(name)
            c.config.set("osd_op_num_shards", shards)
            c.config.set("osd_shard_threads", False)
            c.config.set("ms_local_delivery", True)
            return c
        return f

    async def run(shards):
        cl = Cluster(ctx_factory=ctx_f(shards))
        admin = await cl.start(4)
        await admin.pool_create("shsm", pg_num=2,
                                pool_type="erasure", k=2, m=2)
        io = admin.open_ioctx("shsm")
        payload_mod.reset_counters()
        blobs = {f"g{i:03d}": bytes([i]) * 8192 for i in range(32)}
        await cl.write_burst(io, blobs, iodepth=16)
        win = cl.window_counters()
        enc = payload_mod.counters()
        routers = [osd.messenger.shard_router
                   for osd in cl.osds.values()]
        for k, v in blobs.items():
            assert await io.read(k) == v
        # after the reads: the sub-reads' counters are among them
        sc = {}
        for osd in cl.osds.values():
            for k, v in osd.shards.counters().items():
                if isinstance(v, (int, float)):
                    sc[k] = sc.get(k, 0) + v
        await cl.stop()
        return win, enc, sc, routers

    win, enc, sc, routers = asyncio.run(run(4))
    assert enc["msg_encode_calls"] == 0, enc
    assert win["mean_inflight_depth"] > 1.0, win
    assert sc["handoff_ops"] > 0, sc
    assert sc["handoff_wakeups"] < sc["handoff_ops"], sc
    assert sc["subop_inline"] > 0, sc
    assert sc["subread_inline"] > 0, sc
    assert all(r is not None for r in routers)

    # shards=1 compat pin: plane fully off, zero-encode still holds
    win1, enc1, sc1, routers1 = asyncio.run(run(1))
    assert enc1["msg_encode_calls"] == 0, enc1
    assert win1["mean_inflight_depth"] > 1.0, win1
    assert sc1["handoff_ops"] == 0, sc1
    assert all(r is None for r in routers1)


def test_sanitizer_fully_off_path_when_disabled():
    """ISSUE 7 off-path guard: with lockdep=false the invariant
    sanitizer must leave ZERO footprint on the write path — the
    commit-thread and payload-path locks are plain stdlib locks (no
    wrapper allocation), the order graph stays empty, nothing is
    recorded — while the pipelining/zero-encode evidence counters look
    exactly as they do with the sanitizer on (the suite's other
    perf-smoke tests run under FAST_CFG's lockdep=true, so the two
    configurations are both continuously proven)."""
    from ceph_tpu.common import lockdep
    from ceph_tpu.msg import payload as payload_mod
    from ceph_tpu.qa.cluster import Cluster, make_ctx

    def ctx_off(name):
        c = make_ctx(name)
        c.config.set("lockdep", False)
        c.config.set("ms_local_delivery", True)
        return c

    async def run():
        cl = Cluster(ctx_factory=ctx_off)
        admin = await cl.start(3)
        assert not lockdep.is_enabled()
        await admin.pool_create("offpool", pg_num=1)
        io = admin.open_ioctx("offpool")
        payload_mod.reset_counters()
        blobs = {f"o{i:03d}": bytes([i]) * 4096 for i in range(24)}
        await cl.write_burst(io, blobs, iodepth=24)
        win = cl.window_counters()
        enc = payload_mod.counters()
        # no lockdep allocations anywhere on this cluster's stores
        for osd in cl.osds.values():
            committer = getattr(osd.store, "_committer", None)
            if committer is not None:
                assert not isinstance(committer._lock,
                                      lockdep.DepThreadLock)
        assert lockdep.GRAPH.edges == {}
        assert lockdep.report() == []
        await cl.stop()
        return win, enc

    win, enc = asyncio.run(run())
    # the same evidence the lockdep=true twin tests assert: window
    # pipelining engages and the local path encodes nothing
    assert win["mean_inflight_depth"] > 1.0, win
    assert enc["msg_encode_calls"] == 0, enc


def test_save_meta_bytes_per_write_are_o1_in_log_length():
    """ISSUE 13 guard: the write path's meta persistence must stay
    O(1) in log length.  save_meta_log at a ~100-entry log and at a
    ~1200-entry log must encode about the same number of omap bytes
    (one cached entry frame + info + loghead) — the old full-blob
    save grew linearly and profiled as the biggest per-op CPU slice.
    The full snapshot (peering-time save_meta) is the contrast: it
    MUST still grow with the log."""
    from ceph_tpu.osd.messages import EVersion
    from ceph_tpu.osd.pglog import LogEntry
    from ceph_tpu.store.objectstore import Transaction

    async def run():
        cl = Cluster()
        admin = await cl.start(2)
        await admin.pool_create("o1", pg_num=1, size=2)
        io = admin.open_ioctx("o1")
        await io.write_full("seed", b"x")
        pg = next(pg for osd in cl.osds.values()
                  for pg in osd.pgs.values() if pg.is_primary())

        def one_append_bytes():
            v = EVersion(pg.info.last_update.epoch or 1,
                         pg.info.last_update.version + 1)
            e = LogEntry(oid="guard", version=v,
                         prior_version=pg.info.last_update)
            txn = Transaction()
            pg.append_log(txn, e)
            return sum(len(k) + len(val)
                       for op in txn.ops
                       if getattr(op, "kv", None)
                       for k, val in op.kv.items())

        def grow_to(n):
            while len(pg.log.entries) < n:
                one_append_bytes()

        grow_to(100)
        small = one_append_bytes()
        grow_to(1200)
        large = one_append_bytes()
        assert large <= small * 1.5, (small, large)

        # contrast: the full snapshot is O(len(log)) by design
        txn = Transaction()
        pg.save_meta(txn)
        full = sum(len(k) + len(val)
                   for op in txn.ops
                   if getattr(op, "kv", None)
                   for k, val in op.kv.items())
        assert full > 10 * large, (full, large)
        await cl.stop()

    asyncio.run(run())


def test_device_kernel_compile_count_plateaus():
    """ISSUE 14 guard (runtime half of the device-seam pass): a
    steady-state EC workload through the cross-PG device queue must
    PLATEAU at a fixed jit compile count — the lane-bucket padding
    (osd/ec_queue.py LANE_BUCKETS) means every round after the first
    replays already-compiled signatures, so kernel launches keep
    growing while compiles (distinct signatures per common/devstats)
    stay flat.  A per-op retrace — the regression JIT16 can't see
    statically (unhashable statics, shape-per-call drift) — fails
    here, in tier-1, not in a bench review.  msg_encode_calls stays
    pinned at 0 throughout: the device path must never touch the
    message codec."""
    import numpy as np

    from ceph_tpu.common import devstats
    from ceph_tpu.common.context import Context
    from ceph_tpu.ec import gf256
    from ceph_tpu.msg import payload
    from ceph_tpu.osd.ec_queue import ECBatchQueue

    enc0 = payload.counters()["msg_encode_calls"]
    devstats.reset()

    async def run():
        q = ECBatchQueue(Context("osd.0"), mode="force",
                         window_ms=2.0, min_device_bytes=256)
        mat = gf256.rs_vandermonde_matrix(4, 2)[4:]
        rng = np.random.default_rng(7)
        snaps = []
        for _round in range(3):
            # varied per-request lengths, same folded lane bucket:
            # the steady-state shape of a running cluster
            ins = [rng.integers(0, 256, (4, 900 + 128 * i),
                                dtype=np.uint8) for i in range(6)]
            outs = await asyncio.gather(
                *[q.apply(mat, c) for c in ins])
            for c, o in zip(ins, outs):
                assert np.array_equal(o, gf256.host_apply(mat, c)), \
                    "device bytes diverged from the host kernel"
            snaps.append(devstats.counters())
        await q.stop()
        return snaps

    snaps = asyncio.run(run())
    compiles = [s["compiles"].get("ec_apply", 0) for s in snaps]
    launches = [s["launches"].get("ec_apply", 0) for s in snaps]
    assert launches[0] >= 1 and launches[2] > launches[1] > \
        launches[0], launches               # work kept flowing
    assert compiles[0] >= 1, compiles       # ...through the device
    assert compiles[2] == compiles[1] == compiles[0], \
        (f"jit compile count kept growing across steady-state rounds "
         f"{compiles}: a per-op retrace slipped into the kernel path")
    assert payload.counters()["msg_encode_calls"] == enc0, \
        "device-queue workload bumped the message codec"


def test_degraded_read_decode_plateaus_and_zero_encode():
    """ISSUE 17 guard (recovery under fire): with one EC shard-holder
    dead and UNREPLACEABLE (pool width == cluster size, so recovery
    keeps retrying but can never remap the hole), every read of an
    object whose data shard died must reconstruct it through the
    device decode queue — and the decode signatures must PLATEAU: the
    first round of degraded reads pays the jit compiles, every later
    round replays them while launches keep growing.  A per-read
    retrace in the decode path (shape drift, unhashable matrix key)
    fails here in tier-1 instead of in a bench review.  The whole
    degraded window — client reads, shard gathers, recovery retries —
    rides the local path with ZERO message-body encodes."""
    from ceph_tpu.common import devstats
    from ceph_tpu.msg import payload as payload_mod
    from ceph_tpu.qa.cluster import Cluster, make_ctx

    def ctx_f(name):
        c = make_ctx(name)
        c.config.set("ms_local_delivery", True)
        c.config.set("osd_ec_batch_device", "force")
        c.config.set("osd_ec_batch_min_bytes", 1)
        return c

    async def run():
        cl = Cluster(ctx_factory=ctx_f)
        admin = await cl.start(4)
        # width k+m == n_osds: killing any osd leaves a hole no
        # backfill target can fill — the degraded window stays open
        await admin.pool_create("degpool", pg_num=4,
                                pool_type="erasure", k=2, m=2)
        await _settle(cl, 4 * 4)
        io = admin.open_ioctx("degpool")
        blobs = {f"dg{i:03d}": bytes([i + 1]) * 8192 for i in range(16)}
        for k, v in blobs.items():
            await io.write_full(k, v)
        # kill an osd that holds a DATA shard (shard < k) somewhere:
        # reads of those objects must decode, not just re-route
        victim = next(o.whoami for o in cl.osds.values()
                      if any(pg.pgid.shard < 2 for pg in o.pgs.values()))
        await cl.kill_osd(victim)
        await cl.mark_down_and_wait(admin, victim)
        devstats.reset()
        payload_mod.reset_counters()
        snaps = []
        for _round in range(3):
            got = await asyncio.gather(*[io.read(k) for k in blobs])
            assert list(got) == list(blobs.values())
            snaps.append(devstats.counters())
        enc = payload_mod.counters()
        await cl.stop()
        return snaps, enc

    snaps, enc = asyncio.run(run())
    compiles = [s["compiles"].get("ec_apply", 0) for s in snaps]
    launches = [s["launches"].get("ec_apply", 0) for s in snaps]
    assert compiles[0] >= 1, (compiles, launches)   # decode engaged
    assert launches[2] > launches[1] >= 1, launches  # and kept flowing
    assert compiles[2] == compiles[1] == compiles[0], \
        (f"degraded-read decode compiles kept growing {compiles}: "
         f"a per-read retrace slipped into the decode path")
    # the degraded window (including recovery retrying in the
    # background) never touched the message codec on the local path
    assert enc["msg_encode_calls"] == 0, enc
    assert enc["msg_encode_bytes"] == 0, enc


def test_objecter_cork_is_one_placement_kernel_launch():
    """ISSUE 16 guard (batched CRUSH in the data path): ONE corked
    Objecter flush computes placement for the whole burst in exactly
    ONE batched placement-kernel launch (devstats "crush_place"), not
    one scalar descent per op; steady-state bursts replay the same
    launch signature (compile plateau), and map churn recompiles the
    rule exactly once (guarded per-map compile cache)."""
    from ceph_tpu.client.objecter import Objecter, _InFlight
    from ceph_tpu.common import devstats
    from ceph_tpu.common.context import Context
    from ceph_tpu.crush.builder import (build_hierarchy,
                                        make_replicated_rule)
    from ceph_tpu.crush.types import CrushMap
    from ceph_tpu.msg.types import EntityAddr
    from ceph_tpu.osd.messages import (MOSDOp, MOSDOpBatch, OP_WRITEFULL,
                                       OSDOp)
    from ceph_tpu.osd.osdmap import Incremental, OSDMap
    from ceph_tpu.osd.types import (OSD_IN_WEIGHT, ObjectLocator,
                                    POOL_TYPE_REPLICATED, PGPool)

    def build_map():
        m = OSDMap()
        m.fsid = "cork-fsid"
        crush = CrushMap()
        crush.max_devices = 8
        build_hierarchy(crush, 8, 2)
        rep = make_replicated_rule(crush, "replicated_rule")
        m.crush = crush
        m.set_max_osd(8)
        inc = Incremental(1)
        for o in range(8):
            inc.new_up[o] = EntityAddr("127.0.0.1", 6800 + o, o + 1)
            inc.new_weight[o] = OSD_IN_WEIGHT
        m.apply_incremental(inc)
        m.pools[1] = PGPool(POOL_TYPE_REPLICATED, size=3,
                            crush_ruleset=rep, pg_num=32)
        m.pool_names[1] = "rbd"
        return m

    class FakeMessenger:
        nonce = 1

        def __init__(self):
            self.sent = []

        def add_dispatcher(self, d):
            pass

        def send_message(self, msg, addr, peer_type=None):
            self.sent.append(msg)

    class FakeMonc:
        def __init__(self, m):
            self.osdmap = m

        def on_osdmap(self, cb):
            pass

        def sub_want(self, *a, **k):
            pass

    async def run():
        m = build_map()
        msgr = FakeMessenger()
        obj = Objecter(Context("client"), msgr, FakeMonc(m))
        assert obj._batching
        devstats.reset()
        loop = asyncio.get_running_loop()

        async def burst(tag, n=16):
            before = len(msgr.sent)
            for i in range(n):
                obj._tid += 1
                op = _InFlight(obj._tid, f"{tag}-{i:03d}",
                               ObjectLocator(1),
                               [OSDOp(OP_WRITEFULL, data=b"x")],
                               loop.create_future())
                obj._inflight[op.tid] = op
                obj._send(op)
            assert len(msgr.sent) == before, \
                "corked ops must not ship before the flush"
            await asyncio.sleep(0)      # run the call_soon flush
            frames = msgr.sent[before:]
            shipped = sum(len(f.msgs) if isinstance(f, MOSDOpBatch)
                          else 1 for f in frames)
            assert shipped == n, (shipped, n)
            assert all(isinstance(f, (MOSDOp, MOSDOpBatch))
                       for f in frames)
            # grouped per target OSD: far fewer frames than ops
            assert len(frames) <= 8 < n

        def stats(domain):
            c = devstats.counters()
            return (c["launches"].get(domain, 0),
                    c["compiles"].get(domain, 0))

        await burst("a")
        # ONE cork = ONE placement-kernel launch for all 16 ops, which
        # cost exactly one guarded rule compile
        assert stats("crush_place") == (1, 1), stats("crush_place")
        assert stats("crush_compile")[1] == 1, stats("crush_compile")

        # steady state: new names, same map — the acting cache and the
        # repeated (pool, rule, chunk) launch signature keep the
        # compile counts FLAT (any extra launch replays a seen sig)
        await burst("b")
        await burst("c")
        assert stats("crush_place")[1] == 1, stats("crush_place")
        assert stats("crush_compile")[1] == 1, stats("crush_compile")

        # map churn: a NEW crush object recompiles the rule exactly
        # once, and the next cork is again one launch (cache cleared)
        inc = Incremental(m.epoch + 1)
        inc.new_crush = CrushMap.from_bytes(m.crush.to_bytes())
        m.apply_incremental(inc)
        place_launches = stats("crush_place")[0]
        await burst("d")
        assert stats("crush_place") == (place_launches + 1, 1), \
            stats("crush_place")
        assert stats("crush_compile")[1] == 2, stats("crush_compile")
        await burst("e")
        assert stats("crush_compile")[1] == 2, stats("crush_compile")

    asyncio.run(run())

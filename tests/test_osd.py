"""End-to-end data-plane tests: mon + OSDs + rados client in-process.

Models the reference's vstart.sh + qa/workunits rados suites
(SURVEY §4): replicated and EC pool I/O, osd failure → re-peer →
recovery, degraded writes, restart-with-data.
"""

import asyncio

import pytest

from ceph_tpu.client import ObjectOperationError, Rados
from ceph_tpu.common.context import Context
from ceph_tpu.mon import Monitor
from ceph_tpu.mon.monmap import MonMap
from ceph_tpu.msg.messenger import Messenger
from ceph_tpu.msg.types import EntityName
from ceph_tpu.osd import OSD
from ceph_tpu.store.kv import MemDB
from ceph_tpu.store.memstore import MemStore

from ceph_tpu.qa.cluster import FAST_CFG, Cluster, make_ctx  # noqa: F401,E402


def test_replicated_put_get_cycle():
    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("data", pg_num=8)
        io = admin.open_ioctx("data")
        await io.write_full("hello", b"world" * 100)
        assert await io.read("hello") == b"world" * 100
        assert await io.read("hello", length=5, offset=5) == b"world"
        assert await io.stat("hello") == 500
        await io.setxattr("hello", "user.k", b"v")
        assert await io.getxattr("hello", "user.k") == b"v"
        await io.omap_set("hello", {b"a": b"1"})
        assert await io.omap_get("hello") == {b"a": b"1"}
        # partial overwrite
        await io.write("hello", b"WORLD", offset=0)
        assert (await io.read("hello"))[:5] == b"WORLD"
        # many objects spread over pgs + listing
        for i in range(20):
            await io.write_full(f"obj-{i}", bytes([i]) * 64)
        names = await io.list_objects()
        assert set(names) >= {f"obj-{i}" for i in range(20)}
        # delete
        await io.remove("hello")
        with pytest.raises(ObjectOperationError):
            await io.read("hello")
        # data is actually replicated 3x on the osd stores
        found = 0
        for osd in cl.osds.values():
            for cid in osd.store.list_collections():
                for soid in osd.store.collection_list(cid):
                    if soid.name == "obj-3":
                        found += 1
        assert found == 3
        await cl.stop()
    asyncio.run(run())


def test_ec_pool_io():
    async def run():
        cl = Cluster()
        admin = await cl.start(6)
        await admin.pool_create("ecpool", pg_num=8, pool_type="erasure",
                                k=4, m=2)
        io = admin.open_ioctx("ecpool")
        payload = bytes(range(256)) * 64    # 16 KiB
        await io.write_full("big", payload)
        assert await io.read("big") == payload
        assert await io.stat("big") == len(payload)
        assert await io.read("big", length=100, offset=1000) == \
            payload[1000:1100]
        await io.setxattr("big", "tag", b"ec")
        assert await io.getxattr("big", "tag") == b"ec"
        # every live shard holds 1/4-size chunks (k=4 of 16KiB)
        chunk_sizes = []
        for osd in cl.osds.values():
            for cid in osd.store.list_collections():
                for soid in osd.store.collection_list(cid):
                    if soid.name == "big":
                        chunk_sizes.append(
                            osd.store.stat(cid, soid)["size"])
        assert len(chunk_sizes) == 6
        assert all(s == 4096 for s in chunk_sizes)
        # omap rejected on EC pools
        with pytest.raises(ObjectOperationError):
            await io.omap_set("big", {b"x": b"y"})
        await io.remove("big")
        with pytest.raises(ObjectOperationError):
            await io.read("big")
        await cl.stop()
    asyncio.run(run())


def test_replicated_osd_failure_and_recovery():
    async def run():
        cl = Cluster()
        admin = await cl.start(4)
        await admin.pool_create("rep", pg_num=8, size=3)
        io = admin.open_ioctx("rep")
        for i in range(10):
            await io.write_full(f"o{i}", f"payload-{i}".encode() * 20)
        # kill an osd; mark down via mon command (heartbeat path tested
        # separately); out-aging then remaps pgs
        victim = 1
        await cl.kill_osd(victim)
        await cl.mark_down_and_wait(admin, victim)
        # cluster still serves reads and writes (degraded)
        for i in range(10):
            assert (await io.read(f"o{i}")) == \
                f"payload-{i}".encode() * 20
        await io.write_full("during-degraded", b"x" * 100)
        # after down-out interval the osd goes out; data re-replicates
        deadline = asyncio.get_event_loop().time() + 30
        while admin.monc.osdmap.is_in(victim):
            assert asyncio.get_event_loop().time() < deadline
            await asyncio.sleep(0.1)
        await asyncio.sleep(1.0)   # let recovery run
        # every object has 3 live replicas again
        for name in [f"o{i}" for i in range(10)] + ["during-degraded"]:
            copies = 0
            for osd in cl.osds.values():
                for cid in osd.store.list_collections():
                    for soid in osd.store.collection_list(cid):
                        if soid.name == name:
                            copies += 1
            assert copies == 3, (name, copies)
        await cl.stop()
    asyncio.run(run())


def test_ec_shard_failure_reconstruction():
    async def run():
        cl = Cluster()
        admin = await cl.start(7)
        await admin.pool_create("ec", pg_num=4, pool_type="erasure",
                                k=4, m=2)
        io = admin.open_ioctx("ec")
        payload = b"erasure-coded-payload" * 300
        for i in range(5):
            await io.write_full(f"e{i}", payload + bytes([i]))
        victim = 2
        await cl.kill_osd(victim)
        await cl.mark_down_and_wait(admin, victim)
        # degraded reads still work (decode from surviving shards)
        for i in range(5):
            assert (await io.read(f"e{i}")) == \
                payload + bytes([i])
        # osd goes out; crush repositions; recovery reconstructs shards
        deadline = asyncio.get_event_loop().time() + 30
        while admin.monc.osdmap.is_in(victim):
            assert asyncio.get_event_loop().time() < deadline
            await asyncio.sleep(0.1)
        await asyncio.sleep(2.0)
        for i in range(5):
            copies = 0
            for osd in cl.osds.values():
                for cid in osd.store.list_collections():
                    for soid in osd.store.collection_list(cid):
                        if soid.name == f"e{i}":
                            copies += 1
            assert copies == 6, (i, copies)
            assert (await io.read(f"e{i}")) == \
                payload + bytes([i])
        # recovery observability (ISSUE 18): the rebuild left
        # first-class counters in the osd.recovery perf group that
        # `perf dump --cluster` scrapes per daemon and merges
        rec = {}
        for osd in cl.osds.values():
            assert "recovery" in osd.ctx.perf.dump()
            for k, v in osd.perf_recovery.dump().items():
                rec[k] = rec.get(k, 0) + int(v)
        assert rec["objects_pushed"] > 0, rec
        assert rec["objects_pulled"] > 0, rec
        assert rec["push_bytes"] > 0 and rec["pull_bytes"] > 0, rec
        # converged: every backfill cursor back at LB_MAX, no lag left
        assert rec["cursor_lag"] == 0, rec
        await cl.stop()
    asyncio.run(run())


def test_osd_restart_rejoins_with_data():
    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("rep", pg_num=4, size=3)
        io = admin.open_ioctx("rep")
        await io.write_full("keep", b"original")
        store = await cl.kill_osd(0)
        await cl.mark_down_and_wait(admin, 0)
        # write while it's gone: osd.0 misses this
        await io.write_full("keep", b"updated!!")
        await io.write_full("new-obj", b"fresh")
        # restart with its old store
        await cl.start_osd(0, store=store)
        await cl.osds[0].wait_for_boot()
        await asyncio.sleep(1.5)   # peering + log-based catch-up
        # osd.0's copy caught up to the authoritative version
        osd0 = cl.osds[0]
        data = None
        for cid in osd0.store.list_collections():
            for soid in osd0.store.collection_list(cid):
                if soid.name == "keep":
                    data = osd0.store.read(cid, soid)
        assert data == b"updated!!"
        await cl.stop()
    asyncio.run(run())


def test_heartbeat_failure_reporting():
    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("p", pg_num=4, size=3)
        io = admin.open_ioctx("p")
        await io.write_full("x", b"1")   # PGs exist → osds are hb peers
        # hard-kill osd.2 (no mon command): peers must report it
        await cl.kill_osd(2)
        deadline = asyncio.get_event_loop().time() + 20
        while admin.monc.osdmap.is_up(2):
            assert asyncio.get_event_loop().time() < deadline, \
                "peers never reported the dead osd"
            await asyncio.sleep(0.1)
        await cl.stop()
    asyncio.run(run())


def test_ec_profile_persisted_and_honored():
    """ADVICE r1: a profile with m=3 must actually run 3 parity shards —
    the k/m live in the osdmap's ec_profiles, never derived from size."""
    async def run():
        cl = Cluster()
        admin = await cl.start(6)
        await admin.mon_command({
            "prefix": "osd erasure-code-profile set", "name": "p33",
            "profile": {"k": "3", "m": "3"}})
        ack = await admin.mon_command(
            {"prefix": "osd erasure-code-profile get", "name": "p33"})
        assert ack.retcode == 0 and '"m": "3"' in ack.outs
        # contradicting k/m at pool create is rejected
        from ceph_tpu.mon.client import CommandError
        with pytest.raises(CommandError):
            await admin.mon_command({
                "prefix": "osd pool create", "pool": "bad", "pg_num": 4,
                "pool_type": "erasure", "erasure_code_profile": "p33",
                "k": 4, "m": 2})
        await admin.pool_create("ec33", pg_num=4, pool_type="erasure",
                                erasure_code_profile="p33")
        pid = admin.monc.osdmap.lookup_pool("ec33")
        pool = admin.monc.osdmap.pools[pid]
        assert pool.size == 6 and \
            admin.monc.osdmap.ec_profiles["p33"]["m"] == "3"
        io = admin.open_ioctx("ec33")
        payload = bytes(range(256)) * 48   # 12 KiB -> 4 KiB chunks (k=3)
        await io.write_full("obj", payload)
        assert await io.read("obj") == payload
        # 3 data + 3 parity shards on distinct osds
        chunks = 0
        for osd in cl.osds.values():
            for cid in osd.store.list_collections():
                for soid in osd.store.collection_list(cid):
                    if soid.name == "obj":
                        chunks += 1
        assert chunks == 6
        # in-use profile can't be removed
        with pytest.raises(CommandError):
            await admin.mon_command(
                {"prefix": "osd erasure-code-profile rm", "name": "p33"})
        await cl.stop()
    asyncio.run(run())


def test_full_resync_removes_peer_only_objects():
    """ADVICE r1: an object deleted beyond the log window must not
    survive on a peer that was down across the deletion (backfill scans
    both sides in the reference)."""
    from ceph_tpu.osd.pglog import PGLog

    async def run():
        old_max = PGLog.MAX_ENTRIES
        PGLog.MAX_ENTRIES = 8    # force the catch-up window shut fast
        try:
            cl = Cluster()
            admin = await cl.start(2)
            await admin.pool_create("rep", pg_num=1, size=2)
            io = admin.open_ioctx("rep")
            await io.write_full("doomed", b"zombie" * 10)
            await io.write_full("keep", b"alive")
            store1 = await cl.kill_osd(1)
            await cl.mark_down_and_wait(admin, 1)
            await io.remove("doomed")
            # push the delete out of the log window
            for i in range(12):
                await io.write_full(f"fill-{i}", bytes([i]) * 16)
            # osd.1 comes back with its stale store -> full resync
            await cl.start_osd(1, store=store1)
            await cl.osds[1].wait_for_boot()
            await asyncio.sleep(2.0)
            osd1 = cl.osds[1]
            names = set()
            for cid in osd1.store.list_collections():
                for soid in osd1.store.collection_list(cid):
                    names.add(soid.name)
            assert "doomed" not in names, "deleted object resurrected"
            assert "keep" in names and "fill-5" in names
            await cl.stop()
        finally:
            PGLog.MAX_ENTRIES = old_max
    asyncio.run(run())


def test_pool_quota_full_flag_blocks_writes():
    """Pool quotas (OSDMonitor set-quota + PGMap check_full role): the
    mon flips FLAG_FULL_QUOTA when usage crosses the quota; writes
    fail EDQUOT, deletes still pass (dig-out), and clearing the quota
    or deleting objects unblocks."""
    import errno as _errno

    async def run():
        import time as _time
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("q", pg_num=4)
        io = admin.open_ioctx("q")
        await admin.mon_command({"prefix": "osd pool set", "pool": "q",
                                 "var": "quota_max_objects", "val": "2"})
        await io.write_full("a", b"x" * 100)
        await io.write_full("b", b"y" * 100)

        # stats propagate -> mon flags the pool full -> writes EDQUOT
        deadline = _time.monotonic() + 30.0
        while _time.monotonic() < deadline:
            try:
                await io.write_full("c", b"z")
                await io.remove("c")          # not yet flagged: undo
                await asyncio.sleep(0.3)
            except ObjectOperationError as e:
                assert e.retcode == -_errno.EDQUOT, e
                break
        else:
            raise AssertionError("pool never went quota-full")

        # deletes pass while full (dig-out), then usage drops below
        # the quota and the mon clears the flag
        await io.remove("b")
        deadline = _time.monotonic() + 30.0
        while _time.monotonic() < deadline:
            try:
                await io.write_full("d", b"w")
                break
            except ObjectOperationError:
                await asyncio.sleep(0.3)
        else:
            raise AssertionError("pool never un-flagged after delete")
        # raise the quota entirely: a third object fits now
        await admin.mon_command({"prefix": "osd pool set", "pool": "q",
                                 "var": "quota_max_objects", "val": "0"})
        deadline = _time.monotonic() + 30.0
        while _time.monotonic() < deadline:
            try:
                await io.write_full("e", b"v")
                break
            except ObjectOperationError:
                await asyncio.sleep(0.3)
        else:
            raise AssertionError("quota=0 never unblocked")
        await cl.stop()
    asyncio.run(run())


def test_cluster_flag_noout_holds_down_osd_in():
    """`osd set noout` (OSDMap cluster flags): a down osd is NOT aged
    out while the flag is set; unset resumes the down-out clock; the
    flag shows in the osdmap summary."""
    async def run():
        import time as _time
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("nf", pg_num=4)   # heartbeat peers
        io = admin.open_ioctx("nf")
        await io.write_full("x", b"y")
        ack = await admin.mon_command({"prefix": "osd set",
                                       "key": "noout"})
        assert "noout" in ack.outs
        ack = await admin.mon_command({"prefix": "status"})
        assert "noout" in ack.outs

        await cl.kill_osd(2)
        grace = FAST_CFG["mon_osd_down_out_interval"]
        # wait until it's seen DOWN, then well past the out-grace
        deadline = _time.monotonic() + 30.0
        while _time.monotonic() < deadline and \
                admin.monc.osdmap.is_up(2):
            await asyncio.sleep(0.2)
        await asyncio.sleep(grace + 2.0)
        m = admin.monc.osdmap
        assert not m.is_up(2) and m.is_in(2), "noout must hold it in"

        await admin.mon_command({"prefix": "osd unset", "key": "noout"})
        deadline = _time.monotonic() + 30.0
        while _time.monotonic() < deadline:
            if not admin.monc.osdmap.is_in(2):
                break
            await asyncio.sleep(0.3)
        assert not admin.monc.osdmap.is_in(2), \
            "unset noout must resume down-out"
        # unknown flag is rejected loudly
        with pytest.raises(Exception) as ei:
            await admin.mon_command({"prefix": "osd set",
                                     "key": "nosuchflag"})
        assert "nosuchflag" in str(ei.value)
        await cl.stop()
    asyncio.run(run())


# ----------------------- an EC gather's sub-read waves (ISSUE 35)
#
# The asking side of an EC read on the deterministic loop (virtual
# time): a wave of sub-reads is sent in place and waits on ONE future
# under ONE deadline, whatever happens to the replies.

_GATHER_POOLS = {
    # id: (osds, k, m, OSDs of the PG marked down first)
    "k2m1_healthy": (3, 2, 1, 0),
    "k4m2_one_down": (6, 4, 2, 1),
}


def _backend_calls(loop):
    """Count `loop.create_task` and `loop.call_at` calls made from
    osd/backend.py (the nearest frame outside asyncio), and keep the
    timer handles.  Returns (counts, handles, undo)."""
    import sys
    counts = {"create_task": 0, "call_at": 0}
    handles = []
    real_task, real_at = loop.create_task, loop.call_at

    def from_backend() -> bool:
        f = sys._getframe(2)
        while f is not None and "asyncio" in f.f_code.co_filename:
            f = f.f_back
        return f is not None and \
            f.f_code.co_filename.endswith("osd/backend.py")

    def create_task(*a, **kw):
        if from_backend():
            counts["create_task"] += 1
        return real_task(*a, **kw)

    def call_at(*a, **kw):
        h = real_at(*a, **kw)
        if from_backend():
            counts["call_at"] += 1
            handles.append(h)
        return h

    loop.create_task, loop.call_at = create_task, call_at

    def undo():
        del loop.create_task, loop.call_at
    return counts, handles, undo


def _run_gather_case(pool: str, case: str):
    import errno

    from ceph_tpu.devtools import schedule as sched
    from ceph_tpu.osd.backend import (VERSION_XATTR, PGIntervalChanged,
                                      _SubReadWave)
    from ceph_tpu.osd.messages import MOSDECSubOpReadReply
    from ceph_tpu.qa.cluster import make_sim_ctx
    from ceph_tpu.store.objectstore import Transaction

    n_osds, k, m, n_down = _GATHER_POOLS[pool]
    old, new = b"o" * 6000, b"n" * 9000

    async def main():
        loop = asyncio.get_running_loop()
        cl = Cluster(ctx_factory=make_sim_ctx)
        admin = await cl.start(n_osds)
        await admin.pool_create("g", pg_num=1, pool_type="erasure",
                                k=k, m=m)
        io = admin.open_ioctx("g")
        await io.write_full("obj", old)
        v_old = _pool_pg(cl, io, primary=True)[1].info.last_update
        await io.write_full("obj", new)

        def shard_pgs():
            return sorted(((p.pgid.shard, o, p)
                           for o in cl.osds.values()
                           for p in o.pgs.values()
                           if p.pool_id == io.pool_id
                           and not p.is_primary()),
                          key=lambda t: t[0])

        for _ in range(n_down):
            # a shard in the middle of the preference order goes down:
            # its position reads NONE and the gather skips it
            _s, dosd, _p = shard_pgs()[1]
            await cl.kill_osd(dosd.whoami)
            await cl.mark_down_and_wait(admin, dosd.whoami)
        for _ in range(600):
            posd, ppg = _pool_pg(cl, io, primary=True)
            if ppg.state == "active" and not ppg.missing \
                    and len(shard_pgs()) == k + m - 1 - n_down:
                break
            await asyncio.sleep(0.1)
        assert await io.read("obj") == new
        be = ppg.backend
        want = be._auth_version("obj")
        assert want == ppg.info.last_update.to_bytes()
        shards = shard_pgs()
        # the first wave asks the k-1 first candidates: the first of
        # them is the one the case tampers with, the last candidate
        # is the top-up
        assert len(shards) >= k
        t_shard, t_osd, t_pg = shards[0]
        spare = shards[-1][0]
        counts, handles, undo = _backend_calls(loop)
        t0 = loop.time()
        try:
            if case == "healthy":
                streams, attrs = await be._gather_shards(
                    "obj", want_version=want)
                assert sorted(streams) == \
                    [ppg.pgid.shard] + [s for s, _o, _p in shards[:k - 1]]
                assert attrs[VERSION_XATTR] == want
                # one wave: no task, one timer, cancelled at the last
                # reply's dispatch
                assert counts == {"create_task": 0, "call_at": 1}
                assert handles[0].cancelled()
                assert loop.time() - t0 < 1.0
            elif case == "silent_shard":
                t_pg.backend._handle_ec_sub_read = lambda m: None
                streams, _ = await be._gather_shards(
                    "obj", want_version=want)
                # dropped at the wave's deadline, the wave topped up
                assert loop.time() - t0 >= 15.0
                assert t_shard not in streams and spare in streams
                assert len(streams) == k
                assert counts == {"create_task": 0, "call_at": 2}
            elif case == "eagain":
                def refuse(m):
                    t_osd.send_osd(int(m.src_name.id),
                                   MOSDECSubOpReadReply(
                                       t_pg.pgid, m.tid, t_shard,
                                       -errno.EAGAIN, [b""], {}))
                t_pg.backend._handle_ec_sub_read = refuse
                streams, _ = await be._gather_shards(
                    "obj", want_version=want)
                assert loop.time() - t0 < 1.0
                assert t_shard not in streams and spare in streams
                assert len(streams) == k
                assert counts == {"create_task": 0, "call_at": 2}
            elif case == "interval_change":
                t_pg.backend._handle_ec_sub_read = lambda m: None
                g = asyncio.ensure_future(be._gather_shards(
                    "obj", want_version=want))
                for _ in range(50):
                    await asyncio.sleep(0)
                    if be._inflight:
                        break
                waves = {e[0] for e in be._inflight.values()}
                assert len(waves) == 1 and all(
                    isinstance(w, _SubReadWave) for w in waves)
                await asyncio.sleep(0.5)     # the other replies land
                assert len(be._inflight) == 1 and not g.done()
                be.on_interval_change()
                with pytest.raises(PGIntervalChanged):
                    await g
                assert handles and all(h.cancelled() for h in handles)
                assert counts["create_task"] == 0
            elif case == "mixed_generations":
                # the first shard asked holds the OLD generation whole
                # (bytes, length and version): the cohort check sees
                # two, the second wave asks the rest, the newest
                # consistent cohort serves
                soid = t_pg.object_id("obj")
                stale = bytes(be.codec.encode(
                    set(range(be.n)), old)[t_shard])
                attrs = dict(t_osd.store.getattrs(t_pg.cid, soid))
                attrs[VERSION_XATTR] = v_old.to_bytes()
                txn = Transaction()
                txn.remove(t_pg.cid, soid)
                txn.write(t_pg.cid, soid, 0, stale)
                txn.setattrs(t_pg.cid, soid, attrs)
                t_osd.store.apply_transaction(txn)
                for want_version in (want, None):
                    del handles[:]
                    counts.update(create_task=0, call_at=0)
                    streams, gattrs = await be._gather_shards(
                        "obj", want_version=want_version)
                    assert t_shard not in streams and spare in streams
                    assert len(streams) == k
                    assert gattrs[VERSION_XATTR] == want
                    assert counts == {"create_task": 0, "call_at": 2}
            else:
                raise AssertionError(case)
            assert be._inflight == {}
            assert all(h.cancelled() or h.when() <= loop.time()
                       for h in handles)
        finally:
            undo()
            t_pg.backend.__dict__.pop("_handle_ec_sub_read", None)
        if case != "mixed_generations":
            assert await io.read("obj") == new
        await cl.stop()

    sched.run_deterministic(main, seed=35)


def _pool_pg(cl, io, primary: bool):
    return next((o, p) for o in cl.osds.values()
                for p in o.pgs.values()
                if p.pool_id == io.pool_id
                and p.is_primary() == primary)


@pytest.mark.parametrize("pool", sorted(_GATHER_POOLS))
@pytest.mark.parametrize("case", ["healthy", "silent_shard", "eagain",
                                  "interval_change",
                                  "mixed_generations"])
def test_ec_gather_wave_one_future_one_deadline(pool, case):
    """An EC read's gather makes NO task and ONE timer per wave; a
    shard that never answers is dropped at the wave's deadline and the
    wave tops up; an -EAGAIN tops up at once; an interval change
    mid-wave aborts the gather; the mixed-generation second wave picks
    the newest consistent cohort; `_inflight` ends empty in all."""
    _run_gather_case(pool, case)

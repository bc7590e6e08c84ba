"""Targeted cases beside the model checker (tests/test_thrash.py): a
crash mid-backfill proving the backfill_complete marker forces a resync
retry (VERDICT r2 ask #8), the windowed backfill listing with its
cursor resume, and the op-intake throttle.
"""

import asyncio

from ceph_tpu.qa.cluster import Cluster


def test_crash_mid_backfill_forces_retry():
    """Kill the backfill TARGET mid-resync: on restart its
    backfill_complete=False marker must force a fresh full resync
    instead of trusting the half-copied object set."""
    from ceph_tpu.osd.pglog import PGLog

    async def run():
        old_max = PGLog.MAX_ENTRIES
        PGLog.MAX_ENTRIES = 8     # shut the log window fast
        try:
            cl = Cluster()
            admin = await cl.start(3)
            await admin.pool_create("p", pg_num=1, size=3)
            io = admin.open_ioctx("p")
            for i in range(10):
                await io.write_full(f"a{i}", bytes([i]) * 512)
            # take osd.2 down; write far past the log window so catch-up
            # requires a FULL resync, with many objects to copy
            store2 = await cl.kill_osd(2)
            await cl.mark_down_and_wait(admin, 2)
            for i in range(40):
                await io.write_full(f"b{i}", bytes([i]) * 2048)
            # restart the stale osd; let backfill BEGIN and stamp a
            # partial cursor, then crash it before it can finish
            from ceph_tpu.osd.pglog import LB_MAX
            osd2 = await cl.start_osd(2, store=store2)
            deadline = asyncio.get_running_loop().time() + 20
            started = False
            while not started:
                for pg in osd2.pgs.values():
                    if not pg.info.backfill_complete \
                            and pg.info.last_backfill \
                            and pg.info.last_backfill != LB_MAX:
                        started = True
                assert asyncio.get_running_loop().time() < deadline, \
                    "backfill never started"
                await asyncio.sleep(0.002)
            store2 = await cl.kill_osd(2)
            await cl.mark_down_and_wait(admin, 2)
            # the crashed copy must have persisted the incomplete marker
            # (that is the crash-safety claim under test) — and its
            # DURABLE last_backfill cursor, which the retry must resume
            # FROM rather than restarting the copy from scratch
            from ceph_tpu.osd.pg import PGInfo
            killed_cursor = ""
            # scan every collection's meta object for a pg info row
            for cid in store2.list_collections():
                for o in store2.collection_list(cid):
                    try:
                        _, omap = store2.omap_get(cid, o)
                    except Exception:
                        continue
                    if b"info" in omap:
                        info = PGInfo.from_bytes(omap[b"info"])
                        killed_cursor = max(killed_cursor,
                                            info.last_backfill)
            assert killed_cursor and killed_cursor != LB_MAX, \
                "no durable partial cursor found on the killed store"
            # restart again: the marker forces a retry; eventually every
            # object lands and the copy is trusted — and the cursor
            # NEVER regresses below its killed-time durable value
            osd2 = await cl.start_osd(2, store=store2)
            deadline = asyncio.get_running_loop().time() + 40
            while True:
                for pg in osd2.pgs.values():
                    if not pg.info.backfill_complete:
                        lb = pg.info.last_backfill
                        assert lb >= killed_cursor, \
                            (f"resume regressed below the durable "
                             f"cursor: {lb!r} < {killed_cursor!r}")
                pgs = list(osd2.pgs.values())
                if pgs and all(p.info.backfill_complete for p in pgs):
                    names = {o.name
                             for pg in pgs
                             for o in osd2.store.collection_list(pg.cid)
                             if o.name != pg.meta_oid.name}
                    want = ({f"a{i}" for i in range(10)}
                            | {f"b{i}" for i in range(40)})
                    if want <= names:
                        break
                assert asyncio.get_running_loop().time() < deadline, \
                    "resync never completed after mid-backfill crash"
                await asyncio.sleep(0.2)
            # and the data is right everywhere
            for i in range(40):
                assert await io.read(f"b{i}") == bytes([i]) * 2048
            await cl.stop()
        finally:
            PGLog.MAX_ENTRIES = old_max
    asyncio.run(run())


def test_backfill_windowed_listing_and_cursor_resume():
    """Large-PG backfill with a tiny scan window (osd_backfill_scan_max)
    must page the listing in bounded messages, and a target killed
    mid-backfill must RESUME from its persisted last_backfill cursor
    rather than restarting from scratch (PG.h:1911)."""
    from ceph_tpu.osd.pglog import LB_MAX, PGLog

    async def run():
        old_max = PGLog.MAX_ENTRIES
        PGLog.MAX_ENTRIES = 8
        try:
            from ceph_tpu.qa.cluster import make_ctx

            def ctx_f(name):
                c = make_ctx(name)
                c.config.set("osd_backfill_scan_max", 7)
                return c
            cl = Cluster(ctx_factory=ctx_f)
            admin = await cl.start(3)
            await admin.pool_create("p", pg_num=1, size=3)
            io = admin.open_ioctx("p")
            store2 = await cl.kill_osd(2)
            await cl.mark_down_and_wait(admin, 2)
            # 60 objects, far beyond the log window -> full backfill
            # paged across ~9 windows of 7
            for i in range(60):
                await io.write_full(f"obj{i:03d}", bytes([i]) * 1024)
            osd2 = await cl.start_osd(2, store=store2)
            # catch it mid-backfill with a partial cursor, then kill
            deadline = asyncio.get_running_loop().time() + 30
            cursor = None
            while cursor is None:
                for pg in osd2.pgs.values():
                    lb = pg.info.last_backfill
                    if lb and lb != LB_MAX:
                        cursor = lb
                assert asyncio.get_running_loop().time() < deadline, \
                    "no partial cursor observed"
                await asyncio.sleep(0.002)
            store2 = await cl.kill_osd(2)
            await cl.mark_down_and_wait(admin, 2)
            osd2 = await cl.start_osd(2, store=store2)
            deadline = asyncio.get_running_loop().time() + 60
            while True:
                pgs = list(osd2.pgs.values())
                if pgs and all(p.info.backfill_complete for p in pgs):
                    break
                assert asyncio.get_running_loop().time() < deadline, \
                    "backfill never completed after resume"
                await asyncio.sleep(0.05)
            # every object must be present and correct on the resumed
            # copy (read each back through the cluster)
            for i in range(60):
                got = await io.read(f"obj{i:03d}")
                assert got == bytes([i]) * 1024, f"obj{i:03d} corrupt"
            await cl.stop()
        finally:
            PGLog.MAX_ENTRIES = old_max
    asyncio.run(run())


def test_op_intake_throttle_bounds_memory():
    """Flood one OSD with more write bytes than the intake cap: the
    dispatch throttle must bound in-flight bytes (clients block on TCP
    backpressure, ops still all complete) — VERDICT r3 weak #6."""
    async def run():
        from ceph_tpu.qa.cluster import make_ctx

        def ctx_f(name):
            c = make_ctx(name)
            c.config.set("osd_client_message_size_cap", 262144)
            return c
        cl = Cluster(ctx_factory=ctx_f)
        admin = await cl.start(1)
        await admin.pool_create("p", pg_num=1, size=1)
        io = admin.open_ioctx("p")
        osd = next(iter(cl.osds.values()))
        thr = osd.messenger.dispatch_throttle
        assert thr is not None and thr.max == 262144
        peak = 0

        async def watch():
            nonlocal peak
            while True:
                peak = max(peak, thr.cur)
                await asyncio.sleep(0.001)
        w = asyncio.get_running_loop().create_task(watch())
        # 8 MiB of writes vs a 256 KiB budget
        writes = [io.write_full(f"o{i}", bytes([i % 256]) * 65536)
                  for i in range(128)]
        await asyncio.gather(*writes)
        w.cancel()
        assert peak <= 262144, f"throttle exceeded: {peak}"
        assert thr.waited > 0, "flood never hit the throttle"
        # drained: nothing leaked budget
        for _ in range(100):
            if thr.cur == 0:
                break
            await asyncio.sleep(0.01)
        assert thr.cur == 0, f"leaked {thr.cur} bytes of intake budget"
        for i in range(0, 128, 17):
            assert await io.read(f"o{i}") == bytes([i % 256]) * 65536
        await cl.stop()
    asyncio.run(run())


def test_model_checker_reports_a_wedged_pg_once(monkeypatch):
    """run_model against a pg that cannot come back: once the thrasher
    has healed the cluster, every replica of one pg is killed.  The
    settle wait must FAIL the run with that pg's state, the final
    verify must report each of its objects unavailable after ONE
    deadline for all of them, in oid order, and the cluster must be
    stopped when run_model returns."""
    from ceph_tpu.osd.types import ObjectLocator
    from ceph_tpu.qa import rados_model

    seen = {}
    heal, wait_clean = rados_model.Thrasher._heal, rados_model._wait_clean

    async def heal_then_wedge(self):
        await heal(self)
        assert await wait_clean(self.cl) == []
        m = self.admin.monc.osdmap
        pid = m.lookup_pool("model")

        def acting(oid):
            raw = m.object_locator_to_pg(oid, ObjectLocator(pid))
            return m.pg_to_up_acting_osds(
                m.pools[pid].raw_pg_to_pg(raw))[2]
        victims = acting("m0")
        seen["lost"] = [f"m{i}" for i in range(24)
                        if set(acting(f"m{i}")) <= set(victims)]
        seen["daemons"] = list(self.cl.osds.values()) + self.cl.mons
        for v in victims:
            await self.cl.kill_osd(v)
            await self.cl.mark_down_and_wait(self.admin, v)
        # until a survivor has taken the dead pg over: _wait_clean
        # believes the first clean look it gets
        while all(pg.state == "active" for o in self.cl.osds.values()
                  for pg in o.pgs.values() if pg.is_primary()):
            await asyncio.sleep(0.05)

    monkeypatch.setattr(rados_model.Thrasher, "_heal", heal_then_wedge)
    # the settle wait is not what is timed here
    monkeypatch.setattr(rados_model, "WAIT_CLEAN_S", 5.0)
    res = asyncio.run(rados_model.run_model(7, rounds=12))

    assert not res["ok"]
    assert seen["lost"] and "m0" in seen["lost"]
    unavailable = [f.split()[2] for f in res["failures"]
                   if f.startswith("final read ")]
    # one failure per object, in oid order; a pg with a survivor may be
    # unavailable too (peering cannot rule out writes in an interval
    # the thrasher made whose acting set is wholly dead)
    assert unavailable == [f"m{i}" for i in range(24)
                           if f"m{i}" in unavailable], res["failures"]
    assert set(seen["lost"]) <= set(unavailable), res["failures"]
    assert [f for f in res["failures"] if f.startswith("wait_clean: ")
            and "pgs not clean after 5s" in f], res["failures"]
    assert res["seconds"]["verify"] <= rados_model.FINAL_VERIFY_S + 15
    assert not any(d.running for d in seen["daemons"])

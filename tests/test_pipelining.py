"""Per-PG op pipelining invariants (ISSUE 5).

The dependency-tracked in-flight window (osd/sequencer.py) replaced the
serial one-op-per-PG worker; these tests pin the invariants that make
that safe:
  * same-object ops serialize in admission (client) order even at
    window depth 16 — last write wins, reads see the chain;
  * pglog versions stay DENSE and ordered under concurrency (version
    assignment is atomic with the log append);
  * barrier-class work drains the window and runs alone;
  * a replica failure mid-window re-peers cleanly: every in-flight
    write either completes or is retried by the client, nothing is
    lost, the cluster serves consistent reads after;
  * the store commit thread's gather window auto-tunes from observed
    barrier cost, clamped to [0, 4x] of the static value.
"""

import asyncio
import time

import pytest

from ceph_tpu.osd.sequencer import OpSequencer
from ceph_tpu.qa.cluster import Cluster, make_ctx
from ceph_tpu.store.commit import KVSyncThread


# ------------------------------------------------------ sequencer (unit)

def test_sequencer_same_object_writes_chain_in_admission_order():
    async def run():
        seq = OpSequencer(16)
        order = []

        async def op(slot, name, delay):
            await slot.wait()
            # later admissions must not overtake even when faster
            await asyncio.sleep(delay)
            order.append(name)
            seq.release(slot)

        s1 = seq.admit("obj", True)
        s2 = seq.admit("obj", True)
        s3 = seq.admit("obj", True)
        await asyncio.gather(op(s1, "a", 0.03), op(s2, "b", 0.02),
                             op(s3, "c", 0.0))
        assert order == ["a", "b", "c"]
        assert seq.active == 0

    asyncio.run(run())


def test_sequencer_disjoint_objects_run_concurrently():
    async def run():
        seq = OpSequencer(16)
        running = set()
        peak = []

        async def op(slot, name):
            await slot.wait()
            running.add(name)
            await asyncio.sleep(0.02)
            peak.append(len(running))
            running.discard(name)
            seq.release(slot)

        slots = [(seq.admit(f"o{i}", True), f"o{i}") for i in range(8)]
        await asyncio.gather(*[op(s, n) for s, n in slots])
        assert max(peak) == 8     # all disjoint writes overlapped

    asyncio.run(run())


def test_sequencer_readers_share_writers_exclude():
    async def run():
        seq = OpSequencer(16)
        trace = []

        async def op(slot, name, delay=0.01):
            await slot.wait()
            trace.append(("start", name))
            await asyncio.sleep(delay)
            trace.append(("end", name))
            seq.release(slot)

        w1 = seq.admit("obj", True)
        r1 = seq.admit("obj", False)
        r2 = seq.admit("obj", False)
        w2 = seq.admit("obj", True)
        await asyncio.gather(op(w1, "w1"), op(r1, "r1"),
                             op(r2, "r2"), op(w2, "w2"))
        idx = {(ev, n): i for i, (ev, n) in enumerate(trace)}
        # readers start only after w1 ends, and overlap each other
        assert idx[("end", "w1")] < idx[("start", "r1")]
        assert idx[("end", "w1")] < idx[("start", "r2")]
        assert idx[("start", "r2")] < idx[("end", "r1")] \
            or idx[("start", "r1")] < idx[("end", "r2")]
        # w2 waits for BOTH readers
        assert idx[("end", "r1")] < idx[("start", "w2")]
        assert idx[("end", "r2")] < idx[("start", "w2")]

    asyncio.run(run())


def test_sequencer_counts_what_a_hand_made_admission_order_says():
    """The three counters of what skew does to a PG's window: ops
    admitted behind an in-flight WRITE of their own object, admissions
    that found the window full, and the most ops one object ever had
    in the window at once."""
    from ceph_tpu.common.context import Context

    async def run():
        perf = Context("osd.0").perf.create("osd_op_window")
        for key in ("ops_admitted", "max_inflight_depth",
                    "same_object_waits", "window_full_waits",
                    "chain_peak"):
            perf.add_u64(key)
        perf.add_avg("inflight_depth")
        seq = OpSequencer(4, perf=perf)

        def counts():
            d = perf.dump()
            return (d["same_object_waits"], d["window_full_waits"],
                    d["chain_peak"])

        # reads of one object share: nobody is behind a write
        r1 = seq.admit("hot", False)
        r2 = seq.admit("hot", False)
        assert counts() == (0, 0, 2)
        # a write behind readers waits, but not behind a WRITE
        w1 = seq.admit("hot", True)
        assert counts() == (0, 0, 3)
        # a read and a write behind that write: two same-object waits
        r3 = seq.admit("hot", False)
        assert counts() == (1, 0, 4)
        # the window (4) is full: the admitter waits once, and counts
        # once however long it waits
        waiter = asyncio.ensure_future(seq.wait_slot())
        await asyncio.sleep(0)
        assert not waiter.done() and counts() == (1, 1, 4)
        for s in (r1, r2):
            seq.release(s)
        await asyncio.wait_for(waiter, 1.0)
        w2 = seq.admit("hot", True)
        assert counts() == (2, 1, 4)        # hot holds w1, r3, w2: three
        # another object is a chain of its own; a free slot is no wait
        await asyncio.wait_for(seq.wait_slot(), 1.0)
        c1 = seq.admit("cold", True)
        assert counts() == (2, 1, 4)
        for s in (w1, r3, w2, c1):
            seq.release(s)
        assert seq.balanced()
        # the chain is counted per object and starts again from empty
        seq.admit("hot", True)
        assert counts() == (2, 1, 4)
        # without a perf group nothing is counted and nothing breaks
        bare = OpSequencer(1)
        slot = bare.admit("o", True)
        blocked = asyncio.ensure_future(bare.wait_slot())
        await asyncio.sleep(0)
        bare.release(slot)
        await asyncio.wait_for(blocked, 1.0)

    asyncio.run(run())


def test_sequencer_failed_op_never_wedges_successors():
    async def run():
        seq = OpSequencer(16)

        async def fail(slot):
            await slot.wait()
            try:
                raise RuntimeError("boom")
            finally:
                seq.release(slot)     # the _run_windowed contract

        async def ok(slot):
            await slot.wait()
            seq.release(slot)
            return "ran"

        s1 = seq.admit("obj", True)
        s2 = seq.admit("obj", True)
        t1 = asyncio.ensure_future(fail(s1))
        t2 = asyncio.ensure_future(ok(s2))
        with pytest.raises(RuntimeError):
            await t1
        assert await asyncio.wait_for(t2, 2.0) == "ran"

    asyncio.run(run())


def test_sequencer_drain_barriers_the_window():
    async def run():
        seq = OpSequencer(16)
        done = []

        async def op(slot, name):
            await slot.wait()
            await asyncio.sleep(0.02)
            done.append(name)
            seq.release(slot)

        slots = [(seq.admit(f"o{i}", True), f"o{i}") for i in range(4)]
        tasks = [asyncio.ensure_future(op(s, n)) for s, n in slots]
        assert seq.active == 4
        await seq.drain()
        # every in-flight op finished before the barrier proceeded
        assert seq.active == 0 and len(done) == 4
        await asyncio.gather(*tasks)
        # window is reusable after a drain
        s = seq.admit("o0", True)
        await s.wait()
        seq.release(s)

    asyncio.run(run())


def test_sequencer_window_slot_backpressure():
    async def run():
        seq = OpSequencer(2)
        s1 = seq.admit("a", True)
        s2 = seq.admit("b", True)

        async def admit_third():
            await seq.wait_slot()
            return seq.admit("c", True)

        t = asyncio.ensure_future(admit_third())
        await asyncio.sleep(0.01)
        assert not t.done()           # window full: admitter parked
        seq.release(s1)
        s3 = await asyncio.wait_for(t, 2.0)
        seq.release(s2)
        seq.release(s3)

    asyncio.run(run())


# --------------------------------------------- e2e ordering + density

def test_same_object_write_ordering_and_dense_versions():
    """16 concurrent writes to ONE object land in client-issue order
    (last write wins) while 32 disjoint-object writes interleave; the
    primary's pglog versions stay dense and strictly ordered."""
    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("ord", pg_num=1)
        io = admin.open_ioctx("ord")
        # warm the pg (activation) so the burst measures the window
        await io.write_full("hot", b"seed")

        async def hot(i):
            await io.write_full("hot", bytes([i]) * 2048)

        async def cold(i):
            await io.write_full(f"cold{i:03d}", bytes([i]) * 512)

        await asyncio.gather(*[hot(i) for i in range(16)],
                             *[cold(i) for i in range(32)])
        assert await io.read("hot") == bytes([15]) * 2048
        for i in range(32):
            assert await io.read(f"cold{i:03d}") == bytes([i]) * 512
        # dense/ordered pglog on every copy that hosts the pg
        checked = 0
        for osd in cl.osds.values():
            for pg in osd.pgs.values():
                if pg.pool_id != io.pool_id or not pg.log.entries:
                    continue
                vs = [e.version.version for e in pg.log.entries]
                assert vs == list(range(vs[0], vs[0] + len(vs))), vs
                checked += 1
        assert checked >= 1
        win = cl.window_counters()
        await cl.stop()
        return win

    win = asyncio.run(run())
    assert win["mean_inflight_depth"] > 1.0, win


def test_scrub_barrier_drains_window_under_load():
    """A scrub issued mid-burst drains the window (runs alone) and the
    cluster stays consistent: all writes land, scrub reports clean."""
    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("sb", pg_num=1)
        io = admin.open_ioctx("sb")
        await io.write_full("warm", b"x")
        burst = asyncio.ensure_future(cl.write_burst(
            io, {f"s{i:03d}": bytes([i]) * 4096 for i in range(24)},
            iodepth=24))
        await asyncio.sleep(0.01)     # let the window fill
        pgid = next(pg.pgid.without_shard()
                    for osd in cl.osds.values()
                    for pg in osd.pgs.values()
                    if pg.pool_id == io.pool_id)
        await admin.mon_command({"prefix": "pg scrub",
                                 "pgid": str(pgid)})
        await burst
        # scrub completed (stamp advanced / result recorded) and found
        # nothing inconsistent despite the concurrent burst
        deadline = time.monotonic() + 20.0
        result = None
        while time.monotonic() < deadline:
            for osd in cl.osds.values():
                for pg in osd.pgs.values():
                    if pg.pool_id == io.pool_id and pg.is_primary() \
                            and pg.last_scrub_result is not None:
                        result = pg.last_scrub_result
            if result is not None:
                break
            await asyncio.sleep(0.1)
        assert result is not None, "scrub never ran"
        assert result.get("errors", 0) == 0, result
        win = cl.window_counters()
        assert win["window_drains"] >= 1, win
        for i in range(24):
            assert await io.read(f"s{i:03d}") == bytes([i]) * 4096
        await cl.stop()

    asyncio.run(run())


def test_replica_failure_mid_window_repeers_cleanly():
    """Kill an OSD while an EC pool has a full window of writes in
    flight: aborted ops surface as EAGAIN to the objecter (which
    resends), peering drains the window before adopting the new
    interval, and every write is durable and readable after."""
    async def run():
        cl = Cluster()
        admin = await cl.start(5)
        await admin.pool_create("fi", pg_num=4,
                                pool_type="erasure", k=2, m=2)
        io = admin.open_ioctx("fi")
        await io.write_full("warm", b"x")
        blobs = {f"f{i:03d}": bytes([i % 251]) * 8192 for i in range(32)}
        burst = asyncio.ensure_future(
            cl.write_burst(io, blobs, iodepth=16))
        await asyncio.sleep(0.05)     # mid-window
        victim = 4
        await cl.kill_osd(victim)
        await cl.mark_down_and_wait(admin, victim)
        await asyncio.wait_for(burst, 90.0)
        for k, v in blobs.items():
            assert await io.read(k) == v
        await cl.stop()

    asyncio.run(run())


# --------------------------------------------- commit window auto-tune

def test_gather_window_autotune_tracks_barrier_cost():
    ewma_sleep = 0.004
    th = KVSyncThread("t_auto",
                      data_sync=lambda: time.sleep(ewma_sleep),
                      kv_sync=lambda s: None,
                      gather_window=0.002)
    th.start()
    try:
        for i in range(6):
            th.submit(seq=i, wrote_data=True)
            th.flush()
        assert th._barrier_ewma is not None
        eff = th._effective_window()
        # tracks the ~4ms barrier but clamps at 4x the 2ms static
        assert 0.0 < eff <= 4 * 0.002 + 1e-9
        assert eff > 0.002, eff       # grew beyond the static guess
        c = th.counters()
        assert c["gather_window_ms"] == round(eff * 1e3, 4)
        assert c["gather_window_static_ms"] == 2.0
        assert c["commit_inflight"] >= 0.0
    finally:
        th.stop()


def test_gather_window_autotune_clamps_and_gates():
    # clamp: a pathological 1s barrier must not stretch the window
    # beyond 4x static
    th = KVSyncThread("t_clamp", data_sync=lambda: None,
                      kv_sync=lambda s: None, gather_window=0.001)
    th._barrier_ewma = 1.0
    assert th._effective_window() == pytest.approx(0.004)
    # no auto-tune signal (RAM store: no barrier hooks, ewma stays
    # None) -> the static window keeps ruling
    th2 = KVSyncThread("t_ram", gather_window=0.0003)
    assert th2._effective_window() == pytest.approx(0.0003)
    assert th2._barrier_ewma is None   # nothing to learn from
    # disabled: static wins even with a signal
    th3 = KVSyncThread("t_off", data_sync=lambda: None,
                       gather_window=0.008, auto_tune=False)
    th3._barrier_ewma = 0.001
    assert th3._effective_window() == pytest.approx(0.008)

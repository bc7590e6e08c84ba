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
    barrier cost, clamped to [0, 4x] of the static value;
  * writes to ONE object pipeline (ISSUE 33): the chain links at the
    submit section, replies leave in submit order, reads wait for
    acks, only a plain client write takes the early link, a duplicate
    of an in-flight write is not acked before its original, and a full
    write's encode starts at admission.
"""

import asyncio
import time

import pytest

from ceph_tpu.osd.sequencer import OpSequencer
from ceph_tpu.qa.cluster import Cluster, make_ctx
from ceph_tpu.store.commit import KVSyncThread
from schedule_fixtures import copies_not_holding


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


# ------------------------------- sequencer: the early link (ISSUE 33)

async def _blocked(aw) -> "asyncio.Future":
    """Start `aw` and let the loop turn: the future of an awaitable
    that is (still) held back."""
    fut = asyncio.ensure_future(aw)
    for _ in range(3):
        await asyncio.sleep(0)
    return fut


def test_early_link_writes_chain_at_submitted():
    async def run():
        seq = OpSequencer(16)
        w1 = seq.admit("obj", True, True)
        w2 = seq.admit("obj", True, True)
        w3 = seq.admit("obj", True, True)
        await asyncio.wait_for(w1.wait(), 1.0)   # nobody before it
        assert w2.must_wait() and w3.must_wait()
        t2, t3 = await _blocked(w2.wait()), await _blocked(w3.wait())
        assert not t2.done() and not t3.done()
        w1.mark_submitted()                      # w1's submit section
        await asyncio.wait_for(t2, 1.0)          # ... frees w2 only
        assert not w1.done.done() and seq.active == 3
        await asyncio.sleep(0)
        assert not t3.done()                     # w3 waits for w2's too
        w2.mark_submitted()
        await asyncio.wait_for(t3, 1.0)
        w2.mark_submitted()                      # idempotent
        for s in (w1, w2, w3):
            seq.release(s)
        assert seq.balanced()

    asyncio.run(run())


def test_reader_waits_for_the_done_of_every_writer_in_flight():
    async def run():
        seq = OpSequencer(16)
        w1 = seq.admit("obj", True, True)
        w2 = seq.admit("obj", True, True)
        r = seq.admit("obj", False)
        for w in (w1, w2):
            w.mark_submitted()                   # both writes in flight
        tr = await _blocked(r.wait())
        assert not tr.done()
        seq.release(w2)                          # the LAST writer acked
        await asyncio.sleep(0)
        assert not tr.done()                     # ... is not enough
        seq.release(w1)
        await asyncio.wait_for(tr, 1.0)
        seq.release(r)
        assert seq.balanced()

    asyncio.run(run())


def test_early_link_writer_waits_for_the_done_of_readers():
    async def run():
        seq = OpSequencer(16)
        w1 = seq.admit("obj", True, True)
        r1 = seq.admit("obj", False)
        r2 = seq.admit("obj", False)
        w2 = seq.admit("obj", True, True)
        w1.mark_submitted()
        t2 = await _blocked(w2.wait())
        seq.release(w1)                          # readers may run now
        await asyncio.wait_for(r1.wait(), 1.0)
        await asyncio.wait_for(r2.wait(), 1.0)
        seq.release(r1)
        await asyncio.sleep(0)
        assert not t2.done()                     # r2 still reads
        seq.release(r2)
        await asyncio.wait_for(t2, 1.0)
        seq.release(w2)
        assert seq.balanced()

    asyncio.run(run())


def test_early_link_write_replies_after_the_writers_before_it():
    async def run():
        seq = OpSequencer(16)
        w1 = seq.admit("obj", True, True)
        w2 = seq.admit("obj", True, True)
        w3 = seq.admit("obj", True, True)
        assert await w1.wait_reply() is False    # nothing before it
        for w in (w1, w2, w3):
            w.mark_submitted()
        t3 = await _blocked(w3.wait_reply())
        seq.release(w2)                          # w2 first: still w1
        await asyncio.sleep(0)
        assert not t3.done()
        seq.release(w1)
        assert await asyncio.wait_for(t3, 1.0) is True
        seq.release(w3)
        assert seq.balanced()

    asyncio.run(run())


def test_writer_refused_before_its_submit_frees_its_successors():
    async def run():
        seq = OpSequencer(16)
        w1 = seq.admit("obj", True, True)
        w2 = seq.admit("obj", True, True)
        t2 = await _blocked(w2.wait())
        assert not t2.done()
        seq.release(w1)              # EAGAIN / error / cancel: no submit
        assert w1.submitted.done() and w1.done.done()
        await asyncio.wait_for(t2, 1.0)
        assert await w2.wait_reply() is False
        seq.release(w2)
        assert seq.balanced()

    asyncio.run(run())


def test_exclusive_op_without_the_early_link_keeps_the_whole_exclusion():
    """A tier's read admitted exclusive, an op with a guard, a cls
    call: behind the RELEASE of the writes before it, and the writes
    behind it wait for ITS release, whatever its backend marks."""
    from ceph_tpu.common.context import Context

    async def run():
        perf = Context("osd.0").perf.create("osd_op_window")
        for key in ("ops_admitted", "max_inflight_depth", "chain_peak",
                    "same_object_waits", "writes_pipelined"):
            perf.add_u64(key)
        perf.add_avg("inflight_depth")
        seq = OpSequencer(16, perf=perf)
        w1 = seq.admit("obj", True, True)
        ex = seq.admit("obj", True)              # no early link
        w3 = seq.admit("obj", True, True)
        assert ex.submitted is ex.done and not ex.reply_waits
        w1.mark_submitted()
        te, t3 = await _blocked(ex.wait()), await _blocked(w3.wait())
        assert not te.done() and not t3.done()
        seq.release(w1)
        await asyncio.wait_for(te, 1.0)
        ex.mark_submitted()          # its backend's submit section
        await asyncio.sleep(0)
        assert not ex.done.done() and not t3.done()
        seq.release(ex)
        await asyncio.wait_for(t3, 1.0)
        w3.mark_submitted()
        seq.release(w3)
        assert seq.balanced()
        # nobody's submit section ran beside an earlier write's
        assert perf.dump()["writes_pipelined"] == 0
        a, b = seq.admit("o", True, True), seq.admit("o", True, True)
        a.mark_submitted()
        b.mark_submitted()           # ... while `a` is in flight: one
        assert perf.dump()["writes_pipelined"] == 1
        seq.release(a)
        seq.release(b)
        assert seq.balanced()

    asyncio.run(run())


def _osd_ops(*kinds):
    from ceph_tpu.osd import messages as M
    mk = {"read": lambda: M.OSDOp(M.OP_READ),
          "stat": lambda: M.OSDOp(M.OP_STAT),
          "write_full": lambda: M.OSDOp(M.OP_WRITEFULL, data=b"x"),
          "append": lambda: M.OSDOp(M.OP_APPEND, data=b"x"),
          "delete": lambda: M.OSDOp(M.OP_DELETE),
          "setxattr": lambda: M.OSDOp(M.OP_SETXATTR, name="a", data=b"1"),
          "guard": lambda: M.OSDOp(M.OP_CMPXATTR, name="a", data=b"1"),
          "exists": lambda: M.OSDOp(M.OP_ASSERT_EXISTS),
          "cls_write": lambda: M.OSDOp(M.OP_CALL, name="lock.lock"),
          "cls_read": lambda: M.OSDOp(M.OP_CALL, name="lock.get_info"),
          "watch": lambda: M.OSDOp(M.OP_WATCH, offset=1)}
    return [mk[k]() for k in kinds]


@pytest.mark.parametrize("tier,kinds,want", [
    ("", ("read",), (False, False)),
    ("", ("stat", "cls_read"), (False, False)),
    ("", ("write_full",), (True, True)),
    ("", ("setxattr", "write_full"), (True, True)),
    ("", ("append",), (True, True)),
    ("", ("delete",), (True, True)),
    ("", ("watch",), (True, True)),
    ("", ("read", "write_full"), (True, False)),
    ("", ("guard", "write_full"), (True, False)),
    ("", ("exists", "delete"), (True, False)),
    ("", ("cls_write",), (True, False)),
    ("", ("cls_read", "write_full"), (True, False)),
    ("writeback", ("read",), (True, False)),
    ("writeback", ("write_full",), (True, False)),
    ("readonly", ("read",), (False, False)),
    ("readonly", ("write_full",), (True, False)),
])
def test_admission_class_gives_the_early_link_to_plain_writes_only(
        tier, kinds, want):
    from types import SimpleNamespace

    from ceph_tpu.osd.messages import MOSDOp
    from ceph_tpu.osd.pg import PG
    pool = SimpleNamespace(is_tier=lambda: bool(tier), cache_mode=tier)
    m = MOSDOp(oid="o", ops=_osd_ops(*kinds))
    assert PG._admission_class(SimpleNamespace(pool=pool), m) == want


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


# ------------------------------ writes to ONE object pipeline (ISSUE 33)

_POOLS = {"ec_k2m1": dict(pool_type="erasure", k=2, m=1),
          "replicated": {}}


def _pool_pgs(cl, io):
    return [(osd, pg) for osd in cl.osds.values()
            for pg in osd.pgs.values() if pg.pool_id == io.pool_id]


def _window_sum(cl, key):
    return sum(int(osd.perf_window.dump()[key])
               for osd in cl.osds.values())


def _hold_acks(pg):
    """Hold the shards' acks back at the primary until the returned
    event is set: its writes are submitted and logged, not acked.
    With `gate.lost` set meanwhile the wait then FAILS, as it does
    when the interval changes under it."""
    gate = asyncio.Event()
    gate.lost = False
    real = pg.backend._await_acks

    async def held(fut, timeout=None):
        await gate.wait()
        return False if gate.lost else await real(fut, timeout)

    pg.backend._await_acks = held
    return gate


def _assert_logs_dense(cl, io, name=None):
    for osd, pg in _pool_pgs(cl, io):
        vs = [e.version.version for e in pg.log.entries]
        assert not vs or vs == list(range(vs[0], vs[0] + len(vs))), vs
        if name is not None:
            mine = [e.version.version for e in pg.log.entries
                    if e.oid == name]
            assert mine == sorted(mine)


@pytest.mark.parametrize("pool", sorted(_POOLS))
def test_overlapping_writes_of_one_object_pipeline_in_order(pool):
    """32 write_fulls of ONE object submitted at once by one client,
    a read after every fourth: acks leave in submit order, every read
    returns the last write submitted before it, versions are dense
    and in admission order, and when all is acked the object and
    every stored copy are the last write."""
    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("pw", pg_num=1, **_POOLS[pool])
        io = admin.open_ioctx("pw")
        await io.write_full("hot", b"seed" * 300)
        payloads = [bytes([i + 1]) * (1000 + i) for i in range(32)]
        acked, reads = [], {}

        async def write(i):
            await io.write_full("hot", payloads[i])
            acked.append(i)

        async def read(after):
            reads[after] = await io.read("hot")

        ops = []
        for i in range(32):
            ops.append(write(i))      # gather starts them in this order
            if i % 4 == 3:
                ops.append(read(i))
        await asyncio.wait_for(asyncio.gather(*ops), 60.0)
        assert acked == list(range(32)), acked
        assert sorted(reads) == list(range(3, 32, 4))
        for after, got in reads.items():
            assert got == payloads[after], (after, len(got))
        assert await io.read("hot") == payloads[31]
        seen, bad = copies_not_holding(cl, io.pool_id, "hot",
                                       payloads[31])
        assert seen == 3 and not bad, (seen, bad)
        _assert_logs_dense(cl, io, "hot")
        primary = next(pg for _o, pg in _pool_pgs(cl, io)
                       if pg.is_primary())
        assert len([e for e in primary.log.entries
                    if e.oid == "hot"]) == 33
        assert all(pg.op_window.balanced()
                   for _o, pg in _pool_pgs(cl, io))
        counts = {k: _window_sum(cl, k) for k in (
            "writes_pipelined", "early_encodes",
            "early_encodes_dropped")}
        await cl.stop()
        return counts

    counts = asyncio.run(run())
    assert counts["writes_pipelined"] > 0, counts
    assert counts["early_encodes_dropped"] == 0, counts
    # a full write that waits in its chain encodes meanwhile, where
    # the pool encodes at all
    assert (counts["early_encodes"] > 0) == (pool == "ec_k2m1"), counts


@pytest.mark.parametrize("interval", ["holds", "changes"])
@pytest.mark.parametrize("pool", sorted(_POOLS))
def test_resend_of_an_inflight_write_is_not_acked_before_it(pool, interval):
    """The duplicate short-cut: append_log puts a write's reqid into
    pg.reqids at SUBMIT, so a resend that follows its original down
    the chain finds it there while no shard has acked yet.  It must
    answer after the original, not before; and when the interval
    changes under the original (its ack wait fails: EAGAIN) the
    resend must not vouch for the entry either."""
    import errno
    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("dup", pg_num=1, **_POOLS[pool])
        io = admin.open_ioctx("dup")
        await io.write_full("obj", b"seed")
        posd, pg = next((o, p) for o, p in _pool_pgs(cl, io)
                        if p.is_primary())
        gate = _hold_acks(pg)
        seen, replies = [], []
        orig_queue, orig_reply = pg.queue_op, posd.reply_to

        def queue(m):
            seen.append(m)
            orig_queue(m)

        def reply(req, msg):
            replies.append((req.tid, msg.result))
            if req.tid < 1 << 40:       # the resend has no client
                orig_reply(req, msg)

        pg.queue_op, posd.reply_to = queue, reply
        w = asyncio.ensure_future(io.write_full("obj", b"new" * 100))
        for _ in range(200):
            await asyncio.sleep(0.01)
            if seen and seen[0].reqid in pg.reqids:
                break
        first = seen[0]
        assert first.reqid and first.reqid in pg.reqids   # submitted
        assert not replies
        dup = first.local_view()
        dup.tid = (1 << 40) + 1
        dup.src_name, dup.src_addr = first.src_name, first.src_addr
        pg.queue_op(dup)
        await asyncio.sleep(0.2)
        assert not replies, replies     # neither acked: no shard has
        want = 0
        if interval == "changes":
            # what an interval change does to the primary's in-flight
            # writes (the PG itself stays as it is: the client's
            # resend of the EAGAIN then finds the entry and is acked)
            gate.lost = True
            pg.interval_epoch += 1
            want = -errno.EAGAIN
        gate.set()
        await asyncio.wait_for(w, 20.0)
        for _ in range(200):
            if len(replies) >= 2:
                break
            await asyncio.sleep(0.01)
        assert replies[:2] == [(first.tid, want), (dup.tid, want)], replies
        assert await io.read("obj") == b"new" * 100
        # the duplicate was not applied a second time
        assert len([e for e in pg.log.entries if e.oid == "obj"]) == 2
        assert pg.op_window.balanced()
        await cl.stop()

    asyncio.run(run())


def test_early_encode_of_a_refused_write_is_dropped_quietly(caplog):
    """A full write that waited in its chain and is then refused (here:
    the pool went over quota meanwhile) never takes its encode: the
    task is cancelled, counted as dropped, no fallback is counted and
    nothing is left to warn about."""
    import errno
    import gc
    import logging

    from ceph_tpu.client.objecter import ObjectOperationError
    from ceph_tpu.osd.types import FLAG_FULL_QUOTA

    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("dq", pg_num=1, pool_type="erasure",
                                k=2, m=1)
        io = admin.open_ioctx("dq")
        await io.write_full("obj", b"seed")
        pg = next(p for _o, p in _pool_pgs(cl, io) if p.is_primary())
        gate = _hold_acks(pg)
        w1 = asyncio.ensure_future(io.write_full("obj", b"a" * 2000))
        r = asyncio.ensure_future(io.read("obj"))       # waits for w1
        w2 = asyncio.ensure_future(io.write_full("obj", b"b" * 2000))
        for _ in range(200):
            await asyncio.sleep(0.01)
            if _window_sum(cl, "early_encodes"):
                break
        assert _window_sum(cl, "early_encodes") == 1    # w2's
        pg.pool.flags |= FLAG_FULL_QUOTA                # w2 will be refused
        gate.set()
        await asyncio.wait_for(w1, 20.0)
        assert await asyncio.wait_for(r, 20.0) == b"a" * 2000
        with pytest.raises(ObjectOperationError) as ei:
            await asyncio.wait_for(w2, 20.0)
        assert ei.value.retcode == -errno.EDQUOT
        pg.pool.flags &= ~FLAG_FULL_QUOTA
        assert _window_sum(cl, "early_encodes_dropped") == 1
        assert not pg._window_tasks and pg.op_window.balanced()
        fallbacks = sum(
            int(o.ec_batch_queue().perf.dump()["device_fallbacks"])
            for o in cl.osds.values())
        assert fallbacks == 0
        assert await io.read("obj") == b"a" * 2000
        await cl.stop()

    with caplog.at_level(logging.ERROR, logger="asyncio"):
        asyncio.run(run())
        gc.collect()
    assert "never retrieved" not in caplog.text


@pytest.mark.parametrize("shape", ["distinct_objects", "one_object"])
def test_replica_failure_mid_window_repeers_cleanly(shape):
    """Kill an OSD while an EC pool has a full window of writes in
    flight: aborted ops surface as EAGAIN to the objecter (which
    resends), peering drains the window before adopting the new
    interval, and every write is durable and readable after.  With
    the window full of ONE object's writes (pipelined: several of
    them submitted and unacked at the kill) every one of them answers
    in the end, the object is one of the payloads whole, on every
    surviving shard, and the logs stay dense."""
    async def run():
        cl = Cluster()
        admin = await cl.start(5)
        await admin.pool_create("fi", pg_num=4,
                                pool_type="erasure", k=2, m=2)
        io = admin.open_ioctx("fi")
        await io.write_full("warm", b"x")
        if shape == "one_object":
            payloads = [bytes([i + 1]) * 8192 for i in range(32)]
            sem = asyncio.Semaphore(16)

            async def one(data):
                async with sem:
                    await io.write_full("hot", data)

            burst = asyncio.ensure_future(
                asyncio.gather(*[one(d) for d in payloads]))
        else:
            blobs = {f"f{i:03d}": bytes([i % 251]) * 8192
                     for i in range(32)}
            burst = asyncio.ensure_future(
                cl.write_burst(io, blobs, iodepth=16))
        await asyncio.sleep(0.05)     # mid-window
        victim = 4
        await cl.kill_osd(victim)
        await cl.mark_down_and_wait(admin, victim)
        await asyncio.wait_for(burst, 90.0)
        if shape == "one_object":
            got = await io.read("hot")
            assert got in payloads
            assert await io.read("hot") == got
            _assert_logs_dense(cl, io, "hot")
            assert all(pg.op_window.balanced()
                       for _o, pg in _pool_pgs(cl, io))
        else:
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

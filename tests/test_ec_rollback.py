"""An EC overwrite keeps what it replaced (ROADMAP M15, closed by
ISSUE 33's second round).

An EC write changes its shards in place, and an interval change can
leave it on some shards only.  With writes to one object pipelined
(ISSUE 33) several unacked versions can be out at once, so:
  * a write that replaces a version NOT yet on every shard keeps it
    on every shard, as a rollback generation, until all shards have
    acked the write; then the generation goes (with the next write's
    transactions, or alone after a moment).  A write that replaces an
    acked version keeps nothing: every shard that lacks the new one
    still has the old;
  * peering puts an object whose newest logged version fewer than k
    shards hold back to the newest version k of them still have — an
    acked version is on all of them — as a logged LOG_ROLLBACK entry;
  * the writes it undid are void: a resend of one is applied, not
    acked as a duplicate.
"""

import asyncio
import errno

import pytest

from ceph_tpu.client.objecter import ObjectOperationError
from ceph_tpu.osd.backend import VERSION_XATTR
from ceph_tpu.osd.messages import EVersion, MOSDECSubOpWrite
from ceph_tpu.osd.pglog import (LOG_DELETE, LOG_MODIFY, LOG_ROLLBACK,
                                LogEntry, PGLog)
from ceph_tpu.qa.cluster import Cluster
from schedule_fixtures import copies_not_holding
from test_pipelining import _assert_logs_dense, _hold_acks, _pool_pgs


# ------------------------------------------------------------ log (unit)

def _log(*entries):
    log = PGLog()
    for op, oid, v, prior, reqid in entries:
        log.append(LogEntry(op, oid, EVersion(1, v), EVersion(
            1 if prior else 0, prior), reqid))
    return log


def test_rollback_entry_voids_the_reqids_of_what_it_undid():
    log = _log((LOG_MODIFY, "a", 1, 0, "r1"), (LOG_MODIFY, "a", 2, 1, "r2"),
               (LOG_MODIFY, "b", 3, 0, "r3"), (LOG_MODIFY, "a", 4, 2, "r4"),
               (LOG_ROLLBACK, "a", 5, 1, ""), (LOG_MODIFY, "a", 6, 5, "r2"))
    # a's writes after version 1 are void; b's and the acked one stay;
    # r2 written again after the rollback is a duplicate again
    assert log.reqids() == {"r1": EVersion(1, 1), "r3": EVersion(1, 3),
                            "r2": EVersion(1, 6)}
    live = {"r1": EVersion(1, 1), "r2": EVersion(1, 2),
            "r3": EVersion(1, 3), "r4": EVersion(1, 4)}
    log.void_reqids(log.entries[4], live)
    assert sorted(live) == ["r1", "r3"]


@pytest.mark.parametrize("op,v,prior,gen,deleted", [
    (LOG_MODIFY, 7, 4, 4, False),     # an overwrite keeps version 4
    (LOG_MODIFY, 7, 0, 0, False),     # a first write keeps nothing
    (LOG_MODIFY, 7, 7, 0, False),     # what it replaced is not known
    (LOG_DELETE, 7, 4, 4, True),
    (LOG_ROLLBACK, 7, 4, 4, False),   # back to version 4
    (LOG_ROLLBACK, 7, 0, 0, True),    # back to not being there
])
def test_log_entry_says_what_it_kept_and_what_it_left(op, v, prior, gen,
                                                      deleted):
    e = LogEntry(op, "o", EVersion(2, v), EVersion(2 if prior else 0,
                                                   prior), "")
    assert e.kept_generation() == gen
    assert e.is_delete() is deleted
    assert LogEntry.from_bytes(e.to_bytes()).kept_generation() == gen


@pytest.mark.parametrize("case,want", [
    ("witness_holds_the_target", True),
    ("witness_came_later", False),       # not in that interval's set
    ("interval_forgotten", False),       # the PG was clean since
    ("only_a_generation", False),        # it applied the write
    ("nothing_with_a_log", True),        # to not existing
    ("nothing_without_a_log", False),    # a store made anew
    ("nothing_but_it_has_one", False),
])
def test_gone_shards_prove_nothing(case, want):
    """Rolling back needs a WITNESS that the writes were never acked:
    a shard at hand that stood at its position in the interval of each
    write and never applied the oldest of them.  That the version is
    on fewer than k shards proves nothing: the others may have died
    after the ack, and then the PG waits for them."""
    from types import SimpleNamespace as NS
    from ceph_tpu.osd.backend import ECBackend
    from ceph_tpu.osd.pglog import PastInterval, PGInfo
    v = lambda n: EVersion(10, n)                     # noqa: E731
    acting_then = [0, 1, 2]
    first, lu, held = 8, v(3), {v(4): "head"}
    to, undone = v(4), [v(5), v(6)]
    if case == "witness_came_later":
        acting_then = [0, 9, 2]
    elif case == "interval_forgotten":
        first = 11
    elif case == "only_a_generation":
        held = {v(6): "head", v(4): "gen"}
    elif case.startswith("nothing"):
        to, held = EVersion.zero(), {}
        if case == "nothing_without_a_log":
            lu = EVersion.zero()
        elif case == "nothing_but_it_has_one":
            held = {v(2): "head"}
    info = PGInfo()
    info.last_update = lu
    pg = NS(lu_at_peering=v(6), peer_info={1: info},
            past_intervals=[PastInterval(first, 12, acting_then,
                                         acting_then, 0, True)])
    me = NS(pg=pg, osd=NS(whoami=0))
    shown = {0: {v(6): "head", v(4): "gen"}, 1: held}
    assert ECBackend._never_acked(me, undone, to, shown,
                                  {0: 0, 1: 1}) is want


# --------------------------------------------------------- on a cluster

def _generations(cl, io, name):
    """[(osd, pg, soid)] of every rollback generation of `name`."""
    return [(osd, pg, s) for osd, pg in _pool_pgs(cl, io)
            for s in osd.store.collection_list(pg.cid)
            if s.name == name and s.generation]


async def _settled(cl, io, name):
    for _ in range(100):
        if not _generations(cl, io, name):
            return True
        await asyncio.sleep(0.05)
    return False


@pytest.mark.parametrize("then", ["write", "nothing"])
def test_a_generation_is_kept_until_every_shard_acked(then):
    """A write that replaces a version every shard has keeps nothing
    (the shards that lack the new one all hold the old one).  A write
    that replaces a version NOT yet acked by all keeps it: every shard
    that applied it holds what it replaced, under the replaced
    version's number, bytes and xattrs whole; once acked the
    generation goes: with the next write's transactions, or alone
    after a moment.  The object keeps its xattrs through full
    writes."""
    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("g", pg_num=1, pool_type="erasure",
                                k=2, m=1)
        io = admin.open_ioctx("g")
        old, new1, new2 = b"old" * 500, b"new" * 700, b"newer" * 600
        await io.write_full("o", old)
        await io.setxattr("o", "tag", b"kept")
        posd, pg = next((o, p) for o, p in _pool_pgs(cl, io)
                        if p.is_primary())
        gate = _hold_acks(pg)
        head = pg.info.last_update.version
        w1 = asyncio.ensure_future(io.write_full("o", new1))
        for _ in range(200):
            await asyncio.sleep(0.01)
            if pg.info.last_update.version == head + 1:
                break
        first = pg.log.entries[-1]
        assert first.oid == "o" and not first.kept_generation()
        assert not _generations(cl, io, "o") and not w1.done()
        w2 = asyncio.ensure_future(io.write_full("o", new2))
        for _ in range(200):
            await asyncio.sleep(0.01)
            if len(_generations(cl, io, "o")) == 3:
                break
        gens = _generations(cl, io, "o")
        assert len(gens) == 3 and not w2.done()
        for osd, gpg, soid in gens:
            assert soid.generation == first.version.version
            attrs = osd.store.getattrs(gpg.cid, soid)
            assert EVersion.from_bytes(attrs[VERSION_XATTR]) \
                == first.version
            assert attrs["tag"] == b"kept"
            want = bytes(gpg.backend.codec.encode(
                set(range(3)), new1)[gpg.pgid.shard])
            assert bytes(osd.store.read(gpg.cid, soid)) == want
        entry = pg.log.entries[-1]
        assert entry.prior_version == first.version
        assert entry.kept_generation() == first.version.version
        gate.set()
        await asyncio.wait_for(asyncio.gather(w1, w2), 20.0)
        assert await io.getxattr("o", "tag") == b"kept"
        assert not pg.backend._unacked and not pg.backend._kept
        if then == "write":
            await io.write_full("other", b"x")   # carries the remove
            assert not _generations(cl, io, "o")
        assert await _settled(cl, io, "o")
        assert await io.read("o") == new2
        seen, bad = copies_not_holding(cl, io.pool_id, "o", new2)
        assert seen == 3 and not bad, (seen, bad)
        await cl.stop()

    asyncio.run(run())


def _drop_sub_writes(posd, drops):
    """Lose the primary's shard writes on the way, as a shard does
    that already lives in the next interval (rule EPOCH10): `drops`
    holds one set of target OSDs per write, in submit order."""
    real, seen = posd.send_osd, []

    def send(osd_id, msg):
        if isinstance(msg, MOSDECSubOpWrite):
            if msg.version not in seen:
                seen.append(msg.version)
            nth = seen.index(msg.version)
            if nth < len(drops) and osd_id in drops[nth]:
                return
        real(osd_id, msg)

    posd.send_osd = send
    return lambda: setattr(posd, "send_osd", real)


async def _lose_writes_then_flap(cl, admin, io, name, payloads, drops,
                                 flap):
    """`payloads` written to `name` at once, their shard writes lost
    as `drops` says, the client giving up on them (NO resend); then an
    interval change that leaves every OSD alive (`flap` is marked down
    wrongly and boots again) and the wait for a clean PG.  Returns the
    writes' MOSDOps as the primary saw them."""
    posd, pg = next((o, p) for o, p in _pool_pgs(cl, io)
                    if p.is_primary())
    undo = _drop_sub_writes(posd, drops)
    seen, orig_queue = [], pg.queue_op

    def queue(m):
        seen.append(m)
        orig_queue(m)

    pg.queue_op = queue
    head = pg.info.last_update.version
    writes = [asyncio.ensure_future(io.write_full(name, d))
              for d in payloads]
    for _ in range(400):
        await asyncio.sleep(0.01)
        if pg.info.last_update.version == head + len(payloads):
            break
    assert pg.info.last_update.version == head + len(payloads)
    assert not any(w.done() for w in writes)
    for w in writes:
        w.cancel()
    await asyncio.gather(*writes, return_exceptions=True)
    undo()
    pg.queue_op = orig_queue
    n = len(pg.acting)
    await cl.mark_down_and_wait(admin, flap)
    for _ in range(600):
        await asyncio.sleep(0.05)
        pgs = [p for _o, p in _pool_pgs(cl, io)]
        prim = [p for p in pgs if p.is_primary()]
        if len(pgs) == n and prim and admin.monc.osdmap.is_up(flap) \
                and len([o for o in prim[0].acting if o >= 0]) == n \
                and prim[0].is_fully_clean():
            break
    prim = next(p for _o, p in _pool_pgs(cl, io) if p.is_primary())
    assert prim.is_fully_clean(), prim.describe()
    return [m for m in seen if m.oid == name]


@pytest.mark.parametrize("inflight", [1, 2, 3])
def test_unacked_ec_overwrites_roll_back_at_the_next_peering(inflight):
    """Unacked full writes of ONE object reach some shards and not
    others (EC k=2 m=1: with two of them in flight the three shards
    hold three versions, none on two), the interval changes with every
    OSD alive, and the client does NOT resend: the object must read
    back its last acked version or a later one, the PG must get clean,
    and every shard must hold that version.  (One in flight, lost on
    both other shards, wedged the program before pipelining as well:
    its log named a version only the primary held.)"""
    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("rb", pg_num=1, pool_type="erasure",
                                k=2, m=1)
        io = admin.open_ioctx("rb")
        seed = b"seed" * 300
        await io.write_full("hot", seed)
        await io.write_full("cold", b"c" * 700)
        pg = next(p for _o, p in _pool_pgs(cl, io) if p.is_primary())
        others = [o for o in pg.acting if o != pg.osd.whoami]
        # the first write reaches one other shard at most, the later
        # ones none
        drops = {1: [set(others)],
                 2: [{others[1]}, set(others)],
                 3: [{others[1]}, set(others), set(others)]}[inflight]
        payloads = [bytes([i + 1]) * (900 + i) for i in range(inflight)]
        ops = await _lose_writes_then_flap(cl, admin, io, "hot",
                                           payloads, drops, others[1])
        got = await asyncio.wait_for(io.read("hot"), 30.0)
        # one in flight: the version before it; more: the first of
        # them, which two shards hold (one as a generation)
        assert got == ([seed] + payloads)[min(inflight - 1, 1)]
        assert await io.read("cold") == b"c" * 700
        seen, bad = copies_not_holding(cl, io.pool_id, "hot", got)
        assert seen == 3 and not bad, (seen, bad)
        _assert_logs_dense(cl, io, "hot")
        prim = next(p for _o, p in _pool_pgs(cl, io) if p.is_primary())
        backs = [e for e in prim.log.entries if e.op == LOG_ROLLBACK]
        assert [e.oid for e in backs] == ["hot"]
        assert int(sum(o.perf_recovery.dump()["objects_rolled_back"]
                       for o in cl.osds.values())) == 1
        assert await _settled(cl, io, "hot")
        # what was undone is no duplicate: sent again it is applied
        undone = ops[-1]
        assert undone.reqid and undone.reqid not in prim.reqids
        for _o, p in _pool_pgs(cl, io):
            assert undone.reqid not in p.log.reqids()
        replies = []
        real_reply = prim.osd.reply_to
        prim.osd.reply_to = lambda req, msg: replies.append(
            (req.tid, msg.result)) if req.tid >= 1 << 40 \
            else real_reply(req, msg)
        again = undone.local_view()
        again.tid = (1 << 40) + 7
        again.src_name, again.src_addr = undone.src_name, undone.src_addr
        prim.queue_op(again)
        for _ in range(400):
            if replies:
                break
            await asyncio.sleep(0.01)
        assert replies == [(again.tid, 0)], replies
        assert prim.log.entries[-1].reqid == undone.reqid
        assert await io.read("hot") == payloads[-1]
        seen, bad = copies_not_holding(cl, io.pool_id, "hot",
                                       payloads[-1])
        assert seen == 3 and not bad, (seen, bad)
        await cl.stop()

    asyncio.run(run())


def test_pipelined_writes_split_three_three_at_k4_m2_roll_back():
    """k=4 m=2, two writes of one object in flight: the first reaches
    every shard, the second three of the six, and the interval
    changes with every OSD alive.  The second is on three and, had it
    taken the first away with it, the first would be on three as
    well: neither on four.  The three that took the second kept the
    first: it is on all six, and the object goes back to it."""
    async def run():
        cl = Cluster()
        admin = await cl.start(6)
        await admin.pool_create("s", pg_num=1, pool_type="erasure",
                                k=4, m=2)
        io = admin.open_ioctx("s")
        await io.write_full("o", b"acked" * 1000)
        pg = next(p for _o, p in _pool_pgs(cl, io) if p.is_primary())
        others = [o for o in pg.acting if o != pg.osd.whoami]
        first, second = b"first" * 900, b"second" * 800
        await _lose_writes_then_flap(
            cl, admin, io, "o", [first, second],
            [set(), set(others[2:])], others[4])
        assert await asyncio.wait_for(io.read("o"), 30.0) == first
        seen, bad = copies_not_holding(cl, io.pool_id, "o", first)
        assert seen == 6 and not bad, (seen, bad)
        assert await _settled(cl, io, "o")
        await cl.stop()

    asyncio.run(run())


def test_an_object_only_unacked_writes_made_rolls_back_to_nothing():
    """The object's FIRST write never reached k shards: there is no
    version to go back to, and none was ever acked.  It rolls back to
    not being there (a delete in the log), on every shard, and can be
    written afterwards."""
    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("n", pg_num=1, pool_type="erasure",
                                k=2, m=1)
        io = admin.open_ioctx("n")
        await io.write_full("warm", b"w")
        pg = next(p for _o, p in _pool_pgs(cl, io) if p.is_primary())
        others = [o for o in pg.acting if o != pg.osd.whoami]
        await _lose_writes_then_flap(
            cl, admin, io, "fresh", [b"1" * 600, b"2" * 700],
            [set(others), set(others)], others[0])
        with pytest.raises(ObjectOperationError) as err:
            await asyncio.wait_for(io.read("fresh"), 30.0)
        assert err.value.retcode == -errno.ENOENT
        for osd, p in _pool_pgs(cl, io):
            assert not [s for s in osd.store.collection_list(p.cid)
                        if s.name == "fresh"]
        prim = next(p for _o, p in _pool_pgs(cl, io) if p.is_primary())
        last = prim.log.latest_entry_for("fresh")
        assert last.op == LOG_ROLLBACK and last.is_delete()
        await io.write_full("fresh", b"now" * 100)
        assert await io.read("fresh") == b"now" * 100
        assert await io.read("warm") == b"w"
        await cl.stop()

    asyncio.run(run())

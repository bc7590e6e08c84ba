"""The Objecter's op budget (objecter_inflight_ops /
objecter_inflight_op_bytes; Objecter.cc _take_op_budget, _throttle_op,
calc_op_budget) on a live toy cluster with a tiny budget: what is in
flight never passes it, an op that finds it used up waits in submit
order and is counted, writes to one object keep their order through a
wait, an op larger than the whole budget passes alone, every exit gives
the budget back, and the wait is the tracer's `client_throttle_wait`,
read from no clock while `op_tracing` is off."""

import asyncio
from types import SimpleNamespace

import pytest

from ceph_tpu.client.objecter import ObjectOperationError, _InFlight
from ceph_tpu.common import tracer as tracer_mod
from ceph_tpu.osd.messages import (OP_GETXATTR, OP_READ, OP_WRITE,
                                   OP_WRITEFULL, OP_ZERO, OSDOp)
from ceph_tpu.qa.cluster import Cluster, make_ctx


def budget_ctx(ops, nbytes, tracing=False):
    def make(name):
        ctx = make_ctx(name)
        ctx.config.set("ms_local_delivery", True)
        if name.startswith("client"):
            ctx.config.set("objecter_inflight_ops", ops)
            ctx.config.set("objecter_inflight_op_bytes", nbytes)
            ctx.config.set("op_tracing", tracing)
        return ctx
    return make


class Wire:
    """The client's sends to OSDs, held back until `open()`: while it
    is shut every op that took budget stays in flight.  `ops` are the
    ops in the order in which they entered flight (their first `_send`),
    `sent` their names."""

    def __init__(self, objecter):
        self.ob, self.held, self.ops, self.shut = objecter, [], [], False
        self.peak_ops = self.peak_bytes = 0
        self._send, self._wire = objecter._send, \
            objecter.messenger.send_message
        objecter._send = self.send
        objecter.messenger.send_message = self.wire

    def send(self, op):
        if not op.sent:
            self.ops.append(op)
        self.peak_ops = max(self.peak_ops, len(self.ob._inflight))
        self.peak_bytes = max(self.peak_bytes, sum(
            o.budget for o in self.ob._inflight.values()))
        self._send(op)

    @property
    def sent(self):
        return [op.oid for op in self.ops]

    def wire(self, m, addr, peer_type=None, **kw):
        if self.shut and peer_type == "osd":
            self.held.append((m, addr))
        else:
            self._wire(m, addr, peer_type=peer_type, **kw)

    def open(self):
        self.shut = False
        for m, addr in self.held:
            self._wire(m, addr, peer_type="osd")
        self.held = []


def with_cluster(body, ops=4, nbytes="1m", tracing=False, osds=3):
    async def run():
        cl = Cluster(ctx_factory=budget_ctx(ops, nbytes, tracing))
        try:
            admin = await cl.start(osds)
            await admin.pool_create("rp", pg_num=4)
            io = admin.open_ioctx("rp")
            await io.write_full("warm", b"x")       # pool is active
            await asyncio.wait_for(body(cl, admin, io), 60.0)
        finally:
            await cl.stop()
    asyncio.run(run())


def line(ob):
    """The ops that wait for budget, in the order of their lines: the
    op budget's first (an op in the byte budget's line holds an op
    already)."""
    return [w[2].args[0] for thr in (ob._byte_budget, ob._op_budget)
            for w in thr._waiters if not w[0].done()]


async def settle(turns=20):
    for _ in range(turns):
        await asyncio.sleep(0)


def test_cost_of_an_op_is_its_write_data_or_the_length_it_reads():
    def cost(*ops):
        return _InFlight(1, "o", None, list(ops), None).budget
    assert cost(OSDOp(OP_WRITEFULL, length=5, data=b"12345")) == 5
    assert cost(OSDOp(OP_WRITE, offset=9, length=3, data=b"abc")) == 3
    assert cost(OSDOp(OP_READ, offset=4, length=4096)) == 4096
    assert cost(OSDOp(OP_READ)) == 0            # "whole object": unknown
    assert cost(OSDOp(OP_ZERO, length=1 << 20)) == 0   # moves no data
    assert cost(OSDOp(OP_GETXATTR, name="a")) == 0
    assert cost(OSDOp(OP_WRITE, length=2, data=b"ab"),
                OSDOp(OP_READ, length=10)) == 12


def test_in_flight_never_passes_the_budget_and_waits_are_fifo():
    async def body(cl, admin, io):
        ob = admin.objecter
        assert ob._op_budget.max == 4 and ob._byte_budget.max == 1 << 20
        wire = Wire(ob)
        waits0, names = ob.throttle_waits, [f"o{i}" for i in range(12)]
        wire.shut = True
        tasks = [asyncio.ensure_future(io.write_full(n, n.encode() * 9))
                 for n in names]
        await settle()
        # four hold the budget; eight wait, in the order they came
        assert wire.sent == names[:4] and len(ob._inflight) == 4
        assert [o.oid for o in line(ob)] == names[4:]
        assert ob.throttle_waits - waits0 == 8
        assert ob._op_budget.cur == 4
        wire.open()
        await asyncio.gather(*tasks)
        assert wire.sent == names               # FIFO through the wait
        assert wire.peak_ops == 4 == ob.inflight_ops_peak
        assert not line(ob) and not ob._inflight
        assert ob._op_budget.cur == 0 and ob._byte_budget.cur == 0
        for n in names:
            assert await io.read(n) == n.encode() * 9
        # a calm client: no further wait is counted
        assert ob.throttle_waits - waits0 == 8
    with_cluster(body)


def test_byte_budget_counts_write_data_and_read_lengths():
    async def body(cl, admin, io):
        ob = admin.objecter
        await io.write_full("big", b"r" * 3000)
        wire = Wire(ob)
        wire.shut = True
        waits0 = ob.throttle_waits
        # 4,096 bytes: a 3,000 byte write and a 1,000 byte read fit,
        # the next 200 byte write does not, nor (FIFO) the 10 byte read
        # behind it, though that one alone would
        t = [asyncio.ensure_future(io.write_full("w1", b"a" * 3000)),
             asyncio.ensure_future(io.read("big", length=1000)),
             asyncio.ensure_future(io.write_full("w2", b"b" * 200)),
             asyncio.ensure_future(io.read("big", length=10))]
        await settle()
        assert ob._byte_budget.cur == 4000 and len(ob._inflight) == 2
        assert [o.budget for o in line(ob)] == [200, 10]
        assert ob._op_budget.cur == 4       # each holds an op already
        assert ob.throttle_waits - waits0 == 2
        wire.open()
        got = await asyncio.gather(*t)
        assert got[1] == b"r" * 1000 and got[3] == b"r" * 10
        assert wire.peak_bytes <= 4096 and ob.inflight_bytes_peak == 4000
        assert ob._byte_budget.cur == 0 and ob._op_budget.cur == 0
    with_cluster(body, ops=64, nbytes=4096)


def test_same_object_writes_keep_their_order_through_a_wait():
    async def body(cl, admin, io):
        ob = admin.objecter
        wire = Wire(ob)
        wire.shut = True
        # 40 one-byte writes to two objects, two at a time in flight;
        # arrivals keep coming while the line moves
        order = [("a" if i % 3 else "b", bytes([65 + i])) for i in range(40)]
        tasks = []
        for i, (name, data) in enumerate(order):
            tasks.append(asyncio.ensure_future(io.write_full(name, data)))
            if i == 10:
                await settle()
                wire.open()
            if i > 10 and i % 4 == 0:
                await asyncio.sleep(0)
        acks = []
        for i, t in enumerate(tasks):
            t.add_done_callback(lambda _t, i=i: acks.append(i))
        await asyncio.gather(*tasks)
        assert [(op.oid, op.ops[0].data) for op in wire.ops] == order
        assert ob.throttle_waits >= 30 and wire.peak_ops <= 2
        # acked per object in the order submitted, and the last
        # submitted write is what the object holds
        for name in "ab":
            mine = [i for i in acks if order[i][0] == name]
            assert mine == sorted(mine)
            last = [d for n, d in order if n == name][-1]
            assert await io.read(name) == last
    with_cluster(body, ops=2)


def test_an_op_larger_than_the_budget_passes_alone():
    async def body(cl, admin, io):
        ob = admin.objecter
        wire = Wire(ob)
        wire.shut = True
        big = [asyncio.ensure_future(io.write_full(f"big{i}", b"B" * 5000))
               for i in range(2)]
        small = asyncio.ensure_future(io.write_full("small", b"s" * 10))
        await settle()
        # the first passes because nothing else holds budget; the second
        # and the small one behind it wait
        assert [o.oid for o in ob._inflight.values()] == ["big0"]
        assert [o.oid for o in line(ob)] == ["big1", "small"]
        wire.open()
        await asyncio.gather(*big, small)
        assert wire.sent == ["big0", "big1", "small"]
        assert wire.peak_ops == 1               # each big one was alone
        assert await io.read("big1") == b"B" * 5000
        assert ob._byte_budget.cur == 0
    with_cluster(body, ops=8, nbytes=1024)


def test_budget_is_returned_on_error_timeout_and_cancel():
    async def body(cl, admin, io):
        ob = admin.objecter

        def idle():
            return (ob._op_budget.cur, ob._byte_budget.cur,
                    len(ob._inflight), len(line(ob)))
        # an error reply
        with pytest.raises(ObjectOperationError):
            await io.read("no_such_object", length=8)
        assert idle() == (0, 0, 0, 0)
        # an error on the way out (nothing was sent)
        real = ob._send

        def broken(op):
            raise RuntimeError("no way out")
        ob._send = broken
        with pytest.raises(RuntimeError, match="no way out"):
            await io.write_full("e", b"e" * 10)
        ob._send = real
        assert idle() == (0, 0, 0, 0)
        # a timeout in flight, and one in the line behind it
        wire = Wire(ob)
        wire.shut = True
        slow = [asyncio.ensure_future(ob.op_submit(
            f"t{i}", io._loc(), [OSDOp(OP_WRITEFULL, length=4,
                                       data=b"tttt")], timeout=0.3))
            for i in range(3)]
        await settle()
        assert idle() == (2, 8, 2, 1)
        for t in slow:
            with pytest.raises(asyncio.TimeoutError):
                await t
        assert idle() == (0, 0, 0, 0)
        # a cancel in flight and a cancel in the line; the op behind the
        # cancelled ones is admitted
        tasks = [asyncio.ensure_future(io.write_full(f"c{i}", b"cc"))
                 for i in range(4)]
        await settle()
        assert idle() == (2, 4, 2, 2)
        tasks[0].cancel()
        tasks[2].cancel()
        await settle()
        assert [o.oid for o in ob._inflight.values()] == ["c1", "c3"]
        assert idle() == (2, 4, 2, 0)
        wire.open()     # c0's message leaves too: its reply finds no op
        await asyncio.gather(tasks[1], tasks[3])
        assert idle() == (0, 0, 0, 0)
        assert await io.read("c3") == b"cc"
    with_cluster(body, ops=2)


def test_leaving_the_byte_line_gives_the_op_slot_back():
    async def body(cl, admin, io):
        ob = admin.objecter
        wire = Wire(ob)
        wire.shut = True
        t = [asyncio.ensure_future(io.write_full("w1", b"a" * 3000)),
             asyncio.ensure_future(io.write_full("w2", b"b" * 2000)),
             asyncio.ensure_future(io.write_full("w3", b"c" * 500))]
        await settle()
        # w2 holds an op and waits for bytes; w3, which alone would
        # fit, stands behind it
        stats = ob.budget_stats()
        assert (stats["inflight_ops"], stats["inflight_bytes"]) == (3, 3000)
        assert [o.oid for o in line(ob)] == ["w2", "w3"]
        t[1].cancel()
        await settle()
        assert ob.budget_stats()["inflight_ops"] == 2
        wire.open()
        await asyncio.gather(t[0], t[2])
        stats = ob.budget_stats()
        assert (stats["inflight_ops"], stats["inflight_bytes"]) == (0, 0)
        assert stats["inflight_bytes_peak"] == 3000
        assert stats["throttle_waits"] == ob.throttle_waits == 2
        assert wire.sent == ["w1", "w3"]        # w2 never left
    with_cluster(body, ops=8, nbytes=4096)


def test_on_grant_runs_in_the_step_of_the_grant():
    from ceph_tpu.common.throttle import AsyncThrottle

    async def body():
        thr, seen = AsyncThrottle("t", 2), []
        held = [thr.get_later(1, lambda i=i: seen.append(i))
                for i in range(4)]
        assert seen == [0, 1] and thr.cur == 2  # room: granted at once
        thr.put(1)
        # 2 was granted INSIDE the put; a newcomer finds no room
        assert seen == [0, 1, 2] and not thr.get_or_fail(1)
        held[3].cancel()                        # leaves the line
        thr.put(1)
        assert seen == [0, 1, 2] and thr.cur == 1 and not thr._waiters
        thr.get_later(1)
        late = thr.get_later(1, lambda: seen.append("late"))
        assert "late" not in seen
        thr.open_wide()                         # teardown admits all
        assert seen[-1] == "late" and late.done()
    asyncio.run(body())


def test_error_while_admitting_from_the_line_reaches_its_own_submit():
    async def body(cl, admin, io):
        ob = admin.objecter
        wire = Wire(ob)
        wire.shut = True
        first = asyncio.ensure_future(io.write_full("f", b"f"))
        second = asyncio.ensure_future(io.write_full("g", b"g"))
        await settle()
        real = ob._send

        def broken(op):
            if op.oid == "g":
                raise RuntimeError("no way out")
            real(op)
        ob._send = broken
        wire.open()
        await first                             # not hurt by g's fault
        with pytest.raises(RuntimeError, match="no way out"):
            await second
        assert ob._op_budget.cur == 0 and not line(ob)
    with_cluster(body, ops=1)


def test_throttle_wait_is_stamped_only_on_a_wait():
    assert "client_throttle_wait" in tracer_mod.AUX_STAGES
    assert "client_throttle_wait" not in tracer_mod.CHAIN_STAGES

    async def body(cl, admin, io):
        ob = admin.objecter

        def stage(name):
            return cl.stage_histograms().get(name)
        await asyncio.gather(*[io.write_full(f"calm{i}", b"c")
                               for i in range(2)])
        assert stage("client_throttle_wait") is None    # nobody waited
        n0 = stage("client_submit").count
        wire = Wire(ob)
        wire.shut = True
        tasks = [asyncio.ensure_future(io.write_full(f"w{i}", b"w"))
                 for i in range(5)]
        await asyncio.sleep(0.05)
        wire.open()
        await asyncio.gather(*tasks)
        h = stage("client_throttle_wait")
        assert h.count == 3 == ob.throttle_waits
        assert h.sum >= 3 * 0.05
        # the span starts when the budget is held: the wait is in front
        # of client_submit and outside op_total
        sub = stage("client_submit")
        assert sub.count == n0 + 5
        assert stage("op_total").sum < h.sum + 5 * 0.05 + 5.0
    with_cluster(body, ops=2, tracing=True)


def test_no_clock_is_read_for_the_budget_with_op_tracing_off(monkeypatch):
    def no_clock():
        raise AssertionError("clock read on the off path")

    async def body(cl, admin, io):
        ob = admin.objecter
        assert not admin.ctx.tracer.enabled
        monkeypatch.setattr(tracer_mod, "time", SimpleNamespace(
            monotonic=no_clock, thread_time=no_clock))
        wire = Wire(ob)
        wire.shut = True
        tasks = [asyncio.ensure_future(io.write_full(f"w{i}", b"w"))
                 for i in range(5)]
        await settle()
        wire.open()
        await asyncio.gather(*tasks)
        assert ob.throttle_waits == 3
        assert tracer_mod.STAGE_GROUP not in admin.ctx.perf._groups
        monkeypatch.undo()
    with_cluster(body, ops=2)


def test_default_budget_is_cephs_and_zero_means_no_limit():
    from ceph_tpu.common.context import Context
    cfg = Context("client.x").config
    assert cfg["objecter_inflight_ops"] == 1024
    assert cfg["objecter_inflight_op_bytes"] == 100 << 20

    async def body(cl, admin, io):
        ob = admin.objecter
        wire = Wire(ob)
        wire.shut = True
        tasks = [asyncio.ensure_future(io.write_full(f"n{i}", b"n" * 100))
                 for i in range(50)]
        await settle()
        assert len(ob._inflight) == 50 and ob.throttle_waits == 0
        wire.open()
        await asyncio.gather(*tasks)
    with_cluster(body, ops=0, nbytes=0)

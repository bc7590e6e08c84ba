"""The loop's time account (common/tracer.py, PR 34): the loop sampler
times the loop's selector (`evloop_idle`: a `select` asked to block;
`evloop_poll`: a `select(0)` between ready callbacks), so that

    loop_wall = evloop_idle + evloop_poll + the callbacks' wall,

sections nest and record their SELF time, a pipelined write's wait to
reply in order is the chain stage `reply_wait` (no longer a second
`dep_wait`), and with `op_tracing` off none of it is there: the loop's
selector is the one the loop was made with.

Nothing here is a number about speed: the tests hold the ACCOUNT to
what they themselves slept and spun."""

import asyncio
import sys
import threading
import time
from types import SimpleNamespace

import pytest

from ceph_tpu.common import tracer as tracer_mod
from ceph_tpu.common.context import Context
from ceph_tpu.common.tracer import (AUX_STAGES, CHAIN_STAGES,
                                    EVLOOP_STAGES, LOOP_SAMPLE_PERIOD,
                                    LOOP_STAGES)

TICK = LOOP_SAMPLE_PERIOD


def traced_ctx(name="osd.0"):
    ctx = Context(name)
    ctx.config.set("op_tracing", True)
    return ctx


def sums(ctx):
    return {name: (h.count, h.sum)
            for name, h in ctx.tracer.hist.histograms().items()}


def spin(seconds):
    t0 = time.monotonic()
    while time.monotonic() - t0 < seconds:
        pass
    return time.monotonic() - t0


async def started(ctx):
    """The first section of an enabled tracer starts its loop's
    sampler, and with it the selector's clock."""
    with ctx.tracer.section("loop_submit"):
        pass
    return asyncio.get_running_loop()


# ------------------------------------------------------ (a) the account
def test_the_account_closes_over_mixed_sleeping_and_spinning():
    async def run():
        ctx = traced_ctx()
        await started(ctx)
        spun = 0.0
        for _ in range(5):
            await asyncio.sleep(0.1)
            spun += spin(0.1)
        await asyncio.sleep(1.5 * TICK)     # the last tick lands
        return sums(ctx), spun

    got, spun = asyncio.run(run())
    wall, idle, poll = (got[s][1] for s in (
        "loop_wall", "evloop_idle", "evloop_poll"))
    assert got["evloop_idle"][0] >= 5 and got["evloop_poll"][0] >= 5
    callbacks = wall - idle - poll
    # what is neither idle nor poll is what the callbacks took: the
    # spinning, to within the sampler's tick (loop_wall ends at a tick,
    # the selector's clock at its last return)
    assert abs(callbacks - spun) < TICK + 0.05, (callbacks, spun, got)
    assert idle == pytest.approx(0.5 + 1.5 * TICK, abs=TICK + 0.1)
    # and the CPU the loop burned is the spinning too
    assert got["loop_cpu"][1] == pytest.approx(spun, abs=TICK + 0.1)


def test_a_sleeping_loop_is_idle_and_a_spinning_loop_is_not():
    async def sleeper():
        ctx = traced_ctx()
        await started(ctx)
        for _ in range(6):
            await asyncio.sleep(0.1)
        await asyncio.sleep(0.5 * TICK)
        return sums(ctx)

    async def spinner():
        ctx = traced_ctx()
        await started(ctx)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 0.6 + 0.5 * TICK:
            spin(0.002)
            await asyncio.sleep(0)          # ready again at once
        return sums(ctx)

    got = asyncio.run(sleeper())
    assert got["evloop_idle"][1] / got["loop_wall"][1] > 0.9, got
    got = asyncio.run(spinner())
    idle = got.get("evloop_idle", (0, 0.0))[1]
    assert idle / got["loop_wall"][1] < 0.1, got
    # every pass of a loop that is never idle polls
    assert got["evloop_poll"][0] > 50


def test_a_thread_that_holds_the_gil_raises_the_polls_mean():
    """`select(0)` gives the GIL up; a thread that spins in Python
    takes it and keeps it for a switch interval, and the wait to win it
    back is what `evloop_poll` reads on top of the call itself.  (The
    call here is made 2 ms long, GIL released, so that the other thread
    wins the race for the GIL on a loaded machine too: a real
    `select(0)` is over before it wakes, most of the time.)"""
    class Slow:
        def __init__(self, inner):
            self.inner = inner

        def select(self, timeout=None):
            if timeout == 0:
                time.sleep(0.002)
            return self.inner.select(timeout)

        def __getattr__(self, name):
            return getattr(self.inner, name)

    async def polls(with_hog):
        ctx = traced_ctx()
        loop = await started(ctx)
        loop._selector._inner = Slow(loop._selector._inner)
        stop = threading.Event()

        def hog():
            n = 0
            while not stop.is_set():
                n += 1

        th = threading.Thread(target=hog, daemon=True)
        if with_hog:
            th.start()
        try:
            for _ in range(40):
                await asyncio.sleep(0)
        finally:
            stop.set()
            if with_hog:
                th.join()
            loop._selector._inner = loop._selector._inner.inner
        n, secs = sums(ctx)["evloop_poll"]
        return secs / n

    alone = asyncio.run(polls(False))
    beside = asyncio.run(polls(True))
    interval = sys.getswitchinterval()
    assert alone >= 0.002, alone
    assert beside > alone + interval / 2, (alone, beside)


# ------------------------------------------------------- (b) off is off
def test_off_the_loops_selector_is_the_one_it_was_made_with():
    async def run():
        loop = asyncio.get_running_loop()
        made_with = loop._selector
        ctx = Context("osd.1")              # op_tracing false
        with ctx.tracer.section("loop_submit"):
            pass
        assert ctx.tracer.start() is None
        assert loop._selector is made_with
        ctx.config.set("op_tracing", True)
        with ctx.tracer.section("loop_submit"):
            pass
        wrapper = loop._selector
        assert isinstance(wrapper, tracer_mod._TimedSelector)
        assert wrapper._inner is made_with
        # everything but select() is the selector's own
        assert wrapper.get_map() is made_with.get_map()
        await asyncio.sleep(0.01)
        ctx.config.set("op_tracing", False)
        assert loop._selector is made_with          # at once
        assert loop not in tracer_mod._sampled_loops
        await asyncio.sleep(1.5 * TICK)             # the timer still out
        assert loop._selector is made_with
        before = sums(ctx)
        await asyncio.sleep(0.05)
        assert sums(ctx) == before                  # nothing records
        # on again: one wrapper, around the same selector, not two
        ctx.config.set("op_tracing", True)
        with ctx.tracer.section("loop_submit"):
            pass
        assert loop._selector._inner is made_with
        await asyncio.sleep(1.5 * TICK)
        assert loop._selector._inner is made_with
        assert len(ctx.tracer._samplers) == 1
        ctx.config.set("op_tracing", False)
        assert loop._selector is made_with and not ctx.tracer._samplers

    asyncio.run(run())


def test_a_second_tracer_on_a_sampled_loop_adds_no_wrapper():
    async def run():
        loop = asyncio.get_running_loop()
        made_with = loop._selector
        a, b = traced_ctx("osd.0"), traced_ctx("osd.1")
        await started(a)
        await started(b)
        assert loop._selector._inner is made_with
        assert len(a.tracer._samplers) == 1 and not b.tracer._samplers
        await asyncio.sleep(0.02)
        assert "evloop_idle" in sums(a) and "evloop_idle" not in sums(b)
        a.config.set("op_tracing", False)
        assert loop._selector is made_with
        # the next enabled tracer to record on the loop takes it over
        await started(b)
        assert loop._selector._inner is made_with
        assert len(b.tracer._samplers) == 1
        b.config.set("op_tracing", False)
        assert loop._selector is made_with

    asyncio.run(run())


def test_the_deterministic_loop_keeps_its_own_selector():
    from ceph_tpu.devtools import schedule as sched

    async def main():
        loop = asyncio.get_running_loop()
        virtual = loop._selector
        assert isinstance(virtual, sched._VirtualSelector)
        ctx = traced_ctx()
        for _ in range(3):
            with ctx.tracer.section("loop_submit"):
                pass
            await asyncio.sleep(TICK)
            assert loop._selector is virtual
        assert not ctx.tracer._samplers
        return sums(ctx)

    got, loop = sched.run_deterministic(main, seed=7)
    assert got["loop_submit"][0] == 3
    assert not set(got) & (set(EVLOOP_STAGES) | {"loop_wall", "loop_cpu"})


def test_the_selectors_clock_reads_nothing_when_off(monkeypatch):
    async def run():
        ctx = Context("osd.2")

        def no_clock():
            raise AssertionError("clock read on the off path")

        # the tracer's own `time`, not the module the loop reads too
        monkeypatch.setattr(tracer_mod, "time", SimpleNamespace(
            monotonic=no_clock, thread_time=no_clock))
        for name in LOOP_STAGES[:-2]:
            with ctx.tracer.section(name) as got:
                assert got is None
        await asyncio.sleep(0.01)
        assert tracer_mod.STAGE_GROUP not in ctx.perf._groups

    asyncio.run(run())


# ----------------------------------------------------------- (c) nesting
def test_a_nested_sections_time_is_in_the_inner_name_only():
    ctx = traced_ctx()
    tr = ctx.tracer
    t0 = time.monotonic()
    with tr.section("loop_submit"):
        spin(0.02)
        with tr.section("loop_msg"):
            spin(0.03)
            with tr.section("loop_dispatch"):
                spin(0.01)
        with tr.section("loop_msg"):
            spin(0.01)
        spin(0.01)
    outer_wall = time.monotonic() - t0
    got = sums(ctx)
    assert got["loop_submit"][0] == 1 and got["loop_msg"][0] == 2
    assert got["loop_submit"][1] == pytest.approx(0.03, abs=0.005)
    assert got["loop_msg"][1] == pytest.approx(0.04, abs=0.005)
    assert got["loop_dispatch"][1] == pytest.approx(0.01, abs=0.005)
    # nothing is counted twice: the names sum to the outer wall
    total = sum(got[s][1] for s in ("loop_submit", "loop_msg",
                                    "loop_dispatch"))
    assert total == pytest.approx(outer_wall, abs=0.002)
    assert not tracer_mod._open_section


def test_sections_on_two_threads_keep_apart_and_an_error_unwinds():
    ctx = traced_ctx()
    tr = ctx.tracer
    inside = threading.Event()
    go_on = threading.Event()

    def other():
        with tr.section("seam_fold"):
            inside.set()
            go_on.wait(5.0)

    th = threading.Thread(target=other)
    with tr.section("loop_submit"):
        th.start()
        assert inside.wait(5.0)
        with pytest.raises(ValueError):
            with tr.section("loop_msg"):
                raise ValueError("boom")
        go_on.set()
        th.join()
        spin(0.005)
    got = sums(ctx)
    # the other thread's section is no child of this thread's
    assert got["loop_submit"][1] + got["loop_msg"][1] >= 0.005
    assert got["seam_fold"][0] == 1 and got["loop_msg"][0] == 1
    assert not tracer_mod._open_section


# ------------------------------------------------------ the declarations
def test_the_new_stages_are_declared_where_the_readers_look():
    for name in ("loop_read", "loop_sub_read", "loop_admit", "loop_msg",
                 "loop_client_reply", "loop_pump"):
        assert name in LOOP_STAGES and name in AUX_STAGES
    assert EVLOOP_STAGES == ("evloop_idle", "evloop_poll")
    for name in EVLOOP_STAGES + ("read_gather",):
        assert name in AUX_STAGES and name not in CHAIN_STAGES
        # not work of the loop: benchmark/spans.py sums every `loop_*`
        # stage but the sampler's two into the named share
        assert not name.startswith("loop_")
    at = CHAIN_STAGES.index("reply_wait")
    assert CHAIN_STAGES[at - 1] == "op_exec"
    assert CHAIN_STAGES[at + 1] == "ack_delivery"


# ------------------------------------------------- reply_wait, on a PG
@pytest.mark.parametrize("pool", ["ec_k2m1", "replicated"])
def test_a_pipelined_writes_wait_to_reply_is_reply_wait_not_dep_wait(pool):
    """Writes of ONE object submitted at once pipeline (PR 33): each
    waits before it runs (`dep_wait`, once per op) and, if it is done
    before the writes ahead of it, to REPLY in order: `reply_wait`."""
    from ceph_tpu.qa.cluster import Cluster, make_ctx

    def ctx_f(name):
        c = make_ctx(name)
        c.config.set("op_tracing", True)
        return c

    async def run():
        cl = Cluster(ctx_factory=ctx_f)
        admin = await cl.start(3)
        kw = dict(pool_type="erasure", k=2, m=1) if pool == "ec_k2m1" \
            else {}
        await admin.pool_create("pw", pg_num=1, **kw)
        io = admin.open_ioctx("pw")
        # (of the later writes' size: an EC pool's first encode of a
        # shape compiles, and that would outlast the late acks below)
        await io.write_full("hot", b"seed" * 1025)
        before = {n: h.count for n, h in cl.stage_histograms().items()}
        # the first write's acks come late: the writes behind it are
        # done sooner and wait to reply
        primary = next(pg for osd in cl.osds.values()
                       for pg in osd.pgs.values()
                       if pg.pool_id == io.pool_id and pg.is_primary())
        real, calls = primary.backend._await_acks, []
        five_done = asyncio.Event()

        async def first_is_late(fut, timeout=None):
            calls.append(fut)
            if len(calls) == 1:
                # held until five writes behind it have their acks
                await asyncio.wait_for(five_done.wait(), 30.0)
                return await real(fut, timeout)
            ok = await real(fut, timeout)
            if len(calls) >= 6:
                five_done.set()
            return ok

        primary.backend._await_acks = first_is_late
        payloads = [bytes([i + 1]) * (4096 + i) for i in range(24)]
        await asyncio.wait_for(asyncio.gather(
            *[io.write_full("hot", p) for p in payloads]), 60.0)
        assert await io.read("hot") == payloads[-1]
        after = {n: h.count for n, h in cl.stage_histograms().items()}
        pipelined = sum(int(o.perf_window.dump()["writes_pipelined"])
                        for o in cl.osds.values())
        await cl.stop()
        return before, after, pipelined

    before, after, pipelined = asyncio.run(run())
    ops = 24 + 1
    assert pipelined > 0
    assert after["op_total"] - before["op_total"] == ops
    # one cut of dep_wait per admitted op, never a second
    assert after["dep_wait"] - before.get("dep_wait", 0) == ops
    waited = after.get("reply_wait", 0) - before.get("reply_wait", 0)
    assert 0 < waited < ops, waited

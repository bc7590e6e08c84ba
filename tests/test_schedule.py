"""Deterministic schedule explorer (ISSUE 9).

Coverage map:
  * determinism — same seed => byte-identical interleaving trace hash
    across two runs (the replay contract every pinned-seed regression
    test depends on);
  * bounded exploration — >= 64 seeded schedules PLUS every enumerated
    commit-thread crash point on the ec mini-workload, zero invariant
    findings on the live tree;
  * seeded-bug fixtures — the two reintroduced historical hazards
    (pre-PR-5 out-of-order version assignment; commit callbacks before
    the durability barrier) are each caught within a bounded schedule
    budget;
  * the sequencer EAGAIN path under a forced adversarial schedule —
    a windowed op that observes a mid-flight interval change releases
    its slot, dispatch-throttle and OpTracker accounting exactly once;
  * the LoopStallMonitor wired to the deterministic loop (virtual
    attach): exhaustive per-callback stall attribution in sim mode.
"""

import asyncio
import errno
import time
from collections import Counter

import pytest

from ceph_tpu.common import lockdep
from ceph_tpu.devtools.schedule import (
    CRASH_POINTS, AdversarialScheduler, ScheduleController,
    explore, run_deterministic, run_ec_mini,
)

# ------------------------------------------------------------ determinism


def test_same_seed_identical_trace_hash():
    kw = dict(pool_type="replicated", n_osds=2, n_objects=4, iodepth=4)
    r1 = run_ec_mini(seed=3, **kw)
    r2 = run_ec_mini(seed=3, **kw)
    assert r1.ok, r1.render()
    assert r2.ok, r2.render()
    assert r1.steps == r2.steps
    assert r1.trace_hash == r2.trace_hash
    # and the hash actually covers the schedule: a different seed's
    # walk through the same workload takes different decisions
    r3 = run_ec_mini(seed=4, **kw)
    assert r3.ok, r3.render()
    assert (r3.trace_hash != r1.trace_hash) or (r3.steps != r1.steps)


def test_virtual_time_no_wall_clock_sleeps():
    """A FAST_CFG cluster boot + write burst sleeps for many seconds of
    cluster time (election, heartbeats, boot retry loops); under the
    deterministic loop that is all VIRTUAL — the run must finish in a
    fraction of the simulated time."""
    t0 = time.monotonic()
    rep = run_ec_mini(seed=0, controller=ScheduleController(),
                      pool_type="replicated", n_osds=2,
                      n_objects=4, iodepth=4)
    wall = time.monotonic() - t0
    assert rep.ok, rep.render()
    # generous bound: simulated boot alone waits multiple seconds of
    # timer time; the wall bound only fails if sleeps became real
    assert wall < 30.0, wall


# --------------------------------------------------- bounded exploration


def test_bounded_exploration_ec_mini_is_clean():
    """>= 64 seeded schedules + every enumerated crash point (all three
    PR-1 fault-injection hooks, occurrence-indexed) on the ec_e2e
    mini-workload: the live tree must hold every machine-checked
    invariant under every explored interleaving."""
    rep = explore(64, max_crash_occurrences=2)
    assert len(rep.schedules) >= 64
    assert {p for _osd, p, _occ in rep.crash_points} == set(CRASH_POINTS), \
        rep.crash_points
    assert rep.crash_runs
    assert not rep.failures, rep.render_failures()


def test_two_shard_sim_exploration_is_clean():
    """ISSUE 10 satellite: the EC mini-workload at osd_op_num_shards=2
    under SIM — shard pumps are ordinary tasks on the seeded
    deterministic loop, so every explored schedule is a different
    interleaving of the two shard threads' work.  The full PR-9
    checklist (dense pglog, durability-before-ack, balanced
    slots/throttle/rings, zero local-path encodes, no acked write
    lost) must hold across >= 64 schedules + every enumerated
    commit-thread crash point."""
    rep = explore(64, max_crash_occurrences=2, num_shards=2)
    assert len(rep.schedules) >= 64
    assert {p for _osd, p, _occ in rep.crash_points} == \
        set(CRASH_POINTS), rep.crash_points
    assert rep.crash_runs
    assert not rep.failures, rep.render_failures()
    # the sharded plane actually engaged: same seed replays identically
    r1 = run_ec_mini(seed=5, num_shards=2)
    r2 = run_ec_mini(seed=5, num_shards=2)
    assert r1.ok and r2.ok, r1.render() + r2.render()
    assert r1.trace_hash == r2.trace_hash


def test_kill_restart_exploration_cursor_invariants():
    """ISSUE 17 tentpole: an osd kill+restart event landing at
    seed-permuted points in >= 64 explored schedules (32 seeds x two
    kill depths), under the backfill-cursor canaries — no shard serves
    a read past its own durable cursor, no cursor regresses within an
    interval, and no acked write is lost across the kill + rebuild
    (the restarted OSD must CONVERGE before acked reads re-verify)."""
    rep = explore(32, with_crashes=False, with_kills=True)
    assert len(rep.kill_runs) >= 64, len(rep.kill_runs)
    assert all(r.kill is not None for r in rep.kill_runs)
    assert not rep.failures, rep.render_failures()


# ----------------------------------------------------- seeded-bug fixtures


def test_explorer_catches_out_of_order_version_assignment():
    from schedule_fixtures import out_of_order_version_assignment
    kw = dict(pool_type="replicated", n_osds=3, n_objects=8, iodepth=8)
    with out_of_order_version_assignment():
        caught = None
        for seed in range(16):          # bounded schedule budget
            rep = run_ec_mini(seed=seed, **kw)
            if any("dense" in f for f in rep.findings):
                caught = rep
                break
        assert caught is not None, \
            "explorer missed the out-of-order version hazard in 16 schedules"
    # and the fix holds: same workload, same seed, bug removed => clean
    rep = run_ec_mini(seed=caught.seed, **kw)
    assert rep.ok, rep.render()


def test_explorer_catches_commit_callbacks_before_durability():
    from schedule_fixtures import commit_callbacks_before_durability
    kw = dict(pool_type="replicated", n_osds=2, n_objects=4, iodepth=4)
    with commit_callbacks_before_durability():
        rep = run_ec_mini(seed=0, controller=ScheduleController(), **kw)
        assert any("ack before durability" in f for f in rep.findings), \
            rep.findings
        # with a crash armed at the first post-warm group the escaped
        # acks vouch for state the crash threw away
        rep2 = run_ec_mini(seed=0, controller=ScheduleController(),
                           crash=(0, "before_data_sync", 0), **kw)
        assert any("ack before durability" in f
                   for f in rep2.findings), rep2.findings
    rep3 = run_ec_mini(seed=0, controller=ScheduleController(), **kw)
    assert rep3.ok, rep3.render()


def test_explorer_catches_boolean_backfill_marker():
    """ISSUE 18 regression fixture: reintroduce the pre-cursor
    boolean backfill marker (a mid-copy EC shard claims authority over
    its whole namespace — absent names answer ENOENT, half-copies
    serve) and assert the backfill-cursor canaries catch it within a
    bounded kill-schedule budget.  A checker that never caught its
    target bug is a no-op with good marketing."""
    from schedule_fixtures import boolean_backfill_marker
    # recovery throttle keeps the backfill-cursor window open long
    # enough for degraded reads to race it
    kw = dict(n_objects=8, iodepth=8,
              cfg={"osd_recovery_max_active": 1,
                   "osd_recovery_sleep": 0.05})
    caught = None
    with boolean_backfill_marker():
        for seed in range(16):          # bounded schedule budget
            # fresh-store restart: full resync, so reads race a live
            # backfill-cursor window (a surviving store does log-based
            # recovery and never opens the window)
            rep = run_ec_mini(seed=seed, kill=(1, 1, True), **kw)
            if any("cursor hole served as ENOENT" in f
                   or "cursor read leak" in f
                   or "served as deletion" in f
                   for f in rep.findings):
                caught = rep
                break
        assert caught is not None, \
            "canaries missed the boolean-marker bug in 16 kill schedules"
    # and the fix holds: same schedule, bug removed => cursor-clean
    rep2 = run_ec_mini(seed=caught.seed, kill=(1, 1, True), **kw)
    assert not any("cursor" in f or "served as deletion" in f
                   for f in rep2.findings), rep2.render()


# ------------------------------ writes to one object pipeline (ISSUE 33)


@pytest.mark.parametrize("pool_type,num_shards", [
    ("erasure", 1), ("replicated", 1), ("erasure", 2)])
def test_two_writes_and_a_read_of_one_object_keep_their_order(
        pool_type, num_shards):
    """Two writes and a read of ONE object in the window at once, the
    second write pipelined behind the first's submit section: in every
    explored schedule the replies keep the per-object order, the read
    returns the write submitted before it, every copy ends as the last
    write, the pglog is dense and the window's slots balance.  On two
    shards the pumps apply the sub-writes and serve the read's
    sub-read off their rings (ISSUE 35)."""
    from schedule_fixtures import run_two_writes_and_a_read
    pipelined = 0
    for seed in range(24):
        rep, n = run_two_writes_and_a_read(seed, pool_type, num_shards)
        assert rep.ok, rep.render()
        pipelined += n
    # the schedules explored the mechanism, not a serial chain
    assert pipelined > 0
    r1, _ = run_two_writes_and_a_read(5, pool_type, num_shards)
    r2, _ = run_two_writes_and_a_read(5, pool_type, num_shards)
    assert r1.trace_hash == r2.trace_hash and r1.steps == r2.steps


# ------------------------------------- sequencer EAGAIN path (satellite)


def test_windowed_eagain_releases_accounting_exactly_once():
    """Forced adversarial schedule: admitted windowed ops are starved
    until a mid-flight interval change (replica marked down) flips the
    PG out of ACTIVE; every such op must abort EAGAIN and release its
    window slot, dispatch-throttle budget and OpTracker entry exactly
    once — then the resent ops complete against the new interval."""
    from ceph_tpu.qa.cluster import Cluster, make_sim_ctx

    box = {"pg": None, "armed": False}

    def starving() -> bool:
        pg = box["pg"]
        return bool(box["armed"] and pg is not None
                    and pg.state == "active")

    controller = AdversarialScheduler("PG._run_windowed",
                                      active=starving)

    async def main():
        cl = Cluster(ctx_factory=make_sim_ctx)
        admin = await cl.start(3)
        await admin.pool_create("ea", pg_num=1)
        io = admin.open_ioctx("ea")
        await io.write_full("warm", b"w")
        posd = next(o for o in cl.osds.values()
                    for pg in o.pgs.values()
                    if pg.pool_id == io.pool_id and pg.is_primary())
        pg = next(p for p in posd.pgs.values()
                  if p.pool_id == io.pool_id)
        box["pg"] = pg

        eagain_windowed = []
        orig_reply = posd.reply_to

        def counting_reply(req, msg):
            if getattr(msg, "result", 0) == -errno.EAGAIN \
                    and getattr(req, "_windowed", False):
                eagain_windowed.append(req.tid)
            orig_reply(req, msg)

        posd.reply_to = counting_reply
        finishes = Counter()
        orig_finish = posd.op_tracker.finish

        def counting_finish(op, event="done"):
            finishes[op.seq] += 1
            orig_finish(op, event)

        posd.op_tracker.finish = counting_finish

        box["armed"] = True

        async def noise():
            # keeps the ready queue non-empty while armed so the
            # starved victims are never the sole runnable candidate
            # (the scheduler's no-livelock fallback would run them);
            # sleep(0) reschedules via call_soon — no timer, so the
            # virtual clock stays frozen during the adversarial phase
            while box["armed"]:
                await asyncio.sleep(0)

        noise_task = asyncio.ensure_future(noise())
        blobs = {f"e{i:03d}": bytes([i]) * 1024 for i in range(24)}
        burst = asyncio.ensure_future(
            cl.write_burst(io, blobs, iodepth=24))
        # let admissions fill the window (the victims stay starved);
        # timer-free polling — time is frozen while noise runs
        for _ in range(5000):
            await asyncio.sleep(0)
            if pg.op_window.active >= 4:
                break
        assert pg.op_window.active >= 1, "window never filled"
        victim_osd = next(o for o in pg.acting if o != posd.whoami)
        cmd = asyncio.ensure_future(admin.mon_command(
            {"prefix": "osd down", "id": victim_osd}))
        # wait for the interval change to reach the primary: from here
        # the scheduler releases the starved windowed ops into a
        # not-active PG — the EAGAIN path under test
        for _ in range(20000):
            await asyncio.sleep(0)
            if pg.state != "active":
                break
        assert pg.state != "active", "interval change never landed"
        box["armed"] = False
        await noise_task
        await asyncio.wait_for(cmd, 60.0)
        await asyncio.wait_for(burst, 300.0)
        for name, data in blobs.items():
            assert await io.read(name) == data
        # quiesce, then the exactly-once accounting must balance
        for _ in range(200):
            if all(p.op_window.active == 0
                   for o in cl.osds.values() for p in o.pgs.values()) \
                    and not posd.op_tracker._inflight:
                break
            await asyncio.sleep(0.1)
        assert eagain_windowed, \
            "no windowed op ever observed the interval change"
        assert all(n == 1 for n in finishes.values()), finishes
        assert pg.op_window.balanced()
        for osd in cl.osds.values():
            thr = osd.messenger.dispatch_throttle
            assert thr is None or thr.cur == 0, \
                (osd.whoami, thr.cur)
        await cl.stop()
        return len(eagain_windowed)

    hits, _loop = run_deterministic(main, seed=0,
                                    controller=controller)
    assert hits >= 1


# -------------------------------------------- virtual stall monitor


def test_stall_monitor_virtual_attach_is_deterministic():
    """Under the deterministic loop the stall monitor times EVERY
    callback (no probe thread, no sampling luck): a synchronous 0.2s
    section with a 50ms budget is flagged with the owning tracer stage
    and the callback label, on every run."""
    from ceph_tpu.common.tracer import Span

    lockdep.reset()
    lockdep.enable()
    try:
        async def main():
            loop = asyncio.get_running_loop()
            mon = lockdep.LoopStallMonitor(loop, budget=0.05)
            mon.attach_virtual(loop)
            await asyncio.sleep(0.1)

            async def stall_task():
                span = Span(1, 1)
                span.cut("prepare")
                time.sleep(0.2)     # deliberate synchronous stall

            # a real task, so the finding names the offending coroutine
            await asyncio.get_running_loop().create_task(stall_task())
            await asyncio.sleep(0.1)
            mon.stop()
            return mon.stalls

        stalls, _loop = run_deterministic(main, seed=0)
        assert stalls >= 1
        rep = [e for e in lockdep.report() if e["kind"] == "loop_stall"]
        assert rep, lockdep.report()
        assert rep[0]["seconds"] >= 0.15
        assert rep[0]["stage"] == "prepare"
        assert "stall_task" in rep[0].get("callback", "")
    finally:
        lockdep.disable()
        lockdep.reset()

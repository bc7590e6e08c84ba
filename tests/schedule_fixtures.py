"""Seeded-bug fixtures for the deterministic schedule explorer.

Each context manager re-introduces one HISTORICAL write-path hazard so
tests/test_schedule.py can assert the explorer actually detects the
class of bug it exists for (a checker that has never caught its target
bug is a no-op with good marketing):

  * ``out_of_order_version_assignment`` — the pre-PR-5 structure:
    pglog version assigned BEFORE a suspension point, log appended
    after it.  Two concurrent ops on disjoint objects can then append
    out of assignment order, leaving the pglog non-dense (a gap the
    in-order group-commit callbacks silently mis-account).  PR 5 fixed
    this by assigning versions inside the await-free submit section
    (rule AF01 guards the structure; the explorer guards the BEHAVIOR).

  * ``commit_callbacks_before_durability`` — a commit thread that runs
    its completion callbacks before the group's durability barrier.
    Acks (client replies, repop acks, last_complete) then vouch for
    writes a crash at the PR-1 fault-injection points would lose —
    the phantom-ack class the data-before-metadata discipline exists
    to prevent.

Both patch at class level and restore on exit; apply them INSIDE the
test, around the run_ec_mini/explore call.

And one WORKLOAD the mini-workload lacks (its writes go to distinct
names): ``run_two_writes_and_a_read`` puts two writes and a read of
ONE object into the window at once, so every explored schedule is an
interleaving of a pipelined pair of writes (ISSUE 33: the second
follows the first from the end of its submit section) with the read
behind them, which waits for both acks, and with the shards' acks.
"""

from __future__ import annotations

import asyncio
import contextlib


@contextlib.contextmanager
def out_of_order_version_assignment():
    """Reintroduce the pre-PR-5 hazard on ReplicatedBackend: a private
    version counter advances at op ARRIVAL, then the op yields once
    before entering the (otherwise unchanged) submit path, which is
    forced to use the early-assigned version.  Any schedule that wakes
    two ops out of assignment order appends a gapped/misordered pglog
    — exactly what dense-version checking must catch."""
    from ceph_tpu.osd.backend import ReplicatedBackend
    from ceph_tpu.osd.messages import EVersion

    orig_submit = ReplicatedBackend.submit_client_write

    async def buggy(self, m):
        pg = self.pg
        cnt = pg.__dict__.get("_fx_version_counter")
        if cnt is None:
            cnt = pg.info.last_update.version
        cnt += 1
        pg.__dict__["_fx_version_counter"] = cnt
        forced = EVersion(pg.osd.osdmap.epoch, cnt)
        # the bug: a suspension point between version assignment and
        # the log append — another op can interleave here
        await asyncio.sleep(0)
        # force the original submit path to use the stale version.
        # The instance attribute shadows the class method and is
        # consumed synchronously (no await precedes next_version in
        # the replicated submit path), so concurrent ops cannot read
        # each other's forced version.
        pg.__dict__["next_version"] = lambda: forced
        try:
            return await orig_submit(self, m)
        finally:
            pg.__dict__.pop("next_version", None)

    ReplicatedBackend.submit_client_write = buggy
    try:
        yield
    finally:
        ReplicatedBackend.submit_client_write = orig_submit


@contextlib.contextmanager
def commit_callbacks_before_durability():
    """Reintroduce the phantom-ack hazard on KVSyncThread: completion
    callbacks fire BEFORE the group's data/kv barrier instead of
    after.  The commit-order observer flags every group ("ack before
    durability"); with a crash armed at before_data_sync the acks have
    already escaped for a group that never became durable."""
    from ceph_tpu.store.commit import KVSyncThread

    orig_commit = KVSyncThread._commit
    orig_complete = KVSyncThread._complete

    def buggy(self, group):
        orig_complete(self, group)          # BUG: acks first
        # suppress the in-order completion the real path runs after
        # durability — the callbacks must not fire twice
        self._complete = lambda g: None
        try:
            orig_commit(self, group)
        finally:
            del self._complete

    KVSyncThread._commit = buggy
    try:
        yield
    finally:
        KVSyncThread._commit = orig_commit


@contextlib.contextmanager
def boolean_backfill_marker():
    """Reintroduce the pre-PR-17 boolean-marker bug on ECBackend: a
    backfilling shard has no per-object cursor, only an all-or-nothing
    "complete" flag, so the sub-read path trusts the LOCAL object set
    over its whole namespace — an absent name inside the unfinished
    copy answers ENOENT (a data statement: "deleted") instead of
    EAGAIN (a topology statement: "ask elsewhere"), and a half-copied
    versionless blob is served as authoritative.  This is the
    historical ~1/6-seed EC model-checker phantom-deletion window the
    per-object last_backfill cursor closed; the explorer's
    watch_backfill_cursors canaries must flag any schedule that
    exercises it."""
    from ceph_tpu.osd.backend import ECBackend
    from ceph_tpu.osd.pglog import LB_MAX

    orig_read = ECBackend._handle_ec_sub_read
    orig_stale = ECBackend._stale_shards

    def buggy_read(self, m):
        pg = self.pg
        real = pg.info.last_backfill
        # the bug, replica half: reads see "backfilled or not" as a
        # boolean — a mid-copy shard claims cursor-complete authority.
        # The read handler is synchronous (no suspension point), so
        # the flip cannot leak into a concurrent op.
        pg.info.last_backfill = LB_MAX
        try:
            return orig_read(self, m)
        finally:
            pg.info.last_backfill = real

    def buggy_stale(self, oid):
        # the bug, primary half: with only a boolean marker the
        # primary has no per-object view of a backfill target — it
        # either drops the shard for the WHOLE copy or trusts it
        # wholesale.  The buggy replica claims completion, so the
        # boolean-era primary trusts it: skip both the cursor clause
        # AND the backfill-tracking missing set for targets mid-copy
        # (log-recovery peers keep their missing-set gate — that
        # plumbing predates the cursor)
        pg = self.pg
        out = set()
        for i, osd_id in enumerate(pg.acting):
            if osd_id in getattr(pg, "_backfilling", ()):
                continue
            pm = pg.peer_missing.get(osd_id)
            if pm is not None and oid in pm:
                out.add(i)
        return out

    ECBackend._handle_ec_sub_read = buggy_read
    ECBackend._stale_shards = buggy_stale
    try:
        yield
    finally:
        ECBackend._handle_ec_sub_read = orig_read
        ECBackend._stale_shards = orig_stale


def copies_not_holding(cl, pool_id: int, name: str, payload: bytes):
    """The stored copies of `name` that differ from what `payload`
    makes them: the payload itself on a replicated pool, on an EC pool
    the shard the codec encodes for that position.  Returns
    (copies seen, [description of each that differs])."""
    seen, bad = 0, []
    for osd in cl.osds.values():
        for pg in osd.pgs.values():
            if pg.pool_id != pool_id:
                continue
            want = payload
            if pg.pool.is_erasure():
                want = bytes(pg.backend.codec.encode(
                    set(range(pg.backend.n)), payload)[pg.pgid.shard])
            seen += 1
            if bytes(osd.store.read(pg.cid, pg.object_id(name))) != want:
                bad.append(f"osd.{osd.whoami} {pg.pgid}")
    return seen, bad


def run_two_writes_and_a_read(seed: int, pool_type: str = "erasure",
                              num_shards: int = 1):
    """(ScheduleReport, writes_pipelined) of one schedule of: write A,
    write B, read of ONE object, submitted in that order by one client
    without waiting for an answer, on a sim cluster (EC k=2 m=1 or
    replicated, one PG; `num_shards` > 1 runs the sharded plane, whose
    pumps serve sub-writes and sub-reads off their rings).  Findings:
    a reply out of the
    per-object order (B acked before A; the read answered before
    either, or with other bytes than B's), a final state other than B
    on the served path or on any stored copy, and everything
    check_cluster_invariants holds a quiesced cluster to (window slots
    balanced, pglog dense and in order, no leaked accounting)."""
    from ceph_tpu.devtools import schedule as sched
    from ceph_tpu.msg import payload as payload_mod
    from ceph_tpu.qa.cluster import Cluster

    report = sched.ScheduleReport(seed=seed)
    findings = report.findings
    a, b = b"A" * 1000, b"B" * 1500

    async def main():
        with sched.commit_observation() as obs, \
                sched.watch_last_complete(findings):
            pipelined = await body()
            findings.extend(obs.findings)
        return pipelined

    async def body():
        encode_base = payload_mod.counters()["msg_encode_calls"]
        cl = Cluster(ctx_factory=sched._sim_ctx_factory(num_shards))
        admin = await cl.start(3)
        kw = dict(pool_type="erasure", k=2, m=1) \
            if pool_type == "erasure" else {}
        await admin.pool_create("one", pg_num=1, **kw)
        io = admin.open_ioctx("one")
        await io.write_full("obj", b"seed")
        # answers in the order they REACH the client (its tasks wake
        # in whatever order the scheduler picks)
        answered = []
        dispatch = io.objecter.ms_dispatch

        def noting(msg):
            if getattr(msg, "tid", 0) > tid0 \
                    and getattr(msg, "result", -1) == 0:
                answered.append(msg.tid - tid0)
            return dispatch(msg)

        tid0 = io.objecter._tid
        io.objecter.ms_dispatch = noting

        async def write(data):
            await asyncio.wait_for(io.write_full("obj", data), 45.0)

        async def read():
            got = await asyncio.wait_for(io.read("obj"), 45.0)
            if got != b:
                findings.append(
                    f"read behind the writes returned {got[:1]!r} x "
                    f"{len(got)}, not the write submitted before it")

        async def submitted(coro):
            """Start `coro` and return once its op is submitted (the
            objecter gave it a tid): the scheduler permutes which
            ready task runs next, the ORDER of the three submits is
            the thing under test and must not be its to choose."""
            tid = io.objecter._tid
            task = asyncio.ensure_future(coro)
            while io.objecter._tid == tid and not task.done():
                await asyncio.sleep(0)
            return task

        await asyncio.gather(await submitted(write(a)),
                             await submitted(write(b)),
                             await submitted(read()))
        io.objecter.ms_dispatch = dispatch
        if answered != [1, 2, 3]:
            findings.append(
                f"answers out of submit order: {answered}")
        if await io.read("obj") != b:
            findings.append("final read is not the last write")
        await sched._quiesce(cl)
        for where in copies_not_holding(cl, io.pool_id, "obj", b)[1]:
            findings.append(f"{where} does not hold the last write")
        for osd in cl.osds.values():
            for pg in osd.pgs.values():
                if pg.pool_id != io.pool_id:
                    continue
                mine = [e.version.version for e in pg.log.entries
                        if e.oid == "obj"]
                if len(mine) != 3 or mine != sorted(mine):
                    findings.append(
                        f"osd.{osd.whoami} {pg.pgid} logged {mine} "
                        f"for three writes of the object")
        sched.check_cluster_invariants(cl, encode_base=encode_base,
                                       findings=findings)
        pipelined = sum(int(o.perf_window.dump()["writes_pipelined"])
                        for o in cl.osds.values())
        await cl.stop()
        return pipelined

    pipelined = 0
    try:
        pipelined, loop = sched.run_deterministic(main, seed=seed)
        report.trace_hash = loop.trace_hash()
        report.steps = loop.steps
        report.trace_tail = list(loop.trace_tail)
    except (Exception, asyncio.CancelledError) as e:
        findings.append(
            f"schedule did not complete: {type(e).__name__}: {e}")
    return report, pipelined

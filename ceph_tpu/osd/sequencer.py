"""Per-PG op pipelining: a dependency-tracked in-flight window.

Reference parity: the combination of ShardedOpWQ (osd/OSD.h:1748 — many
ops in flight per PG) with ObjectContext rw-state tracking
(osd/osd_types.h ObjectContext::RWState) and the in-order repop
completion discipline (ReplicatedPG::eval_repop applies commits in
pglog order).  What RWState really says (v11.0.2): `get_write_lock`
COUNTS the writers in state RWWRITE and `get_read_lock` the readers in
RWREAD — writers share the object with each other, readers share it
with each other, and the two states exclude one another.  Writes to
one object do NOT wait for each other's commit there:
ReplicatedPG::execute_ctx prepares op N+1 against the projected object
state as soon as op N is issued, and their order is kept by the PG's
log and the connections' FIFO.  The reference can afford that on an
EC pool only because every log entry there carries its way back
(ECBackend.cc keeps what an overwrite replaced as a rollback
generation until the write is on all shards, and peering rolls
divergent entries back): several unacked versions of one object
out at once must not cost an ACKED one.  Here that precondition is
ECBackend._keep_prior / plan_rollbacks (osd/backend.py; ROADMAP
Invariants, "An EC overwrite keeps what it replaced").  PR 1 left the
window at ONE client op per PG; PR 5 widened it with writes to one
object chained from ack to ack; since PR 33 the chain links at the
SUBMIT section.

Model:
  * the PG worker stays the single ADMITTER: it dequeues in FIFO order,
    waits for a free window slot (osd_pg_max_inflight_ops), registers
    the op's object dependency synchronously — so per-object order is
    exactly queue order — and spawns the op as its own task.
  * dependencies are keyed by object id.  Every admitted op has a
    `done` future (resolved at release, whatever happened); a write
    that takes the EARLY LINK has a second one, `submitted`, which the
    backend's await-free submit section resolves at its end (version
    taken, pglog appended, local shard applied, sub-ops fanned out in
    one step of the loop) and release resolves at the latest.
      - an early-link WRITE waits for the `submitted` of every writer
        before it and for the `done` of every reader since the last
        writer; it REPLIES only after the `done` of the writers before
        it (acks per object in submit order; a duplicate of an
        in-flight write, which finds its reqid in the log from the
        original's submit on, thereby waits for the original's ack);
      - a READ waits for the `done` of EVERY writer admitted before it
        and not yet released (several may be in flight): reads still
        wait for acks, the RWREAD / RWWRITE exclusion;
      - every other op admitted exclusive (a writeback tier's read, an
        op carrying a read, a guard or a cls call, ...: the rule is
        PG._admission_class) waits for the `done` of all of them and is
        waited for by its `done`: for such an op `submitted` IS `done`.
    Ops on disjoint objects run fully concurrently.
  * BARRIER ops (scrub boundaries, tier-agent passes, pool-scope ops
    with no object id, peering/epoch changes) drain the window first
    and run alone — the whole-PG dependency class.
  * versions/commit order: admission fixes per-object order only; log
    versions are assigned inside the backend's await-free submit
    section (version -> append_log -> queue_transactions -> fan-out
    with no await between them), so pglog versions stay dense, per
    object in admission order (a write enters its submit section only
    after the writers before it left theirs), and the PR-1
    group-commit callbacks — last_complete, repop acks, EC sub-op
    acks — still fire in exact pglog submission order.  Shards apply
    sub-ops in arrival order, so every copy sees one object's writes
    in that order too.
"""

from __future__ import annotations

import asyncio
from typing import Dict, List, Optional


class _ObjGate:
    """Per-object dependency tail: the writers admitted and not yet
    released, in admission order, plus every reader admitted since the
    last of them."""

    __slots__ = ("writers", "readers", "inflight")

    def __init__(self):
        self.writers: List["OpSlot"] = []
        self.readers: List[asyncio.Future] = []
        self.inflight = 0          # admitted on this object, not released


class OpSlot:
    """One admitted op's place in the window: what it must wait for
    before it runs and before it replies, and the futures later ops
    key their own waits on."""

    __slots__ = ("oid", "write", "done", "submitted", "waits",
                 "reply_waits", "_perf")

    def __init__(self, oid: str, write: bool, done: asyncio.Future,
                 submitted: asyncio.Future, waits: List[asyncio.Future],
                 reply_waits: List[asyncio.Future], perf=None):
        self.oid = oid
        self.write = write
        self.done = done
        self.submitted = submitted      # `done` itself without the early link
        self.waits = waits
        self.reply_waits = reply_waits
        self._perf = perf

    async def wait(self) -> None:
        """Block until every predecessor on this object let go.
        Predecessors resolve their futures unconditionally (success,
        error or abort), so a failed op can never wedge its chain."""
        for f in self.waits:
            if not f.done():
                await f

    def must_wait(self) -> bool:
        """True while a predecessor still holds this op back."""
        return any(not f.done() for f in self.waits)

    async def wait_reply(self) -> bool:
        """Block until every writer admitted before this early-link
        write is released: its reply must not overtake theirs.  True
        when there was anything to wait for."""
        waited = False
        for f in self.reply_waits:
            if not f.done():
                waited = True
                await f
        return waited

    def mark_submitted(self) -> None:
        """The backend's submit section ended (a synchronous call from
        inside it): writes queued behind this one may enter theirs.
        Nothing for an op without the early link, whose `submitted` is
        its `done`: release alone resolves that."""
        if self.submitted is not self.done and not self.submitted.done():
            if self._perf is not None \
                    and any(not f.done() for f in self.reply_waits):
                # ... while a write before it is still in the window
                self._perf.inc("writes_pipelined")
            self.submitted.set_result(None)


class OpSequencer:
    """The per-PG in-flight window (see module docstring).

    All registration/release steps are synchronous; only slot waiting
    and draining await — asyncio's run-to-completion makes the
    bookkeeping race-free without locks."""

    def __init__(self, max_inflight: int, perf=None, tracer=None):
        self.max_inflight = max(1, int(max_inflight))
        self.active = 0            # admitted, not yet released
        self.max_depth = 0         # high-water mark (counter)
        self.chain_peak = 0        # most ops ONE object had in it at once
        self._gates: Dict[str, _ObjGate] = {}
        self._slot_free = asyncio.Event()
        self._slot_free.set()
        self._idle = asyncio.Event()
        self._idle.set()
        self.perf = perf           # shared "osd_op_window" group or None
        self.tracer = tracer       # op tracer (stage histograms) or None

    # -------------------------------------------------------------- admit
    async def wait_slot(self, span=None) -> None:
        """Admission backpressure: block the admitter while the window
        is full (the op queue keeps buffering behind it, and the
        messenger dispatch throttle pushes back on clients).  A traced
        op cuts `queue_wait_pump` (dispatch -> here: PG op-queue dwell
        behind a busy worker — one of the named queue-wait causes) on
        entry and `admit_wait` (a full window's slot wait) on exit."""
        if span is not None and self.tracer is not None:
            span.cut("queue_wait_pump", self.tracer.hist)
        if self.perf is not None and self.active >= self.max_inflight:
            self.perf.inc("window_full_waits")
        while self.active >= self.max_inflight:
            self._slot_free.clear()
            await self._slot_free.wait()
        if span is not None and self.tracer is not None:
            span.cut("admit_wait", self.tracer.hist)

    # awaitfree:begin sequencer-admit-release (admission registration
    # and slot release are synchronous BY CONTRACT — the window's
    # bookkeeping is race-free only because no suspension point can
    # interleave two admissions; devtools rule AF01 enforces it)
    def admit(self, oid: str, write: bool,
              early_link: bool = False) -> OpSlot:
        """Synchronously register one op: takes a window slot and links
        it into its object's dependency chain.  MUST be called from the
        single admitter with a free slot (wait_slot).  `early_link`
        (writes only): queue behind the SUBMIT of the writers before
        it, reply behind their release (module docstring)."""
        loop = asyncio.get_running_loop()
        done = loop.create_future()
        gate = self._gates.get(oid)
        if gate is None:
            gate = self._gates[oid] = _ObjGate()
        # what skew does to the window: the most ops one object ever
        # had in it at once (this one included), and ops queued behind
        # a write of their own object still in flight
        gate.inflight += 1
        if gate.inflight > self.chain_peak:
            self.chain_peak = gate.inflight
            if self.perf is not None:
                self.perf.set_max("chain_peak", self.chain_peak)
        if self.perf is not None and gate.writers:
            self.perf.inc("same_object_waits")
        released = [w.done for w in gate.writers]
        if early_link:
            # runs behind the submit section of every writer in
            # flight, replies behind their release
            slot = OpSlot(oid, write, done, loop.create_future(),
                          [w.submitted for w in gate.writers],
                          released, self.perf)
        else:
            # a reader, or exclusive without the early link: runs
            # behind the release of EVERY writer in flight
            slot = OpSlot(oid, write, done, done, released, [],
                          self.perf)
        if write:
            # ... and behind every reader since the last of them
            slot.waits.extend(gate.readers)
            gate.writers.append(slot)
            gate.readers = []
        else:
            gate.readers.append(done)
        self.active += 1
        self._idle.clear()
        if self.active > self.max_depth:
            self.max_depth = self.active
            if self.perf is not None:
                # set_max, not set: the group is OSD-wide and shared by
                # every PG — a shallower PG's new personal best must
                # not clobber a deeper PG's high-water mark
                self.perf.set_max("max_inflight_depth", self.max_depth)
        if self.perf is not None:
            self.perf.inc("ops_admitted")
            # depth sampled at BOTH edges (admission here, release
            # below): a single-edge sample systematically undercounts
            # the time-averaged depth during ramp-up bursts; the
            # two-edge mean is the pipelining evidence bench ec_e2e
            # and test_perf_smoke assert on (> 1, serial pins it at 1)
            self.perf.tinc("inflight_depth", self.active)
        return slot

    def count(self, key: str) -> None:
        """One more of `key` in the window's counter group, if any."""
        if self.perf is not None:
            self.perf.inc(key)

    # ------------------------------------------------------------ release
    def release(self, slot: OpSlot) -> None:
        """Op finished (any outcome): resolve its futures so successors
        run, unlink it, free the slot.  `submitted` is resolved here at
        the latest: an op refused before its submit section, failed or
        cancelled never wedges the writes behind it."""
        if not slot.submitted.done():
            slot.submitted.set_result(None)
        if not slot.done.done():
            slot.done.set_result(None)
        gate = self._gates.get(slot.oid)
        if gate is not None:
            gate.inflight -= 1
            if slot.write:
                gate.writers.remove(slot)
            else:
                try:
                    # gone already when a writer was admitted since
                    gate.readers.remove(slot.done)
                except ValueError:
                    pass
            if not gate.writers and not gate.readers:
                del self._gates[slot.oid]
        if self.perf is not None:
            # release-edge depth sample (see admit)
            self.perf.tinc("inflight_depth", self.active)
        self.active -= 1
        self._slot_free.set()
        if self.active == 0:
            self._idle.set()
    # awaitfree:end sequencer-admit-release

    def balanced(self) -> bool:
        """True when every admitted slot has been released and no
        object gate is left dangling — the quiesced-window invariant
        the schedule explorer asserts after every explored schedule
        (a leaked slot wedges the PG's dependency chains forever)."""
        return self.active == 0 and not self._gates

    # -------------------------------------------------------------- drain
    async def drain(self) -> None:
        """Wait for the window to empty — the whole-PG barrier.  Used
        before scrub scans, tier-agent passes, pool-scope ops and on
        peering/epoch changes (window-drain-on-epoch-change is a
        ROADMAP invariant: a new interval must never interleave with
        ops admitted under the old one)."""
        if self.perf is not None and self.active:
            self.perf.inc("window_drains")
        while self.active:
            self._idle.clear()
            await self._idle.wait()

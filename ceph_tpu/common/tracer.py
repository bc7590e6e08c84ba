"""Distributed per-op span tracing for the RADOS write path.

Reference parity: the combination of blkin/zipkin tracing hooks
(common/zipkin_trace.h), TrackedOp event marks (common/TrackedOp.h) and
PerfHistogram (common/perf_histogram.h) — Dapper-style spans (Sigelman
et al., 2010) threaded through client → messenger → PG → backend →
store, with every named stage interval landing in a log2-bucketed
latency histogram so "37 ms/op of overhead" decomposes into named
microseconds.

Design:

  * The Objecter issues (trace_id, span_id) per client op.  The ids
    ride the op-path messages as versioned trailing fields (MOSDOp v3,
    MOSDOpReply/MOSDRepOp/MOSDECSubOpWrite v2); zero-encode local
    delivery carries the LIVE ``Span`` object itself (``Message._span``
    survives ``local_view()``), so co-located daemons cut stages on the
    client's span under one shared monotonic clock.  A TCP receiver
    adopts a fresh span handle from the wire ids and records its local
    stages into its own histograms under the same trace.

  * A span is a CUT CHAIN: ``cut(stage)`` attributes everything since
    the previous cut to ``stage`` and advances the cursor, so the chain
    stages tile the op's wall time with no gaps and no double counting.
    The difference between an externally measured e2e latency and the
    chain sum is therefore an honest *unattributed-time fraction*
    (event-loop resume hops, uninstrumented paths) — bench ec_e2e
    reports it and test_perf_smoke guards it ≥90% attributed.

  * Auxiliary stages (``repl_*`` replica-side work, ``op_total``)
    OVERLAP chain stages (a replica applies inside the primary's
    ``replica_rtt``) and are excluded from the chain sum.

  * SECTIONS (``Tracer.section(name)``) name synchronous work by the
    thread that ran it: a ``with`` block that never awaits, timed into
    the same histogram group under ``name`` and entered as a
    ``jax.profiler.TraceAnnotation`` of the same name, so the block
    lies in the profiler's trace on the profiler's clock.  Sections
    may NEST (a send inside ``loop_submit`` runs the messenger's
    ``loop_msg``): each records its SELF time, its wall minus the wall
    of the sections it enclosed, so the sum over names counts no
    instant twice.  ``loop_*`` sections run on the event-loop thread
    (their sum is loop time with a name); ``seam_*`` sections run on
    the EC queue's device thread, ``store_*`` sections on a store's kv-sync
    thread (``store_data_write``: a write-behind store's staged data
    written out; then the group's two barriers, ``store_data_sync``
    and ``store_kv_sync``).  INTERVALS (``Tracer.interval``) are the
    awaited counterpart: histogram only, from a ``Tracer.stamp()``.

  * While tracing is on, one sampler per event loop records the loop
    thread's wall and CPU time (``loop_wall`` / ``loop_cpu``) every
    100 ms: their ratio is the share of the one Python loop that is
    burning CPU.  The same sampler times the loop's selector for as
    long as it lives (``evloop_idle``: a ``select`` that was asked to
    block, the loop asleep with nothing ready; ``evloop_poll``: a
    ``select(0)`` between ready callbacks, which does no waiting of
    its own, so it reads the syscall plus the wait to win the GIL
    back), which closes the account: loop_wall = evloop_idle +
    evloop_poll + the callbacks' wall, and loop_wall - evloop_idle -
    loop_cpu is the time the loop was runnable and off its core (the
    GIL, or the kernel's scheduler).

  * Fully off-path when disabled (``op_tracing=false``, the default):
    no span allocation, no clock reads — every call site guards on
    ``tracer.enabled`` / ``span is not None``, and the tracer caches
    the config flag with an observer so the check is one attribute
    load per op.  ``section()`` then returns one shared no-op object;
    a bare ``with tracer.section(..)`` still costs three Python calls
    (``section``, the no-op's ``__enter__`` and ``__exit__``), which a
    64 KiB read of some 1,200 calls feels at twenty sites, so a site
    whose body is ONE call guards on ``tracer.enabled`` instead
    (``if tr.enabled: with tr.section(..): f() else: f()``).
"""

from __future__ import annotations

import asyncio
import contextlib
import random
import sys
import threading
import time
import weakref
from typing import Dict, List, Optional, Tuple

from ceph_tpu.common.perf_counters import PerfHistogram

#: thread id -> the stage most recently cut on that thread.  The
#: lockdep LoopStallMonitor reads this to name the owning stage of an
#: over-budget synchronous section; written only when tracing is on
#: (cut() never runs otherwise), so the off-path guarantee holds.
_last_stage: Dict[int, str] = {}


def last_stage(thread_id: Optional[int] = None) -> Optional[str]:
    return _last_stage.get(
        threading.get_ident() if thread_id is None else thread_id)

#: Stages that tile the client-visible op timeline (the cut chain, in
#: path order).  Everything else (repl_*, op_total) is auxiliary and
#: overlaps these — never sum the two sets together.
CHAIN_STAGES = (
    "client_submit",    # objecter: op build + target calc + send
    "deliver",          # messenger transit + intake queue (pre-throttle)
    "throttle_wait",    # dispatch-throttle wait (OSD intake budget)
    "lane_codec",       # process-lane hop: wire encode + decode cost
    "ring_wait",        # process-lane hop: parent push -> lane pop
    "queue_wait_ring",  # shard-ring dwell (handoff backpressure)
    "queue_wait_pump",  # PG op-queue dwell (pump/worker busy)
    "admit_wait",       # sequencer window-slot wait (window full)
    "dep_wait",         # per-object dependency chain wait
    "prepare",          # guards, recover-before-write, cow, txn build
    "ec_encode",        # EC: encode awaits + per-shard txn build
    "store_apply",      # version + pglog append + store apply/enqueue
    "submit",           # payload seal + replica/shard fan-out sends
    "replica_rtt",      # all replica/shard acks gathered
    "commit_wait",      # residual local group-commit wait (post-acks)
    "op_exec",          # read-class execution (reads only)
    "reply_wait",       # a pipelined write's wait to REPLY in order
    "ack_delivery",     # reply transit back to the client dispatch
)

#: The cause taxonomy that replaced the old monolithic ``queue_wait``
#: stage: every second an op spends queued before admission now lands
#: under the stage that NAMES its cause — the attribution the
#: <20%-queueing-share work keys on.  (``admit_wait`` — a full window —
#: and ``dep_wait`` — an object-order chain — were already split out.)
QUEUE_WAIT_CAUSES = (
    "throttle_wait",    # dispatch-throttle budget full (intake cap)
    "ring_wait",        # process-lane ring dwell / backpressure
    "queue_wait_ring",  # shard handoff ring dwell (pump not scheduled)
    "queue_wait_pump",  # PG worker busy with ops ahead in its queue
)

#: One device request's trip through the seam (osd/ec_queue.py), below
#: the chain's ``ec_encode`` / the aux ``decode_rebuild``.  Intervals
#: on the loop's side, sections on the ec-device executor thread:
#: seam_pending + [seam_fold .. seam_finish] + seam_resume ~ seam_apply.
SEAM_STAGES = (
    "seam_apply",       # interval: the whole apply() await
    "seam_pending",     # interval: apply() entry -> executor takes the group
    "seam_fold",        # section: the group into bucket-wide windows
    "seam_h2d",         # section: jax.device_put of one window
    "seam_launch",      # section: device_call on one staged window
    "seam_window",      # interval: one window's seam_h2d + seam_launch
    "seam_d2h",         # section: np.asarray of every window's result
    "seam_split",       # section: result copies (plain apply() requests)
    "seam_finish",      # section: the requests' continuations (apply_then)
    "seam_resume",      # interval: executor done -> awaiter runs again
)

#: Synchronous work on the event-loop thread, by section, plus the
#: loop sampler's two per-tick stages.  A section records its SELF
#: time (nested sections take theirs out), so the sum over loop_cpu is
#: the share of the loop's CPU that has a name.
LOOP_STAGES = (
    "loop_client",        # objecter: placement + message build of a cork
    "loop_client_reply",  # objecter: an MOSDOpReply -> its op's future
    "loop_msg",           # messenger: the local hand-over, both sides
    "loop_pump",          # OSD: one item of a shard's ring (its own
                          # sections inside take their time out)
    "loop_dispatch",      # OSD: delivered client op / sub-op ack -> its PG
    "loop_admit",         # PG: queue_op, the window admission
    "loop_read",          # read at the primary: local shard, fan-out,
                          # the sub-reads' sends, the replies' streams
    "loop_sub_read",      # read at a shard: the whole sub-read handler
    "loop_prepare",       # EC write: cls, cow, per-shard txns before encode
    "loop_ec_host",       # EC: a full write's shard txn build (its split,
                          # tobytes and crc only where they run inline: a
                          # padded payload, a continuation on the host
                          # path), decode glue
    "loop_store_apply",   # store apply at the primary and the sub-op handler
    "loop_store_commit",  # store: one inline (ack-on-apply) commit group
    "loop_submit",        # payload seal + fan-out in the submit regions
    "loop_reply",         # PG: reply build + send, tracker/budget release
    "loop_wall",          # sampler: monotonic delta per tick
    "loop_cpu",           # sampler: time.thread_time() delta per tick
)

#: A store's THREADED commit group (store/commit.py), the off-loop twin
#: of loop_store_commit: what durability adds to a transaction.  Per
#: transaction store_commit_wait = wait for the kv-sync thread + gather
#: + store_data_write + store_data_sync + store_kv_sync + store_resume.
STORE_STAGES = (
    "store_commit_wait",  # interval: submit() -> completion record on the loop
    "store_data_write",   # section, kv-sync thread: staged data written out
    "store_data_sync",    # section, kv-sync thread: the group's data barrier
    "store_kv_sync",      # section, kv-sync thread: the group's kv WAL sync
    "store_resume",       # interval: barriers done -> completion record runs
)

#: The loop sampler's timing of the loop's selector, per call of
#: ``select(timeout)``; histogram only (no annotation: the loop's sleep
#: overlaps the other threads' sections) and NOT named ``loop_*``:
#: they are not work.  loop_wall = evloop_idle + evloop_poll + the
#: callbacks' wall.
EVLOOP_STAGES = (
    "evloop_idle",        # select asked to block: asleep, nothing ready
    "evloop_poll",        # select(0): the syscall + the GIL won back
)

#: Auxiliary (non-chain) stages, for dump annotation.  recovery_pull
#: (one recovered object: gather -> decode -> push ack) and
#: decode_rebuild (the decode slice alone, batched through the EC
#: queue / mesh plane) overlap client chain stages — recovery runs
#: CONCURRENTLY with the op path, so they must never join the chain
#: sum.
#: extent_write / extent_read are the zero-copy lane transport's two
#: real payload copies (publish into / materialize out of a shared-
#: memory extent pool, osd/extents.py): they are exactly the bytes
#: REMOVED from lane_codec, so the pair next to a flat lane_codec is
#: the evidence the copy moved rather than vanished.
#: client_throttle_wait is the objecter's wait for its op budget
#: (objecter_inflight_ops / objecter_inflight_op_bytes): an interval
#: from op_submit's entry to the budget held, recorded only for an op
#: that had to wait.  It lies IN FRONT of the chain: the op's span
#: starts when the budget is held, so client_submit and op_total do
#: not hold it.
#: read_gather is the read's twin of replica_rtt, seen from the
#: primary: the first sub-read's send -> k shard streams in hand (the
#: sub-read's trip through the shard OSD's messenger, PG queue and
#: worker), once per gather that asked a remote shard.
AUX_STAGES = ("op_total", "repl_apply", "repl_commit",
              "recovery_pull", "decode_rebuild",
              "extent_write", "extent_read", "client_throttle_wait",
              "read_gather") \
    + SEAM_STAGES + LOOP_STAGES + STORE_STAGES + EVLOOP_STAGES

STAGE_GROUP = "op_stages"


class Span:
    """One traced op (or sub-op): ids + the stage cut chain."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "t0",
                 "_cursor", "stages", "events", "finished")

    def __init__(self, trace_id: int, span_id: int, name: str = "op",
                 parent_id: int = 0, t0: Optional[float] = None):
        now = time.monotonic() if t0 is None else t0
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.t0 = now
        self._cursor = now
        self.stages: List[Tuple[str, float]] = []
        self.events: List[Tuple[float, str]] = []
        self.finished = False

    def cut(self, stage: str, hist=None) -> float:
        """Attribute everything since the last cut to `stage`, advance
        the cursor, and (optionally) record into `hist` — the calling
        daemon's op_stages group, so attribution lands where the time
        was actually spent."""
        if self.finished:
            return 0.0
        now = time.monotonic()
        dt = now - self._cursor
        self._cursor = now
        self.stages.append((stage, dt))
        _last_stage[threading.get_ident()] = stage
        if hist is not None:
            hist.hinc(stage, dt)
        return dt

    def attribute(self, stage: str, dt: float, now: Optional[float] = None,
                  hist=None) -> None:
        """Record an EXPLICIT-duration chain sample and (optionally)
        advance the cursor to ``now``.  The lane seam uses this where
        the interval endpoints live on different clocks (parent push /
        lane pop): the caller computes the duration from the
        PING/PONG-calibrated offset, and the stage still tiles the
        chain because the cursor lands exactly at the hop's end."""
        if self.finished:
            return
        self.stages.append((stage, max(0.0, dt)))
        _last_stage[threading.get_ident()] = stage
        if now is not None:
            self._cursor = now
        if hist is not None:
            hist.hinc(stage, max(0.0, dt))

    def rebase(self, t: float) -> None:
        """Advance the cursor to ``t`` without attributing the skipped
        interval to any local stage.  The reply path of a process-lane
        op uses this: the skipped window is the lane worker's service
        time, which the LANE's continuation span recorded into the
        lane's own histograms — re-attributing it here would double
        count the merged cluster view.  Clamped to now: a clock-offset
        estimation error must never park the cursor in the future and
        make the next cut record a negative interval."""
        t = min(t, time.monotonic())
        if t > self._cursor:
            self._cursor = t

    def event(self, name: str) -> None:
        """Point-in-time span event (OpTracker marks land here)."""
        self.events.append((time.monotonic(), name))

    def finish(self, hist=None) -> float:
        """Close the span; records the aux `op_total` (t0 → now) which
        the coverage guard measures the chain sum against."""
        if self.finished:
            return 0.0
        self.finished = True
        total = time.monotonic() - self.t0
        self.stages.append(("op_total", total))
        if hist is not None:
            hist.hinc("op_total", total)
        return total

    def dump(self) -> Dict[str, object]:
        return {
            "trace_id": f"{self.trace_id:x}",
            "span_id": f"{self.span_id:x}",
            "name": self.name,
            "stages": [{"stage": s, "ms": round(dt * 1e3, 4)}
                       for s, dt in self.stages],
            "events": [e for _, e in self.events],
        }


#: what ``Tracer.section`` returns while tracing is off: one shared
#: reusable no-op, no clock read, no allocation
_NO_SECTION = contextlib.nullcontext()

#: jax.profiler.TraceAnnotation once found.  Taken only from a jax that
#: is ALREADY imported: the process that owns the chip has it; a lane
#: worker or a CPU-pinned daemon must not import jax for this.
_annotation_cls = None


def _annotation(name: str):
    global _annotation_cls
    if _annotation_cls is None:
        prof = sys.modules.get("jax.profiler")
        if prof is None:
            return None
        _annotation_cls = prof.TraceAnnotation
    return _annotation_cls(name)


#: thread id -> the innermost section open on that thread (each links
#: to the one around it): how a section finds its parent
_open_section: Dict[int, "_Section"] = {}


class _Section:
    """One timed synchronous block: histogram + profiler annotation
    under one name.  Records its SELF time: on exit its wall minus the
    wall of the sections it enclosed, and its whole wall goes to the
    enclosed sum of the section around it."""

    __slots__ = ("hist", "name", "ann", "t0", "parent", "enclosed")

    def __init__(self, hist, name: str):
        self.hist = hist
        self.name = name

    def __enter__(self):
        self.ann = _annotation(self.name)
        if self.ann is not None:
            self.ann.__enter__()
        tid = threading.get_ident()
        self.parent = _open_section.get(tid)
        _open_section[tid] = self
        self.enclosed = 0.0
        self.t0 = time.monotonic()

    def __exit__(self, *exc):
        dt = time.monotonic() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
        tid = threading.get_ident()
        parent = self.parent
        if parent is None:
            _open_section.pop(tid, None)
        else:
            _open_section[tid] = parent
            parent.enclosed += dt
        self.hist.hinc(self.name, dt - self.enclosed)
        _last_stage[tid] = self.name
        return False


#: seconds between two ticks of a loop's sampler
LOOP_SAMPLE_PERIOD = 0.1

#: the event loops that have a live sampler (at most one per loop)
_sampled_loops: "weakref.WeakSet" = weakref.WeakSet()


class _TimedSelector:
    """The loop's selector with a clock around ``select``: what the
    loop sampler puts in ``loop._selector`` for as long as it lives
    (the seam devtools/schedule.py's virtual selector sits in).  Two
    clock reads and one histogram record per call; everything else is
    the selector's own."""

    __slots__ = ("_inner", "_hist")

    def __init__(self, inner, hist):
        self._inner = inner
        self._hist = hist

    def select(self, timeout=None):
        t0 = time.monotonic()
        try:
            return self._inner.select(timeout)
        finally:
            self._hist.hinc("evloop_poll" if timeout == 0
                            else "evloop_idle", time.monotonic() - t0)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class _LoopSampler:
    """Records the loop thread's wall and CPU time per tick, and the
    wall time of every ``select`` of its loop, into the histograms of
    the tracer that started it.  A timer chain, not a task: nothing to
    cancel when the loop closes.  Ends when its tracer is switched off
    (at once: ``Tracer._on_cfg``) or collected, or the loop is closed,
    and then hands the loop its own selector back; the next enabled
    tracer on that loop starts a new one."""

    __slots__ = ("loop", "tracer", "wall", "cpu", "selector", "alive")

    def __init__(self, loop, tracer: "Tracer"):
        self.loop = loop
        self.tracer = weakref.ref(tracer)
        self.alive = True
        self.selector = None
        inner = getattr(loop, "_selector", None)
        if inner is not None:
            self.selector = loop._selector = _TimedSelector(
                inner, tracer.hist)
        self.wall = time.monotonic()
        self.cpu = time.thread_time()
        loop.call_later(LOOP_SAMPLE_PERIOD, self._tick)

    def stop(self) -> None:
        """The loop's own selector back in its place; idempotent.  The
        timer that is still out finds nothing to do."""
        if not self.alive:
            return
        self.alive = False
        _sampled_loops.discard(self.loop)
        sel, self.selector = self.selector, None
        if sel is not None and getattr(self.loop, "_selector",
                                       None) is sel:
            self.loop._selector = sel._inner
        tr = self.tracer()
        if tr is not None and self in tr._samplers:
            tr._samplers.remove(self)

    def _tick(self) -> None:
        tr = self.tracer()
        if tr is None or not tr.enabled or self.loop.is_closed():
            self.stop()
        if not self.alive:
            return
        wall, cpu = time.monotonic(), time.thread_time()
        tr.hist.hinc("loop_wall", wall - self.wall)
        tr.hist.hinc("loop_cpu", cpu - self.cpu)
        self.wall, self.cpu = wall, cpu
        self.loop.call_later(LOOP_SAMPLE_PERIOD, self._tick)


def _ensure_sampler(tracer: "Tracer") -> None:
    """Start this thread's loop's sampler if it has none.  No-op off
    the loop (executor threads) and under the deterministic sim loop,
    whose clock is virtual and whose schedule a timer would perturb
    (and whose selector is the schedule's own wrapper)."""
    loop = asyncio._get_running_loop()
    if loop is None or loop in _sampled_loops \
            or getattr(loop, "deterministic", False):
        return
    for old in list(tracer._samplers):
        if old.loop.is_closed():    # its timer will never fire
            old.stop()
    _sampled_loops.add(loop)
    tracer._samplers.append(_LoopSampler(loop, tracer))


class Tracer:
    """Per-context tracing frontend: enablement cache + stage group.

    One per Context (client and every daemon own one); spans travel
    between them, histogram records stay local to the recorder."""

    def __init__(self, ctx):
        self.ctx = ctx
        self._hist = None
        #: the loop samplers this tracer started and that still live
        self._samplers: List[_LoopSampler] = []
        if ctx is None:         # OFF, below: belongs to no daemon
            self.enabled = False
            return
        try:
            self.enabled = bool(ctx.config["op_tracing"])
        except KeyError:
            self.enabled = False
        try:
            ctx.config.add_observer(["op_tracing"], self._on_cfg)
        except Exception:
            pass

    def _on_cfg(self, changed: set) -> None:
        self.enabled = bool(self.ctx.config["op_tracing"])
        if not self.enabled:
            # off is off at once: no wrapper stays on a loop's selector
            for sampler in list(self._samplers):
                sampler.stop()

    @property
    def hist(self):
        """This daemon's stage-histogram group (lazy: groups only exist
        on contexts that actually record)."""
        if self._hist is None:
            self._hist = self.ctx.perf.create(STAGE_GROUP)
        return self._hist

    def start(self, name: str = "osd_op") -> Optional[Span]:
        """New root span, or None when tracing is off (callers guard
        every downstream touch on that None)."""
        if not self.enabled:
            return None
        _ensure_sampler(self)
        return Span(random.getrandbits(63) | 1,
                    random.getrandbits(63) | 1, name)

    def section(self, name: str):
        """``with tracer.section("loop_x"):`` around synchronous work
        (never an await inside).  On: the block's time lands in this
        tracer's histograms under ``name`` and, where jax is loaded,
        in the profiler's trace under the same name on the thread that
        ran it.  Off: the shared no-op."""
        if not self.enabled:
            return _NO_SECTION
        _ensure_sampler(self)
        return _Section(self.hist, name)

    def stamp(self) -> float:
        """Start of an awaited interval (0.0 when tracing is off: no
        clock read); close it with ``interval``."""
        return time.monotonic() if self.enabled else 0.0

    def interval(self, name: str, t0: float) -> None:
        """Record the aux interval ``t0`` (a ``stamp``) -> now under
        ``name``.  A zero stamp (tracing was off when it was taken)
        records nothing."""
        if t0 and self.enabled:
            self.hist.hinc(name, time.monotonic() - t0)

    def adopt(self, trace_id: int, span_id: int,
              t0: Optional[float] = None) -> Span:
        """Span handle for wire-propagated ids (TCP receive side): the
        cursor starts at t0 (receive stamp) so local stages attribute
        correctly; the network transit itself stays unattributed here."""
        return Span(trace_id, span_id, "remote", parent_id=span_id,
                    t0=t0)

    def finish(self, span: Span) -> float:
        return span.finish(self.hist)


#: The tracer of code that no daemon has mounted or started (a store in
#: a tool or a test, before an OSD hands it its own): never on, so every
#: section is the shared no-op, every stamp 0.0 with no clock read, and
#: every interval records nothing.
OFF = Tracer(None)


# ---------------------------------------------------------- aggregation

def merge_stage_histograms(ctxs, extra_dumps=()) -> Dict[str, PerfHistogram]:
    """Merge every context's op_stages group into fresh per-stage
    histograms (bench + qa aggregate client and all daemons of an
    in-process cluster with this).  ``extra_dumps`` takes iterable
    ``{stage: dump_full dict}`` mappings — the cross-PROCESS form a
    lane worker ships over FRAME_STATS/FRAME_RPC — merged bucket-wise
    via ``PerfHistogram.from_dump``."""
    merged: Dict[str, PerfHistogram] = {}
    for ctx in ctxs:
        group = ctx.perf._groups.get(STAGE_GROUP) \
            if hasattr(ctx.perf, "_groups") else None
        if group is None:
            continue
        for stage, h in group.histograms().items():
            merged.setdefault(stage, PerfHistogram()).merge(h)
    for dump in extra_dumps:
        for stage, d in (dump or {}).items():
            if isinstance(d, dict) and "buckets" in d:
                merged.setdefault(stage, PerfHistogram()).merge(
                    PerfHistogram.from_dump(d))
    return merged


def stage_table(perf_collection, extra_dumps=(),
                full: bool = False) -> Dict[str, object]:
    """`dump_op_stages` admin-socket body: per-stage quantiles from this
    daemon's op_stages group, chain stages in path order first.
    ``extra_dumps``: per-lane ``{stage: dump_full}`` mappings merged in
    (the parent's lane-complete dump); ``full=True`` keeps the raw
    bucket vectors so the OUTPUT itself stays mergeable upstream."""
    group = perf_collection._groups.get(STAGE_GROUP)
    hists: Dict[str, PerfHistogram] = {}
    if group is not None:
        for name, h in group.histograms().items():
            hists[name] = PerfHistogram().merge(h)
    for dump in extra_dumps:
        for name, d in (dump or {}).items():
            if isinstance(d, dict) and "buckets" in d:
                hists.setdefault(name, PerfHistogram()).merge(
                    PerfHistogram.from_dump(d))
    stages: Dict[str, Dict] = {}
    for name in CHAIN_STAGES:
        if name in hists:
            stages[name] = (hists[name].dump_full() if full
                            else hists[name].dump())
    for name, h in sorted(hists.items()):
        if name not in stages:
            d = h.dump_full() if full else h.dump()
            d["aux"] = True
            stages[name] = d
    chain_s = sum(hists[n].sum for n in CHAIN_STAGES if n in hists)
    return {"stages": stages, "chain_s": round(chain_s, 6)}


def breakdown(merged: Dict[str, PerfHistogram],
              measured_e2e_s: Optional[float] = None) -> Dict[str, object]:
    """Stage breakdown + unattributed fraction from merged histograms.

    measured_e2e_s: externally measured total op seconds (sum of
    client-observed latencies).  Falls back to the op_total histogram
    (span creation → reply dispatch) when absent."""
    stages = {}
    for name in CHAIN_STAGES + AUX_STAGES:
        h = merged.get(name)
        if h is not None and h.count:
            stages[name] = h.dump()
    attributed = sum(merged[n].sum for n in CHAIN_STAGES if n in merged)
    total = measured_e2e_s
    if total is None:
        ot = merged.get("op_total")
        total = ot.sum if ot is not None else 0.0
    unattr = max(0.0, 1.0 - attributed / total) if total else 0.0
    return {
        "stages": stages,
        "attributed_s": round(attributed, 6),
        "measured_s": round(total, 6),
        "unattributed_frac": round(unattr, 4),
    }

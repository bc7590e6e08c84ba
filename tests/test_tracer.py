"""Distributed op tracing (common/tracer.py): histogram bucket/quantile
math, span cut-chain tiling, trace-header propagation (byte-identity
across local vs forced-TCP delivery, old-version decode tolerance),
op_tracker monotonic clocks + slow-op complaints, and the new
admin-socket commands (perf histogram dump / dump_op_stages /
dump_historic_slow_ops) on a live mini-cluster.
"""

import asyncio
import sys
import tempfile
import time

import pytest

sys.path.insert(0, __file__.rsplit("/", 1)[0])

from ceph_tpu.common.config import Config
from ceph_tpu.common.context import Context
from ceph_tpu.common.op_tracker import OpTracker
from ceph_tpu.common.perf_counters import PerfCounters, PerfHistogram
from ceph_tpu.common import tracer as tracer_mod
from ceph_tpu.common.tracer import CHAIN_STAGES, Span, Tracer
from ceph_tpu.osd.messages import MOSDOp, MOSDOpReply, MOSDRepOp, OSDOp
from ceph_tpu.osd.messages import OP_WRITE
from ceph_tpu.osd.types import PGId


# ---------------------------------------------------------- histograms

def test_histogram_buckets_and_quantiles():
    h = PerfHistogram()
    # 100 samples at ~1ms, 10 at ~16ms, 1 at ~1s
    for _ in range(100):
        h.add(0.001)
    for _ in range(10):
        h.add(0.016)
    h.add(1.0)
    assert h.count == 111
    assert abs(h.sum - (0.1 + 0.16 + 1.0)) < 1e-9
    # p50 must land in 1000us's bucket [512us, 1024us)
    assert 512e-6 <= h.quantile(0.5) < 1024e-6
    # p99 in 16000us's bucket [8192us, 16384us)
    assert 8192e-6 <= h.quantile(0.99) < 16384e-6
    # the max sample dominates the extreme tail
    assert h.quantile(0.9999) >= 0.5
    d = h.dump()
    assert d["count"] == 111 and d["p50_ms"] < d["p99_ms"]


def test_histogram_bucket_edges():
    h = PerfHistogram()
    # sub-microsecond -> bucket 0; exact powers land in their own bucket
    assert h._bucket_of(0.0) == 0
    assert h._bucket_of(0.5e-6) == 0
    assert h._bucket_of(1e-6) == 0
    assert h._bucket_of(2e-6) == 1
    assert h._bucket_of(1024e-6) == 10
    # huge samples clamp into the last (open-ended) bucket
    assert h._bucket_of(1e9) == PerfHistogram.N_BUCKETS - 1


def test_histogram_merge_and_dump_roundtrip():
    a, b = PerfHistogram(), PerfHistogram()
    for _ in range(5):
        a.add(0.002)
    for _ in range(7):
        b.add(0.050)
    merged = PerfHistogram().merge(a).merge(b)
    assert merged.count == 12
    assert abs(merged.sum - (0.010 + 0.350)) < 1e-9
    # per-PG/per-daemon merging = bucket-wise addition
    assert merged.buckets[a._bucket_of(0.002)] == 5
    assert merged.buckets[a._bucket_of(0.050)] == 7
    # full dumps round-trip for cross-process merging
    rt = PerfHistogram.from_dump(merged.dump_full())
    assert rt.buckets == merged.buckets
    assert rt.count == merged.count
    assert rt.dump() == merged.dump()


def test_perfcounters_hist_auto_register_and_dump():
    pc = PerfCounters("t")
    pc.hinc("stage_x", 0.004)
    pc.hinc("stage_x", 0.004)
    d = pc.dump()
    assert d["stage_x"]["count"] == 2
    full = pc.dump_histograms()
    assert sum(full["stage_x"]["buckets"]) == 2


# ---------------------------------------------------------------- spans

def test_span_cut_chain_tiles_total():
    pc = PerfCounters("op_stages")
    sp = Span(1, 2, "op")
    time.sleep(0.002)
    sp.cut("client_submit", pc)
    time.sleep(0.004)
    sp.cut("replica_rtt", pc)
    total = sp.finish(pc)
    # the chain cuts tile t0 -> finish with no gap and no double count
    chain = sum(dt for s, dt in sp.stages if s != "op_total")
    assert abs(chain - total) < 2e-3
    assert [s for s, _ in sp.stages] == ["client_submit", "replica_rtt",
                                         "op_total"]
    # post-finish cuts are inert (late replies must not corrupt stats)
    assert sp.cut("ack_delivery", pc) == 0.0
    assert pc.dump()["op_total"]["count"] == 1


def test_tracer_disabled_by_default_and_off_path():
    assert Config()["op_tracing"] is False
    ctx = Context("client.test")
    assert ctx.tracer.enabled is False
    assert ctx.tracer.start() is None          # no span allocation
    # runtime enable via config observer (injectargs path)
    ctx.config.set("op_tracing", True)
    sp = ctx.tracer.start()
    assert sp is not None and sp.trace_id and sp.span_id
    ctx.config.set("op_tracing", False)
    assert ctx.tracer.start() is None


def test_section_and_interval_are_off_path_when_disabled(monkeypatch):
    """Off, a section site is one attribute load and a no-op `with`:
    the one shared object, no clock read, no histogram group, and
    stamp/interval read no clock and record nothing either."""
    from ceph_tpu.common.tracer import STAGE_GROUP
    ctx = Context("osd.7")
    tr = ctx.tracer

    def no_clock():
        raise AssertionError("clock read on the off path")

    monkeypatch.setattr(tracer_mod.time, "monotonic", no_clock)
    monkeypatch.setattr(tracer_mod.time, "thread_time", no_clock)
    a, b = tr.section("loop_ec_host"), tr.section("seam_launch")
    assert a is b is tracer_mod._NO_SECTION
    with a as got:
        assert got is None
    assert tr.stamp() == 0.0
    tr.interval("seam_apply", 0.0)
    tr.interval("seam_apply", 12.5)                # switched off since
    assert STAGE_GROUP not in ctx.perf._groups


def test_tracing_on_in_a_process_without_jax_imports_none():
    """A lane worker or a CPU-pinned daemon traces without the
    profiler: a section there must not be what imports jax."""
    import subprocess
    code = (
        "import sys\n"
        "from ceph_tpu.common.context import Context\n"
        "ctx = Context('osd.1')\n"
        "ctx.config.set('op_tracing', True)\n"
        "with ctx.tracer.section('loop_submit'):\n"
        "    pass\n"
        "t0 = ctx.tracer.stamp()\n"
        "ctx.tracer.interval('seam_apply', t0)\n"
        "d = ctx.perf.dump()['op_stages']\n"
        "assert d['loop_submit']['count'] == 1, d\n"
        "assert d['seam_apply']['count'] == 1, d\n"
        "assert 'jax' not in sys.modules\n")
    repo = __file__.rsplit("/", 2)[0]
    p = subprocess.run([sys.executable, "-c", code], cwd=repo,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def test_section_records_histogram_last_stage_and_annotation():
    """On: one name for the histogram key, the last-stage mark and the
    profiler annotation; nests under an enclosing TraceAnnotation
    without error when no profiler runs."""
    import jax
    ctx = Context("osd.8")
    ctx.config.set("op_tracing", True)
    tr = ctx.tracer
    with jax.profiler.TraceAnnotation("outer"):
        sec = tr.section("loop_store_apply")
        assert sec is not tracer_mod._NO_SECTION
        with sec:
            time.sleep(0.003)
            assert isinstance(sec.ann, jax.profiler.TraceAnnotation)
    h = tr.hist.histograms()["loop_store_apply"]
    assert h.count == 1 and 0.003 <= h.sum < 0.1
    assert tracer_mod.last_stage() == "loop_store_apply"
    # an exception leaves through the section and is still recorded
    with pytest.raises(KeyError):
        with tr.section("loop_store_apply"):
            raise KeyError("x")
    assert tr.hist.histograms()["loop_store_apply"].count == 2
    # every section and interval name is a declared aux stage
    for name in tracer_mod.SEAM_STAGES + tracer_mod.LOOP_STAGES:
        assert name in tracer_mod.AUX_STAGES
        assert name not in CHAIN_STAGES


def test_interval_records_from_its_stamp():
    ctx = Context("osd.9")
    ctx.config.set("op_tracing", True)
    tr = ctx.tracer
    t0 = tr.stamp()
    assert t0 > 0.0
    time.sleep(0.002)
    tr.interval("seam_pending", t0)
    t1 = time.monotonic()
    tr.interval("seam_resume", 0.0)        # never stamped: not recorded
    hs = tr.hist.histograms()
    assert hs["seam_pending"].count == 1
    assert 0.002 <= hs["seam_pending"].sum <= t1 - t0
    assert "seam_resume" not in hs


def test_loop_sampler_one_per_loop_records_wall_and_cpu():
    """The first enabled tracer on a loop starts its sampler; a second
    tracer on the same loop starts none; the ratio of the two stages is
    the loop's CPU share; switching tracing off ends it."""
    first, second = Context("osd.10"), Context("osd.11")
    for c in (first, second):
        c.config.set("op_tracing", True)

    async def run():
        loop = asyncio.get_running_loop()
        with first.tracer.section("loop_submit"):
            pass
        with second.tracer.section("loop_submit"):
            pass
        assert second.tracer.start() is not None
        assert loop in tracer_mod._sampled_loops
        spin_until = time.monotonic() + 0.15
        while time.monotonic() < spin_until:
            pass                                    # burn the loop's CPU
        await asyncio.sleep(0.3)                    # then let it wait
        hs = first.tracer.hist.histograms()
        assert 2 <= hs["loop_wall"].count == hs["loop_cpu"].count
        assert 0.1 <= hs["loop_cpu"].sum <= hs["loop_wall"].sum
        assert hs["loop_cpu"].sum < 0.8 * hs["loop_wall"].sum
        assert "loop_wall" not in second.tracer.hist.histograms()
        first.config.set("op_tracing", False)
        await asyncio.sleep(2.5 * tracer_mod.LOOP_SAMPLE_PERIOD)
        assert loop not in tracer_mod._sampled_loops
        n = hs["loop_wall"].count
        await asyncio.sleep(2.5 * tracer_mod.LOOP_SAMPLE_PERIOD)
        assert first.tracer.hist.histograms()["loop_wall"].count == n
        # the next enabled tracer on the loop takes over
        with second.tracer.section("loop_submit"):
            pass
        await asyncio.sleep(1.5 * tracer_mod.LOOP_SAMPLE_PERIOD)
        assert second.tracer.hist.histograms()["loop_wall"].count >= 1
        # off the loop (an executor thread) a section starts no sampler
        second.config.set("op_tracing", False)
        await asyncio.sleep(1.5 * tracer_mod.LOOP_SAMPLE_PERIOD)
        first.config.set("op_tracing", True)

        def off_loop():
            with first.tracer.section("seam_fold"):
                pass
        await loop.run_in_executor(None, off_loop)
        assert loop not in tracer_mod._sampled_loops
        assert first.tracer.hist.histograms()["seam_fold"].count == 1

    asyncio.run(run())


# --------------------------------------------------- wire propagation

def test_mosdop_trace_header_roundtrip_and_old_version_decode():
    ops = [OSDOp(OP_WRITE, 0, 4, data=b"data")]
    m = MOSDOp(PGId(1, 2), "obj", None, ops, tid=7, map_epoch=3,
               reqid="c.7")
    m.trace_id, m.span_id = 0xabc123, 0xdef456
    rt = MOSDOp.from_bytes(m.to_bytes())
    assert (rt.trace_id, rt.span_id) == (0xabc123, 0xdef456)
    # an untraced op encodes zeros and decodes as untraced
    m2 = MOSDOp(PGId(1, 2), "obj", None, ops, tid=8)
    rt2 = MOSDOp.from_bytes(m2.to_bytes())
    assert rt2.trace_id == 0 and rt2.span_id == 0
    # OLD (v2) bytes — the trace ids are the trailing 16 payload bytes;
    # strip them and rewrite the struct header the way a v2 encoder
    # would have: the new decoder must accept and read "untraced"
    blob = bytearray(m.to_bytes())
    body_len = int.from_bytes(blob[2:6], "little")
    blob[0] = 2                                  # struct_v = 2
    blob[2:6] = (body_len - 16).to_bytes(4, "little")
    old = bytes(blob[:-16])
    rt3 = MOSDOp.from_bytes(old)
    assert rt3.oid == "obj" and rt3.tid == 7
    assert rt3.trace_id == 0 and rt3.span_id == 0
    # replies mirror the header the same way
    r = MOSDOpReply(7, 0, ops, 3)
    r.trace_id, r.span_id = 5, 6
    rr = MOSDOpReply.from_bytes(r.to_bytes())
    assert (rr.trace_id, rr.span_id) == (5, 6)


def test_span_propagation_local_vs_tcp_byte_identity():
    """The same traced op delivered locally hands the receiver the LIVE
    span object; forced over TCP the ids survive decode, the receiver
    adopts a span with the same identity, and the wire frame is
    byte-identical to an eagerly built untraced-constructor message
    with the same fields."""
    import test_msg as tm

    async def run():
        # --- local: live span rides local_view
        a, b, _, cb = await tm._pair(ms_local_delivery=True,
                                     op_tracing=True)
        sp = Tracer(a.ctx).start("osd_op")
        m = MOSDOp(PGId(1, 0), "o1", None,
                   [OSDOp(OP_WRITE, 0, 2, data=b"hi")], tid=1)
        m.trace_id, m.span_id = sp.trace_id, sp.span_id
        m._span = sp
        a.send_message(m, b.addr)
        await cb.wait_for(lambda c: len(c.msgs) >= 1)
        got = cb.msgs[0]
        assert got._span is sp                 # the live span itself
        assert (got.trace_id, got.span_id) == (sp.trace_id, sp.span_id)
        await a.shutdown()
        await b.shutdown()

        # --- TCP (armed fault injection disables the local path)
        c_, d, _, cd = await tm._pair(ms_local_delivery=True,
                                      op_tracing=True,
                                      ms_inject_socket_failures=10**9)
        sp2 = Tracer(c_.ctx).start("osd_op")
        m2 = MOSDOp(PGId(1, 0), "o1", None,
                    [OSDOp(OP_WRITE, 0, 2, data=b"hi")], tid=1)
        m2.trace_id, m2.span_id = sp2.trace_id, sp2.span_id
        m2._span = sp2
        c_.send_message(m2, d.addr)
        await cd.wait_for(lambda col: len(col.msgs) >= 1)
        got2 = cd.msgs[0]
        assert (got2.trace_id, got2.span_id) == (sp2.trace_id,
                                                 sp2.span_id)
        assert got2._span is not None          # adopted remote handle
        assert got2._span is not sp2
        assert got2._span.trace_id == sp2.trace_id
        # wire bytes: identical to a fresh message with the same fields
        eager = MOSDOp(PGId(1, 0), "o1", None,
                       [OSDOp(OP_WRITE, 0, 2, data=b"hi")], tid=1)
        eager.trace_id, eager.span_id = sp2.trace_id, sp2.span_id
        assert m2.wire_bytes() == eager.to_bytes()
        await c_.shutdown()
        await d.shutdown()

    asyncio.run(run())


def test_subop_trace_header_propagates():
    m = MOSDRepOp(PGId(2, 1), 9)
    m.trace_id, m.span_id = 11, 22
    rt = MOSDRepOp.from_bytes(m.to_bytes())
    assert (rt.trace_id, rt.span_id) == (11, 22)


# ------------------------------------------------- op tracker satellites

def test_op_tracker_uses_monotonic_and_wall_only_in_dump():
    t = OpTracker()
    op = t.create("op-a")
    # measuring clock is monotonic: start must sit on the monotonic
    # timeline, never the wall clock epoch
    now_m = time.monotonic()
    assert abs(op.start - now_m) < 5.0
    assert op.age() >= 0.0
    d = op.dump()
    # dump output shows WALL time (human-readable), reconstructed from
    # the anchor — initiated_at must sit on the wall timeline
    assert abs(d["initiated_at"] - time.time()) < 5.0
    assert abs(d["events"][0]["time"] - d["initiated_at"]) < 0.5


def test_op_tracker_slow_op_complaints():
    class _Log:
        def __init__(self):
            self.lines = []

        def warning(self, msg):
            self.lines.append(msg)

    pc = PerfCounters("osd")
    pc.add_u64("slow_ops")
    log = _Log()
    t = OpTracker(complaint_time=0.01, perf=pc, logger=log)
    op = t.create("slow-op")
    fast = t.create("fast-op")
    assert t.check_slow() == 0                 # not old enough yet
    time.sleep(0.02)
    t.finish(fast)                             # finished before scan
    assert t.check_slow() == 1
    assert t.check_slow() == 0                 # complains ONCE per op
    assert pc.dump()["slow_ops"] == 1
    assert len(log.lines) == 1 and "slow request" in log.lines[0]
    assert t.slow_op_count == 1
    # lands in the slow history ring on completion
    t.finish(op)
    d = t.dump_historic_slow_ops()
    assert d["num_ops"] == 1
    assert d["total_slow_ops"] == 1
    assert d["ops"][0]["description"] == "slow-op"
    assert any(e["event"] == "slow_op_complaint"
               for e in d["ops"][0]["events"])


def test_tracked_op_marks_become_span_events():
    t = OpTracker()
    op = t.create("traced")
    sp = Span(1, 2, "op")
    op.span = sp
    op.mark("queued_for_pg")
    assert [e for _, e in sp.events] == ["queued_for_pg"]
    assert "trace" in op.dump()


# --------------------------------------------- admin socket (live OSD)

def test_admin_socket_tracer_commands():
    """End to end on a mini-cluster with tracing on: the OSD admin
    socket serves `perf histogram dump`, `dump_op_stages` and
    `dump_historic_slow_ops`, and the stage table carries real write
    path samples."""
    from ceph_tpu.common.admin_socket import admin_command
    from ceph_tpu.qa.cluster import Cluster, make_ctx

    td = tempfile.mkdtemp()

    def ctx_f(name):
        c = make_ctx(name)
        c.config.set("ms_local_delivery", True)
        c.config.set("op_tracing", True)
        if name.startswith("osd"):
            c.config.set("admin_socket", f"{td}/$name.asok")
        return c

    async def run():
        cl = Cluster(ctx_factory=ctx_f)
        admin = await cl.start(3)
        await admin.pool_create("tp", pg_num=4)
        io = admin.open_ioctx("tp")
        for i in range(6):
            await io.write_full(f"t{i}", bytes([i]) * 1024)
        loop = asyncio.get_running_loop()

        def cmd(osd_id, c):
            return admin_command(f"{td}/osd.{osd_id}.asok", c)

        # not every OSD is primary for the written objects — at least
        # one must expose chain-stage samples; every OSD serves the
        # commands with a well-formed shape
        chain_seen = False
        for osd_id in cl.osds:
            hist = await loop.run_in_executor(
                None, cmd, osd_id, "perf histogram dump")
            stages = await loop.run_in_executor(
                None, cmd, osd_id, "dump_op_stages")
            assert stages["op_tracing"] is True
            for d in stages["stages"].values():
                assert d["count"] > 0
            if any(s in stages["stages"] for s in CHAIN_STAGES):
                chain_seen = True
                assert "op_stages" in hist, hist.keys()
            slow = await loop.run_in_executor(
                None, cmd, osd_id, "dump_historic_slow_ops")
            assert slow["num_ops"] == 0        # nothing slow in a burst
            assert slow["complaint_time"] > 0
        assert chain_seen
        # cluster-wide merge sees client + every OSD's share; the chain
        # must include the client-side and osd-side stages
        merged = cl.stage_histograms()
        assert merged["op_total"].count >= 6
        assert "client_submit" in merged and "ack_delivery" in merged
        await cl.stop()

    asyncio.run(run())


#: the loop sections that were there before sections could nest (PR 34)
OLD_LOOP_SECTIONS = ("loop_client", "loop_dispatch", "loop_prepare",
                     "loop_ec_host", "loop_store_apply",
                     "loop_store_commit", "loop_submit", "loop_reply")
#: the hops every op takes and the read path (PR 34)
HOP_SECTIONS = ("loop_msg", "loop_pump", "loop_admit", "loop_client_reply",
                "loop_read", "loop_sub_read")


def test_loop_sections_nest_in_order_and_seam_sections_run_off_the_loop(
        monkeypatch):
    """The rule the readers lean on, on a live EC mini-cluster through
    the device seam: `loop_*` sections run on the loop thread; they may
    lie inside one another (a send inside `loop_submit` is the
    messenger's `loop_msg`, a ring item's `loop_pump` holds what it
    dispatches) and each records its SELF time, so their sum is loop
    time with a name, counted once; the eight that were there before
    the hops had names never lie inside one another (so no reading of
    theirs was ever counted twice); the `seam_*` sections run on the
    ec-device thread, a full write's continuation (`seam_finish`: its
    shards' bytes and digests) among them and never on the loop; and
    the loop sampler's stages are there beside them."""
    import threading
    from ceph_tpu.qa.cluster import Cluster, make_ctx

    loop_thread = threading.get_ident()
    open_loop = []
    seen = {}
    faults = []
    real_enter = tracer_mod._Section.__enter__
    real_exit = tracer_mod._Section.__exit__

    nested = set()

    def enter(self):
        on_loop = threading.get_ident() == loop_thread
        seen.setdefault(self.name, set()).add(on_loop)
        if self.name.startswith("loop_"):
            for outer in open_loop:
                nested.add((outer, self.name))
                if outer in OLD_LOOP_SECTIONS \
                        and self.name in OLD_LOOP_SECTIONS:
                    faults.append(f"{self.name} inside {outer}")
            open_loop.append(self.name)
        return real_enter(self)

    def exit_(self, *exc):
        if self.name.startswith("loop_"):
            if open_loop.pop() != self.name:
                faults.append(f"{self.name} closed out of order")
        elif threading.get_ident() == loop_thread and open_loop:
            faults.append(f"{self.name} inside {open_loop[-1]}")
        return real_exit(self, *exc)

    monkeypatch.setattr(tracer_mod._Section, "__enter__", enter)
    monkeypatch.setattr(tracer_mod._Section, "__exit__", exit_)

    def ctx_f(name):
        c = make_ctx(name)
        c.config.set("ms_local_delivery", True)
        c.config.set("op_tracing", True)
        c.config.set("osd_op_num_shards", 4)
        c.config.set("osd_shard_threads", False)    # the one-loop plane
        c.config.set("osd_ec_batch_device", "force")
        c.config.set("osd_ec_batch_min_bytes", 1024)
        return c

    async def run():
        cl = Cluster(ctx_factory=ctx_f)
        admin = await cl.start(4)
        await admin.pool_create("ecs", pg_num=4, pool_type="erasure",
                                k=2, m=1)
        await admin.pool_create("rep", pg_num=4)
        io, rio = admin.open_ioctx("ecs"), admin.open_ioctx("rep")
        blobs = {f"o{i}": bytes([i + 1]) * (8192 + 64 * i)
                 for i in range(12)}
        await asyncio.gather(*[io.write_full(k, v)
                               for k, v in blobs.items()])
        await asyncio.gather(*[rio.write_full(k, v)
                               for k, v in blobs.items()])
        for k, v in blobs.items():
            assert await io.read(k) == v
        await asyncio.sleep(2.5 * tracer_mod.LOOP_SAMPLE_PERIOD)
        merged = cl.stage_histograms()
        seam = [osd.ec_queue.perf.dump() for osd in cl.osds.values()]
        await cl.stop()
        return merged, seam

    merged, seam = asyncio.run(run())
    # every EC write went to the device thread, its continuation too
    assert sum(d["device_requests"] for d in seam) == 12
    assert sum(d["finish_thread"] for d in seam) == 12
    assert sum(d["finish_inline"] for d in seam) == 0
    assert not faults, faults[:5]
    assert not open_loop and not tracer_mod._open_section
    for name in OLD_LOOP_SECTIONS + HOP_SECTIONS:
        assert seen[name] == {True}, (name, seen[name])
    # the local hand-over runs on its sender's stack, a ring item holds
    # its dispatch, a dispatched op its admission
    for pair in (("loop_submit", "loop_msg"), ("loop_reply", "loop_msg"),
                 ("loop_read", "loop_msg"), ("loop_sub_read", "loop_msg"),
                 ("loop_msg", "loop_client_reply"),
                 ("loop_dispatch", "loop_admit"),
                 ("loop_pump", "loop_store_apply"),
                 # a sub-read is served inside its ring item (PR 35)
                 ("loop_pump", "loop_sub_read")):
        assert pair in nested, (pair, sorted(nested))
    # (no seam_split: that is the result copy of a request WITHOUT a
    # continuation, and every request here is a full write's)
    for name in ("seam_fold", "seam_h2d", "seam_launch", "seam_d2h",
                 "seam_finish"):
        assert seen[name] == {False}, (name, seen[name])
    assert "seam_split" not in seen
    for name in list(seen) + ["seam_apply", "seam_pending",
                              "seam_resume", "seam_window", "loop_wall",
                              "loop_cpu", "evloop_idle", "evloop_poll",
                              "read_gather"]:
        assert merged[name].count > 0, name
    # a read of a k=2 m=1 object asks one remote shard: one gather each
    assert merged["read_gather"].count == 12
    assert merged["loop_sub_read"].count == 12
    # 12 EC writes: one split and one shard-txn build each (the
    # shards' tobytes and digests are the continuation's), and one
    # assembly per read; every write of both pools applies at its
    # primary and at each replica / shard (a traced sub-op's apply
    # records repl_apply)
    assert merged["loop_ec_host"].count == 12 * 2 + 12
    assert merged["loop_submit"].count == 24
    assert merged["loop_store_apply"].count == \
        24 + merged["repl_apply"].count


def test_per_daemon_disable_drops_foreign_spans():
    """A daemon with op_tracing=false must stay fully off-path even
    when the CLIENT traced the op: the span riding local delivery is
    dropped at OSD intake, no OSD-side stage histograms appear, and
    the client books the server gap into ack_delivery."""
    from ceph_tpu.common.tracer import STAGE_GROUP
    from ceph_tpu.qa.cluster import Cluster, make_ctx

    def ctx_f(name):
        c = make_ctx(name)
        c.config.set("ms_local_delivery", True)
        if name.startswith("client"):
            c.config.set("op_tracing", True)   # OSDs/mon stay off
        return c

    async def run():
        cl = Cluster(ctx_factory=ctx_f)
        admin = await cl.start(3)
        await admin.pool_create("mx", pg_num=2)
        io = admin.open_ioctx("mx")
        for i in range(4):
            await io.write_full(f"m{i}", b"x" * 512)
        for osd in cl.osds.values():
            assert STAGE_GROUP not in osd.ctx.perf._groups, osd.whoami
        merged = cl.stage_histograms()
        assert merged["op_total"].count >= 4    # client side still traces
        assert "ack_delivery" in merged
        assert "prepare" not in merged          # no OSD-side cuts
        await cl.stop()

    asyncio.run(run())


def test_stage_table_and_breakdown_helpers():
    ctx = Context("osd.5")
    ctx.config.set("op_tracing", True)
    tr = ctx.tracer
    tr.hist.hinc("prepare", 0.002)
    tr.hist.hinc("replica_rtt", 0.010)
    tr.hist.hinc("repl_apply", 0.001)          # aux
    tr.hist.hinc("op_total", 0.014)
    table = tracer_mod.stage_table(ctx.perf)
    assert set(table["stages"]) == {"prepare", "replica_rtt",
                                    "repl_apply", "op_total"}
    assert table["stages"]["repl_apply"].get("aux") is True
    assert abs(table["chain_s"] - 0.012) < 1e-9
    merged = tracer_mod.merge_stage_histograms([ctx])
    bd = tracer_mod.breakdown(merged)
    # chain sum vs the aux op_total: 12ms attributed of 14ms measured
    assert abs(bd["attributed_s"] - 0.012) < 1e-9
    assert abs(bd["measured_s"] - 0.014) < 1e-9
    assert abs(bd["unattributed_frac"] - (1 - 12 / 14)) < 1e-3
    # aux stages never count into the attributed sum
    assert "repl_apply" in bd["stages"]

"""Invariant sanitizer: static checker (devtools lint) + runtime
lockdep/loop-stall sanitizer (common/lockdep.py).

Three layers of coverage:

  1. The live package must lint CLEAN — any write-path invariant
     regression (an await sneaking into a submit section, a wall clock
     in an op path, a slot release escaping its finally) is a tier-1
     test failure right here, not a review comment.
  2. Fixture snippets per rule: each must trip EXACTLY its rule, so a
     rule that rots into a no-op (or starts over-matching) fails too.
  3. Runtime injection: a real ``_mu -> _io`` lock-order inversion, a
     cross-loop asyncio-lock misuse and an over-budget synchronous
     loop section must each land in the lockdep report with the
     offending acquisition stacks / owning stage attached.
"""

import asyncio
import json
import subprocess
import sys
import threading
import time

import pytest

from ceph_tpu.common import lockdep
from ceph_tpu.devtools.lint import (lint_paths, lint_project_sources,
                                    lint_source)

# ===================================================== 1. live tree clean


def test_live_package_lints_clean():
    violations, errors = lint_paths()
    assert not errors, errors
    assert not violations, \
        "invariant lint violations on the live tree:\n" + \
        "\n".join(v.render() for v in violations)


def test_cli_entry_point_runs_standalone():
    # the console entry the CI/tooling satellite promises: standalone
    # module invocation, exit 0 on the clean tree
    out = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.devtools.lint",
         "--list-rules"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    for rid in ("AF01", "FP02", "SEND03", "BLK04", "MONO05",
                "LOCK06", "FIN07", "PROTO08", "REPLY09", "EPOCH10",
                "SHARD11", "ESC12", "PORT13", "ATOM14", "SYNC15",
                "JIT16", "XFER17", "STAGE18", "RETRY19", "QOS20"):
        assert rid in out.stdout


def test_cli_json_smoke_schema_roundtrips():
    """The CI satellite: `python -m ceph_tpu.devtools.lint --json` on
    the live tree exits 0 with a schema-versioned document whose
    per-rule summary is complete and which round-trips through json."""
    from ceph_tpu.devtools.lint import JSON_SCHEMA
    from ceph_tpu.devtools.rules import RULE_IDS
    out = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.devtools.lint", "--json"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    doc = json.loads(out.stdout)
    assert doc["schema"] == JSON_SCHEMA
    assert doc["clean"] is True and doc["exit"] == 0
    assert doc["violations"] == [] and doc["errors"] == []
    assert doc["files"] > 100
    assert set(doc["rules"]) == set(RULE_IDS)
    for rid, summary in doc["rules"].items():
        assert summary["violations"] == 0, (rid, summary)
        assert summary["waived"] >= 0
        assert summary["description"]
    # the documented waivers exist (MONO05 persisted stamps etc)
    assert doc["rules"]["MONO05"]["waived"] >= 1
    # schema v2: per-rule analysis wall time rides the summary
    for rid, summary in doc["rules"].items():
        assert "ms" in summary and summary["ms"] >= 0.0, rid
    # schema v2: the unused-waiver audit ran and every in-source
    # waiver (the four documented MONO05/EPOCH10 ones included) still
    # suppresses something — a stale allow is at least a warning
    assert doc["unused_waivers"] == [], doc["unused_waivers"]
    assert doc["strict_waivers"] is False
    # schema v2: the full-package run carries the seam inventory
    assert doc["seam"]["seam_schema"] >= 1
    assert doc["seam"]["summary"]["unprotected_structures"] == 0
    # schema v3: ... and the device inventory, clean on the live tree
    assert doc["device"]["device_schema"] >= 1
    assert doc["device"]["summary"]["unclassified_kernel_sites"] == 0
    assert doc["device"]["summary"]["unsanctioned_syncs"] == 0
    assert doc["device"]["summary"]["per_call_jit"] == 0
    assert "device_analysis_ms" in doc
    # byte-true JSON round trip (CI stores and diffs these)
    assert json.loads(json.dumps(doc)) == doc


def test_cli_exit_code_is_stable_on_violations():
    """Exit contract: 1 = violations (not a crash), stderr carries the
    per-rule summary; the JSON document mirrors the code in 'exit'."""
    import os
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        # explicit file target keeps this hermetic; its rel path won't
        # start with osd/, so use a rule that is not module-scoped
        path = os.path.join(td, "fixture.py")
        with open(path, "w") as f:
            f.write("async def run(self, m, slot):\n"
                    "    await self.do_op(m)\n"
                    "    self.op_window.release(slot)\n")
        out = subprocess.run(
            [sys.executable, "-m", "ceph_tpu.devtools.lint", "--json",
             path],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 1, out.stdout + out.stderr
        doc = json.loads(out.stdout)
        assert doc["exit"] == 1 and doc["clean"] is False
        assert doc["rules"]["FIN07"]["violations"] == 1


# ================================================ 2. one fixture per rule


def _rules_of(src: str, rel: str):
    return sorted({v.rule for v in lint_source(src, rel)})


def test_af01_await_inside_submit_section():
    src = (
        "async def submit(pg):\n"
        "    # awaitfree:begin fixture-submit\n"
        "    version = pg.next_version()\n"
        "    await pg.flush()\n"
        "    # awaitfree:end fixture-submit\n"
        "    return version\n"
    )
    assert _rules_of(src, "osd/fixture.py") == ["AF01"]


def test_af01_async_with_and_unbalanced_sentinel():
    src = (
        "async def submit(pg, lock):\n"
        "    # awaitfree:begin fixture\n"
        "    async with lock:\n"
        "        pg.append_log()\n"
        "    # awaitfree:end fixture\n"
    )
    assert _rules_of(src, "osd/fixture.py") == ["AF01"]
    src2 = (
        "async def submit(pg):\n"
        "    # awaitfree:begin never-closed\n"
        "    pg.append_log()\n"
    )
    assert _rules_of(src2, "osd/fixture.py") == ["AF01"]


def test_af01_clean_region_passes():
    src = (
        "async def submit(pg):\n"
        "    chunks = await pg.encode()\n"
        "    # awaitfree:begin fixture\n"
        "    version = pg.next_version()\n"
        "    pg.append_log(version, chunks)\n"
        "    # awaitfree:end fixture\n"
        "    await pg.gather_acks()\n"
    )
    assert _rules_of(src, "osd/fixture.py") == []


def test_fp02_mutating_a_local_view():
    src = (
        "def deliver(msg):\n"
        "    view = msg.local_view()\n"
        "    view.ops = []\n"
    )
    assert _rules_of(src, "msg/fixture.py") == ["FP02"]


def test_fp02_mutator_call_on_peeked_payload():
    src = (
        "def apply(m, pg):\n"
        "    entry = m.log_entry()\n"
        "    entry.xattrs.update({'a': 1})\n"
    )
    assert _rules_of(src, "osd/fixture.py") == ["FP02"]


def test_fp02_mutation_through_subscript_chain():
    # mutating an op INSIDE the frozen view's list — the most
    # realistic receiver-side slip (result fields belong on the
    # receiver's own result_copy op shells, not the sender's)
    src = (
        "def fill(msg):\n"
        "    view = msg.local_view()\n"
        "    view.ops[0].rval = 0\n"
    )
    assert _rules_of(src, "osd/fixture.py") == ["FP02"]
    src2 = (
        "def fill(msg, data):\n"
        "    view = msg.local_view()\n"
        "    view.ops[0].outdata.append(data)\n"
    )
    assert _rules_of(src2, "osd/fixture.py") == ["FP02"]


def test_fp02_envelope_stamp_and_mutable_copy_pass():
    src = (
        "def deliver(msg, seq):\n"
        "    view = msg.local_view()\n"
        "    view.seq = seq\n"            # receiver-owned envelope
        "    txn = view.payload.mutable(Transaction)\n"
        "    txn.ops = []\n"              # sanctioned mutable copy
    )
    assert _rules_of(src, "msg/fixture.py") == []


def test_send03_mutation_after_first_send():
    src = (
        "def fan_out(osd, peer, rep):\n"
        "    osd.send_osd(peer, rep)\n"
        "    rep.version = 3\n"
    )
    assert _rules_of(src, "osd/fixture.py") == ["SEND03"]


def test_send03_reply_to_request_stays_mutable():
    # reply_to(request, reply) SENDS the reply; stamping tracker state
    # onto the request afterwards is the normal intake path
    src = (
        "def intake(osd, m, tracker):\n"
        "    osd.reply_to(m, make_reply(m))\n"
        "    m.oid = normalize(m.oid)\n"
    )
    assert _rules_of(src, "osd/fixture.py") == []


def test_blk04_blocking_call_in_async_def():
    src = (
        "import time as _time\n"
        "async def tick(self):\n"
        "    _time.sleep(0.1)\n"          # alias must not hide it
    )
    assert _rules_of(src, "osd/fixture.py") == ["BLK04"]
    src2 = (
        "async def load(path):\n"
        "    with open(path) as f:\n"
        "        return f.read()\n"
    )
    assert _rules_of(src2, "mon/fixture.py") == ["BLK04"]


def test_blk04_commit_thread_module_exempt():
    src = (
        "import time\n"
        "async def gather(self):\n"
        "    time.sleep(0.001)\n"
    )
    assert _rules_of(src, "store/commit.py") == []


def test_mono05_wall_clock_in_op_path():
    src = (
        "import time\n"
        "def age(op):\n"
        "    return time.time() - op.start\n"
    )
    assert _rules_of(src, "osd/fixture.py") == ["MONO05"]
    # same code outside the op-path module set is fine (mon leases,
    # rgw mtimes and friends are wall-clock protocol data)
    assert _rules_of(src, "mon/fixture.py") == []


def test_mono05_waiver_comment_is_honored():
    src = (
        "import time\n"
        "def stamp(info):\n"
        "    # lint: allow[MONO05] persisted cross-restart stamp\n"
        "    info.last_scrub_stamp = time.time()\n"
    )
    assert _rules_of(src, "osd/fixture.py") == []


def test_lock06_io_acquired_under_mu():
    src = (
        "def bad(self, txn):\n"
        "    with self._mu:\n"
        "        with self._io:\n"
        "            self.apply(txn)\n"
    )
    assert _rules_of(src, "store/fixture.py") == ["LOCK06"]
    good = (
        "def good(self, txn):\n"
        "    with self._io:\n"
        "        with self._mu:\n"
        "            self.apply(txn)\n"
    )
    assert _rules_of(good, "store/fixture.py") == []


def test_fin07_slot_release_outside_finally():
    src = (
        "async def run(self, m, slot):\n"
        "    await self.do_op(m)\n"
        "    self.op_window.release(slot)\n"
    )
    assert _rules_of(src, "osd/fixture.py") == ["FIN07"]
    good = (
        "async def run(self, m, slot):\n"
        "    try:\n"
        "        await self.do_op(m)\n"
        "    finally:\n"
        "        self.op_window.release(slot)\n"
    )
    assert _rules_of(good, "osd/fixture.py") == []


def test_reply09_early_return_without_discharge():
    src = (
        "def handle(self, m):\n"
        "    if m.stale:\n"
        "        return\n"                     # consumed, never answered
        "    self.osd.reply_to(m, make_reply(m))\n"
    )
    assert _rules_of(src, "osd/fixture.py") == ["REPLY09"]
    # same code outside osd/ is out of scope (mon handlers use their
    # own reply helper and are not dispatch-throttled consumers)
    assert _rules_of(src, "mon/fixture.py") == []


def test_reply09_branch_discharge_does_not_leak_to_fallthrough():
    """A reply inside ONE branch must not discharge the fall-through
    path: the not-cached+stopping path below consumes the op and never
    answers — exactly the client-timeout bug the rule exists for."""
    src = (
        "def handle(self, m):\n"
        "    if m.cached:\n"
        "        self.osd.reply_to(m, cached(m))\n"
        "    if self.stopping:\n"
        "        return\n"
        "    self.osd.reply_to(m, make_reply(m))\n"
    )
    assert _rules_of(src, "osd/fixture.py") == ["REPLY09"]
    # discharged in BOTH arms => the fall-through really is discharged
    both = (
        "def handle(self, m, pg):\n"
        "    if m.cached:\n"
        "        self.osd.reply_to(m, cached(m))\n"
        "    else:\n"
        "        pg.queue_op(m)\n"
        "    if self.stopping:\n"
        "        return\n"
        "    self.osd.reply_to(m, make_reply(m))\n"
    )
    assert _rules_of(both, "osd/fixture.py") == []
    # an arm that RETURNS does not fall through: the state after the
    # if comes from the discharging straight-line path alone
    returns = (
        "def handle(self, m, pg):\n"
        "    if m.bad:\n"
        "        self.osd.reply_to(m, err(m))\n"
        "        return\n"
        "    pg.queue_op(m)\n"
        "    return\n"
    )
    assert _rules_of(returns, "osd/fixture.py") == []


def test_reply09_reply_requeue_handoff_and_waiver_pass():
    replied = (
        "def handle(self, m):\n"
        "    if m.stale:\n"
        "        self.osd.reply_to(m, eagain(m))\n"
        "        return\n"
        "    self.osd.reply_to(m, make_reply(m))\n"
    )
    assert _rules_of(replied, "osd/fixture.py") == []
    requeued = (
        "def handle(self, m, pg):\n"
        "    if not pg.ready:\n"
        "        pg.queue_op(m)\n"
        "        return\n"
        "    self.osd.reply_to(m, make_reply(m))\n"
    )
    assert _rules_of(requeued, "osd/fixture.py") == []
    handoff = (
        "def handle(self, m, loop):\n"
        "    if m.slow:\n"
        "        loop.create_task(self.slow_path(m))\n"
        "        return\n"
        "    self.osd.reply_to(m, make_reply(m))\n"
    )
    assert _rules_of(handoff, "osd/fixture.py") == []
    waived = (
        "def handle(self, m):\n"
        "    if m.stale:\n"
        "        # lint: allow[REPLY09] stale dup: sender already acked\n"
        "        return\n"
        "    self.osd.reply_to(m, make_reply(m))\n"
    )
    assert _rules_of(waived, "osd/fixture.py") == []


def test_epoch10_unguarded_pg_mutation():
    src = (
        "def on_pg_log(self, m):\n"
        "    self.log = m.adopt()\n"
        "    self.save_meta(txn)\n"
    )
    assert _rules_of(src, "osd/fixture.py") == ["EPOCH10"]
    # out of osd/ scope
    assert _rules_of(src, "mon/fixture.py") == []


def test_epoch10_guard_before_mutation_passes():
    good = (
        "def on_pg_log(self, m):\n"
        "    if m.epoch < self.info.same_interval_since:\n"
        "        return\n"
        "    self.log = m.adopt()\n"
        "    self.save_meta(txn)\n"
    )
    assert _rules_of(good, "osd/fixture.py") == []
    waived = (
        "# lint: allow[EPOCH10] staleness arbitrated per object\n"
        "def on_push(self, m):\n"
        "    self.backend.apply_push(m)\n"
    )
    assert _rules_of(waived, "osd/fixture.py") == []


def test_shard11_pg_mutation_from_intake_path():
    """ISSUE 10: PG-state mutation from an intake/heartbeat-path
    function must go through the shard handoff seam."""
    src = (
        "def ms_dispatch(self, m):\n"
        "    pg = self._pg_for(m.pgid)\n"
        "    pg.queue_op(m)\n"
    )
    assert _rules_of(src, "osd/fixture.py") == ["SHARD11"]
    # PG-field assignment from a heartbeat-path function trips too
    src2 = (
        "def _scrub_scheduler(self, m):\n"
        "    pg = self._load_stray_pg(m.pgid)\n"
        "    pg.info.last_scrub_stamp = 0\n"
    )
    assert _rules_of(src2, "osd/fixture.py") == ["SHARD11"]
    # out of intake-module scope (a PG method itself is fine)
    assert _rules_of(src, "common/fixture.py") == []


def test_shard11_seam_routing_and_waiver_pass():
    good = (
        "def ms_dispatch(self, m):\n"
        "    pg = self._pg_for(m.pgid)\n"
        "    self.shards.route(m.pgid, pg.queue_op, m)\n"
    )
    assert _rules_of(good, "osd/fixture.py") == []
    # reads stay legal from intake (status/describe/is_primary)
    good2 = (
        "def _report_stats(self):\n"
        "    pg = self._pg_for(self.pgid)\n"
        "    if pg.is_primary():\n"
        "        x = pg.describe()\n"
    )
    assert _rules_of(good2, "osd/fixture.py") == []
    waived = (
        "def ms_dispatch(self, m):\n"
        "    pg = self._pg_for(m.pgid)\n"
        "    # lint: allow[SHARD11] single-loop teardown sweep\n"
        "    pg.stop()\n"
    )
    assert _rules_of(waived, "osd/fixture.py") == []


def test_proto08_unhandled_wire_type_trips_and_handled_passes():
    messages = (
        "from ceph_tpu.msg.message import Message, register_message\n"
        "@register_message\n"
        "class MFixtureProbe(Message):\n"
        "    TYPE = 9999\n"
    )
    sender = (
        "class OSD:\n"
        "    def kick(self, mon_addr):\n"
        "        self.messenger.send_message(MFixtureProbe(), mon_addr,\n"
        "                                    peer_type=\"mon\")\n"
    )
    mon_missing = (
        "class Monitor:\n"
        "    def ms_dispatch(self, m):\n"
        "        if isinstance(m, MPing):\n"
        "            return True\n"
        "        return False\n"
    )
    vio = lint_project_sources([
        ("osd/fixture_messages.py", messages),
        ("osd/fixture_daemon.py", sender),
        ("mon/monitor.py", mon_missing),
    ])
    assert [v.rule for v in vio] == ["PROTO08"], vio
    assert "MFixtureProbe" in vio[0].msg and "'mon'" in vio[0].msg
    mon_handles = mon_missing.replace("MPing", "MFixtureProbe")
    assert lint_project_sources([
        ("osd/fixture_messages.py", messages),
        ("osd/fixture_daemon.py", sender),
        ("mon/monitor.py", mon_handles),
    ]) == []
    # an edge into a role with NO module in the linted set is skipped
    # (single-file lint must not fabricate missing-handler noise)
    assert lint_project_sources([
        ("osd/fixture_messages.py", messages),
        ("osd/fixture_daemon.py", sender),
    ]) == []


def test_proto08_send_osd_and_local_variable_resolution():
    messages = (
        "from ceph_tpu.msg.message import Message, register_message\n"
        "@register_message\n"
        "class MFixtureSub(Message):\n"
        "    TYPE = 9998\n"
    )
    sender = (
        "class PG:\n"
        "    def fan_out(self, peer):\n"
        "        rep = MFixtureSub()\n"
        "        self.osd.send_osd(peer, rep)\n"
    )
    osd_missing = (
        "class OSD:\n"
        "    def ms_dispatch(self, m):\n"
        "        return False\n"
    )
    vio = lint_project_sources([
        ("osd/fixture_messages.py", messages),
        ("osd/fixture_pg.py", sender),
        ("osd/daemon.py", osd_missing),
    ])
    assert [v.rule for v in vio] == ["PROTO08"], vio


def test_proto08_container_frame_contributes_inner_edges():
    """The MOSDOpBatch satellite: a THROTTLE_SPLIT envelope's send
    contributes its INNER (type, role) edges — a receiver that handles
    only the envelope but not the unpacked inner type is still a
    silent drop."""
    messages = (
        "from ceph_tpu.msg.message import Message, register_message\n"
        "@register_message\n"
        "class MFixInner(Message):\n"
        "    TYPE = 9996\n"
        "@register_message\n"
        "class MFixBatch(Message):\n"
        "    TYPE = 9997\n"
        "    THROTTLE_SPLIT = True\n"
        "    @classmethod\n"
        "    def decode_payload(cls, dec, struct_v):\n"
        "        return cls([MFixInner.from_bytes(b) "
        "for b in dec.list_(lambda d: d.bytes_())])\n"
    )
    sender = (
        "class PG:\n"
        "    def fan_out(self, peer):\n"
        "        self.osd.send_osd(peer, MFixBatch())\n"
    )
    envelope_only = (
        "class OSD:\n"
        "    def ms_dispatch(self, m):\n"
        "        if isinstance(m, MFixBatch):\n"
        "            return True\n"
        "        return False\n"
    )
    vio = lint_project_sources([
        ("osd/fixture_messages.py", messages),
        ("osd/fixture_pg.py", sender),
        ("osd/daemon.py", envelope_only),
    ])
    assert [v.rule for v in vio] == ["PROTO08"], vio
    assert "MFixInner" in vio[0].msg
    assert "container frame MFixBatch" in vio[0].msg
    both = envelope_only.replace("isinstance(m, MFixBatch)",
                                 "isinstance(m, (MFixBatch, MFixInner))")
    assert lint_project_sources([
        ("osd/fixture_messages.py", messages),
        ("osd/fixture_pg.py", sender),
        ("osd/daemon.py", both),
    ]) == []


# ===================================== 2b. seam rules (ESC12/PORT13/ATOM14)


def test_esc12_cross_side_mutation_without_declaration():
    """ISSUE 12 tentpole: a structure written from a shard-lane
    function while the intake side reads it — with no lock, region or
    waiver — escapes the seam."""
    src = (
        "class OSD:\n"
        "    def __init__(self):\n"
        "        self.pgs = {}\n"
        "    def ms_dispatch(self, m):\n"          # intake side reads
        "        return self.pgs.get(m.pgid)\n"
        "    def _run_pg(self, m):\n"              # shard side writes
        "        self.pgs.pop(m.pgid, None)\n"
        "    def kick(self, m):\n"
        "        self.shards.route(m.pgid, self._run_pg, m)\n"
    )
    vio = lint_project_sources([("osd/daemon.py", src)])
    assert [v.rule for v in vio] == ["ESC12"], vio
    assert "pgs" in vio[0].msg


def test_esc12_gil_atomic_region_and_lock_pass():
    declared = (
        "class OSD:\n"
        "    def __init__(self):\n"
        "        self.pgs = {}\n"
        "    def ms_dispatch(self, m):\n"
        "        return self.pgs.get(m.pgid)\n"
        "    def _run_pg(self, m):\n"
        "        # gil-atomic:begin pgs single GIL-step pop\n"
        "        self.pgs.pop(m.pgid, None)\n"
        "        # gil-atomic:end\n"
        "    def kick(self, m):\n"
        "        self.shards.route(m.pgid, self._run_pg, m)\n"
    )
    assert lint_project_sources([("osd/daemon.py", declared)]) == []
    locked = declared.replace(
        "        # gil-atomic:begin pgs single GIL-step pop\n"
        "        self.pgs.pop(m.pgid, None)\n"
        "        # gil-atomic:end\n",
        "        with self._pg_lock:\n"
        "            self.pgs.pop(m.pgid, None)\n")
    assert lint_project_sources([("osd/daemon.py", locked)]) == []


def test_esc12_rmw_scalar_counter():
    """An augassign is never atomic whatever the type: a counter
    bumped from a seam-crossing function is flagged too (the live-tree
    catch: OSD.next_tid could mint duplicate tids across shards)."""
    src = (
        "class OSD:\n"
        "    def _mint(self, m):\n"
        "        self._tid += 1\n"
        "    def ms_dispatch(self, m):\n"
        "        self.shards.route(m.pgid, self._mint, m)\n"
    )
    vio = lint_project_sources([("osd/daemon.py", src)])
    assert [v.rule for v in vio] == ["ESC12"], vio
    assert "_tid" in vio[0].msg


def test_port13_live_object_reference_crossing_the_seam():
    """The live-tree catch: a PG object passed as DATA through
    shards.route cannot exist in the sending process once lanes
    split — pass the routing key and re-resolve."""
    src = (
        "class OSD:\n"
        "    def ms_dispatch(self, m):\n"
        "        pg = self._pg_for(m.pgid)\n"
        "        self.shards.route(m.pgid, self._run_pg, pg)\n"
        "    def _run_pg(self, pg):\n"
        "        pass\n"
    )
    vio = lint_project_sources([("osd/daemon.py", src)])
    assert [v.rule for v in vio] == ["PORT13"], vio
    assert "live shared-object reference" in vio[0].msg


def test_port13_closure_and_clean_handoff():
    closure = (
        "class OSD:\n"
        "    def ms_dispatch(self, m):\n"
        "        self.shards.route(m.pgid, lambda: self.apply(m))\n"
    )
    vio = lint_project_sources([("osd/daemon.py", closure)])
    assert [v.rule for v in vio] == ["PORT13"], vio
    assert "lambda/closure" in vio[0].msg
    # the sanctioned shapes: bound method + wire message + routing key
    clean = (
        "class OSD:\n"
        "    def ms_dispatch(self, m):\n"
        "        self.shards.route(m.pgid, self._run_pg, m)\n"
        "    def _run_pg(self, m):\n"
        "        pass\n"
    )
    assert lint_project_sources([("osd/daemon.py", clean)]) == []
    waived = closure.replace(
        "        self.shards.route",
        "        # lint: allow[PORT13] fixture waiver\n"
        "        self.shards.route")
    assert lint_project_sources([("osd/daemon.py", waived)]) == []


def test_port13_keyword_arguments_cannot_evade():
    """A kwarg-passed live ref or closure crosses the seam exactly
    like a positional one and must classify the same way."""
    live_kw = (
        "class OSD:\n"
        "    def ms_dispatch(self, m):\n"
        "        pg = self._pg_for(m.pgid)\n"
        "        self.shards.route(m.pgid, self._run_pg, pg=pg)\n"
        "    def _run_pg(self, pg=None):\n"
        "        pass\n"
    )
    vio = lint_project_sources([("osd/daemon.py", live_kw)])
    assert [v.rule for v in vio] == ["PORT13"], vio
    closure_kw = (
        "class OSD:\n"
        "    def ms_dispatch(self, m):\n"
        "        self.shards.route(m.pgid, fn=lambda: self.apply(m))\n"
    )
    vio = lint_project_sources([("osd/daemon.py", closure_kw)])
    assert [v.rule for v in vio] == ["PORT13"], vio
    assert "lambda/closure" in vio[0].msg


def test_port13_raw_bytes_over_threshold_escape():
    """ISSUE 20: bulk payload bytes crossing the seam INLINE are the
    escape the shared-memory extent pool exists to close — one ring
    copy in, one out, per hop.  A conventional payload name handed
    through shards.route is flagged with the extent-pool remedy; the
    sanctioned shape (publish once, pass the (pool, gen, off, len)
    handle) is clean."""
    raw = (
        "class OSD:\n"
        "    def ms_dispatch(self, m):\n"
        "        payload = m.data\n"
        "        self.shards.route(m.pgid, self._apply, payload)\n"
        "    def _apply(self, payload):\n"
        "        pass\n"
    )
    vio = lint_project_sources([("osd/daemon.py", raw)])
    assert [v.rule for v in vio] == ["PORT13"], vio
    assert "extent pool" in vio[0].msg and "handle" in vio[0].msg
    # the zero-copy shape: the handle is a named segment + scalars
    clean = (
        "class OSD:\n"
        "    def ms_dispatch(self, m):\n"
        "        handle = self.ext_pool.put(m.data)\n"
        "        self.shards.route(m.pgid, self._apply, handle)\n"
        "    def _apply(self, handle):\n"
        "        pass\n"
    )
    assert lint_project_sources([("osd/daemon.py", clean)]) == []


def test_atom14_write_outside_declared_region():
    """Once a structure is declared gil-atomic, EVERY write in the
    module must sit inside a region — the region set stays exhaustive,
    so the seam inventory it compiles into can be trusted."""
    src = (
        "class Shard:\n"
        "    def __init__(self):\n"          # construction is exempt
        "        self.ring = []\n"
        "    def post(self, item):\n"
        "        # gil-atomic:begin ring single-producer append\n"
        "        self.ring.append(item)\n"
        "        # gil-atomic:end\n"
        "    def sneak(self, item):\n"
        "        self.ring.append(item)\n"   # outside any region
    )
    vio = lint_project_sources([("osd/shards.py", src)])
    assert [v.rule for v in vio] == ["ATOM14"], vio
    assert "'ring'" in vio[0].msg


def test_atom14_region_hygiene():
    unbalanced = (
        "class Shard:\n"
        "    def post(self, item):\n"
        "        # gil-atomic:begin ring never closed\n"
        "        self.ring.append(item)\n"
    )
    vio = lint_project_sources([("osd/shards.py", unbalanced)])
    assert [v.rule for v in vio] == ["ATOM14"], vio
    missing_reason = (
        "class Shard:\n"
        "    def post(self, item):\n"
        "        # gil-atomic:begin ring\n"
        "        self.ring.append(item)\n"
        "        # gil-atomic:end\n"
    )
    vio = lint_project_sources([("osd/shards.py", missing_reason)])
    assert [v.rule for v in vio] == ["ATOM14"], vio
    assert "reason" in vio[0].msg


def test_seam_report_fixture_inventory():
    """The seam inventory classifies every crossing value and every
    declared region with source locations (fixture-scale check; the
    live-tree inventory is covered by the subprocess smoke)."""
    from ceph_tpu.devtools.rules import FileInfo
    from ceph_tpu.devtools.seam import SeamAnalysis
    src = (
        "class OSD:\n"
        "    def __init__(self):\n"
        "        self.pgs = {}\n"
        "    def ms_dispatch(self, m):\n"
        "        self.shards.route(m.pgid, self._run_pg, m)\n"
        "    def _run_pg(self, m):\n"
        "        # gil-atomic:begin pgs one-GIL-step insert\n"
        "        self.pgs[m.pgid] = m\n"
        "        # gil-atomic:end\n"
    )
    an = SeamAnalysis([FileInfo("osd/daemon.py", src)])
    assert an.violations == []
    rep = an.report()
    assert rep["seam_schema"] >= 1
    assert rep["summary"]["sites"] == 1
    site = rep["sites"][0]
    assert site["kind"] == "shard-route" and site["line"] == 5
    classes = {v["class"] for v in site["values"]}
    assert classes == {"primitive", "home-bound", "wire"}
    assert rep["gil_atomic_regions"][0]["attrs"] == ["pgs"]
    (entry,) = rep["shared_state"]
    assert entry["attr"] == "pgs"
    assert entry["classification"] == "gil-atomic"
    assert json.loads(json.dumps(rep)) == rep


# ============================ 2c. device rules (SYNC15/JIT16/XFER17)


def test_sync15_device_sync_in_async_op_path():
    """ISSUE 14 tentpole: an implicit device->host sync inside an
    async op-path function stalls the shard loop — violation."""
    src = (
        "class ECBackend:\n"
        "    async def _encode_object(self, data):\n"
        "        y = self.kernel.device_call(data)\n"
        "        return float(y)\n"
    )
    vio = lint_project_sources([("osd/fixture.py", src)])
    assert [v.rule for v in vio] == ["SYNC15"], vio
    assert "device->host sync" in vio[0].msg
    # the sanctioned shape: await the executor, fetch nothing inline
    clean = (
        "class ECBackend:\n"
        "    async def _encode_object(self, data):\n"
        "        parity = await self.ec_queue.apply(self.gen, data)\n"
        "        return parity\n"
    )
    assert lint_project_sources([("osd/fixture.py", clean)]) == []


def test_sync15_declared_region_in_sync_fn_passes():
    """A declared device-sync region sanctions the fetch — but only in
    a SYNC function (the executor shape); the same region inside an
    async def is itself a violation."""
    import textwrap
    region = textwrap.dedent("""\
        def _run_group(self, chunks):
            out = self.kernel.device_call(chunks)
            # device-sync:begin executor-thread group fetch
            res = np.asarray(out)
            # device-sync:end
            return res
        """)
    assert lint_project_sources([("ec/kernel.py", region)]) == []
    bare = region.replace(
        "    # device-sync:begin executor-thread group fetch\n", "") \
        .replace("    # device-sync:end\n", "")
    vio = lint_project_sources([("ec/kernel.py", bare)])
    assert [v.rule for v in vio] == ["SYNC15"], vio
    async_region = "async " + region
    vio = lint_project_sources([("ec/kernel.py", async_region)])
    assert vio and all(v.rule == "SYNC15" for v in vio), vio
    assert any("async" in v.msg for v in vio)
    waived = bare.replace(
        "    res = np.asarray(out)\n",
        "    # lint: allow[SYNC15] fixture: measured fetch\n"
        "    res = np.asarray(out)\n")
    assert lint_project_sources([("ec/kernel.py", waived)]) == []


def test_sync15_region_hygiene():
    no_reason = (
        "def fetch(self, out):\n"
        "    # device-sync:begin\n"
        "    return np.asarray(out)\n"
        "    # device-sync:end\n"
    )
    vio = lint_project_sources([("ec/kernel.py", no_reason)])
    assert [v.rule for v in vio] == ["SYNC15"], vio
    assert "reason" in vio[0].msg
    unclosed = (
        "def fetch(self, out):\n"
        "    # device-sync:begin fixture fetch\n"
        "    return out\n"
    )
    vio = lint_project_sources([("ec/kernel.py", unclosed)])
    assert [v.rule for v in vio] == ["SYNC15"], vio


def test_jit16_per_call_jit_lambda():
    """The live-tree catch: the ec/kernel.py autotuner built a
    jax.jit(lambda ...) per variant per sweep — a fresh compile cache
    every call."""
    src = (
        "def _tune(self, d):\n"
        "    import jax\n"
        "    fetch = jax.jit(lambda x: x + 1)\n"
        "    return fetch(d)\n"
    )
    vio = lint_project_sources([("ec/fixture.py", src)])
    assert vio and {v.rule for v in vio} == {"JIT16"}, vio
    assert any("lambda" in v.msg for v in vio)


def test_jit16_builder_return_and_guarded_cache_pass():
    builder = (
        "def make_step(step):\n"
        "    import jax\n"
        "    return jax.jit(step)\n"
    )
    assert lint_project_sources([("ops/fixture.py", builder)]) == []
    guarded = (
        "_fn_cache = {}\n"
        "def get_step(self, key, step):\n"
        "    import jax\n"
        "    if key not in _fn_cache:\n"
        "        _fn_cache[key] = jax.jit(step)\n"
        "    return _fn_cache[key]\n"
    )
    assert lint_project_sources([("ops/fixture.py", guarded)]) == []
    # the guarded-GLOBAL shape (crush_kernel._get_winners_fn):
    # construct once behind `x is None`, invoke the cached object
    global_cache = (
        "_fn = None\n"
        "def step_fn(self, step, x):\n"
        "    import jax\n"
        "    global _fn\n"
        "    if _fn is None:\n"
        "        _fn = jax.jit(step)\n"
        "    return _fn(x)\n"
    )
    assert lint_project_sources([("ops/fixture.py", global_cache)]) == []
    # construct-and-invoke with NO cache guard: every call retraces
    unguarded = (
        "def run_step(self, step, x):\n"
        "    import jax\n"
        "    fn = jax.jit(step)\n"
        "    return fn(x)\n"
    )
    vio = lint_project_sources([("ops/fixture.py", unguarded)])
    assert vio and {v.rule for v in vio} == {"JIT16"}, vio
    # an UNRELATED is/in comparison in the body must not silence the
    # rule: only a guard on the jit binding itself sanctions it
    decoy_guard = (
        "def run_step(self, step, x, mode=None):\n"
        "    import jax\n"
        "    if mode is None:\n"
        "        mode = 'a'\n"
        "    fn = jax.jit(step)\n"
        "    return fn(x)\n"
    )
    vio = lint_project_sources([("ops/fixture.py", decoy_guard)])
    assert vio and {v.rule for v in vio} == {"JIT16"}, vio


def test_xfer17_opaque_transfer_trips_staged_and_wire_pass():
    opaque = (
        "def _stage(self, blob):\n"
        "    import jax.numpy as jnp\n"
        "    return jnp.asarray(blob)\n"
    )
    vio = lint_project_sources([("osd/fixture.py", opaque)])
    assert [v.rule for v in vio] == ["XFER17"], vio
    assert "stage it" in vio[0].msg
    clean = (
        "def _stage(self, chunks, table):\n"
        "    import jax\n"
        "    import jax.numpy as jnp\n"
        "    a = jnp.asarray(chunks)\n"          # wire-classified buffer
        "    b = jax.device_put(table)\n"        # declared staging
        "    return a, b\n"
    )
    assert lint_project_sources([("osd/fixture.py", clean)]) == []
    waived = opaque.replace(
        "    return jnp.asarray(blob)\n",
        "    # lint: allow[XFER17] fixture: blob layout pinned upstream\n"
        "    return jnp.asarray(blob)\n")
    assert lint_project_sources([("osd/fixture.py", waived)]) == []


def test_device_report_fixture_inventory():
    """The device inventory classifies candidate kernel sites with
    sync/retrace/transfer verdicts (fixture-scale; the live tree is
    covered by the subprocess smoke)."""
    from ceph_tpu.devtools.device import DeviceAnalysis
    from ceph_tpu.devtools.rules import FileInfo
    src = (
        "class Objecter:\n"
        "    def _flush_cork(self):\n"
        "        pend, self._cork = self._cork, []\n"
        "        # device-candidate:crush-placement@landed one batched\n"
        "        # kernel call per cork (CHUNK_SIZES-bucketed)\n"
        "        self.messenger.send_message(pend)\n"
    )
    an = DeviceAnalysis([FileInfo("client/fixture.py", src)])
    assert an.violations == []
    rep = an.report()
    assert rep["device_schema"] >= 1
    (site,) = rep["kernel_sites"]
    assert site["kind"] == "crush-placement"
    assert site["fn"].endswith("_flush_cork")
    assert site["sync"] == "clean"
    assert site["retrace"] == "CHUNK_SIZES"
    assert site["landed"] is True
    assert rep["summary"]["landed_kernel_sites"] == 1
    assert rep["summary"]["unclassified_kernel_sites"] == 0
    assert json.loads(json.dumps(rep)) == rep


# ===================================== 2d. STAGE18 (stage coverage)


def test_stage18_undeclared_stage_name_trips():
    """ISSUE 15 CI satellite: a span cut naming a stage that is not
    declared in CHAIN_STAGES/AUX_STAGES silently falls out of the
    attributed chain sum — violation; declared names pass."""
    src = (
        "def _admit(self, m):\n"
        "    m._span.cut(\"que_wait\", self.tracer.hist)\n"
    )
    vio = lint_project_sources([("osd/fixture.py", src)])
    assert [v.rule for v in vio] == ["STAGE18"], vio
    assert "undeclared stage" in vio[0].msg
    clean = src.replace("que_wait", "queue_wait_pump")
    assert lint_project_sources([("osd/fixture.py", clean)]) == []
    # explicit-duration attribution sites (Span.attribute) are held to
    # the same declaration discipline as cut()
    attr = (
        "def _hop(self, span, dwell):\n"
        "    span.attribute(\"ringe_wait\", dwell)\n"
    )
    vio = lint_project_sources([("osd/fixture.py", attr)])
    assert [v.rule for v in vio] == ["STAGE18"], vio
    ok = attr.replace("ringe_wait", "ring_wait")
    assert lint_project_sources([("osd/fixture.py", ok)]) == []
    # the tracer's sections and intervals name stages the same way: a
    # misspelt section falls out of every reader that sums loop_* / seam_*
    for call, bad, good in (
            ("with self.tracer.section(\"{}\"):\n        pass\n",
             "loop_ec_hots", "loop_ec_host"),
            ("self.tracer.interval(\"{}\", t0)\n",
             "seam_pendign", "seam_pending")):
        body = "def _f(self, t0):\n    " + call
        vio = lint_project_sources([("osd/fixture.py",
                                     body.format(bad))])
        assert [v.rule for v in vio] == ["STAGE18"], vio
        assert lint_project_sources([("osd/fixture.py",
                                      body.format(good))]) == []
    # waiver escape hatch
    waived = src.replace(
        "    m._span.cut(",
        "    # lint: allow[STAGE18] fixture: exotic local stage\n"
        "    m._span.cut(")
    assert lint_project_sources([("osd/fixture.py", waived)]) == []


def test_stage18_coverage_half_needs_whole_tree():
    """The every-declared-stage-has-a-cut-site half only runs on a
    whole-op-path file set (all anchors present): a partial lint must
    not report every stage as uncovered.  The live tree IS whole and
    lints clean (test_live_package_lints_clean), which proves every
    CHAIN stage currently has a site."""
    from ceph_tpu.devtools.rules import (_STAGE_COVERAGE_ANCHORS,
                                         check_stage18, FileInfo)
    # partial set: one file with one legal cut, no anchors -> clean
    fi = FileInfo("osd/fixture.py",
                  "def f(s):\n    s.cut(\"prepare\")\n")
    assert list(check_stage18([fi])) == []
    # the anchors the gate keys on must all exist in the live package
    import os
    pkg = os.path.dirname(os.path.dirname(
        os.path.abspath(__import__("ceph_tpu").__file__)))
    for rel in _STAGE_COVERAGE_ANCHORS:
        assert os.path.exists(os.path.join(pkg, "ceph_tpu", rel)), rel


def test_lint_json_carries_stage_coverage_block():
    """lint --json schema 4: whole-package runs expose the per-stage
    cut-site inventory (diffable, like the seam/device blocks)."""
    from ceph_tpu.common.tracer import CHAIN_STAGES
    from ceph_tpu.devtools.lint import JSON_SCHEMA, lint_report
    assert JSON_SCHEMA >= 4
    doc = lint_report()
    assert doc["stages"]["declared_chain"] == list(CHAIN_STAGES)
    sites = doc["stages"]["sites"]
    for name in ("ring_wait", "lane_codec", "queue_wait_ring",
                 "queue_wait_pump"):
        assert sites.get(name, 0) >= 1, (name, sites)
    assert json.loads(json.dumps(doc["stages"])) == doc["stages"]


# ================================ 2d2. RETRY19 (retry-backoff policy)


def test_retry19_fixed_sleep_retry_loop_trips():
    """ISSUE 18: a constant-interval sleep inside a retry/poll while
    loop of an async op-path function hammers a degraded cluster in
    lockstep — violation; the same loop riding the shared Backoff
    passes."""
    src = (
        "import asyncio\n"
        "async def wait_primary(self):\n"
        "    while self.primary < 0:\n"
        "        await asyncio.sleep(0.05)\n"
    )
    vio = lint_source(src, "osd/fixture.py", rule="RETRY19")
    assert [v.rule for v in vio] == ["RETRY19"], vio
    assert "shared jittered backoff" in vio[0].msg
    backed = (
        "import asyncio\n"
        "from ceph_tpu.common.backoff import Backoff\n"
        "async def wait_primary(self):\n"
        "    bo = Backoff(\"primary_wait\", base=0.05)\n"
        "    while self.primary < 0:\n"
        "        await bo.sleep()\n"
    )
    assert lint_source(backed, "osd/fixture.py", rule="RETRY19") == []


def test_retry19_same_loop_backoff_covers_aux_sleep():
    """A loop already riding the policy may carry an extra literal
    sleep (e.g. a post-resend settle) — the Backoff await in the SAME
    loop is the discipline, so it passes."""
    src = (
        "import asyncio\n"
        "from ceph_tpu.common.backoff import Backoff\n"
        "async def resend(self):\n"
        "    bo = Backoff(\"resend\")\n"
        "    while True:\n"
        "        await bo.wait_for(self.fut)\n"
        "        await asyncio.sleep(0.1)\n"
    )
    assert lint_source(src, "osd/fixture.py", rule="RETRY19") == []


def test_retry19_exemptions_yield_config_scope():
    """sleep(0) yield-to-loop, config-driven delays, sync functions and
    files outside osd//client/ are all out of scope."""
    yield_idiom = (
        "import asyncio\n"
        "async def drain(self):\n"
        "    while self.q:\n"
        "        await asyncio.sleep(0)\n"
    )
    assert lint_source(yield_idiom, "osd/fixture.py", rule="RETRY19") == []
    config_driven = (
        "import asyncio\n"
        "async def throttle(self):\n"
        "    d = float(self.cfg[\"osd_recovery_sleep\"])\n"
        "    while self.more():\n"
        "        await asyncio.sleep(d)\n"
    )
    assert lint_source(config_driven, "osd/fixture.py", rule="RETRY19") == []
    fixed = (
        "import asyncio\n"
        "async def wait(self):\n"
        "    while self.primary < 0:\n"
        "        await asyncio.sleep(0.05)\n"
    )
    # common/ (the policy's own home) is not held to the rule
    assert lint_source(fixed, "common/fixture.py", rule="RETRY19") == []


def test_retry19_swallowed_timeout_trips():
    """`except TimeoutError: pass` (either flavour — 3.10 still splits
    asyncio.TimeoutError from TimeoutError) silently drops a deadline
    with no counter or give-up tag — violation; a waiver stating why
    the silence is safe passes."""
    src = (
        "import asyncio\n"
        "async def notify(self, fut):\n"
        "    try:\n"
        "        await asyncio.wait_for(fut, 5.0)\n"
        "    except asyncio.TimeoutError:\n"
        "        pass\n"
    )
    vio = lint_source(src, "osd/fixture.py", rule="RETRY19")
    assert [v.rule for v in vio] == ["RETRY19"], vio
    assert "swallows" in vio[0].msg
    bare = src.replace("asyncio.TimeoutError", "TimeoutError")
    vio = lint_source(bare, "client/fixture.py", rule="RETRY19")
    assert [v.rule for v in vio] == ["RETRY19"], vio
    waived = src.replace(
        "    except asyncio.TimeoutError:",
        "    # lint: allow[RETRY19] fixture: timeout is the protocol\n"
        "    except asyncio.TimeoutError:")
    assert lint_source(waived, "osd/fixture.py", rule="RETRY19") == []
    # a handler that DOES something with the timeout is fine
    handled = src.replace("        pass\n",
                          "        self.perf.inc(\"notify_timeout\")\n")
    assert lint_source(handled, "osd/fixture.py", rule="RETRY19") == []


def test_retry19_waiver_on_sleep_line():
    """Waiver escape hatch for legitimate fixed cadences (pump belts,
    heartbeat-scale polls) — on the sleep line or the line above."""
    src = (
        "import asyncio\n"
        "async def pump(self):\n"
        "    while not self._stopping:\n"
        "        # lint: allow[RETRY19] fixture: pump belt cadence\n"
        "        await asyncio.sleep(0.2)\n"
    )
    assert lint_source(src, "osd/fixture.py", rule="RETRY19") == []


def test_qos20_untagged_op_queue_put_trips():
    """ISSUE 19: an op enqueued to a PG op queue without an explicit
    class rides the 'client' default — under dmClock that bills
    foreign work against the client reservation; violation.  The
    tagged put (positional or klass=) passes."""
    src = (
        "def requeue(self, m):\n"
        "    self._op_queue.put_nowait(m)\n"
    )
    vio = lint_source(src, "osd/fixture.py", rule="QOS20")
    assert [v.rule for v in vio] == ["QOS20"], vio
    assert "QoS class" in vio[0].msg
    tagged = (
        "def requeue(self, m):\n"
        "    self._op_queue.put_nowait(m, \"background\")\n"
    )
    assert lint_source(tagged, "osd/fixture.py", rule="QOS20") == []
    kw = (
        "def requeue(self, m):\n"
        "    self.pg._op_queue.put_nowait(m, klass=\"scrub\")\n"
    )
    assert lint_source(kw, "osd/fixture.py", rule="QOS20") == []


def test_qos20_scope_and_waiver():
    """Only op-queue receivers in osd/ are in scope: plain asyncio
    queues and non-osd modules pass untagged; a documented
    default-class put passes with the waiver."""
    plain_queue = (
        "def hand_off(self, m):\n"
        "    self._ring.put_nowait(m)\n"
    )
    assert lint_source(plain_queue, "osd/fixture.py", rule="QOS20") == []
    outside = (
        "def requeue(self, m):\n"
        "    self._op_queue.put_nowait(m)\n"
    )
    assert lint_source(outside, "client/fixture.py", rule="QOS20") == []
    waived = (
        "def requeue(self, m):\n"
        "    # lint: allow[QOS20] fixture: deliberate default class\n"
        "    self._op_queue.put_nowait(m)\n"
    )
    assert lint_source(waived, "osd/fixture.py", rule="QOS20") == []


# ================================ 2e. waiver audit + lint performance


def test_unused_waiver_detection_and_strict_promotion():
    import os
    import tempfile
    from ceph_tpu.devtools.lint import lint_report
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "fixture.py")
        with open(path, "w") as f:
            f.write("def f():\n"
                    "    # lint: allow[MONO05] stale: nothing here\n"
                    "    return 1\n")
        doc = lint_report([path])
        assert doc["exit"] == 0      # a stale waiver alone is a warning
        (uw,) = doc["unused_waivers"]
        assert uw["rel"].endswith("fixture.py")
        assert uw["line"] == 2 and uw["rule"] == "MONO05"
        strict = lint_report([path], strict_waivers=True)
        assert strict["exit"] == 1 and strict["clean"] is False
        (vio,) = strict["violations"]
        assert vio["rule"] == "WAIVER" and "MONO05" in vio["msg"]


def test_waiver_usage_is_per_run_despite_parse_cache():
    """FileInfo objects persist in the parse cache across lint runs;
    usage recorded by an EARLIER run (or injected) must not mask a
    waiver that suppresses nothing THIS run."""
    import os
    import tempfile
    from ceph_tpu.devtools import lint as lint_mod
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "fixture.py")
        with open(path, "w") as f:
            f.write("def f():\n"
                    "    # lint: allow[MONO05] stale\n"
                    "    return 1\n")
        doc = lint_mod.lint_report([path], strict_waivers=True)
        assert doc["exit"] == 1          # stale, flagged
        # simulate a prior run having consumed the waiver: the cached
        # FileInfo carries stale usage into the next run
        ap = os.path.abspath(path)
        fi = lint_mod._FILE_CACHE[ap][2]
        fi.waiver_used.add(("MONO05", 2))
        doc = lint_mod.lint_report([path], strict_waivers=True)
        assert doc["exit"] == 1, \
            "stale waiver masked by usage leaked from a previous run"


def test_live_waiver_is_counted_used_not_stale():
    import os
    import tempfile
    from ceph_tpu.devtools.lint import lint_report
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "fixture.py")
        with open(path, "w") as f:
            # FIN07 is not module-scoped, so it fires on any rel path
            f.write("async def run(self, m, slot):\n"
                    "    await self.do_op(m)\n"
                    "    # lint: allow[FIN07] fixture: failure handled upstream\n"
                    "    self.op_window.release(slot)\n")
        doc = lint_report([path], strict_waivers=True)
        assert doc["exit"] == 0, doc["violations"]
        assert doc["unused_waivers"] == []
        assert doc["rules"]["FIN07"]["waived"] == 1


def test_cli_strict_waivers_live_tree_clean():
    """The audit satellite's acceptance: every in-source waiver in the
    live package — the documented MONO05/EPOCH10 set included — still
    suppresses a real would-be violation even under --strict-waivers."""
    out = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.devtools.lint",
         "--strict-waivers", "--json"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    doc = json.loads(out.stdout)
    assert doc["strict_waivers"] is True
    assert doc["unused_waivers"] == []
    # the documented wall-clock/epoch waivers are all live (the
    # fourth MONO05: the fastpath forward envelope's wire recv_stamp)
    assert doc["rules"]["MONO05"]["waived"] == 4
    assert doc["rules"]["EPOCH10"]["waived"] == 1


def test_lint_parse_cache_cuts_full_tree_wall_time():
    """The performance satellite: each module parses ONCE into a
    shared FileInfo cache used by all rules; a second full-tree lint
    in the same process re-parses nothing and must be faster."""
    from ceph_tpu.devtools import lint as lint_mod
    lint_mod._FILE_CACHE.clear()
    lint_mod.CACHE_STATS.update(hits=0, misses=0)
    t0 = time.perf_counter()
    lint_paths()
    cold = time.perf_counter() - t0
    misses = lint_mod.CACHE_STATS["misses"]
    assert misses > 100          # the whole package really parsed
    # best-of-two warm runs: the drop is structural (no parse, no
    # seam re-analysis), but a single run can eat a CI scheduler
    # stall — requiring BOTH to stall before flaking
    warms = []
    for _ in range(2):
        t0 = time.perf_counter()
        lint_paths()
        warms.append(time.perf_counter() - t0)
    warm = min(warms)
    assert lint_mod.CACHE_STATS["misses"] == misses, \
        "warm lints re-parsed files the cache should have served"
    assert lint_mod.CACHE_STATS["hits"] >= misses
    assert warm < cold, (warm, cold)


def test_cli_changed_mode_smoke():
    """--changed reports only git-touched package files (pre-commit
    mode) but ANALYZES the whole package — a subset call graph can't
    see the callers that prove a function single-sided, so the seam
    rules would flag phantom cross-side escapes in untouched
    architecture whenever a seam-adjacent file is in the diff.  Exit
    must be clean whether the worktree is dirty (touched files are
    part of the clean live tree) or pristine."""
    out = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.devtools.lint", "--changed"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr


# ============================= 2e. committed inventories (seam+device)


def test_cli_seam_report_roundtrips_and_matches_committed():
    """Acceptance: `ceph-tpu-lint --seam-report` emits a
    schema-versioned JSON inventory of every seam-crossing value,
    region and shared structure; the committed SEAM_INVENTORY.json is
    the same inventory structurally (line numbers aside), so the
    GIL-escape work-list cannot silently rot."""
    import pathlib
    from ceph_tpu.devtools.seam import SEAM_SCHEMA
    out = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.devtools.lint",
         "--seam-report"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    assert doc["seam_schema"] == SEAM_SCHEMA
    assert doc["partial"] is False   # whole-package work-list
    assert json.loads(json.dumps(doc)) == doc
    # the structures the ISSUE names are inventoried
    shared = {(e["module"], e["attr"]): e["classification"]
              for e in doc["shared_state"]}
    assert shared[("osd/shards.py", "ring")] == "gil-atomic"
    assert shared[("osd/shards.py", "_ring")] == "gil-atomic"
    assert shared[("store/commit.py", "_staged")] == "gil-atomic"
    assert shared[("osd/daemon.py", "pgs")] == "gil-atomic"
    assert shared[("msg/payload.py", "encode_calls")] == "gil-atomic"
    assert shared[("osd/daemon.py", "_waiting_maps")] == "lock"
    assert doc["summary"]["unprotected_structures"] == 0
    assert doc["summary"]["sites"] >= 20
    # every value at every site is classified
    for site in doc["sites"]:
        for v in site["values"]:
            assert v["class"] and v["role"]
    # committed work-list stays structurally in sync (regenerate with
    # `python -m ceph_tpu.devtools.lint --seam-report` when it drifts)
    committed_path = pathlib.Path(__file__).parent.parent \
        / "SEAM_INVENTORY.json"
    committed = json.loads(committed_path.read_text())
    assert committed["seam_schema"] == doc["seam_schema"]
    assert committed["partial"] is False, \
        "a partial (--changed / explicit-path) inventory was " \
        "committed over the whole-package work-list"

    def shape(d):
        return {
            "shared": sorted((e["module"], e["class"] or "", e["attr"],
                              e["classification"])
                             for e in d["shared_state"]),
            "regions": sorted((r["rel"], ",".join(r["attrs"]))
                              for r in d["gil_atomic_regions"]),
            "sites": sorted((s["rel"], s["kind"],
                             tuple(sorted(v["class"]
                                          for v in s["values"])))
                            for s in d["sites"]),
        }
    assert shape(committed) == shape(doc), \
        "SEAM_INVENTORY.json drifted from the live tree — regenerate " \
        "with: python -m ceph_tpu.devtools.lint --seam-report > " \
        "SEAM_INVENTORY.json"


def test_cli_device_report_roundtrips_and_matches_committed():
    """Acceptance (ISSUE 14): `ceph-tpu-lint --device-report` emits a
    schema-versioned inventory with every candidate kernel call site
    classified (sync/retrace/transfer), zero unsanctioned syncs, zero
    unportable transfers, zero per-call jit — and the committed
    DEVICE_INVENTORY.json stays structurally in sync, so the
    batched-CRUSH-in-the-data-path work-list cannot silently rot."""
    import pathlib
    from ceph_tpu.devtools.device import DEVICE_SCHEMA
    out = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.devtools.lint",
         "--device-report"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    assert doc["device_schema"] == DEVICE_SCHEMA
    assert doc["partial"] is False    # whole-package work-list
    assert json.loads(json.dumps(doc)) == doc
    s = doc["summary"]
    assert s["unclassified_kernel_sites"] == 0
    assert s["unsanctioned_syncs"] == 0
    assert s["unportable_transfers"] == 0
    assert s["per_call_jit"] == 0
    # the ISSUE-named candidate sites are all inventoried + classified
    kinds = {k["kind"]: k for k in doc["kernel_sites"]}
    assert "crush-placement" in kinds       # Objecter corked batch
    assert "ec-encode" in kinds             # ECBackend via ec_queue
    assert "ec-decode" in kinds             # degraded-read rebuild
    assert "decode-rebuild" in kinds        # recovery rebuild
    assert "ec-dispatch" in kinds           # the live executor launch
    assert kinds["crush-placement"]["retrace"] == "CHUNK_SIZES"
    assert kinds["ec-encode"]["sync"] == "clean"
    assert kinds["ec-dispatch"]["side"] == "executor"
    assert kinds["ec-dispatch"]["sync"] == "declared-region"
    assert kinds["ec-dispatch"]["transfer"] == "staged"
    # ISSUE 16: the batched-placement PR consumed the work-list — every
    # inventoried site is marked landed in-source
    assert all(k["landed"] for k in kinds.values()), kinds
    assert s["landed_kernel_sites"] == s["kernel_sites"]
    # every jit entry carries a cache kind; none are per-call
    for j in doc["jit_entries"]:
        assert j["cache"] in ("module", "builder-return",
                              "guarded-cache"), j
    # the fixed live-tree findings stay fixed: the autotuner probe is
    # a module-level jit entry, the winners kernel a guarded cache
    names = {(j["rel"], j["name"]): j["cache"]
             for j in doc["jit_entries"]}
    assert names[("ec/kernel.py", "_pallas_probe_sum")] == "module"
    assert names[("ops/crush_kernel.py",
                  "_get_winners_fn")] == "guarded-cache"
    # committed work-list stays structurally in sync (regenerate with
    # `python -m ceph_tpu.devtools.lint --device-report` on drift)
    committed_path = pathlib.Path(__file__).parent.parent \
        / "DEVICE_INVENTORY.json"
    committed = json.loads(committed_path.read_text())
    assert committed["device_schema"] == doc["device_schema"]
    assert committed["partial"] is False

    def shape(d):
        return {
            "sites": sorted((s["rel"], s["kind"], s["side"], s["sync"],
                             s["retrace"], s["transfer"], s["landed"])
                            for s in d["kernel_sites"]),
            "regions": sorted(r["rel"] for r in d["sync_regions"]),
            "jits": sorted((j["rel"], j["name"], j["cache"])
                           for j in d["jit_entries"]),
            "syncs": sorted((s["rel"], s["api"], s["sanction"])
                            for s in d["sync_sites"]),
        }
    assert shape(committed) == shape(doc), \
        "DEVICE_INVENTORY.json drifted from the live tree — " \
        "regenerate with: python -m ceph_tpu.devtools.lint " \
        "--device-report > DEVICE_INVENTORY.json"


# ============================================= 3. runtime lockdep layer


@pytest.fixture
def clean_lockdep():
    lockdep.reset()
    lockdep.enable()
    yield
    lockdep.disable()
    lockdep.reset()


def test_injected_mu_io_inversion_is_reported(clean_lockdep):
    """The FileDB invariant as a CHECKED edge: establish the legal
    _io -> _mu order, then take the locks inverted from another thread
    — the report must carry both acquisition stacks."""
    mu = lockdep.DepThreadLock("filedb:/x:_mu", rlock=True)
    io = lockdep.DepThreadLock("filedb:/x:_io")
    with io:
        with mu:                       # legal order: _io -> _mu
            pass

    def inverted():
        with mu:
            with io:                   # inversion
                pass

    t = threading.Thread(target=inverted)
    t.start()
    t.join(5.0)
    rep = [e for e in lockdep.report() if e["kind"] == "lock_order"]
    assert len(rep) == 1, lockdep.report()
    e = rep[0]
    assert e["acquiring"] == "filedb:/x:_io"
    assert e["holding"] == "filedb:/x:_mu"
    # both backtraces: where the legal order was established, and the
    # offending acquisition
    assert "in inverted" in e["stack"]
    assert e["prior_stack"].strip()


def test_lockdep_cycle_reports_dedupe_per_edge_pair(clean_lockdep):
    """The same lock-order inversion hit from two different acquisition
    sites renders as ONE finding carrying both stacks (satellite: the
    report used to repeat once per site)."""
    a = lockdep.DepThreadLock("dd:a")
    b = lockdep.DepThreadLock("dd:b")
    with a:
        with b:                        # legal order: a -> b
            pass

    def inversion_site_one():
        with b:
            with a:
                pass

    def inversion_site_two():
        with b:
            with a:
                pass

    inversion_site_one()
    inversion_site_two()
    rep = [e for e in lockdep.report() if e["kind"] == "lock_order"]
    assert len(rep) == 1, rep
    e = rep[0]
    assert e["count"] == 2
    assert e["acquiring"] == "dd:a" and e["holding"] == "dd:b"
    stacks = e["stacks"]
    assert len(stacks) == 2
    assert "inversion_site_one" in stacks[0]
    assert "inversion_site_two" in stacks[1]
    # the rendered report names the extra site
    assert "also observed" in lockdep.render_report([e])


def test_rlock_reentrancy_is_not_a_cycle(clean_lockdep):
    mu = lockdep.DepThreadLock("r:_mu", rlock=True)
    with mu:
        with mu:                       # reentrant, legal
            pass
    assert lockdep.report() == []


def test_cross_loop_asyncio_misuse_is_reported(clean_lockdep):
    """An asyncio lock bound to one event loop, then acquired from a
    second loop on another thread: the release callbacks of loop A can
    never wake a waiter on loop B — report it at the acquisition."""
    lock = lockdep.DepLock("mds.mutex")

    async def use():
        async with lock:
            pass

    asyncio.run(use())                 # binds the lock to loop 1

    result = {}

    def second_loop():
        try:
            asyncio.run(use())         # fresh loop: misuse
        except lockdep.LockOrderViolation as e:
            result["err"] = e

    t = threading.Thread(target=second_loop)
    t.start()
    t.join(5.0)
    assert "err" in result
    rep = [e for e in lockdep.report() if e["kind"] == "cross_loop"]
    assert len(rep) == 1
    assert rep[0]["name"] == "mds.mutex"
    assert rep[0]["prior_stack"].strip() and rep[0]["stack"].strip()


def test_asyncio_lock_order_cycle_still_raises(clean_lockdep):
    """The original DepLock contract (test_mgr_tools covers it too):
    recorded AND raised."""
    async def run():
        a, b = lockdep.DepLock("a"), lockdep.DepLock("b")
        async with a:
            async with b:
                pass
        with pytest.raises(lockdep.LockOrderViolation):
            async with b:
                async with a:
                    pass

    asyncio.run(run())
    assert any(e["kind"] == "lock_order" for e in lockdep.report())


def test_loop_stall_monitor_detects_and_attributes(clean_lockdep):
    """A synchronous 0.3s section on the loop with a 50ms budget must
    be flagged, attributed to the last tracer stage cut on the loop
    thread."""
    from ceph_tpu.common.tracer import Span

    async def main():
        mon = lockdep.LoopStallMonitor(
            asyncio.get_running_loop(), budget=0.05).start()
        await asyncio.sleep(0.1)       # monitor sees a healthy loop
        span = Span(1, 1)
        span.cut("prepare")            # names the owning stage
        time.sleep(0.3)                # the stall (deliberate, BLK04-
        #   exempt here: tests are not linted)
        await asyncio.sleep(0.1)       # heartbeat lands, stall closes
        mon.stop()
        return mon.stalls

    stalls = asyncio.run(main())
    assert stalls >= 1
    rep = [e for e in lockdep.report() if e["kind"] == "loop_stall"]
    assert rep, lockdep.report()
    assert rep[0]["seconds"] >= 0.2
    assert rep[0]["stage"] == "prepare"


def test_factories_are_off_path_when_disabled():
    """The zero-overhead-when-off contract: disabled factories hand
    back PLAIN stdlib locks — no wrapper, no graph participation."""
    lockdep.disable()
    lockdep.reset()
    assert type(lockdep.make_thread_lock("x")) is type(threading.Lock())
    assert type(lockdep.make_thread_lock("x", rlock=True)) \
        is type(threading.RLock())
    assert isinstance(lockdep.make_async_lock("x"), asyncio.Lock)
    assert not isinstance(lockdep.make_async_lock("x"),
                          lockdep.DepLock)
    # and nothing records
    lk = lockdep.make_thread_lock("y")
    with lk:
        pass
    assert lockdep.GRAPH.edges == {}
    assert lockdep.report() == []


def test_filedb_locks_follow_the_gate(tmp_path):
    from ceph_tpu.store.kv import FileDB
    lockdep.disable()
    plain = FileDB(str(tmp_path / "plain"))
    assert not isinstance(plain._mu, lockdep.DepThreadLock)
    plain.close()
    lockdep.enable()
    try:
        checked = FileDB(str(tmp_path / "checked"))
        assert isinstance(checked._mu, lockdep.DepThreadLock)
        assert isinstance(checked._io, lockdep.DepThreadLock)
        # exercise the real write path: the _io -> _mu edge lands in
        # the graph and no violation is recorded (clean order)
        t = checked.create_transaction()
        t.set("p", b"k", b"v")
        checked.submit(t, sync=True)
        checked.close()
        assert [e for e in lockdep.report()
                if e["kind"] == "lock_order"] == []
        assert any("_mu" in str(dsts)
                   for dsts in lockdep.GRAPH.edges.values()) or \
            lockdep.GRAPH.edges, "expected _io -> _mu edges recorded"
    finally:
        lockdep.disable()
        lockdep.reset()


def test_cluster_teardown_fails_loudly_on_findings():
    """The qa satellite: an e2e test that leaks a sanitizer finding
    must fail at Cluster.stop() with the report attached — and the
    process-wide state must still be reset for the next test."""
    from ceph_tpu.qa.cluster import Cluster

    async def run():
        cl = Cluster()
        admin = await cl.start(1)
        assert lockdep.is_enabled()
        lockdep.record("lock_order", domain="thread",
                       order="a -> b -> a", acquiring="a", holding="b",
                       prior_stack="prior", stack="now")
        with pytest.raises(AssertionError,
                           match="invariant sanitizer"):
            await cl.stop()
        assert admin is not None

    asyncio.run(run())
    assert not lockdep.is_enabled()
    assert lockdep.report() == []


def test_cluster_teardown_clean_when_no_findings():
    from ceph_tpu.qa.cluster import Cluster

    async def run():
        cl = Cluster()
        admin = await cl.start(1)
        await admin.mon_command({"prefix": "status"})
        await cl.stop()

    asyncio.run(run())
    assert not lockdep.is_enabled()

"""Named invariant-lint rules over per-file ASTs.

Each rule mechanically enforces one PR-landed write-path invariant
(the ROADMAP "Invariants" block cross-references these IDs):

  AF01  awaitfree        — no await/async-with/async-for/yield inside a
                           ``# awaitfree:begin`` / ``# awaitfree:end``
                           region (the PR-5 submit-section invariant:
                           version -> append_log -> queue_transactions
                           -> fan-out with no suspension point).
  FP02  frozen-payload   — no payload-field mutation on objects obtained
                           from ``Message.local_view()`` /
                           ``LazyPayload.peek()`` / ``m.log_entry()``;
                           receivers that mutate must rebind through
                           ``mutable()`` / ``mutable_copy()`` (PR-4 copy
                           discipline).  Envelope/transport stamps
                           (seq, src_*, recv_stamp, ...) are receiver-
                           owned and exempt.
  SEND03 sealed-send     — never mutate a message after its first send
                           (its wire bytes may already be cached / its
                           graph already handed to a local receiver).
  BLK04 no-blocking      — no blocking calls (time.sleep, sync file
                           open, os.fsync, socket/subprocess
                           constructors) inside ``async def`` bodies;
                           the store commit-thread modules are exempt
                           (their blocking runs on the kv-sync thread).
  MONO05 monotonic       — no wall-clock ``time.time()`` in op-path
                           modules (PR-6 discipline: ages/durations use
                           time.monotonic; wall time only in dump
                           output or persisted cross-restart stamps,
                           which carry an explicit waiver).
  LOCK06 lock-order      — never acquire ``_io`` inside a ``with
                           self._mu`` block: the FileDB order is
                           strictly ``_io -> _mu`` (PR-4 invariant; the
                           runtime lockdep checks the same edge
                           dynamically).
  FIN07 finally-release  — every windowed-op slot release
                           (``*window*.release(...)``) sits in a
                           ``finally`` block, so a failed op can never
                           wedge its dependency chain (PR-5 invariant).
  PROTO08 protocol-map   — cross-daemon message-graph exhaustiveness
                           (PROJECT rule: runs over the whole linted
                           set, not one file).  Every registered
                           message type sent to a daemon role via a
                           ``peer_type="..."`` literal (or
                           ``send_osd``) must have an
                           ``isinstance``-dispatch handler in that
                           role's dispatcher modules — an unhandled
                           wire type is a silent drop the sender waits
                           out as a timeout.
  REPLY09 reply-or-requeue — in osd/ modules, any function that owns a
                           reply path (calls ``reply_to``) must
                           discharge the consumed op on every early
                           ``return``: a reply, a requeue
                           (``queue_op``/``put_nowait``), or a task
                           handoff (``create_task``) must precede the
                           return on its path, else the client waits
                           out the full objecter timeout and the
                           dispatch-throttle budget leaks until
                           completion paths notice.
  EPOCH10 epoch-guard    — osd/ message handlers (``on_*``,
                           ``_handle_*``, ``handle_sub_message``) that
                           mutate PG/daemon state must compare an
                           epoch/interval field (``.epoch``,
                           ``same_interval_since``, ``interval_epoch``,
                           ``map_epoch``) before the first mutation —
                           applying a stale-interval message is the
                           classic split-brain write race.
  SHARD11 home-shard     — PG-state mutation is only legal from the
                           PG's home shard (osd/shards.py): functions
                           on the intake/heartbeat path (ms_dispatch,
                           ``_handle_*``, the heartbeat/scrub/tier
                           loops, the messenger reader/worker) must
                           not call PG-mutating methods or assign PG
                           fields directly — they route through the
                           shard handoff seam
                           (``self.shards.route(pgid, fn, ...)``;
                           passing the bound method through the seam
                           is the sanctioned pattern).

  STAGE18 stage-coverage — the tracer's cut chain and the code stay
                           mechanically in sync (PROJECT rule, the
                           PROTO08 shape applied to observability):
                           every literal stage name passed to
                           ``span.cut(...)`` / ``span.attribute(...)``
                           / ``tracer.section(...)`` /
                           ``tracer.interval(...)``
                           must be declared in CHAIN_STAGES /
                           AUX_STAGES (common/tracer.py), and — when
                           the linted set spans the op-path modules —
                           every declared CHAIN stage must have at
                           least one cut site in the tree.  A renamed
                           stage with a stale cut site (or a declared
                           stage nothing ever cuts) silently un-names
                           part of the write path's attribution.

  RETRY19 retry-backoff  — degraded-path retry discipline in osd/ and
                           client/ modules: (a) an ``await
                           asyncio.sleep(<numeric literal>)`` inside a
                           ``while`` loop of an ``async def`` is a
                           fixed-interval retry/poll — it must ride
                           the shared policy (common/backoff.py: a
                           ``Backoff(...)`` whose ``.sleep()`` /
                           ``.wait_for()`` is awaited in the same
                           loop) or carry a waiver; fixed intervals
                           re-synchronize a storm of peers into
                           thundering herds against whatever they are
                           all waiting on.  (b) an ``except
                           [asyncio.]TimeoutError:`` whose handler
                           body is only ``pass`` swallows a timeout
                           with no backoff, counter or give-up —
                           waiver required (``asyncio.sleep(0)`` — a
                           pure yield — is exempt).

  QOS20 qos-class-tag    — every enqueue to a PG op queue
                           (``*op_queue*.put_nowait(...)`` in osd/
                           modules) must pass the QoS class explicitly
                           (second positional argument or ``klass=``).
                           The op-queue seam is scheduler-polymorphic
                           (wpq | dmClock): an untagged put silently
                           rides the "client" default, which under
                           dmClock bills foreign work against the
                           client class's reservation and under wpq
                           jumps the weighted rotation.  ``queue_op``
                           is the sanctioned tagging front door; a
                           deliberate default-class put carries a
                           waiver.

Waivers: a site that is allowed to break a rule for a documented reason
carries ``# lint: allow[RULE] reason`` on the same line or the line
directly above.  Waivers are counted and reported; an undocumented
violation fails the lint (and therefore tier-1).
"""

from __future__ import annotations

import ast
import io
import re
import tokenize
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, List, Optional, Set, Tuple

# ------------------------------------------------------------------ model


@dataclass(frozen=True)
class Violation:
    rule: str
    rel: str          # package-relative path ("osd/pg.py")
    line: int
    msg: str

    def render(self) -> str:
        return f"{self.rel}:{self.line}: {self.rule} {self.msg}"


class FileInfo:
    """One parsed source file + the comment/waiver side channel the AST
    does not carry."""

    WAIVER_RE = re.compile(r"#\s*lint:\s*allow\[([A-Z0-9]+)\]")

    def __init__(self, rel: str, source: str):
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=rel)
        #: lineno -> REAL comment token text (tokenize, not a naive
        #: '#' scan: a docstring documenting the sentinel syntax must
        #: never register as a sentinel)
        self.comments: Dict[int, str] = {}
        try:
            for tok in tokenize.generate_tokens(
                    io.StringIO(source).readline):
                if tok.type == tokenize.COMMENT:
                    self.comments[tok.start[0]] = tok.string
        except (tokenize.TokenError, IndentationError):
            pass
        #: lineno -> {rule id: waiver COMMENT line} (a waiver covers its
        #: own line and the line directly below, so it can sit above a
        #: long call).  The comment line rides along so waiver USAGE can
        #: be attributed back to the comment that did the suppressing —
        #: the unused-waiver audit keys on it.
        self.waivers: Dict[int, Dict[str, int]] = {}
        #: every waiver comment in the file: (comment line, rule id)
        self.waiver_comments: List[Tuple[int, str]] = []
        #: (rule, comment line) pairs that actually suppressed something
        #: this run — a waiver never queried by a would-be violation is
        #: stale and reported by the unused-waiver audit
        self.waiver_used: Set[Tuple[str, int]] = set()
        for ln, c in self.comments.items():
            m = self.WAIVER_RE.search(c)
            if m:
                rid = m.group(1)
                self.waiver_comments.append((ln, rid))
                self.waivers.setdefault(ln, {})[rid] = ln
                self.waivers.setdefault(ln + 1, {})[rid] = ln
        self.aliases = _import_aliases(self.tree)

    def waived(self, rule: str, line: int) -> bool:
        cover = self.waivers.get(line)
        if cover is None or rule not in cover:
            return False
        self.waiver_used.add((rule, cover[rule]))
        return True

    def unused_waivers(self) -> List[Tuple[int, str]]:
        """Waiver comments that suppressed nothing: (comment line,
        rule).  Only meaningful after every rule has run over the
        file (a single-rule lint leaves other rules' waivers unused
        by construction — callers gate on that)."""
        return sorted((ln, rid) for ln, rid in self.waiver_comments
                      if (rid, ln) not in self.waiver_used)


def _import_aliases(tree: ast.AST) -> Dict[str, str]:
    """Local name -> canonical dotted origin, so ``import time as
    _time; _time.time()`` still normalizes to ``time.time``."""
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[a.asname or a.name.split(".")[0]] = a.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                out[a.asname or a.name] = f"{node.module}.{a.name}"
    return out


def _dotted(node: ast.AST, aliases: Dict[str, str]) -> Optional[str]:
    """Normalized dotted name of a Name/Attribute chain, aliases
    resolved on the root segment; None for anything else."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    parts.reverse()
    parts[0] = aliases.get(parts[0], parts[0])
    return ".".join(parts)


def _attr_text(node: ast.AST) -> Optional[str]:
    """Raw dotted source text (no alias resolution): for receiver
    matching like ``self.op_window``."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


# ----------------------------------------------------------- AF01 regions

_AF_BEGIN = "awaitfree:begin"
_AF_END = "awaitfree:end"

_SUSPEND_NODES = (ast.Await, ast.AsyncWith, ast.AsyncFor,
                  ast.Yield, ast.YieldFrom)


def check_af01(fi: FileInfo) -> Iterator[Violation]:
    regions: List[Tuple[int, int]] = []
    start: Optional[int] = None
    for ln in sorted(fi.comments):
        c = fi.comments[ln]
        if _AF_BEGIN in c:
            if start is not None:
                yield Violation("AF01", fi.rel, ln,
                                f"nested awaitfree:begin (previous at "
                                f"line {start} not closed)")
            start = ln
        elif _AF_END in c:
            if start is None:
                yield Violation("AF01", fi.rel, ln,
                                "awaitfree:end without begin")
            else:
                regions.append((start, ln))
                start = None
    if start is not None:
        yield Violation("AF01", fi.rel, start,
                        "awaitfree:begin never closed")
    if not regions:
        return
    for node in ast.walk(fi.tree):
        if isinstance(node, _SUSPEND_NODES):
            ln = node.lineno
            for lo, hi in regions:
                if lo < ln < hi:
                    kind = type(node).__name__.lower()
                    yield Violation(
                        "AF01", fi.rel, ln,
                        f"{kind} inside awaitfree region (lines "
                        f"{lo}-{hi}): the submit section must hold no "
                        f"suspension point")
                    break


# ------------------------------------------------------------------- FP02

#: methods whose result is the SENDER'S frozen object (read-only view)
_TAINT_METHODS = {"local_view", "peek", "log_entry"}
#: methods whose result is a receiver-owned mutable copy (sanctioned)
_SANCTION_METHODS = {"mutable", "mutable_copy", "result_copy", "copy",
                     "deepcopy"}
#: transport/envelope fields the messenger stamps per delivery — the
#: receiver owns the envelope, only the payload graph is frozen
_ENVELOPE_FIELDS = {"seq", "src_name", "src_addr", "recv_stamp",
                    "connection", "transport_id", "_span", "_wire",
                    "_tracked", "_windowed", "throttle_cost"}
_MUTATOR_CALLS = {"append", "extend", "insert", "add", "update",
                  "clear", "remove", "pop", "popitem", "setdefault",
                  "sort", "reverse"}


class _FnScan(ast.NodeVisitor):
    """Shared per-function linear scan for the dataflow-ish rules
    (FP02 taint tracking, SEND03 sent tracking).  Visits statements in
    source order; nested function defs open their own scope."""

    def __init__(self, fi: FileInfo, out: List[Violation]):
        self.fi = fi
        self.out = out
        self.tainted: Dict[str, int] = {}     # name -> taint line
        self.sent: Dict[str, int] = {}        # name -> first-send line

    # -- helpers
    def _call_attr(self, call: ast.Call) -> Optional[str]:
        if isinstance(call.func, ast.Attribute):
            return call.func.attr
        return None

    def _root_name(self, node: ast.AST) -> Optional[str]:
        # walk through attribute AND subscript links: the root of
        # `view.ops[0].rval` is `view` (mutating an op inside a frozen
        # view's list is the most realistic receiver-side violation)
        while isinstance(node, (ast.Attribute, ast.Subscript)):
            node = node.value
        return node.id if isinstance(node, ast.Name) else None

    # -- taint/sent bookkeeping
    def visit_Assign(self, node: ast.Assign) -> None:
        self._check_store_targets(node.targets, node.lineno)
        taints = False
        if isinstance(node.value, ast.Call):
            attr = self._call_attr(node.value)
            if attr in _TAINT_METHODS:
                taints = True
        for t in node.targets:
            if isinstance(t, ast.Name):
                self.sent.pop(t.id, None)
                if taints:
                    self.tainted[t.id] = node.lineno
                else:
                    self.tainted.pop(t.id, None)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._check_store_targets([node.target], node.lineno)
        self.generic_visit(node)

    def _field_off_root(self, node: ast.AST) -> Optional[str]:
        """The FIRST attribute above the root name: for
        `view.ops[0].rval` that is "ops" — the envelope-field check
        applies to the field actually hanging off the frozen view."""
        field = None
        while True:
            if isinstance(node, ast.Attribute):
                field = node.attr
                node = node.value
            elif isinstance(node, ast.Subscript):
                node = node.value
            else:
                break
        return field if isinstance(node, ast.Name) else None

    def _check_store_targets(self, targets, line: int) -> None:
        for t in targets:
            stores = t.elts if isinstance(t, ast.Tuple) else [t]
            for s in stores:
                if not isinstance(s, (ast.Attribute, ast.Subscript)):
                    continue
                root = self._root_name(s)
                field = self._field_off_root(s)
                if root is None or field is None:
                    continue
                if root in self.tainted and \
                        field not in _ENVELOPE_FIELDS:
                    if not self.fi.waived("FP02", line):
                        self.out.append(Violation(
                            "FP02", self.fi.rel, line,
                            f"mutation of frozen view {root!r} "
                            f"(tainted at line {self.tainted[root]}): "
                            f"take mutable()/mutable_copy() first"))
                if root in self.sent and \
                        field not in _ENVELOPE_FIELDS:
                    if not self.fi.waived("SEND03", line):
                        self.out.append(Violation(
                            "SEND03", self.fi.rel, line,
                            f"mutation of {root!r} after its first "
                            f"send (line {self.sent[root]}): wire "
                            f"bytes may already be cached — build a "
                            f"fresh message"))

    def visit_Call(self, node: ast.Call) -> None:
        attr = self._call_attr(node)
        # frozen-view mutator method call (view.ops.append(...))
        if attr in _MUTATOR_CALLS and isinstance(node.func,
                                                 ast.Attribute):
            recv = node.func.value
            root = self._root_name(recv)
            # only receiver chains rooted AT the tainted name itself
            # (entry.xattrs.update) — a tainted name merely appearing
            # as an argument is fine
            if root in self.tainted and \
                    not self.fi.waived("FP02", node.lineno):
                self.out.append(Violation(
                    "FP02", self.fi.rel, node.lineno,
                    f"mutating call .{attr}() on frozen view "
                    f"{root!r}: take mutable()/mutable_copy() first"))
        # which positional argument is the MESSAGE being sent
        # (reply_to(request, reply) sends its second arg — the first
        # is the request being answered, which stays mutable)
        send_arg = {"send_osd": 1, "send_message": 0,
                    "reply_to": 1}.get(attr or "")
        if send_arg is not None and len(node.args) > send_arg:
            arg = node.args[send_arg]
            if isinstance(arg, ast.Name):
                self.sent.setdefault(arg.id, node.lineno)
        self.generic_visit(node)

    # nested defs get their own scope
    def visit_FunctionDef(self, node):          # noqa: N802
        _scan_function(self.fi, node, self.out)

    visit_AsyncFunctionDef = visit_FunctionDef


def _scan_function(fi: FileInfo, fn, out: List[Violation]) -> None:
    scan = _FnScan(fi, out)
    for stmt in fn.body:
        scan.visit(stmt)


def check_fp02_send03(fi: FileInfo) -> Iterator[Violation]:
    out: List[Violation] = []
    for node in fi.tree.body:
        _walk_defs(fi, node, out)
    yield from out


def _walk_defs(fi: FileInfo, node: ast.AST, out: List[Violation]) -> None:
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        _scan_function(fi, node, out)
    elif isinstance(node, ast.ClassDef):
        for child in node.body:
            _walk_defs(fi, child, out)


# ------------------------------------------------------------------- BLK04

#: commit-thread modules (their blocking runs on the kv-sync thread,
#: never the event loop) and the offline CLI tools (each runs its own
#: short-lived loop; reading a local file inline is the point)
_BLK_EXEMPT_FILES = {"store/commit.py", "store/wal.py", "store/kv.py"}
_BLK_EXEMPT_PREFIXES = ("tools/",)
_BLOCKING_CALLS = {
    "time.sleep", "os.fsync", "os.fdatasync", "os.sync",
    "subprocess.run", "subprocess.call", "subprocess.check_call",
    "subprocess.check_output", "subprocess.Popen",
    "socket.socket", "socket.create_connection",
    "open", "io.open",
}


class _AsyncScan(ast.NodeVisitor):
    def __init__(self, fi: FileInfo, out: List[Violation]):
        self.fi = fi
        self.out = out
        self.async_depth = 0

    def visit_AsyncFunctionDef(self, node):     # noqa: N802
        self.async_depth += 1
        self.generic_visit(node)
        self.async_depth -= 1

    def visit_FunctionDef(self, node):          # noqa: N802
        # a nested sync def's body is not (necessarily) loop-side
        saved, self.async_depth = self.async_depth, 0
        self.generic_visit(node)
        self.async_depth = saved

    def visit_Call(self, node: ast.Call) -> None:
        if self.async_depth:
            name = _dotted(node.func, self.fi.aliases)
            if name in _BLOCKING_CALLS and \
                    not self.fi.waived("BLK04", node.lineno):
                self.out.append(Violation(
                    "BLK04", self.fi.rel, node.lineno,
                    f"blocking call {name}() in async def: this "
                    f"stalls the whole event loop (move it to the "
                    f"commit thread or an executor)"))
        self.generic_visit(node)


def check_blk04(fi: FileInfo) -> Iterator[Violation]:
    if fi.rel in _BLK_EXEMPT_FILES or \
            fi.rel.startswith(_BLK_EXEMPT_PREFIXES):
        return
    out: List[Violation] = []
    _AsyncScan(fi, out).visit(fi.tree)
    yield from out


# ------------------------------------------------------------------ MONO05

_OP_PATH_PREFIXES = ("osd/", "msg/", "client/", "store/", "ec/")
_OP_PATH_FILES = {"common/op_tracker.py", "common/tracer.py",
                  "common/throttle.py", "common/wpq.py"}


def _is_op_path(rel: str) -> bool:
    return rel.startswith(_OP_PATH_PREFIXES) or rel in _OP_PATH_FILES


def check_mono05(fi: FileInfo) -> Iterator[Violation]:
    if not _is_op_path(fi.rel):
        return
    for node in ast.walk(fi.tree):
        if isinstance(node, ast.Call) and \
                _dotted(node.func, fi.aliases) == "time.time" and \
                not fi.waived("MONO05", node.lineno):
            yield Violation(
                "MONO05", fi.rel, node.lineno,
                "wall-clock time.time() in an op-path module: ages/"
                "durations must use time.monotonic() (wall time only "
                "in dump output / persisted stamps, with a waiver)")


# ------------------------------------------------------------------ LOCK06

#: (inner, outer) pairs that must never nest: acquiring `inner` while
#: lexically inside a `with ...outer` block inverts the checked order
_FORBIDDEN_NESTING = (("_io", "_mu"),)


class _WithScan(ast.NodeVisitor):
    def __init__(self, fi: FileInfo, out: List[Violation]):
        self.fi = fi
        self.out = out
        self.stack: List[str] = []

    def _items(self, node) -> List[str]:
        names = []
        for item in node.items:
            t = _attr_text(item.context_expr)
            if t:
                names.append(t.rsplit(".", 1)[-1])
        return names

    def _visit_with(self, node) -> None:
        names = self._items(node)
        for name in names:
            for inner, outer in _FORBIDDEN_NESTING:
                if name == inner and outer in self.stack and \
                        not self.fi.waived("LOCK06", node.lineno):
                    self.out.append(Violation(
                        "LOCK06", self.fi.rel, node.lineno,
                        f"acquiring {inner!r} while holding "
                        f"{outer!r}: the checked lock order is "
                        f"{inner} -> {outer} (FileDB invariant)"))
        self.stack.extend(names)
        self.generic_visit(node)
        del self.stack[len(self.stack) - len(names):]

    visit_With = _visit_with
    visit_AsyncWith = _visit_with


def check_lock06(fi: FileInfo) -> Iterator[Violation]:
    out: List[Violation] = []
    _WithScan(fi, out).visit(fi.tree)
    yield from out


# ------------------------------------------------------------------- FIN07


def check_fin07(fi: FileInfo) -> Iterator[Violation]:
    in_finally: Set[int] = set()
    for node in ast.walk(fi.tree):
        if isinstance(node, ast.Try):
            for stmt in node.finalbody:
                for sub in ast.walk(stmt):
                    in_finally.add(id(sub))
    for node in ast.walk(fi.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "release"):
            continue
        recv = _attr_text(node.func.value) or ""
        if "window" not in recv:
            continue
        if id(node) not in in_finally and \
                not fi.waived("FIN07", node.lineno):
            yield Violation(
                "FIN07", fi.rel, node.lineno,
                f"windowed-slot release on {recv!r} outside a "
                f"finally block: a failed op would wedge its "
                f"object-dependency chain (PR-5 invariant)")


# ------------------------------------------------------------------ REPLY09

#: osd/ functions that call one of these OWN a reply path
_R9_TRIGGERS = {"reply_to"}
#: statements containing one of these discharge the consumed op on the
#: path they sit on: a reply, a requeue, or a task handoff (kept
#: narrow — a generic container .append() is NOT a discharge)
_R9_DISCHARGE = {"reply_to", "queue_op", "put_nowait", "create_task",
                 "send_osd", "send_message", "requeue"}


def _terminates(stmts) -> bool:
    """True when the block can never fall through (its last statement
    returns or raises)."""
    return bool(stmts) and isinstance(stmts[-1], (ast.Return, ast.Raise))


def _own_body_calls(fn) -> Iterator[ast.Call]:
    """Calls in fn's own body, not descending into nested defs."""
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if isinstance(node, ast.Call):
            yield node
        stack.extend(ast.iter_child_nodes(node))


def _has_call_attr(node: ast.AST, names: Set[str]) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) \
                and isinstance(sub.func, ast.Attribute) \
                and sub.func.attr in names:
            return True
    return False


class _ReplyScan:
    """Path-sensitive-ish scan: walk statements in order carrying a
    "discharged on this path" flag.  A compound statement's branches
    each inherit the flag at entry; a discharge inside ONE branch
    leaks to the code after the compound only when every branch that
    can fall through discharged (a branch ending in return/raise does
    not fall through).  Loop bodies may run zero times, so their
    discharges never propagate past the loop."""

    def __init__(self, fi: FileInfo, out: List[Violation]):
        self.fi = fi
        self.out = out

    def scan(self, stmts, discharged: bool) -> bool:
        """Check every return in this block; returns the discharge
        state at the block's fall-through."""
        d = discharged
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(st, ast.Return):
                ok = d or (st.value is not None
                           and _has_call_attr(st.value, _R9_DISCHARGE))
                if not ok and not self.fi.waived("REPLY09", st.lineno):
                    self.out.append(Violation(
                        "REPLY09", self.fi.rel, st.lineno,
                        "early return without replying/requeuing the "
                        "consumed op on this path: the client waits "
                        "out its full timeout (reply, queue_op, or "
                        "waive with the drop's justification)"))
                continue
            if isinstance(st, ast.If):
                d_body = self.scan(st.body, d)
                d_else = self.scan(st.orelse, d) if st.orelse else d
                outs = []
                if not _terminates(st.body):
                    outs.append(d_body)
                if not st.orelse:
                    outs.append(d)          # implicit empty else
                elif not _terminates(st.orelse):
                    outs.append(d_else)
                # both arms terminate => code below is unreachable on
                # this path; keep d (harmlessly conservative)
                d = all(outs) if outs else d
            elif isinstance(st, (ast.With, ast.AsyncWith)):
                d = self.scan(st.body, d)   # single path: propagates
            elif isinstance(st, ast.Try):
                # body/handlers are conditional paths: scan them for
                # returns but don't let their discharges leak; the
                # finally block always runs and propagates
                self.scan(st.body, d)
                for h in st.handlers:
                    self.scan(h.body, d)
                self.scan(st.orelse, d)
                d = self.scan(st.finalbody, d)
            elif isinstance(st, (ast.For, ast.AsyncFor, ast.While)):
                # may run zero times: no propagation past the loop
                self.scan(st.body, d)
                self.scan(st.orelse, d)
            elif _has_call_attr(st, _R9_DISCHARGE):
                d = True
        return d


def check_reply09(fi: FileInfo) -> Iterator[Violation]:
    if not fi.rel.startswith("osd/"):
        return
    out: List[Violation] = []
    for node in ast.walk(fi.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not any(isinstance(c.func, ast.Attribute)
                   and c.func.attr in _R9_TRIGGERS
                   for c in _own_body_calls(node)):
            continue
        _ReplyScan(fi, out).scan(node.body, False)
    yield from out


# ------------------------------------------------------------------ EPOCH10

#: method calls that PERSIST or mutate PG/daemon replicated state
_E10_MUT_CALLS = {"save_meta", "save_meta_log", "apply_transaction",
                  "queue_transactions", "apply_push"}
#: state attributes off self/pg whose assignment (or container
#: mutation) is a replicated-state write
_E10_MUT_ATTRS = {"info", "log", "state", "missing", "reqids",
                  "peer_info", "peer_missing", "past_intervals"}
_E10_CONTAINER_MUTS = {"append", "add", "pop", "clear", "update",
                       "remove"}
#: attribute names whose mere mention before the first mutation counts
#: as an interval/epoch guard
_E10_GUARDS = {"epoch", "same_interval_since", "interval_epoch",
               "map_epoch"}
_E10_ROOTS = {"self", "pg"}


def _chain_names(node: ast.AST) -> Tuple[Optional[str], List[str]]:
    """(root Name id, [attr chain bottom-up]) through Attribute and
    Subscript links."""
    attrs: List[str] = []
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        if isinstance(node, ast.Attribute):
            attrs.append(node.attr)
        node = node.value
    root = node.id if isinstance(node, ast.Name) else None
    return root, attrs


def _e10_first_mutation(fn) -> Optional[int]:
    first: Optional[int] = None

    def note(ln: int) -> None:
        nonlocal first
        if first is None or ln < first:
            first = ln

    for node in ast.walk(fn):
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                root, attrs = _chain_names(t)
                if root in _E10_ROOTS and attrs \
                        and attrs[-1] in _E10_MUT_ATTRS:
                    note(node.lineno)
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute):
            attr = node.func.attr
            if attr in _E10_MUT_CALLS:
                note(node.lineno)
            elif attr in _E10_CONTAINER_MUTS:
                root, attrs = _chain_names(node.func.value)
                if root in _E10_ROOTS and \
                        any(a in _E10_MUT_ATTRS for a in attrs):
                    note(node.lineno)
    return first


def check_epoch10(fi: FileInfo) -> Iterator[Violation]:
    if not fi.rel.startswith("osd/"):
        return
    for node in ast.walk(fi.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        name = node.name
        if not (name.startswith("on_") or name.startswith("_handle_")
                or name == "handle_sub_message"):
            continue
        args = node.args.args
        if len(args) < 2:
            continue        # not a (self, m) message handler
        mut_line = _e10_first_mutation(node)
        if mut_line is None:
            continue
        guarded = any(
            isinstance(sub, ast.Attribute) and sub.attr in _E10_GUARDS
            and sub.lineno < mut_line
            for sub in ast.walk(node))
        if guarded:
            continue
        if fi.waived("EPOCH10", mut_line) or \
                fi.waived("EPOCH10", node.lineno):
            continue
        yield Violation(
            "EPOCH10", fi.rel, mut_line,
            f"handler {name}() mutates PG state with no epoch/interval "
            f"guard before the first mutation: a stale-interval "
            f"message must be dropped, not applied "
            f"(compare m.epoch against same_interval_since first)")


# ------------------------------------------------------------------ SHARD11

#: intake/heartbeat-path function names: these run on the OSD's intake
#: loop (or the messenger's reader/worker), NEVER on a PG's home shard
_S11_FUNC_RE = re.compile(
    r"^(ms_dispatch|_handle_\w+|_heartbeat\w*|_scrub_scheduler|"
    r"_tier_agent_loop|_report_stats|_boot_loop|_on_osdmap|"
    r"_advance_pgs|_local_worker|_serve_peer)$")
#: PG methods that mutate PG state or enqueue PG work — calling one
#: from an intake-path function races the home shard.  Passing the
#: bound method THROUGH the seam (`self.shards.route(pgid,
#: pg.queue_op, m)`) is the sanctioned pattern and does not match
#: (only direct calls and attribute stores do).
_S11_MUT_METHODS = {
    "queue_op", "stop", "start", "advance_map", "ensure_peering",
    "on_query", "on_notify", "on_log_request", "on_pg_log", "on_push",
    "on_push_reply", "on_object_list", "on_notify_ack", "handle_notify",
    "handle_watch", "maybe_trim_snaps", "generate_past_intervals",
    "load_meta", "create_onstore", "save_meta", "save_meta_log",
    "complete_to",
    "append_log", "note_reqid", "try_fast_sub_write",
    "try_fast_sub_read"}
#: calls whose result is a PG object
_S11_PG_SOURCES = {"_pg_for", "_load_stray_pg"}


def check_shard11(fi: FileInfo) -> Iterator[Violation]:
    if not fi.rel.startswith(("osd/", "msg/")):
        return
    for fn in ast.walk(fi.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not _S11_FUNC_RE.match(fn.name):
            continue
        # names bound to PG objects in this function: the literal
        # name `pg` plus anything assigned from _pg_for()-family calls
        pg_names = {"pg"}
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Assign) \
                    and isinstance(sub.value, ast.Call) \
                    and isinstance(sub.value.func, ast.Attribute) \
                    and sub.value.func.attr in _S11_PG_SOURCES:
                for t in sub.targets:
                    if isinstance(t, ast.Name):
                        pg_names.add(t.id)
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Call) \
                    and isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr in _S11_MUT_METHODS:
                root, _attrs = _chain_names(sub.func.value)
                if root in pg_names and \
                        not fi.waived("SHARD11", sub.lineno):
                    yield Violation(
                        "SHARD11", fi.rel, sub.lineno,
                        f"{fn.name}() calls {root}.{sub.func.attr}() "
                        f"from an intake/heartbeat-path function: "
                        f"PG-state mutation is only legal on the PG's "
                        f"home shard — route through the shard "
                        f"handoff seam (self.shards.route(pgid, "
                        f"{root}.{sub.func.attr}, ...))")
            elif isinstance(sub, (ast.Assign, ast.AugAssign)):
                targets = sub.targets if isinstance(sub, ast.Assign) \
                    else [sub.target]
                for t in targets:
                    if not isinstance(t, (ast.Attribute, ast.Subscript)):
                        continue
                    root, attrs = _chain_names(t)
                    if root in pg_names and attrs and \
                            not fi.waived("SHARD11", sub.lineno):
                        yield Violation(
                            "SHARD11", fi.rel, sub.lineno,
                            f"{fn.name}() assigns {root}.{attrs[-1]} "
                            f"from an intake/heartbeat-path function: "
                            f"PG fields belong to the home shard — "
                            f"route the mutation through the shard "
                            f"handoff seam (osd/shards.py)")


# ------------------------------------------------------------------ PROTO08

#: daemon role -> the modules whose isinstance-dispatch handles that
#: role's inbound messages (a daemon's embedded MonClient rides the
#: same messenger, so it is part of the daemon's handler surface)
ROLE_MODULES: Dict[str, Tuple[str, ...]] = {
    "osd": ("osd/daemon.py", "osd/tiering.py", "mon/client.py"),
    "mon": ("mon/monitor.py",),
    "mds": ("services/mds.py", "mon/client.py"),
    "client": ("mon/client.py", "client/rados.py",
               "client/objecter.py", "services/cephfs.py"),
}


def _registered_messages(files: List[FileInfo]) -> Set[str]:
    out: Set[str] = set()
    for fi in files:
        for node in ast.walk(fi.tree):
            if isinstance(node, ast.ClassDef) and any(
                    isinstance(d, ast.Name)
                    and d.id == "register_message"
                    for d in node.decorator_list):
                out.add(node.name)
    return out


def _envelope_inner(files: List[FileInfo],
                    registered: Set[str]) -> Dict[str, Set[str]]:
    """Container frames: a registered message that is a pure transport
    ENVELOPE (marked ``THROTTLE_SPLIT = True`` — per-inner-op throttle
    accounting is the envelope contract) carries other registered
    messages inside.  The inner types are read mechanically off the
    class body (the decode path must name them: ``MOSDOp.from_bytes``
    inside ``MOSDOpBatch.decode_payload``), so a batched send
    contributes its INNER (type, role) edges — the receiver dispatches
    the unpacked inner ops, and an unhandled inner type is the same
    silent drop an unhandled top-level type is."""
    out: Dict[str, Set[str]] = {}
    for fi in files:
        for node in ast.walk(fi.tree):
            if not (isinstance(node, ast.ClassDef)
                    and node.name in registered):
                continue
            is_env = any(
                isinstance(st, ast.Assign)
                and any(isinstance(t, ast.Name)
                        and t.id == "THROTTLE_SPLIT"
                        for t in st.targets)
                and isinstance(st.value, ast.Constant)
                and st.value.value is True
                for st in node.body)
            if not is_env:
                continue
            # only the DECODE path names carried types (the docstring
            # contract): a registered class mentioned in an unrelated
            # helper must not fabricate inner edges
            inner: Set[str] = set()
            for item in node.body:
                if isinstance(item, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) \
                        and item.name == "decode_payload":
                    inner |= {
                        sub.id for sub in ast.walk(item)
                        if isinstance(sub, ast.Name)
                        and sub.id in registered
                        and sub.id != node.name}
            if inner:
                out[node.name] = inner
    return out


def _handled_names(fi: FileInfo) -> Set[str]:
    """Every class name this module dispatches on via isinstance()."""
    out: Set[str] = set()
    for node in ast.walk(fi.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "isinstance"
                and len(node.args) == 2):
            continue
        spec = node.args[1]
        names = spec.elts if isinstance(spec, ast.Tuple) else [spec]
        for n in names:
            if isinstance(n, ast.Name):
                out.add(n.id)
            elif isinstance(n, ast.Attribute):
                out.add(n.attr)
    return out


def _send_edges(fi: FileInfo, registered: Set[str]
                ) -> Iterator[Tuple[str, str, int]]:
    """(message class, target role, line) for every send site whose
    message type and target role are statically knowable: a
    peer_type="..." string literal on send_message, or send_osd (peer
    role is osd by construction).  reply_to and variable peer types
    carry no static target and produce no edge."""
    for node in ast.walk(fi.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        local: Dict[str, str] = {}
        for sub in ast.walk(node):
            if isinstance(sub, ast.Assign) \
                    and isinstance(sub.value, ast.Call) \
                    and isinstance(sub.value.func, ast.Name) \
                    and sub.value.func.id in registered:
                for t in sub.targets:
                    if isinstance(t, ast.Name):
                        local[t.id] = sub.value.func.id
        for sub in ast.walk(node):
            if not (isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)):
                continue
            attr = sub.func.attr
            role: Optional[str] = None
            msg_expr: Optional[ast.AST] = None
            if attr == "send_message":
                for kw in sub.keywords:
                    if kw.arg == "peer_type" \
                            and isinstance(kw.value, ast.Constant) \
                            and isinstance(kw.value.value, str):
                        role = kw.value.value
                if sub.args:
                    msg_expr = sub.args[0]
            elif attr == "send_osd" and len(sub.args) >= 2:
                role = "osd"
                msg_expr = sub.args[1]
            if role is None or msg_expr is None:
                continue
            cls: Optional[str] = None
            if isinstance(msg_expr, ast.Call) \
                    and isinstance(msg_expr.func, ast.Name) \
                    and msg_expr.func.id in registered:
                cls = msg_expr.func.id
            elif isinstance(msg_expr, ast.Name):
                cls = local.get(msg_expr.id)
            if cls is not None:
                yield cls, role, sub.lineno


def check_proto08(files: List[FileInfo]) -> Iterator[Violation]:
    """PROJECT rule: needs the whole linted set.  Edges whose target
    role has no module present in the set are skipped (linting a single
    file must not fabricate missing-handler noise)."""
    by_rel = {fi.rel: fi for fi in files}
    registered = _registered_messages(files)
    containers = _envelope_inner(files, registered)
    handled: Dict[str, Set[str]] = {}
    for role, mods in ROLE_MODULES.items():
        present = [by_rel[m] for m in mods if m in by_rel]
        if not present:
            continue
        handled[role] = set()
        for fi in present:
            handled[role] |= _handled_names(fi)
    seen: Set[Tuple[str, str]] = set()
    for fi in files:
        if fi.rel.startswith(("tools/", "devtools/")):
            continue
        for cls, role, line in _send_edges(fi, registered):
            # a container frame contributes its inner types' edges too:
            # the envelope is transport, the inner ops are the protocol
            expanded = [cls] + sorted(containers.get(cls, ()))
            for ecls in expanded:
                if role not in handled:
                    continue
                if ecls in handled[role]:
                    continue
                if fi.waived("PROTO08", line):
                    continue
                if (ecls, role) in seen:
                    continue        # one report per (type, role) pair
                seen.add((ecls, role))
                suffix = "" if ecls == cls else \
                    f" (inner op of container frame {cls})"
                yield Violation(
                    "PROTO08", fi.rel, line,
                    f"{ecls} is sent to role {role!r}{suffix} but no "
                    f"dispatcher in {list(ROLE_MODULES[role])} handles "
                    f"it (isinstance check missing): the send is a "
                    f"silent drop on the receiver")


# ------------------------------------------------------------------ STAGE18

#: modules whose presence marks a file set as "whole-op-path": the
#: coverage half of STAGE18 (every declared chain stage has a cut
#: site) only runs when ALL of these are in the linted set — a partial
#: (--changed / explicit-path) lint must not report every stage as
#: uncovered just because the files that cut them were not handed in.
_STAGE_COVERAGE_ANCHORS = (
    "common/tracer.py", "client/objecter.py", "osd/sequencer.py",
    "osd/pg.py", "osd/daemon.py", "osd/backend.py", "osd/lanes.py",
    "msg/messenger.py",
)

#: stage-recording call names whose first literal argument is a stage:
#: the span's cuts and the tracer's sections and intervals
_STAGE_CALL_ATTRS = ("cut", "attribute", "section", "interval")


def collect_stage_sites(files: List["FileInfo"]) -> Dict[str, list]:
    """stage name -> [(FileInfo, line)] over every ``.cut("x", ...)`` /
    ``.attribute("x", ...)`` / ``.section("x")`` / ``.interval("x", t0)``
    call with a literal first argument.  The
    lint --json document exposes the per-stage site counts so CI can
    diff coverage like it diffs the seam/device inventories."""
    sites: Dict[str, list] = {}
    for fi in files:
        for node in ast.walk(fi.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _STAGE_CALL_ATTRS
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            sites.setdefault(node.args[0].value, []).append(
                (fi, node.lineno))
    return sites


def check_stage18(files: List["FileInfo"]) -> Iterator[Violation]:
    """PROJECT rule: CHAIN_STAGES and the span cut sites stay in sync
    both ways (see module docstring)."""
    from ceph_tpu.common.tracer import AUX_STAGES, CHAIN_STAGES
    declared = set(CHAIN_STAGES) | set(AUX_STAGES)
    sites = collect_stage_sites(files)
    for name in sorted(sites):
        if name in declared:
            continue
        for fi, line in sites[name]:
            if fi.waived("STAGE18", line):
                continue
            yield Violation(
                "STAGE18", fi.rel, line,
                f"span cut / tracer section names undeclared stage "
                f"{name!r} — declare it in CHAIN_STAGES/AUX_STAGES "
                f"(common/tracer.py) or fix the typo; an undeclared "
                f"stage silently falls out of the attributed chain "
                f"sum and of every reader")
    rels = {fi.rel for fi in files}
    if not all(a in rels for a in _STAGE_COVERAGE_ANCHORS):
        return                    # partial lint: skip the coverage half
    tracer_fi = next(fi for fi in files
                     if fi.rel == "common/tracer.py")
    decl_line = next(
        (n.lineno for n in ast.walk(tracer_fi.tree)
         if isinstance(n, ast.Assign)
         and any(isinstance(t, ast.Name) and t.id == "CHAIN_STAGES"
                 for t in n.targets)), 1)
    for name in CHAIN_STAGES:
        if name not in sites and not tracer_fi.waived("STAGE18",
                                                      decl_line):
            yield Violation(
                "STAGE18", tracer_fi.rel, decl_line,
                f"declared chain stage {name!r} has no span.cut/"
                f"attribute site anywhere in the tree — dead stages "
                f"rot the documented chain (remove it or cut it)")


# ----------------------------------------------------------------- RETRY19

_RETRY_PREFIXES = ("osd/", "client/")


def _is_backoff_ctor(node: ast.AST, aliases: Dict[str, str]) -> bool:
    """``Backoff(...)`` / ``backoff.Backoff(...)`` under any import
    alias — the shared-policy constructor (common/backoff.py)."""
    if not isinstance(node, ast.Call):
        return False
    dotted = _dotted(node.func, aliases)
    if dotted and dotted.split(".")[-1] == "Backoff":
        return True
    return isinstance(node.func, ast.Name) and node.func.id == "Backoff"


def _retry19_async_fn(fi: FileInfo, fn,
                      out: List[Violation]) -> None:
    # names bound to a shared-policy Backoff anywhere in this function
    bonames: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and \
                _is_backoff_ctor(node.value, fi.aliases):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    bonames.add(t.id)

    def uses_policy(loop: ast.While) -> bool:
        for node in ast.walk(loop):
            if isinstance(node, ast.Await) and \
                    isinstance(node.value, ast.Call) and \
                    isinstance(node.value.func, ast.Attribute) and \
                    node.value.func.attr in ("sleep", "wait_for"):
                base = node.value.func.value
                if isinstance(base, ast.Name) and base.id in bonames:
                    return True
        return False

    for loop in ast.walk(fn):
        if not isinstance(loop, ast.While):
            continue
        backed = uses_policy(loop)
        for node in ast.walk(loop):
            if not (isinstance(node, ast.Await)
                    and isinstance(node.value, ast.Call)
                    and _dotted(node.value.func,
                                fi.aliases) == "asyncio.sleep"):
                continue
            args = node.value.args
            if not (args and isinstance(args[0], ast.Constant)
                    and isinstance(args[0].value, (int, float))):
                continue              # config-driven / computed delay
            if args[0].value == 0:
                continue              # pure yield-to-loop idiom
            if backed or fi.waived("RETRY19", node.lineno):
                continue
            out.append(Violation(
                "RETRY19", fi.rel, node.lineno,
                f"fixed {args[0].value}s retry/poll interval in a "
                f"while loop: degraded-path retries must use the "
                f"shared jittered backoff (common/backoff.py "
                f"Backoff.sleep/wait_for in the same loop) or carry "
                f"a waiver"))


def _retry19_handler_catches_timeout(handler: ast.ExceptHandler,
                                     aliases: Dict[str, str]) -> bool:
    t = handler.type
    types = t.elts if isinstance(t, ast.Tuple) else ([t] if t else [])
    for ty in types:
        if isinstance(ty, ast.Name) and ty.id == "TimeoutError":
            return True
        if _dotted(ty, aliases) in ("asyncio.TimeoutError",
                                    "concurrent.futures.TimeoutError"):
            return True
    return False


def check_retry19(fi: FileInfo) -> Iterator[Violation]:
    if not fi.rel.startswith(_RETRY_PREFIXES):
        return
    out: List[Violation] = []
    for node in ast.walk(fi.tree):
        if isinstance(node, ast.AsyncFunctionDef):
            _retry19_async_fn(fi, node, out)
        elif isinstance(node, ast.Try):
            for h in node.handlers:
                if _retry19_handler_catches_timeout(h, fi.aliases) \
                        and len(h.body) == 1 \
                        and isinstance(h.body[0], ast.Pass) \
                        and not fi.waived("RETRY19", h.lineno):
                    out.append(Violation(
                        "RETRY19", fi.rel, h.lineno,
                        "bare `except TimeoutError: pass` swallows a "
                        "timeout with no backoff, give-up tag or "
                        "counter — handle it through the shared "
                        "policy (common/backoff.py) or waive with "
                        "the reason the silence is safe"))
    yield from out


# ------------------------------------------------------------------ QOS20

_QOS20_PREFIXES = ("osd/",)


def check_qos20(fi: FileInfo) -> Iterator[Violation]:
    if not fi.rel.startswith(_QOS20_PREFIXES):
        return
    for node in ast.walk(fi.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "put_nowait"):
            continue
        recv = _attr_text(node.func.value) or ""
        if "op_queue" not in recv:
            continue
        tagged = len(node.args) >= 2 or \
            any(kw.arg == "klass" for kw in node.keywords)
        if tagged or fi.waived("QOS20", node.lineno):
            continue
        yield Violation(
            "QOS20", fi.rel, node.lineno,
            f"untagged enqueue {recv}.put_nowait(op): ops entering the "
            f"PG op queue must carry an explicit QoS class (the seam "
            f"is scheduler-polymorphic — an untagged put bills the "
            f"'client' reservation under dmClock).  Route through "
            f"queue_op, pass the class, or waive a deliberate default")


# --------------------------------------------------------------- registry

RULES: Dict[str, Tuple[str, Callable[[FileInfo], Iterator[Violation]]]] = {
    "AF01": ("submit section is await-free", check_af01),
    "FP02": ("frozen-payload copy discipline", check_fp02_send03),
    "BLK04": ("no blocking calls on the event loop", check_blk04),
    "MONO05": ("monotonic clock discipline in op paths", check_mono05),
    "LOCK06": ("FileDB lock order _io -> _mu", check_lock06),
    "FIN07": ("windowed slot release under finally", check_fin07),
    "REPLY09": ("handlers reply or requeue on every path", check_reply09),
    "EPOCH10": ("epoch/interval guard before PG-state mutation",
                check_epoch10),
    "SHARD11": ("PG state is touched only from its home shard",
                check_shard11),
    "RETRY19": ("op-path retry loops ride the shared jittered backoff",
                check_retry19),
    "QOS20": ("op-queue enqueues carry an explicit QoS class tag",
              check_qos20),
}

def _seam_rule(rule_id: str):
    """Late-bound adapter: the seam analysis (devtools/seam.py) builds
    on this module, so the project-rule entries import it lazily."""
    def check(files: List[FileInfo]) -> Iterator[Violation]:
        from ceph_tpu.devtools.seam import analyze
        for v in analyze(files).violations:
            if v.rule == rule_id:
                yield v
    return check


def _device_rule(rule_id: str):
    """Late-bound adapter for the device-seam analysis
    (devtools/device.py): SYNC15 / JIT16 / XFER17 share one pass."""
    def check(files: List[FileInfo]) -> Iterator[Violation]:
        from ceph_tpu.devtools.device import analyze
        for v in analyze(files).violations:
            if v.rule == rule_id:
                yield v
    return check


#: project-wide rules: run over the WHOLE linted file set at once
PROJECT_RULES: Dict[str, Tuple[str,
                               Callable[[List[FileInfo]],
                                        Iterator[Violation]]]] = {
    "PROTO08": ("cross-daemon message graph is exhaustive",
                check_proto08),
    "ESC12": ("no shared-mutable state escapes the shard seam "
              "undeclared", _seam_rule("ESC12")),
    "PORT13": ("every seam-crossing value is process-portable",
               _seam_rule("PORT13")),
    "ATOM14": ("GIL-atomicity reliance sits in declared regions",
               _seam_rule("ATOM14")),
    "SYNC15": ("no implicit device->host sync on the op path",
               _device_rule("SYNC15")),
    "JIT16": ("jit entry points on the op path are retrace-stable",
              _device_rule("JIT16")),
    "XFER17": ("host<->device transfers are staged or wire-classified",
               _device_rule("XFER17")),
    "STAGE18": ("tracer chain stages and span cut sites stay in sync",
                check_stage18),
}

#: SEND03 is produced by the FP02 scanner (shared dataflow pass) but is
#: its own rule id for waivers/filtering
RULE_IDS = tuple(RULES) + tuple(PROJECT_RULES) + ("SEND03",)

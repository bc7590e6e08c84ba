"""Shard-seam escape analysis: interprocedural rules ESC12 / PORT13 /
ATOM14 + the machine-readable seam inventory.

The sharded data plane (PR 10) works because the GIL makes the
lock-free handoff ring and shared daemon-scope object graphs
accidentally safe; moving a shard lane into its own interpreter or
process turns every undeclared shared-mutable reference and every
live (non-wire-encodable) payload into silent corruption or a crash.
This pass proves the data plane is PROCESS-PORTABLE before the GIL
escape by following *data* across the seam, where SHARD11 follows
call sites:

  ESC12  seam escape       — project-wide call graph + reachability:
                             functions are tiled onto execution sides
                             (A = the intake/home event loop, B = the
                             shard lanes, C = the kv-sync commit
                             thread) by seeding the SHARD11 intake set
                             on side A, every callable handed across a
                             seam site on side B, and thread targets
                             on side C, then propagating through a
                             name-resolved call graph.  Any MUTATION
                             of a shared-mutable structure (container
                             attributes initialized in ``__init__``,
                             read-modify-write scalar attributes,
                             module-global counters) of the seam
                             modules that is visible from more than
                             one side — or written at all from the
                             multi-lane side B — must sit under a
                             declared lock, inside a ``# gil-atomic``
                             region, or carry a waiver.  This is
                             SHARD11's big sibling: it follows the
                             data, not the call sites.
  PORT13 process portability — every VALUE crossing a seam site
                             (``shards.route``/``post``, a shard or
                             courier ring, ``call_soon_threadsafe``,
                             the kv-sync queue, ``shard_router
                             .deliver``, ``resolve_future``) must be a
                             frozen lazy payload with a byte-identical
                             wire fallback (a registered message /
                             Encodable), a loop-safe primitive from
                             the explicit allowlist, or a bound
                             method of the object that LIVES on the
                             target lane (expressible on a wire as
                             routing-key + method name).  A lambda or
                             locally-defined closure captures
                             arbitrary live state invisibly; a live
                             object reference (a PG) passed as DATA
                             cannot exist in the sending process once
                             lanes split — both are violations.
  ATOM14 declared GIL reliance — code relying on GIL-atomicity of
                             shared structures (the ring's deque,
                             handoff counters, wakeup flags) must sit
                             inside ``# gil-atomic:begin <attrs>
                             <reason>`` / ``# gil-atomic:end``
                             sentinel regions.  Once an attribute is
                             declared, ANY write to it in that module
                             outside a region is a violation — the
                             region set is therefore exhaustive, and
                             compiles into the seam inventory
                             (``ceph-tpu-lint --seam-report``) that is
                             the work-list the GIL-escape PR consumes.

Waivers use the standard ``# lint: allow[ID] reason`` channel and are
themselves audited (an allow that suppresses nothing is reported).
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from ceph_tpu.devtools.rules import (FileInfo, Violation, _attr_text,
                                     _registered_messages)

#: seam-inventory schema version (bumped on incompatible shape change)
SEAM_SCHEMA = 1

#: the modules whose shared state IS the seam (candidate scope): the
#: handoff ring, the daemon intake surface, the messenger marshalling
#: layer, the lazy-payload counters, the commit-thread staging
SEAM_MODULES = ("osd/shards.py", "osd/daemon.py", "osd/lanes.py",
                "osd/laneipc.py", "osd/extents.py",
                "msg/messenger.py", "msg/payload.py",
                "store/commit.py")

#: call-graph / reachability scope (PROTO08-grade name resolution is
#: only meaningful inside the data plane's own packages; the client
#: stack runs whole on its own loop and holds no seam site, and its
#: generic method names — getxattr, truncate — would wire unrelated
#: subsystems together under name-based resolution)
SCOPE_PREFIXES = ("osd/", "msg/", "store/", "mon/")

#: functions whose body runs on WHICHEVER thread calls them (the
#: marshalling entry points themselves): their accesses are
#: multi-thread by construction, regardless of reachability
ANY_THREAD_FUNCS = {
    ("msg/messenger.py", "_post_home"),
    ("osd/shards.py", "post"),
    ("osd/shards.py", "resolve_future"),
    ("store/commit.py", "submit"),
    ("store/commit.py", "_flush_staged"),
}

#: explicit side-B seeds beyond seam-site callables
SHARD_SEED_FUNCS = {("osd/shards.py", "_pump")}

#: intake-side seed: the SHARD11 intake/heartbeat surface plus the
#: messenger's reader/worker machinery (all home-loop affine)
_INTAKE_RE = re.compile(
    r"^(ms_dispatch|_handle_\w+|_heartbeat\w*|_scrub_scheduler|"
    r"_tier_agent_loop|_report_stats|_boot_loop|_on_osdmap|"
    r"_advance_pgs|_local_worker|_serve_peer|_dispatch|_parse_frame|"
    r"_dispatch_op_batch|_route_batched_op)$")

#: names never resolved as call-graph edges (ubiquitous stdlib-ish
#: method names that would wire everything to everything)
_EDGE_STOPLIST = {
    "get", "items", "values", "keys", "append", "extend", "pop",
    "popleft", "add", "update", "clear", "remove", "setdefault",
    "join", "split", "encode", "decode", "format", "sort", "copy",
    "set", "wait", "acquire", "release", "cancel", "close", "done",
    "result", "info", "debug", "warning", "error", "exception",
    "inc", "tinc", "hinc", "dump", "create", "register", "cut",
    "mark", "send", "recv", "read", "write", "put", "empty",
    "truncate", "seek", "tell", "stat", "getxattr", "setattr",
    "exists", "touch", "getvalue",
}

# ------------------------------------------------------------ gil-atomic

_GIL_BEGIN_RE = re.compile(r"#\s*gil-atomic:begin\b\s*(.*)$")
_GIL_END_RE = re.compile(r"#\s*gil-atomic:end\b")


class GilRegion:
    __slots__ = ("rel", "begin", "end", "attrs", "reason")

    def __init__(self, rel: str, begin: int, end: int,
                 attrs: List[str], reason: str):
        self.rel = rel
        self.begin = begin
        self.end = end
        self.attrs = attrs
        self.reason = reason

    def covers(self, line: int, attr: Optional[str] = None) -> bool:
        if not (self.begin < line < self.end):
            return False
        return attr is None or attr in self.attrs

    def to_json(self) -> dict:
        return {"rel": self.rel, "begin": self.begin, "end": self.end,
                "attrs": list(self.attrs), "reason": self.reason}


def parse_gil_regions(fi: FileInfo) -> Tuple[List[GilRegion],
                                             List[Violation]]:
    """Balanced ``# gil-atomic:begin attrs reason`` / ``:end`` regions
    + the region-hygiene violations (ATOM14's bookkeeping half)."""
    regions: List[GilRegion] = []
    vios: List[Violation] = []
    open_at: Optional[Tuple[int, List[str], str]] = None
    for ln in sorted(fi.comments):
        c = fi.comments[ln]
        m = _GIL_BEGIN_RE.search(c)
        if m:
            if open_at is not None:
                vios.append(Violation(
                    "ATOM14", fi.rel, ln,
                    f"nested gil-atomic:begin (previous at line "
                    f"{open_at[0]} not closed)"))
            rest = m.group(1).strip()
            parts = rest.split(None, 1)
            attrs = [a for a in (parts[0].split(",") if parts else [])
                     if a]
            reason = parts[1].strip() if len(parts) > 1 else ""
            if attrs and not reason:
                # a long attr list may push the reason to the next
                # comment line(s)
                nxt = fi.comments.get(ln + 1, "")
                if not _GIL_BEGIN_RE.search(nxt) \
                        and not _GIL_END_RE.search(nxt):
                    reason = nxt.lstrip("# ").strip()
            if not attrs or not reason:
                vios.append(Violation(
                    "ATOM14", fi.rel, ln,
                    "gil-atomic:begin must declare its structures and "
                    "a reason: `# gil-atomic:begin attr[,attr...] "
                    "why this is GIL-safe`"))
            open_at = (ln, attrs, reason)
        elif _GIL_END_RE.search(c):
            if open_at is None:
                vios.append(Violation(
                    "ATOM14", fi.rel, ln,
                    "gil-atomic:end without begin"))
            else:
                regions.append(GilRegion(fi.rel, open_at[0], ln,
                                         open_at[1], open_at[2]))
                open_at = None
    if open_at is not None:
        vios.append(Violation(
            "ATOM14", fi.rel, open_at[0],
            "gil-atomic:begin never closed"))
    return regions, vios


# -------------------------------------------------------- function model

class FnInfo:
    """One function's summary for the call graph + side propagation."""

    __slots__ = ("rel", "cls", "name", "node", "called", "home_guard",
                 "thread_targets")

    def __init__(self, rel: str, cls: Optional[str], name: str, node):
        self.rel = rel
        self.cls = cls
        self.name = name
        self.node = node
        #: (receiver leaf name or None, callee name) pairs, resolved
        #: later receiver-aware (see _Resolver)
        self.called: Set[Tuple[Optional[str], str]] = set()
        #: begins with the home-thread marshal guard: the body runs on
        #: the home loop no matter which thread entered (a foreign
        #: caller is re-posted through the courier) — reaching it from
        #: side B does NOT make its accesses side-B
        self.home_guard = False
        #: threading.Thread(target=self.X) targets started here
        self.thread_targets: Set[str] = set()

    @property
    def qual(self) -> str:
        return f"{self.rel}:{self.cls + '.' if self.cls else ''}" \
               f"{self.name}"


def _callee_name(call: ast.Call) -> Optional[str]:
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def _collect_functions(files: List[FileInfo],
                       prefixes: Tuple[str, ...] = SCOPE_PREFIXES
                       ) -> List[FnInfo]:
    """Function summaries for the call graph.  The default scope is the
    shard-seam set; the device-seam pass (devtools/device.py) reuses
    the same collector over its wider host+device module set."""
    out: List[FnInfo] = []
    for fi in files:
        if not fi.rel.startswith(prefixes):
            continue

        def walk(node, cls: Optional[str]) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    walk(child, child.name)
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    fn = FnInfo(fi.rel, cls, child.name, child)
                    _summarize(fn, fi)
                    out.append(fn)
                    walk(child, cls)

        walk(fi.tree, None)
    return out


def _recv_leaf(call: ast.Call) -> Optional[str]:
    """The receiver segment directly under the method name: ``self``
    for ``self.f()``, ``messenger`` for ``self.messenger.f()``,
    ``shard_for`` for ``...shard_for(pgid).f()``; None for a bare
    ``f()``."""
    f = call.func
    if not isinstance(f, ast.Attribute):
        return None
    v = f.value
    if isinstance(v, ast.Name):
        return v.id
    if isinstance(v, ast.Attribute):
        return v.attr
    if isinstance(v, ast.Call):
        return _callee_name(v)
    return None


def _summarize(fn: FnInfo, fi: FileInfo) -> None:
    for sub in ast.walk(fn.node):
        if isinstance(sub, ast.Call):
            name = _callee_name(sub)
            if name and name not in _EDGE_STOPLIST:
                fn.called.add((_recv_leaf(sub), name))
            # create_task(self.x()) keeps the caller's loop: edge to x
            if name == "create_task" and sub.args \
                    and isinstance(sub.args[0], ast.Call):
                inner = _callee_name(sub.args[0])
                if inner:
                    fn.called.add((_recv_leaf(sub.args[0]), inner))
            # threading.Thread(target=self._run): _run is a thread side
            if name == "Thread":
                for kw in sub.keywords:
                    if kw.arg == "target" and isinstance(
                            kw.value, ast.Attribute):
                        fn.thread_targets.add(kw.value.attr)
        elif isinstance(sub, ast.Attribute) \
                and sub.attr == "_on_home_thread":
            fn.home_guard = True


#: generic lifecycle names: NEVER resolved globally — only a
#: receiver-class or same-class match produces an edge (a global
#: ``.start()`` edge would wire every subsystem to every other)
_GENERIC_METHODS = {"start", "stop", "run", "shutdown", "sync",
                    "submit", "flush", "reset", "apply", "drain"}


class _Resolver:
    """Receiver-aware call edge resolution.

    ``self.f()`` resolves to the caller's own class (else same file);
    ``pg.start()`` resolves only to classes whose name matches the
    receiver leaf (``pg`` -> PG, ``messenger`` -> Messenger,
    ``shard_for`` -> Shard); anything else falls back to every
    definition of the name — except for _GENERIC_METHODS, which
    produce no edge without a receiver match."""

    def __init__(self, fns: List[FnInfo]):
        self.by_name: Dict[str, List[FnInfo]] = {}
        for fn in fns:
            self.by_name.setdefault(fn.name, []).append(fn)

    @staticmethod
    def _cls_match(recv: str, cls: Optional[str]) -> bool:
        if not cls:
            return False
        r = recv.lower().lstrip("_")
        c = cls.lower().lstrip("_")
        return bool(r) and (r in c or c in r)

    def resolve(self, caller: FnInfo, recv: Optional[str],
                meth: str) -> List[FnInfo]:
        cands = self.by_name.get(meth, [])
        if not cands:
            return []
        if recv is None or recv == "self":
            same_cls = [c for c in cands if c.rel == caller.rel
                        and c.cls == caller.cls]
            if same_cls:
                return same_cls
            same_file = [c for c in cands if c.rel == caller.rel]
            if recv == "self":
                return same_file
            if same_file:
                return same_file
            return [] if meth in _GENERIC_METHODS else cands
        matched = [c for c in cands if self._cls_match(recv, c.cls)]
        if matched:
            return matched
        return [] if meth in _GENERIC_METHODS else cands


# ------------------------------------------------------------ seam sites

#: classification lattice for values crossing the seam
CLS_PRIMITIVE = "primitive"        # loop-safe scalar / routing key
CLS_WIRE = "wire"                  # Encodable/message: byte-identical
#                                    wire fallback exists (PORT13 ok)
CLS_HOME_BOUND = "home-bound"      # bound method of the target lane's
#                                    own object: (routing key, method
#                                    name) is wire-expressible
CLS_FORWARDED = "forwarded"        # seam plumbing re-forwarding its
#                                    already-classified payload
CLS_FUTURE = "target-future"       # future owned by the target loop
CLS_EXTENT = "extent-handle"       # (pool, gen, off, len) shared-
#                                    memory extent handle: a named
#                                    segment plus scalars, portable by
#                                    construction (osd/extents.py; the
#                                    wire carries it as the
#                                    EXTENT_MARK form of data_bytes_)
CLS_CLOSURE = "closure"            # lambda / nested def: VIOLATION
CLS_LIVE = "live-ref"              # live shared object as data: VIOLATION
CLS_RAW_BYTES = "raw-bytes"        # bulk payload bytes as seam DATA:
#                                    VIOLATION — an over-threshold
#                                    payload must publish ONCE to an
#                                    extent pool and cross as a handle
CLS_OPAQUE = "opaque"              # unclassifiable: VIOLATION

_VIOLATING = {CLS_CLOSURE, CLS_LIVE, CLS_OPAQUE, CLS_RAW_BYTES}

_PRIMITIVE_NAMES = {
    "pgid", "pool_id", "pool", "epoch", "key", "cost", "seq", "idx",
    "tid", "n", "now", "count", "size", "value", "flag", "no_light",
    "no_deep", "light_ms", "deep_ms", "peer_type", "whoami", "nbytes",
    "exc", "code", "rank", "name", "note", "cfg", "config", "light",
    "deep",
    # idx-keyed completion/commit RECORDS (store/commit.py _Item,
    # osd/laneipc frame ids): plain-scalar tuples/int lists by
    # construction — the process-portable replacement for the old
    # closure-list handoffs the PR-12 waivers marked.  PORT13 extends
    # its allowlist to the naming convention; the record types
    # themselves carry only seq/idx/flag scalars (rule catalog: see
    # README "Invariant sanitizer" PORT13 notes).
    "rec", "recs", "records", "record",
}
_WIRE_NAMES = {
    "m", "msg", "op", "ops", "reply", "req", "rep", "batch", "view",
    "osdmap", "addr", "info", "entry", "txn",
}
_FUTURE_NAMES = {"fut", "future"}
#: extent-handle conventions (osd/extents.py Handle / ExtentRef): the
#: zero-copy replacement for raw payload bytes on the seam
_EXTENT_NAMES = {"handle", "handles", "ext_handle", "extent",
                 "extent_handle"}
#: bulk payload buffer conventions: crossing a seam INLINE is the
#: raw-bytes-over-threshold escape the extent pool exists to close
_RAW_BYTES_NAMES = {"data", "payload", "payloads", "blob", "raw"}
_LIVE_NAMES = {"pg", "conn", "loop", "task", "store", "shard",
               "writer", "reader", "gate", "q", "osd", "backend"}
#: constructor calls whose result has a wire form
_WIRE_CTOR_EXTRA = {"PGId", "EVersion", "EntityAddr", "EntityName",
                    "CollectionId", "ObjectId", "PGInfo"}
#: method calls whose result is portable
_PORTABLE_CALLS = {"without_shard", "with_shard", "monotonic",
                   "perf_counter", "get_ident", "local_cost",
                   # Tracer.stamp(): time.monotonic() or 0.0
                   "stamp"}
_WIRE_CALLS = {"local_view", "mutable", "mutable_copy", "peek"}
_LIVE_SOURCES = {"_pg_for", "_load_stray_pg", "get_running_loop",
                 "get_event_loop"}


class SeamValue:
    __slots__ = ("expr", "cls", "role")

    def __init__(self, expr: str, cls: str, role: str):
        self.expr = expr
        self.cls = cls
        self.role = role    # "callable" | "data" | "routing-key"

    def to_json(self) -> dict:
        return {"expr": self.expr, "class": self.cls, "role": self.role}


class SeamSite:
    __slots__ = ("rel", "line", "kind", "values", "fn")

    def __init__(self, rel: str, line: int, kind: str, fn: str):
        self.rel = rel
        self.line = line
        self.kind = kind
        self.fn = fn
        self.values: List[SeamValue] = []

    def to_json(self) -> dict:
        return {"rel": self.rel, "line": self.line, "kind": self.kind,
                "fn": self.fn,
                "values": [v.to_json() for v in self.values]}


def _seam_call(call: ast.Call, rel: str
               ) -> Optional[Tuple[str, Optional[int], int]]:
    """(kind, callable-arg index or None, first data-arg index) when
    this Call crosses the shard seam; None otherwise."""
    f = call.func
    if isinstance(f, ast.Name):
        if f.id == "resolve_future":
            return ("future-resolve", None, 0)
        return None
    if not isinstance(f, ast.Attribute):
        return None
    attr = f.attr
    recv = _attr_text(f.value) or ""
    recv_is_shard_chain = (
        "shard" in recv or "courier" in recv
        or (isinstance(f.value, ast.Call)
            and isinstance(f.value.func, ast.Attribute)
            and f.value.func.attr == "shard_for"))
    if attr == "route" and recv_is_shard_chain:
        return ("shard-route", 1, 2)
    if attr == "post" and recv_is_shard_chain:
        # plane.post(pgid, fn, ...) vs shard/courier.post(fn, ...)
        if recv.endswith("shards") or ".shards" in recv:
            return ("shard-post", 1, 2)
        return ("ring-post", 0, 1)
    if attr == "_post_home":
        return ("courier-post", 0, 1)
    if attr == "call_soon_threadsafe":
        return ("cross-loop", 0, 1)
    if attr == "resolve_future":
        return ("future-resolve", None, 0)
    if attr == "deliver" and "router" in recv:
        return ("shard-deliver", None, 0)
    if attr == "put" and recv.endswith("_q") \
            and rel == "store/commit.py":
        return ("kv-queue", None, 0)
    return None


class _FnEnv:
    """Shallow forward dataflow inside one function: name -> class."""

    def __init__(self, fn_node, fi: FileInfo):
        self.fi = fi
        self.env: Dict[str, str] = {}
        #: module-level names assigned constants/sentinels (portable)
        self.mod_consts: Set[str] = set()
        for node in fi.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                v = node.value
                if isinstance(v, ast.Constant) or (
                        isinstance(v, ast.Call)
                        and isinstance(v.func, ast.Name)
                        and v.func.id == "object"):
                    self.mod_consts.add(node.targets[0].id)
        args = fn_node.args
        for a in (list(args.posonlyargs) + list(args.args)
                  + list(args.kwonlyargs)):
            self.env[a.arg] = self._by_name(a.arg)
        if args.vararg:
            self.env[args.vararg.arg] = CLS_FORWARDED
        # one linear pass over the body: assignments refine classes,
        # nested defs become closures
        for st in ast.walk(fn_node):
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and st is not fn_node:
                self.env[st.name] = CLS_CLOSURE
            elif isinstance(st, ast.Assign) and len(st.targets) == 1 \
                    and isinstance(st.targets[0], ast.Name):
                name = st.targets[0].id
                if isinstance(st.value, ast.Lambda):
                    self.env[name] = CLS_CLOSURE
                else:
                    got = self.classify(st.value, binding=name)
                    if got == CLS_OPAQUE:
                        # an unclassifiable producer does not DOWNGRADE
                        # a name whose convention is known (`now =
                        # int(...)`, `msg = self._parse_frame(...)`)
                        got = self._by_name(name)
                    self.env[name] = got

    def _by_name(self, name: str) -> str:
        if name in _PRIMITIVE_NAMES:
            return CLS_PRIMITIVE
        if name in _WIRE_NAMES:
            return CLS_WIRE
        if name in _FUTURE_NAMES:
            return CLS_FUTURE
        if name in _EXTENT_NAMES:
            return CLS_EXTENT
        if name in _RAW_BYTES_NAMES:
            return CLS_RAW_BYTES
        if name in _LIVE_NAMES:
            return CLS_LIVE
        if name in ("fn", "cb", "callback", "post", "on_commit"):
            return CLS_FORWARDED
        return CLS_OPAQUE

    def classify(self, node: ast.AST,
                 binding: Optional[str] = None) -> str:
        if isinstance(node, ast.Constant):
            return CLS_PRIMITIVE
        if isinstance(node, ast.Lambda):
            return CLS_CLOSURE
        if isinstance(node, ast.Starred):
            return self.classify(node.value)
        if isinstance(node, (ast.BinOp, ast.UnaryOp, ast.Compare,
                             ast.BoolOp)):
            parts = [self.classify(v) for v in ast.iter_child_nodes(
                node) if isinstance(v, ast.expr)]
            parts = [p for p in parts if p != CLS_PRIMITIVE]
            return parts[0] if parts else CLS_PRIMITIVE
        if isinstance(node, ast.Subscript):
            # cfg["..."] reads and container indexing classify by the
            # container (a slice of a wire object is wire-derived)
            return self.classify(node.value)
        if isinstance(node, ast.Name):
            if node.id in self.env:
                return self.env[node.id]
            if node.id in self.mod_consts:
                return CLS_PRIMITIVE
            return self._by_name(node.id)
        if isinstance(node, ast.Attribute):
            # classify by the FINAL attribute name (m.pgid -> routing
            # key; self.osdmap -> wire), falling back to the base
            leaf = self._by_name(node.attr)
            if leaf is not CLS_OPAQUE:
                return leaf
            base = self.classify(node.value)
            if base == CLS_WIRE:
                return CLS_WIRE     # field of a wire object
            return CLS_OPAQUE
        if isinstance(node, ast.Call):
            fname = _callee_name(node)
            if fname in _WIRE_CALLS:
                return CLS_WIRE
            if fname == "make_ref":
                return CLS_EXTENT
            if fname in _PORTABLE_CALLS:
                return CLS_PRIMITIVE
            if fname in _LIVE_SOURCES:
                return CLS_LIVE
            if fname in self._registered or fname in _WIRE_CTOR_EXTRA:
                return CLS_WIRE
            return CLS_OPAQUE
        if isinstance(node, (ast.List, ast.Tuple, ast.Set)):
            parts = {self.classify(e) for e in node.elts}
            bad = parts & _VIOLATING
            if bad:
                return sorted(bad)[0]
            return CLS_PRIMITIVE if parts <= {CLS_PRIMITIVE} \
                else CLS_WIRE
        return CLS_OPAQUE

    _registered: Set[str] = set()       # patched per analysis run

    def classify_callable(self, node: ast.AST) -> str:
        if isinstance(node, ast.Lambda):
            return CLS_CLOSURE
        if isinstance(node, ast.Attribute):
            return CLS_HOME_BOUND       # bound method: key + name
        if isinstance(node, ast.Name):
            got = self.env.get(node.id)
            if got == CLS_CLOSURE:
                return CLS_CLOSURE
            if got == CLS_FORWARDED:
                return CLS_FORWARDED
            # module-level function reference
            return CLS_HOME_BOUND
        return CLS_OPAQUE


# ------------------------------------------------------- shared state

_MUTABLE_CTORS = {"dict", "list", "set", "deque", "OrderedDict",
                  "defaultdict"}
_MUTATOR_METHODS = {"append", "extend", "insert", "add", "update",
                    "clear", "remove", "pop", "popleft", "popitem",
                    "setdefault", "appendleft", "sort", "reverse"}


class SharedAttr:
    """One candidate shared-mutable structure of a seam module."""

    __slots__ = ("rel", "cls", "attr", "kind", "writes", "reads")

    def __init__(self, rel: str, cls: Optional[str], attr: str,
                 kind: str):
        self.rel = rel
        self.cls = cls
        self.attr = attr
        self.kind = kind            # "container" | "rmw-scalar"
        #: (rel, line, fn qual, sides, protection)
        self.writes: List[Tuple[str, int, str, str, str]] = []
        self.reads: List[Tuple[str, int, str, str]] = []

    @property
    def key(self) -> Tuple[str, Optional[str], str]:
        return (self.rel, self.cls, self.attr)

    def to_json(self) -> dict:
        return {
            "module": self.rel, "class": self.cls, "attr": self.attr,
            "kind": self.kind,
            "writes": [{"rel": r, "line": ln, "fn": fn, "sides": s,
                        "protection": p}
                       for r, ln, fn, s, p in sorted(self.writes)],
            "reads": [{"rel": r, "line": ln, "fn": fn, "sides": s}
                      for r, ln, fn, s in sorted(self.reads)],
        }


def _candidate_attrs(files: List[FileInfo]) -> Dict[
        Tuple[str, Optional[str], str], SharedAttr]:
    """Shared-mutable candidates: container attributes assigned in a
    seam-module class ``__init__`` (or at module level), plus scalar
    attributes that are read-modify-written (``+=``) ANYWHERE — an
    augassign is never atomic, whatever the type."""
    out: Dict[Tuple[str, Optional[str], str], SharedAttr] = {}
    for fi in files:
        if fi.rel not in SEAM_MODULES:
            continue
        for node in ast.walk(fi.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            for item in node.body:
                if not (isinstance(item, (ast.FunctionDef,
                                          ast.AsyncFunctionDef))
                        and item.name == "__init__"):
                    continue
                for st in ast.walk(item):
                    if isinstance(st, ast.Assign) \
                            and len(st.targets) == 1:
                        t, v = st.targets[0], st.value
                    elif isinstance(st, ast.AnnAssign) \
                            and st.value is not None:
                        t, v = st.target, st.value
                    else:
                        continue
                    if not (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"):
                        continue
                    is_mut = isinstance(v, (ast.Dict, ast.List,
                                            ast.Set)) or (
                        isinstance(v, ast.Call)
                        and _callee_name(v) in _MUTABLE_CTORS)
                    if is_mut:
                        sa = SharedAttr(fi.rel, node.name, t.attr,
                                        "container")
                        out[sa.key] = sa
        # module-global RMW counters (payload.py _C-style): any
        # augassign rooted at a module-level name
        mod_names = {t.id for st in fi.tree.body
                     if isinstance(st, ast.Assign)
                     for t in st.targets if isinstance(t, ast.Name)}
        for node in ast.walk(fi.tree):
            if isinstance(node, ast.AugAssign) \
                    and isinstance(node.target, ast.Attribute):
                root, attrs = _chain(node.target)
                if root in mod_names and attrs:
                    sa = SharedAttr(fi.rel, root, attrs[-1],
                                    "rmw-scalar")
                    out.setdefault(sa.key, sa)
                elif root == "self" and attrs:
                    sa = SharedAttr(fi.rel, None, attrs[-1],
                                    "rmw-scalar")
                    out.setdefault(sa.key, sa)
    return out


def _chain(node: ast.AST) -> Tuple[Optional[str], List[str]]:
    attrs: List[str] = []
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        if isinstance(node, ast.Attribute):
            attrs.append(node.attr)
        node = node.value
    return (node.id if isinstance(node, ast.Name) else None,
            list(reversed(attrs)))


_LOCK_NAME_RE = re.compile(r"(lock|_mu|_io|_cv)$", re.IGNORECASE)


def _lock_lines(fn_node) -> Set[int]:
    """Line numbers lexically inside a ``with <...lock>`` block."""
    out: Set[int] = set()
    for node in ast.walk(fn_node):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        held = False
        for item in node.items:
            t = _attr_text(item.context_expr)
            if t and _LOCK_NAME_RE.search(t.rsplit(".", 1)[-1]):
                held = True
        if held:
            for st in node.body:
                for sub in ast.walk(st):
                    ln = getattr(sub, "lineno", None)
                    if ln is not None:
                        out.add(ln)
    return out


# ---------------------------------------------------------- the analysis

class SeamAnalysis:
    """One full pass over a linted file set.  Violations carry rule ids
    ESC12 / PORT13 / ATOM14; ``report()`` emits the seam inventory."""

    def __init__(self, files: List[FileInfo]):
        #: the FULL input set is retained: the analyze() memo keys on
        #: the ids of ALL handed-in FileInfos, so every one of them
        #: must stay alive as long as the memo entry does — an
        #: out-of-scope FileInfo freed and id-recycled would otherwise
        #: produce a stale memo hit that silently drops violations
        self.all_files = list(files)
        self.files = [fi for fi in files
                      if fi.rel.startswith(SCOPE_PREFIXES)]
        self.by_rel = {fi.rel: fi for fi in self.files}
        self.violations: List[Violation] = []
        self.sites: List[SeamSite] = []
        self.regions: Dict[str, List[GilRegion]] = {}
        self.shared: Dict[Tuple[str, Optional[str], str], SharedAttr] \
            = {}
        self.sides: Dict[str, Set[str]] = {}
        self._alias_cache: Dict[str, Dict[str, Tuple[str, List[str]]]] \
            = {}
        #: waiver queries that suppressed something during
        #: construction — replayed on memo hits (see analyze())
        self.waiver_hits: List[Tuple[str, str, int]] = []
        self._run()

    def _waived(self, fi: FileInfo, rule: str, line: int) -> bool:
        if fi.waived(rule, line):
            self.waiver_hits.append((fi.rel, rule, line))
            return True
        return False

    # ------------------------------------------------------------ phases
    def _run(self) -> None:
        for fi in self.files:
            regions, vios = parse_gil_regions(fi)
            self.regions[fi.rel] = regions
            self.violations.extend(vios)
        self.fns = _collect_functions(self.files)
        self._scan_sites()
        self._propagate_sides()
        self._scan_shared_state()
        self._check_atom14()

    # seam sites + PORT13
    def _scan_sites(self) -> None:
        _FnEnv._registered = _registered_messages(self.files)
        for fn in self.fns:
            if fn.rel.startswith(("tools/", "devtools/")):
                continue
            env: Optional[_FnEnv] = None
            for sub in ast.walk(fn.node):
                if not isinstance(sub, ast.Call):
                    continue
                got = _seam_call(sub, fn.rel)
                if got is None:
                    continue
                kind, call_idx, data_idx = got
                if env is None:
                    env = _FnEnv(fn.node, self.by_rel[fn.rel])
                site = SeamSite(fn.rel, sub.lineno, kind, fn.qual)
                args = list(sub.args)
                for i, a in enumerate(args):
                    src = ast.unparse(a) if hasattr(ast, "unparse") \
                        else "<expr>"
                    if call_idx is not None and i == call_idx:
                        cls = env.classify_callable(a)
                        role = "callable"
                    elif call_idx is not None and i < call_idx:
                        cls = env.classify(a)
                        role = "routing-key"
                    elif kind == "future-resolve" and i == 0:
                        cls = CLS_FUTURE
                        role = "data"
                    else:
                        cls = env.classify(a)
                        role = "data"
                    site.values.append(SeamValue(src, cls, role))
                    if cls in _VIOLATING:
                        self.violations.append(Violation(
                            "PORT13", fn.rel, sub.lineno,
                            self._port13_msg(kind, role, cls, src)))
                # keyword arguments cross the seam exactly like
                # positional ones — a kwarg-passed closure/live ref
                # must not evade the rule (or the side-B seeding)
                for kw in sub.keywords:
                    if kw.arg is None:      # **kwargs forwarding
                        cls, role = CLS_FORWARDED, "data"
                        src = "**" + (ast.unparse(kw.value)
                                      if hasattr(ast, "unparse")
                                      else "<expr>")
                    else:
                        src = ast.unparse(kw.value) \
                            if hasattr(ast, "unparse") else "<expr>"
                        if kw.arg in ("fn", "cb", "callback"):
                            cls = env.classify_callable(kw.value)
                            role = "callable"
                        else:
                            cls = env.classify(kw.value)
                            role = "data"
                    site.values.append(SeamValue(src, cls, role))
                    if cls in _VIOLATING:
                        self.violations.append(Violation(
                            "PORT13", fn.rel, sub.lineno,
                            self._port13_msg(kind, role, cls, src)))
                self.sites.append(site)

    @staticmethod
    def _port13_msg(kind: str, role: str, cls: str, src: str) -> str:
        if cls == CLS_CLOSURE:
            return (f"{role} {src!r} crossing the {kind} seam is a "
                    f"lambda/closure: it captures live state "
                    f"invisibly and has no wire form — pass a bound "
                    f"method of the target lane's object (routing "
                    f"key + method name) or portable data instead")
        if cls == CLS_LIVE:
            return (f"{role} {src!r} crossing the {kind} seam is a "
                    f"live shared-object reference: once shard lanes "
                    f"are processes the sender cannot hold it — pass "
                    f"the routing key (pgid) and re-resolve on the "
                    f"home lane")
        if cls == CLS_RAW_BYTES:
            return (f"{role} {src!r} crossing the {kind} seam is a "
                    f"raw payload byte buffer: copying an over-"
                    f"threshold payload inline through the seam "
                    f"defeats the zero-copy transport — publish it "
                    f"once to a shared-memory extent pool "
                    f"(data_bytes_/ExtentSink, osd/extents.py) and "
                    f"pass the (pool, gen, off, len) handle instead")
        return (f"{role} {src!r} crossing the {kind} seam is not "
                f"classifiable as portable (frozen payload with wire "
                f"fallback, allowlisted primitive, or home-bound "
                f"method): declare it or restructure the handoff")

    # call-graph reachability
    def _propagate_sides(self) -> None:
        resolver = _Resolver(self.fns)
        by_qual = {fn.qual: fn for fn in self.fns}
        # B seeds: every callable handed across a seam site, resolved
        # receiver-aware ("pg.queue_op" seeds PG.queue_op, not every
        # queue_op in the tree)
        b_seeds: Set[str] = set()
        for site in self.sites:
            if site.kind in ("kv-queue",):
                continue
            caller = by_qual.get(site.fn)
            if caller is None:
                continue
            for v in site.values:
                if v.role != "callable" or "(" in v.expr:
                    continue
                parts = v.expr.rsplit(".", 2)
                meth = parts[-1]
                recv = parts[-2] if len(parts) > 1 else None
                for cand in resolver.resolve(caller, recv, meth):
                    b_seeds.add(cand.qual)
        sides: Dict[str, Set[str]] = {fn.qual: set()
                                      for fn in self.fns}
        work: List[Tuple[FnInfo, str]] = []
        for fn in self.fns:
            if _INTAKE_RE.match(fn.name):
                work.append((fn, "A"))
            if fn.qual in b_seeds or (fn.rel, fn.name) \
                    in SHARD_SEED_FUNCS:
                work.append((fn, "B"))
            if (fn.rel, fn.name) in ANY_THREAD_FUNCS:
                work.append((fn, "A"))
                work.append((fn, "B"))
            for tgt in fn.thread_targets:
                for cand in resolver.by_name.get(tgt, []):
                    if cand.rel == fn.rel:
                        work.append((cand, "C"))
        while work:
            fn, side = work.pop()
            eff = "A" if (side == "B" and fn.home_guard) else side
            if eff in sides[fn.qual]:
                continue
            sides[fn.qual].add(eff)
            for recv, meth in fn.called:
                for cand in resolver.resolve(fn, recv, meth):
                    if eff not in sides[cand.qual]:
                        work.append((cand, eff))
        self.sides = sides

    # shared-state ESC12
    def _scan_shared_state(self) -> None:
        cands = _candidate_attrs(self.files)
        #: attr name -> candidate keys (for foreign-receiver matching)
        by_attr: Dict[str, List[Tuple]] = {}
        for key in cands:
            by_attr.setdefault(key[2], []).append(key)
        for fn in self.fns:
            fsides = self.sides.get(fn.qual, set())
            if not fsides:
                continue        # unreachable from any seam side
            side_tag = "".join(sorted(fsides))
            lock_ln = _lock_lines(fn.node)
            fi = self.by_rel[fn.rel]
            regions = self.regions.get(fn.rel, [])

            def match(root: Optional[str],
                      attrs: List[str]) -> Optional[SharedAttr]:
                if root is None or not attrs:
                    return None
                leaf = attrs[-1]
                keys = by_attr.get(leaf)
                if not keys:
                    return None
                if root == "self" and len(attrs) == 1 and fn.cls:
                    key = (fn.rel, fn.cls, leaf)
                    if key in cands:
                        return cands[key]
                    # rmw-scalar candidates are class-agnostic
                    key = (fn.rel, None, leaf)
                    if key in cands:
                        return cands[key]
                    return None
                # foreign receiver (peer._local_pending, _C.calls,
                # osd.pgs): name-scoped match
                for key in keys:
                    if key[1] == root or root != "self":
                        return cands[key]
                return None

            def protection(line: int, attr: str) -> str:
                if line in lock_ln:
                    return "lock"
                for rg in regions:
                    if rg.covers(line, attr):
                        return "gil-atomic"
                if self._waived(fi, "ESC12", line):
                    return "waived"
                return "none"

            for sub in ast.walk(fn.node):
                wrote: Optional[Tuple[SharedAttr, int]] = None
                if isinstance(sub, (ast.Assign, ast.AugAssign)):
                    targets = sub.targets if isinstance(
                        sub, ast.Assign) else [sub.target]
                    for t in targets:
                        if not isinstance(t, (ast.Attribute,
                                              ast.Subscript)):
                            continue
                        root, attrs = _chain(t)
                        # plain rebinds of a scalar are atomic; a
                        # SUBSCRIPT store or any augassign is not
                        deep = isinstance(t, ast.Subscript) \
                            or isinstance(sub, ast.AugAssign) \
                            or len(attrs) > 1
                        sa = match(root, attrs)
                        if sa is not None and (
                                sa.kind == "rmw-scalar"
                                and isinstance(sub, ast.AugAssign)
                                or sa.kind == "container" and deep):
                            wrote = (sa, sub.lineno)
                elif isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Attribute) \
                        and sub.func.attr in _MUTATOR_METHODS:
                    root, attrs = _chain(sub.func.value)
                    # `ring = self.ring; ring.popleft()` aliasing
                    if root is not None and not attrs:
                        alias = self._alias_of(fn, root)
                        if alias is not None:
                            root, attrs = alias
                    sa = match(root, attrs)
                    if sa is not None and sa.kind == "container":
                        wrote = (sa, sub.lineno)
                elif isinstance(sub, ast.Attribute):
                    root, attrs = _chain(sub)
                    sa = match(root, attrs)
                    if sa is not None:
                        sa.reads.append((fn.rel, sub.lineno, fn.qual,
                                         side_tag))
                if wrote is not None:
                    sa, line = wrote
                    prot = protection(line, sa.attr)
                    sa.writes.append((fn.rel, line, fn.qual, side_tag,
                                      prot))
        # verdicts: a write is hazardous when it is reachable from the
        # multi-lane side (B) or its attr is visible from another side
        for sa in cands.values():
            if not sa.writes:
                continue
            all_sides: Set[str] = set()
            for _r, _l, _f, s, _p in sa.writes:
                all_sides.update(s)
            for _r, _l, _f, s in sa.reads:
                all_sides.update(s)
            for rel, line, fnq, s, prot in sa.writes:
                hazardous = "B" in s or (len(all_sides) > 1
                                         and bool(s))
                if not hazardous or prot != "none":
                    continue
                self.violations.append(Violation(
                    "ESC12", rel, line,
                    f"{fnq.split(':', 1)[1]}() mutates "
                    f"{sa.cls + '.' if sa.cls else ''}{sa.attr} "
                    f"(shared {sa.kind}, reachable from seam sides "
                    f"{'+'.join(sorted(all_sides))}) with no declared "
                    f"protection: route it through the shard seam, "
                    f"hold a lock, or declare the GIL reliance in a "
                    f"# gil-atomic region"))
            self.shared[sa.key] = sa

    def _alias_of(self, fn: FnInfo,
                  name: str) -> Optional[Tuple[str, List[str]]]:
        cache = self._alias_cache.get(fn.qual)
        if cache is None:
            cache = {}
            for sub in ast.walk(fn.node):
                if isinstance(sub, ast.Assign) \
                        and len(sub.targets) == 1 \
                        and isinstance(sub.targets[0], ast.Name) \
                        and isinstance(sub.value, ast.Attribute):
                    root, attrs = _chain(sub.value)
                    if root is not None and attrs:
                        cache[sub.targets[0].id] = (root, attrs)
            self._alias_cache[fn.qual] = cache
        return cache.get(name)

    # ATOM14: declared structures may only be written inside regions
    def _check_atom14(self) -> None:
        for fi in self.files:
            regions = self.regions.get(fi.rel, [])
            declared: Set[str] = set()
            for rg in regions:
                declared.update(rg.attrs)
            if not declared:
                continue
            # construction is exempt: an object being built in
            # __init__ is not yet visible to any other thread
            init_lines: Set[int] = set()
            for node in ast.walk(fi.tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) \
                        and node.name == "__init__":
                    for sub in ast.walk(node):
                        ln = getattr(sub, "lineno", None)
                        if ln is not None:
                            init_lines.add(ln)
            for node in ast.walk(fi.tree):
                line = getattr(node, "lineno", None)
                attr: Optional[str] = None
                if isinstance(node, (ast.Assign, ast.AugAssign,
                                     ast.AnnAssign)):
                    targets = node.targets if isinstance(
                        node, ast.Assign) else [node.target]
                    for t in targets:
                        root, attrs = _chain(t)
                        if attrs and attrs[-1] in declared:
                            attr = attrs[-1]
                elif isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr in _MUTATOR_METHODS:
                    root, attrs = _chain(node.func.value)
                    if attrs and attrs[-1] in declared:
                        attr = attrs[-1]
                if attr is None or line is None:
                    continue
                if line in init_lines:
                    continue
                if any(rg.covers(line, attr) for rg in regions):
                    continue
                if self._waived(fi, "ATOM14", line):
                    continue
                self.violations.append(Violation(
                    "ATOM14", fi.rel, line,
                    f"write to {attr!r} outside a gil-atomic region: "
                    f"this module declares {attr!r} GIL-atomic-shared "
                    f"— every mutation must sit inside a "
                    f"# gil-atomic:begin/end region (or carry a "
                    f"waiver) so the seam inventory stays exhaustive"))

    # ------------------------------------------------------------ report
    def report(self) -> dict:
        regions = [rg.to_json()
                   for rel in sorted(self.regions)
                   for rg in self.regions[rel]]
        shared = [self.shared[k].to_json()
                  for k in sorted(self.shared,
                                  key=lambda k: (k[0], k[1] or "",
                                                 k[2]))]
        for entry in shared:
            # classification the GIL-escape PR consumes: how is this
            # structure protected today / what must replace it
            prots = {w["protection"] for w in entry["writes"]}
            wsides: Set[str] = set()
            for w in entry["writes"]:
                wsides.update(w["sides"])
            if prots <= {"lock"}:
                entry["classification"] = "lock"
            elif "none" in prots and "B" not in wsides:
                # single-side writers (the home loop, or the commit
                # thread alone): protected by loop/thread affinity,
                # not by the GIL — stays valid under process lanes
                entry["classification"] = "loop-affine"
            elif "none" in prots:
                entry["classification"] = "UNPROTECTED"
            elif "gil-atomic" in prots:
                entry["classification"] = "gil-atomic"
            else:
                entry["classification"] = "waived"
        sites = [s.to_json() for s in sorted(
            self.sites, key=lambda s: (s.rel, s.line))]
        n_port = sum(1 for s in sites for v in s["values"]
                     if v["class"] in _VIOLATING)
        return {
            "seam_schema": SEAM_SCHEMA,
            "sites": sites,
            "gil_atomic_regions": regions,
            "shared_state": shared,
            "value_classes": {
                "portable": [CLS_PRIMITIVE, CLS_WIRE, CLS_HOME_BOUND,
                             CLS_FORWARDED, CLS_FUTURE, CLS_EXTENT],
                "violating": sorted(_VIOLATING),
            },
            "summary": {
                "sites": len(sites),
                "values": sum(len(s["values"]) for s in sites),
                "unportable_values": n_port,
                "gil_atomic_regions": len(regions),
                "shared_structures": len(shared),
                "unprotected_structures": sum(
                    1 for e in shared
                    if e["classification"] == "UNPROTECTED"),
            },
        }


# --------------------------------------------------------- entry point

_MEMO: Dict[Tuple[int, ...], SeamAnalysis] = {}


def analyze(files: List[FileInfo]) -> SeamAnalysis:
    """Memoized per file set (the three rule adapters and the report
    all share one pass).  On a memo hit the waiver queries the
    analysis made during construction are REPLAYED, so per-run
    waiver-usage accounting (the unused-waiver audit) stays correct
    when the engine resets usage between runs."""
    key = tuple(id(fi) for fi in files)
    got = _MEMO.get(key)
    if got is None:
        # keep a few entries: fixture lints (tiny file sets) must not
        # evict the expensive live-tree analysis between tier-1 runs
        while len(_MEMO) >= 4:
            _MEMO.pop(next(iter(_MEMO)))
        got = _MEMO[key] = SeamAnalysis(files)
    else:
        by_rel = {fi.rel: fi for fi in files}
        for rel, rule, line in got.waiver_hits:
            fi = by_rel.get(rel)
            if fi is not None:
                fi.waived(rule, line)
    return got

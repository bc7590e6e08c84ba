"""Typed configuration system with defaults, observers and runtime injection.

Reference parity: md_config_t (common/config.h:78,96) over the generated
OPTION() table (common/config_opts.h).  Re-designed as a declarative Option
registry: each subsystem registers options at import time; values are layered
(defaults < config file < env < argv < injectargs) and observers are notified
with the set of changed keys, exactly like md_config_t::apply_changes.
"""

from __future__ import annotations

import json
import os
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

OPT_TYPES = ("int", "float", "bool", "str", "addr", "uuid", "size")


def _parse_size(v: str) -> int:
    suffixes = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30, "t": 1 << 40}
    s = str(v).strip().lower()
    if s and s[-1] in suffixes:
        return int(float(s[:-1]) * suffixes[s[-1]])
    return int(s, 0) if isinstance(v, str) else int(v)


def _coerce(type_: str, v: Any) -> Any:
    if type_ == "int":
        return int(v, 0) if isinstance(v, str) else int(v)
    if type_ == "float":
        return float(v)
    if type_ == "bool":
        if isinstance(v, str):
            return v.strip().lower() in ("1", "true", "yes", "on")
        return bool(v)
    if type_ == "size":
        return _parse_size(v)
    return str(v)


@dataclass
class Option:
    name: str
    type: str
    default: Any
    desc: str = ""
    # observer-safe options may change at runtime; others need restart
    runtime: bool = True

    def __post_init__(self):
        assert self.type in OPT_TYPES, self.type
        if self.default is not None:
            self.default = _coerce(self.type, self.default)


class Config:
    """Layered typed config with change observers.

    Meta-variable expansion supports $name/$cluster/$type/$id/$pid like the
    reference's md_config_t::expand_meta.
    """

    def __init__(self, options: Optional[Iterable[Option]] = None):
        self._lock = threading.RLock()
        self._schema: Dict[str, Option] = {}
        self._values: Dict[str, Any] = {}
        self._observers: List[Tuple[Tuple[str, ...], Callable[[set], None]]] = []
        self._meta = {"cluster": "ceph-tpu", "name": "client.admin",
                      "type": "client", "id": "admin", "pid": str(os.getpid())}
        for opt in DEFAULT_OPTIONS:
            self.register(opt)
        for opt in options or ():
            self.register(opt)

    # -- schema ------------------------------------------------------------
    def register(self, opt: Option) -> None:
        with self._lock:
            self._schema[opt.name] = opt

    def register_many(self, opts: Iterable[Option]) -> None:
        for o in opts:
            self.register(o)

    def schema(self) -> Dict[str, Option]:
        return dict(self._schema)

    # -- meta --------------------------------------------------------------
    def set_daemon_name(self, type_: str, id_: str) -> None:
        with self._lock:
            self._meta.update(
                {"type": type_, "id": id_, "name": f"{type_}.{id_}"})

    def expand_meta(self, s: str) -> str:
        if not isinstance(s, str) or "$" not in s:
            return s
        out = s
        for k, v in self._meta.items():
            out = out.replace("$" + k, v)
        return out

    # -- get/set -----------------------------------------------------------
    def get(self, name: str) -> Any:
        with self._lock:
            opt = self._schema[name]
            v = self._values.get(name, opt.default)
            return self.expand_meta(v) if opt.type == "str" else v

    def __getitem__(self, name: str) -> Any:
        return self.get(name)

    def set(self, name: str, value: Any, notify: bool = True) -> None:
        self.set_many({name: value}, notify=notify)

    def set_many(self, kv: Dict[str, Any], notify: bool = True) -> None:
        changed = set()
        with self._lock:
            for name, value in kv.items():
                if name not in self._schema:
                    raise KeyError(f"unknown config option {name!r}")
                opt = self._schema[name]
                cv = _coerce(opt.type, value)
                if self._values.get(name, opt.default) != cv:
                    self._values[name] = cv
                    changed.add(name)
        if notify and changed:
            self._notify(changed)

    # -- layers ------------------------------------------------------------
    def parse_env(self, env: Optional[Dict[str, str]] = None) -> None:
        env = os.environ if env is None else env
        kv = {}
        for name in self._schema:
            ev = env.get("CEPH_TPU_" + name.upper())
            if ev is not None:
                kv[name] = ev
        if kv:
            self.set_many(kv)

    def parse_argv(self, argv: List[str]) -> List[str]:
        """Consume --opt-name value / --opt-name=value; return leftovers."""
        rest, kv, i = [], {}, 0
        while i < len(argv):
            a = argv[i]
            if a.startswith("--"):
                body = a[2:]
                if "=" in body:
                    key, val = body.split("=", 1)
                else:
                    key = body
                    opt = self._schema.get(key.replace("-", "_"))
                    if opt is not None and opt.type == "bool":
                        val = "true"
                    elif i + 1 < len(argv):
                        i += 1
                        val = argv[i]
                    else:
                        val = "true"
                key = key.replace("-", "_")
                if key in self._schema:
                    kv[key] = val
                else:
                    rest.append(a)
            else:
                rest.append(a)
            i += 1
        if kv:
            self.set_many(kv)
        return rest

    def parse_file(self, path: str) -> None:
        """ini-ish conf file: `key = value` lines, [section] headers applying
        to matching daemon names (global/<type>/<type>.<id>)."""
        section = "global"
        wanted = {"global", self._meta["type"], self._meta["name"]}
        kv: Dict[str, Any] = {}
        with open(path) as f:
            for line in f:
                line = line.split("#", 1)[0].split(";", 1)[0].strip()
                if not line:
                    continue
                if line.startswith("[") and line.endswith("]"):
                    section = line[1:-1].strip()
                    continue
                if "=" in line and section in wanted:
                    k, v = line.split("=", 1)
                    k = k.strip().replace(" ", "_").replace("-", "_")
                    if k in self._schema:
                        kv[k] = v.strip()
        if kv:
            self.set_many(kv)

    def injectargs(self, args: str) -> str:
        """Runtime mutation, reference: md_config_t::injectargs via admin
        socket. Returns human-readable report."""
        toks = args.split()
        leftover = self.parse_argv(toks)
        if leftover:
            return f"ignored unknown args: {leftover}"
        return "applied"

    # -- observers ---------------------------------------------------------
    def add_observer(self, keys: Iterable[str], fn: Callable[[set], None]) -> None:
        with self._lock:
            self._observers.append((tuple(keys), fn))

    def remove_observer(self, fn: Callable[[set], None]) -> None:
        with self._lock:
            self._observers = [(k, f) for k, f in self._observers if f is not fn]

    def _notify(self, changed: set) -> None:
        with self._lock:
            obs = list(self._observers)
        for keys, fn in obs:
            hit = changed.intersection(keys)
            if hit:
                fn(hit)

    # -- introspection -----------------------------------------------------
    def dump(self) -> Dict[str, Any]:
        with self._lock:
            return {n: self._values.get(n, o.default)
                    for n, o in sorted(self._schema.items())}

    def diff(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._values)

    def dump_json(self) -> str:
        return json.dumps(self.dump(), default=str, indent=1, sort_keys=True)


# Central defaults table (reference: common/config_opts.h, 1126 OPTIONs; we
# grow this as subsystems land — each entry documents its reference knob).
DEFAULT_OPTIONS: List[Option] = [
    Option("log_level", "int", 1, "global log verbosity"),
    Option("log_file", "str", "", "log sink path; empty = stderr"),
    Option("log_max_recent", "int", 10000, "ring buffer size (log/Log.cc)"),
    Option("admin_socket", "str", "", "unix admin socket path"),
    Option("public_addr", "addr", "127.0.0.1:0", "daemon bind address"),
    Option("ms_type", "str", "async", "messenger implementation"),
    Option("ms_tcp_nodelay", "bool", True, "disable nagle"),
    Option("ms_initial_backoff", "float", 0.2, "reconnect backoff start"),
    Option("ms_max_backoff", "float", 15.0, "reconnect backoff cap"),
    Option("ms_inject_socket_failures", "int", 0,
           "fault injection: fail 1-in-N socket ops (config_opts.h:197)"),
    Option("ms_local_delivery", "bool", False,
           "deliver to co-located (same-process) messengers directly, "
           "skipping TCP framing/crc/acks (AsyncMessenger "
           "local_connection fast-dispatch role); auto-disabled under "
           "socket fault injection or cephx"),
    Option("ms_dispatch_throttle_bytes", "size", "100m",
           "inflight dispatch byte throttle"),
    Option("mon_lease", "float", 5.0, "paxos lease seconds (mon/Paxos.h:912)"),
    Option("mon_tick_interval", "float", 5.0, "monitor tick"),
    Option("mon_election_timeout", "float", 5.0, "elector timeout"),
    Option("mon_osd_min_down_reporters", "int", 1,
           "distinct failure reporters to mark an osd down"),
    Option("mon_osd_down_out_interval", "float", 300.0,
           "seconds down before auto-out (config_opts.h)"),
    Option("mon_data", "str", "", "monitor store path"),
    Option("mon_paxos_batch_interval", "float", 0.05,
           "pending-proposal batching window (PaxosService)"),
    Option("paxos_propose_interval", "float", 1.0,
           "up_thru grant batching window after a down-mark "
           "(OSDMonitor::prepare_alive riding Paxos batching): a grant "
           "held across this window is dropped if its requester dies, "
           "so a doomed solo survivor's interval is never branded "
           "maybe_went_rw"),
    Option("osd_heartbeat_interval", "float", 1.0, "osd/OSD.cc:4223"),
    Option("osd_heartbeat_grace", "float", 6.0, "mark-down grace"),
    Option("osd_pool_default_size", "int", 3, "replica count"),
    Option("osd_pool_default_min_size", "int", 0, "0 = size - size/2"),
    Option("osd_pool_default_pg_num", "int", 8, "pgs per new pool"),
    Option("osd_pg_max_inflight_ops", "int", 16,
           "per-PG client-op window: ops on disjoint objects run "
           "concurrently up to this depth, dependency-tracked by "
           "object id (ShardedOpWQ + ObjectContext rw-state role); "
           "1 = the old serial worker"),
    Option("osd_op_num_shards", "int", 0,
           "sharded data plane (osd/shards.py; ShardedOpWQ + "
           "msgr-worker role): PGs hash to this many shards, each "
           "with its own work ring + pump (own event-loop thread "
           "with osd_shard_threads).  0 = auto (one per core, max "
           "8); 1 = the single-loop plane (today's behavior, "
           "bit-for-bit)"),
    Option("osd_op_num_threads_per_shard", "int", 2, ""),
    Option("osd_shard_lanes", "str", "auto",
           "shard lane backend: inline (pumps as tasks on the host "
           "loop), thread (one event-loop thread per shard — the "
           "msgr-worker split), process (one multiprocessing worker "
           "per shard fed by shared-memory ring frames: real "
           "parallelism outside the GIL; osd/lanes.py).  auto = "
           "thread/inline per osd_shard_threads (the pre-lane knob). "
           "Forced to inline under the deterministic sim loop."),
    Option("osd_lane_ring_bytes", "size", "4m",
           "per-direction shared-memory ring capacity for process "
           "lanes (osd/laneipc.py); the ring bound IS the handoff "
           "backpressure"),
    Option("osd_lane_extent_min_bytes", "size", "32k",
           "object-data payloads at or above this ride the lane "
           "transport as shared-memory extents (one copy + a tiny "
           "handle on the ring) instead of inline wire bytes "
           "(osd/extents.py); 0 disables extents entirely"),
    Option("osd_lane_extent_pool_bytes", "size", "4m",
           "per-direction extent-pool arena per process lane; a full "
           "pool falls back to inline bytes (counted ext_alloc_full), "
           "it never blocks — backpressure belongs to the ring"),
    Option("osd_lane_cork", "bool", True,
           "cork every lane-bound frame queued in one loop pass into "
           "ONE ring frame (FRAME_BURST): one push, one wakeup, one "
           "drain per burst instead of per message"),
    Option("osd_rep_ack_coalesce", "bool", True,
           "coalesce replica commit acks per target OSD per drained "
           "commit burst into one MOSDRepAckBatch frame (the burst "
           "boundary is the store's batched completion callback)"),
    Option("osd_shard_threads", "bool", True,
           "run each shard's event loop on its own thread "
           "(msgr-worker split).  Forced off under the deterministic "
           "sim loop, where shard pumps are ordinary tasks the "
           "schedule explorer permutes; with this off the shards "
           "are cooperatively scheduled lanes on the host loop — "
           "the right choice on GIL-bound few-core hosts, where "
           "thread switches cost more than they parallelize"),
    Option("osd_recovery_max_active", "int", 3, "parallel recovery ops"),
    Option("osd_recovery_sleep", "float", 0.0,
           "pause between recovery windows, yielding the loop to "
           "client ops (graceful-degradation knob; 0 = no pause)"),
    Option("osd_recovery_push_timeout", "float", 20.0,
           "overall monotonic budget awaiting one recovery push ack "
           "before the cause-tagged give-up (common/backoff.py)"),
    Option("osd_ack_timeout", "float", 20.0,
           "overall monotonic budget awaiting replica acks / local "
           "commit before the cause-tagged give-up fails the peer "
           "set (was a hardcoded wait_for(fut, 20.0))"),
    Option("osd_max_object_size", "size", "128m", ""),
    Option("osd_client_message_size_cap", "size", "500m",
           "client op bytes in flight before intake blocks (Throttle)"),
    Option("osd_backfill_scan_max", "int", 512,
           "objects per backfill listing window (config_opts.h)"),
    Option("osd_mesh_mode", "str", "off",
           "on = co-located OSDs share a device mesh: EC writes encode "
           "as one sharded program and shard bytes skip the messenger "
           "(SURVEY §2.4 TPU-native data plane)"),
    Option("osd_scrub_interval", "float", 60.0, "light scrub cadence (test scale)"),
    Option("osd_tier_agent_interval", "float", 2.0,
           "cache-tier agent pass cadence (flush/evict scheduling)"),
    Option("osd_op_queue", "str", "wpq",
           "PG op scheduler (config_opts.h:706): wpq (weighted class "
           "round-robin, WeightedPriorityQueue.h — the deterministic "
           "FAST_CFG default, bit-for-bit the pre-QoS queue) | "
           "mclock (dmClock reservation/weight/limit tags per client "
           "class, common/qos.py; mClockScheduler role) | fifo"),
    Option("osd_qos_specs", "str",
           "client:r=40,w=60,l=0;background:r=8,w=4,l=0;"
           "default:r=0,w=10,l=0",
           "per-class dmClock specs for osd_op_queue=mclock: "
           "';'-separated class:r=<ops/s reservation>,w=<share>,"
           "l=<ops/s limit, 0=uncapped>.  recovery/scrub/agent work "
           "folds into 'background'; unlisted client classes take "
           "'default' (osd_mclock_scheduler_* role)"),
    Option("osd_deep_scrub_interval", "float", 300.0,
           "deep scrub cadence (reads + recomputes every digest)"),
    Option("osd_mon_report_interval", "float", 2.0,
           "pg/osd stats report cadence to the mon (PGMap feed)"),
    Option("mon_cluster_log_file", "str", "",
           "cluster log sink path on the mon ('' = memory only)"),
    Option("osd_ec_batch_device", "str", "auto",
           "EC encode device routing, decided once at OSD start: on "
           "(a real accelerator is REQUIRED, the start fails without "
           "one), auto (the accelerator when the process has one, the "
           "native SIMD kernel otherwise), force (any jax backend, "
           "for tests), off"),
    Option("osd_ec_batch_window_ms", "float", 2.0,
           "batch-collector fill window before a device launch"),
    Option("osd_ec_batch_min_bytes", "size", "64k",
           "lone requests below this take the host SIMD kernel"),
    Option("osd_ec_batch_flush_bytes", "size", "4m",
           "flush the collector early once this many pending encode "
           "bytes accumulate (bytes-quorum; window is the ceiling)"),
    Option("objectstore", "str", "memstore",
           "backend: memstore|filestore|blockstore|kstore"),
    Option("blockstore_compression", "str", "",
           "blob compressor: zlib|bz2|lzma|'' (bluestore_compression_*)"),
    Option("blockstore_compression_min_blob", "size", "4k",
           "smallest blob worth compressing"),
    Option("objectstore_path", "str", "",
           "directory under which every OSD of a disk-backed "
           "objectstore keeps its own osd.<id>/ (an in-process "
           "cluster; daemon processes use their --dir).  Relative: "
           "<TMPDIR>/<path>.<pid>, removed when the cluster stops"),
    Option("filestore_journal_size", "size", "64m", "WAL size"),
    Option("filestore_kill_at", "int", 0,
           "crash injection countdown in queue_transactions batches: "
           "N>0 dies after the Nth batch journals, N<0 before "
           "(config_opts.h:1171)"),
    Option("objecter_inflight_ops", "int", 1024,
           "ops one Objecter keeps in flight; a further op_submit "
           "waits, in submit order, until a reply (or an error, a "
           "timeout, a cancel) gives one back; 0 = no limit "
           "(config_opts.h objecter_inflight_ops; Objecter.cc "
           "_take_op_budget / _throttle_op)"),
    Option("objecter_inflight_op_bytes", "size", "100m",
           "bytes of op data one Objecter keeps in flight: a write "
           "costs its data, a read the length it asks for "
           "(Objecter.cc calc_op_budget); an op larger than the whole "
           "budget passes when nothing else is in flight; 0 = no limit "
           "(config_opts.h objecter_inflight_op_bytes, 100 MB)"),
    Option("objecter_op_batching", "bool", True,
           "cork client ops per target OSD within one loop pass: N "
           "MOSDOps coalesce into ONE wire frame / ONE local-delivery "
           "handoff (MOSDOpBatch), amortizing the per-message "
           "deliver/ack hops the op tracer attributes ~40% of local "
           "e2e to.  Replies stay per-op; resends bypass the cork"),
    Option("objecter_qos_class", "str", "",
           "default dmClock class stamped on this client's ops "
           "('' = client).  Per-task override: common/qos.py "
           "QOS_CLASS contextvar (a multi-tenant gateway sets it per "
           "request task over one shared rados client)"),
    Option("rgw_bucket_index_shards", "int", 1,
           "bucket-index shards for NEW buckets (rgw_override_bucket_"
           "index_max_shards role, config_opts.h:1305): keys hash to "
           "N shard objects so a PUT burst spreads over N PGs instead "
           "of serializing on one index object.  1 = legacy unsharded "
           "layout; existing buckets reshard via radosgw-admin bucket "
           "reshard"),
    Option("ec_batch_window_us", "int", 200,
           "TPU EC batch-collector window (ShardedOpWQ analog)"),
    Option("ec_batch_max_stripes", "int", 64, "max stripes per TPU launch"),
    Option("tpu_backend", "str", "auto", "auto|tpu|cpu for device kernels"),
    Option("crush_backend", "str", "auto", "auto|jax|host placement backend"),
    Option("heartbeat_inject_failure", "int", 0,
           "seconds to fake missed heartbeats (config_opts.h:172)"),
    Option("auth_supported", "str", "none",
           "cephx|none (auth_cluster_required, config_opts.h)"),
    Option("keyring", "str", "", "keyring file path ($name etc expanded)"),
    Option("auth_ticket_ttl", "float", 3600.0,
           "service ticket lifetime (auth_service_ticket_ttl)"),
    Option("lockdep", "bool", False,
           "lock-order cycle detection (common/lockdep.cc role): "
           "asyncio + thread locks built through the lockdep "
           "factories record an acquisition-order graph; inversions "
           "are reported with both backtraces (qa clusters fail at "
           "teardown on findings).  Zero overhead when off"),
    Option("lockdep_stall_budget", "float", 0.0,
           "loop-stall sanitizer: flag synchronous event-loop "
           "sections longer than this many seconds, attributed to "
           "the last op-tracer stage cut on the loop (0 = off; keep "
           "off on shared/loaded hosts — wall-clock stalls from CPU "
           "contention are indistinguishable from code stalls.  "
           "Under the deterministic sim loop (devtools/schedule.py) "
           "the monitor attaches to the loop itself and wall-times "
           "every callback: exhaustive detection, replayable "
           "attribution — sim runs can afford a budget)"),
    Option("op_tracing", "bool", False,
           "Dapper-style per-op span tracing + per-stage latency "
           "histograms (common/tracer.py; blkin/TrackedOp/"
           "perf_histogram role).  Off by default and fully off-path "
           "when off: no span allocation, no extra clock reads"),
    Option("osd_op_complaint_time", "float", 30.0,
           "ops in flight longer than this log one slow-op complaint "
           "and count in the osd.slow_ops counter "
           "(osd_op_complaint_time, osd/OSD.cc check_ops_in_flight)"),
    Option("osd_flight_recorder_size", "int", 64,
           "bounded ring of slow-op stage records kept per daemon for "
           "post-hoc attribution (dump_flight_recorder admin command); "
           "one record at complaint time + one at finish per slow op"),
]

#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tpu-rados still starts on the
chip: the served EC path (client write_full -> Objecter -> primary OSD
-> ECBatchQueue -> fused Pallas GF(2^8) apply on the TPU -> shard
fan-out -> store, then read and degraded read back through device
decode) and the batched CRUSH engine, each checked against the repo's
own plain references.

    python chip_smoke.py [--seed N]

One process: it imports jax itself and starts no child that needs the
chip (a chip belongs to one process).  It REQUIRES a TPU and exits
non-zero without one; `--cpu-dry-run` is the explicit toy-size
rehearsal for a machine with no chip, and stamps platform=cpu on every
line it prints.  It sets no JAX_PLATFORMS.  Any failed check or
uncaught exception in any phase is a non-zero exit.  The last two lines
of stdout are JSON objects: the summary (per-phase flags, counts and wall
times — for sizing later runs, none is a speed claim; it ends with
`"claim": null`; a dry run prefixes it like every other line), then,
last, the verdict the driver reads:
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`,
those keys and no others.
"""

import argparse
import asyncio
import importlib.metadata
import json
import sys
import time

import numpy as np

import jax

T_START = time.monotonic()
_TAG = ""


def say(msg: str) -> None:
    print(f"{_TAG}{msg}", flush=True)


class SmokeFailure(Exception):
    """A check failed; uncaught, so the process exits non-zero."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(f"{_TAG}{what}")


class JaxEvents:
    """jax.monitoring listener: jax's own count of executables built
    (every one, persistent-cache hit or not) and of persistent-cache
    hits and misses."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.n = {self.COMPILE: 0, self.HIT: 0, self.MISS: 0}
        jax.monitoring.register_event_listener(self._count)
        jax.monitoring.register_event_duration_secs_listener(self._count)

    def _count(self, event, *_secs, **_kw):
        if event in self.n:
            self.n[event] += 1

    def snap(self) -> dict:
        return {"compile_events": self.n[self.COMPILE],
                "cache_hits": self.n[self.HIT],
                "cache_misses": self.n[self.MISS]}


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


# --------------------------------------------------------------- kernel

def decode_case(gen: np.ndarray, lost: list):
    """(decode matrix, survivor ids) for losing chunk ids `lost`: the
    first k survivors, as backend._decode_shards picks them."""
    from ceph_tpu.ec import gf256
    k = gen.shape[1]
    present = [i for i in range(gen.shape[0]) if i not in lost][:k]
    return gf256.decode_matrix(gen, present, lost), present


def phase_kernel(rng, size) -> dict:
    from ceph_tpu import native
    from ceph_tpu.ec import gf256
    from ceph_tpu.ec.kernel import matrix_apply
    from ceph_tpu.osd.ec_queue import LANE_BUCKETS

    # north-star shape (BASELINE.json config 2): RS-Vandermonde k=8
    # m=4, `stripes` stripes of 1 MiB folded to [8, stripes * 128 KiB]
    k, m = 8, 4
    gen = gf256.rs_vandermonde_matrix(k, m)
    folded = rng.integers(0, 256, (k, size["stripes"] * (1 << 20) // k),
                          dtype=np.uint8)
    enc = matrix_apply(gen[k:])
    check(enc.fused is size["fused"],
          f"MatrixApply.fused is {enc.fused}, want {size['fused']}")
    parity = enc(folded)
    check(np.array_equal(parity, native.gf_matrix_apply(gen[k:], folded)),
          "k8m4 parity != native.gf_matrix_apply over the whole array")
    check(np.array_equal(parity[:, :65536],
                         gf256.host_apply(gen[k:], folded[:, :65536])),
          "k8m4 parity != gf256.host_apply on the 64 KiB slice")
    lost = [0, 3]
    dec, present = decode_case(gen, lost)
    surv = np.ascontiguousarray(np.concatenate([folded, parity])[present])
    check(np.array_equal(matrix_apply(dec)(surv), folded[lost]),
          "k8m4 2-erasure decode != the lost chunks")

    # every shape the queue can present: each lane bucket x the parity
    # and 1-/2-erasure decode matrices of both profiles
    shapes = 0
    for k, m in ((4, 2), (8, 4)):
        gen = gf256.rs_vandermonde_matrix(k, m)
        mats = [gen[k:], decode_case(gen, [0])[0],
                decode_case(gen, [0, k - 1])[0]]
        for lanes in LANE_BUCKETS[:size["buckets"]]:
            data = rng.integers(0, 256, (k, lanes), dtype=np.uint8)
            for mat in mats:
                check(np.array_equal(matrix_apply(mat)(data),
                                     native.gf_matrix_apply(mat, data)),
                      f"k{k}m{m} mat {mat.shape} lanes {lanes} != native")
                shapes += 1
    return {"north_star_bytes": int(folded.size), "queue_shapes": shapes,
            "fused": enc.fused}


# -------------------------------------------------------------- cluster

def queue_counters(osds) -> dict:
    """ec_batch_queue perf counters summed over `osds` (a list that
    keeps a killed OSD: Cluster drops it, its counters still count)."""
    dumps = [osd.ec_queue.perf.dump() for osd in osds]
    tot = {key: sum(int(d[key]) for d in dumps)
           for key in ("device_launches", "device_requests",
                       "device_bytes", "host_requests", "host_bytes",
                       "device_fallbacks")}
    for key in ("batch_fill", "pending_depth"):
        n = sum(d[key]["avgcount"] for d in dumps)
        tot[key] = round(sum(d[key]["sum"] for d in dumps) / n, 3) \
            if n else 0.0
    return tot


async def run_cluster(rng, size, events, device_mode) -> dict:
    from ceph_tpu.client.objecter import ObjectLocator
    from ceph_tpu.common import devstats
    from ceph_tpu.ec import gf256
    from ceph_tpu.qa.cluster import Cluster, make_ctx
    from ceph_tpu.store.types import CollectionId, ObjectId

    K, M, N_OSD = 4, 2, 6
    n_obj, obj_size, depth = size["objects"], size["obj_size"], 16

    def ctx_factory(name):
        # the data plane bench.py's e2e stage configures
        c = make_ctx(name)
        c.config.set("osd_ec_batch_device", device_mode)
        c.config.set("ms_local_delivery", True)
        c.config.set("osd_op_num_shards", 4)
        c.config.set("osd_shard_threads", False)
        c.config.set("objecter_op_batching", True)
        # FAST_CFG's failure detectors (1.5 s grace, out after 3 s)
        # are unit-test settings; 16 x 4 MiB in flight on one loop must
        # not read as OSD death, and the degraded leg wants ONE map
        # change.  These are Ceph's own defaults.
        c.config.set("osd_heartbeat_grace", 20.0)
        c.config.set("mon_osd_down_out_interval", 600.0)
        return c

    cl = Cluster(ctx_factory=ctx_factory)
    admin = await cl.start(N_OSD)
    await admin.pool_create("smoke", pg_num=32, pool_type="erasure",
                            k=K, m=M)
    io = admin.open_ioctx("smoke")
    osds = list(cl.osds.values())
    blobs = {f"smoke{i:04d}": rng.bytes(obj_size) for i in range(n_obj)}
    names = list(blobs)
    out = {"objects": n_obj, "obj_size": obj_size, "in_flight": depth}

    def ec_compiles():
        return devstats.counters()["compiles"].get("ec_apply", 0)

    # ---- write, in two halves: the second must compile nothing new
    halves = []
    for part in (names[:n_obj // 2], names[n_obj // 2:]):
        c0, e0, q0 = ec_compiles(), events.snap(), queue_counters(osds)
        t0 = time.monotonic()
        await cl.write_burst(io, {n: blobs[n] for n in part},
                             iodepth=depth)
        q1 = queue_counters(osds)
        halves.append({
            "objects": len(part),
            "wall_s": round(time.monotonic() - t0, 2),
            "ec_apply_compiles": ec_compiles() - c0,
            "jax": delta(events.snap(), e0),
            "device_launches": q1["device_launches"]
            - q0["device_launches"]})
        say(f"cluster: wrote half {len(halves)}: {halves[-1]}")
    out["write_halves"] = halves
    q = queue_counters(osds)
    out["after_write"] = q
    written = n_obj * obj_size
    check(q["device_fallbacks"] == 0, f"device_fallbacks {q}")
    check(q["host_bytes"] == 0, f"host_bytes != 0: {q}")
    check(q["device_bytes"] == written,
          f"device_bytes {q['device_bytes']} != bytes written {written}")
    check(q["device_requests"] == n_obj, f"device_requests {q}")
    check(devstats.byte_fraction() == 1.0,
          f"device_byte_fraction {devstats.byte_fraction()}")
    check(halves[1]["ec_apply_compiles"] == 0,
          f"ec_apply compiles grew in the second half: {halves[1]}")

    # ---- read everything back
    sem = asyncio.Semaphore(depth)

    async def read_all():
        async def one(n):
            async with sem:
                got = await io.read(n)
                check(got == blobs[n], f"read {n}: bytes differ")
        t0 = time.monotonic()
        await asyncio.gather(*[one(n) for n in names])
        return round(time.monotonic() - t0, 2)

    out["read_wall_s"] = await read_all()
    say(f"cluster: read {n_obj} objects back, identical")

    # ---- parity as stored on the OSDs vs the plain numpy reference
    omap = admin.monc.osdmap
    pool_id = omap.lookup_pool("smoke")
    gen = gf256.rs_vandermonde_matrix(K, M)
    acting_of = {n: omap.object_to_acting(n, ObjectLocator(pool_id))[:2]
                 for n in names}
    for n in names[:size["parity_objects"]]:
        pgid, acting = acting_of[n]
        chunks = np.frombuffer(blobs[n], np.uint8).reshape(K, -1)
        want = gf256.host_apply(gen[K:], chunks)
        for j in range(M):
            raw = cl.osds[acting[K + j]].store.read(
                CollectionId.pg(pool_id, pgid.seed, K + j),
                ObjectId(n, pool=pool_id))
            check(np.array_equal(np.frombuffer(raw, np.uint8), want[j]),
                  f"{n}: stored parity shard {K + j} != gf256.host_apply")
    out["parity_objects_checked"] = size["parity_objects"]
    say(f"cluster: stored parity of {size['parity_objects']} objects == "
        f"gf256.host_apply")

    # ---- degraded: kill one OSD, read everything through decode
    victim = max(cl.osds)
    need_decode = sum(1 for _, acting in acting_of.values()
                      if victim in acting[:K])
    q0 = queue_counters(osds)
    await cl.kill_osd(victim)
    await cl.mark_down_and_wait(admin, victim)
    # every survivor on the new map and every PG re-peered without the
    # victim: a read that raced the interval change would be executed
    # (and decoded) twice, and the request count below is exact
    epoch, t0 = admin.monc.osdmap.epoch, time.monotonic()
    while any(o.osdmap.epoch < epoch
              or any(pg.state != "active" or victim in pg.acting
                     for pg in o.pgs.values())
              for o in cl.osds.values()):
        check(time.monotonic() - t0 < 120, "PGs did not re-peer in 120 s")
        await asyncio.sleep(0.05)
    deg_wall = await read_all()
    q1 = queue_counters(osds)
    out["degraded"] = {
        "victim": victim, "read_wall_s": deg_wall,
        "objects_needing_decode": need_decode,
        "device_requests": q1["device_requests"] - q0["device_requests"],
        "device_launches": q1["device_launches"] - q0["device_launches"],
        "host_bytes": q1["host_bytes"] - q0["host_bytes"],
        "device_fallbacks": q1["device_fallbacks"]}
    say(f"degraded: {out['degraded']}")
    check(q1["device_fallbacks"] == 0, f"device_fallbacks {q1}")
    check(q1["host_bytes"] == 0, f"host_bytes != 0 degraded: {q1}")
    check(out["degraded"]["device_requests"] == need_decode,
          f"decode requests {out['degraded']['device_requests']} != "
          f"objects that lost a data shard {need_decode}")
    check(devstats.byte_fraction() == 1.0,
          f"device_byte_fraction {devstats.byte_fraction()}")
    out["device_byte_fraction"] = devstats.byte_fraction()
    await cl.stop()
    return out


# ---------------------------------------------------------------- crush

def phase_crush(rng, size) -> dict:
    from ceph_tpu.crush.builder import (build_hierarchy, make_erasure_rule,
                                        make_replicated_rule)
    from ceph_tpu.crush.mapper import do_rule
    from ceph_tpu.crush.types import CrushMap
    from ceph_tpu.ops.crush_kernel import batch_do_rule_arrays, warmup

    # BASELINE.json config 4: 1024 OSDs, 128 hosts x 8, straw2
    n_osd, per_host, n = 1024, 8, size["crush_inputs"]
    cmap = CrushMap()
    cmap.max_devices = n_osd
    build_hierarchy(cmap, n_osd, per_host)
    rep = make_replicated_rule(cmap, "rep")
    ec = make_erasure_rule(cmap, "ec", size=6)
    w = [0x10000] * n_osd
    xs = np.arange(n)
    sample = rng.choice(n, size=size["crush_sample"], replace=False)
    out = {"inputs": n, "sample_rows": len(sample)}
    for name, rule, nr in (("firstn3", rep, 3), ("indep6", ec, 6)):
        t0 = time.monotonic()
        check(warmup(cmap, rule, nr, w, sizes=(n,)), f"{name}: warmup")
        t_warm = time.monotonic() - t0
        t0 = time.monotonic()
        osds, cnt = batch_do_rule_arrays(cmap, rule, xs, nr, w,
                                         engine="jax")
        t_jax = time.monotonic() - t0
        hosds, hcnt = batch_do_rule_arrays(cmap, rule, xs, nr, w,
                                           engine="host")
        differ = int((osds != hosds).any(axis=1).sum())
        check(differ == 0, f"crush {name}: {differ} rows jax != host")
        check(cnt is None or np.array_equal(cnt, hcnt),
              f"crush {name}: counts jax != host")
        for x in sample:
            got = [int(o) for o in
                   (osds[x, :cnt[x]] if cnt is not None else osds[x])]
            check(got == do_rule(cmap, rule, int(x), nr, w),
                  f"crush {name}: x={x} jax != scalar do_rule")
        out[name] = {"warmup_s": round(t_warm, 2),
                     "map_s": round(t_jax, 2), "rows_equal_host": n}
        say(f"crush {name}: {out[name]}")
    return out


# ----------------------------------------------------------------- main

FULL = {"stripes": 32, "buckets": 5, "fused": True, "objects": 128,
        "obj_size": 4 << 20, "parity_objects": 8,
        "crush_inputs": 1_000_000, "crush_sample": 64}
TOY = {"stripes": 1, "buckets": 2, "fused": False, "objects": 8,
       "obj_size": 64 << 10, "parity_objects": 8,
       "crush_inputs": 4096, "crush_sample": 16}


def main() -> int:
    global _TAG
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds every byte of data and every sample")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="toy-size rehearsal on a machine with no TPU")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if args.cpu_dry_run:
        _TAG = f"[dry-run platform={dev.platform}] "
    elif dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found platform={dev.platform}); "
              f"refusing to run", file=sys.stderr)
        return 2
    size = TOY if args.cpu_dry_run else FULL
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}

    from ceph_tpu import native
    from ceph_tpu.common.envutil import enable_compile_cache
    cache_dir = enable_compile_cache()
    events = JaxEvents()

    def version(pkg):
        try:
            return importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            return None
    versions = {p: version(p) for p in ("jax", "jaxlib", "libtpu")}
    say(f"device {device} versions {versions} seed {args.seed}")
    say(f"compile cache: {cache_dir}")
    check(native.available(), "native library unavailable (g++ build)")
    simd = "gfni_avx512" if native.gf_simd_available() else "scalar"
    say(f"native host kernel: {simd}; crc32c: {native.crc32c_impl()}")
    say(f"sizes: {size}")

    rng = np.random.default_rng(args.seed)
    phases = {}

    def run_phase(name, fn):
        e0, t0 = events.snap(), time.monotonic()
        res = fn()
        res["wall_s"] = round(time.monotonic() - t0, 2)
        res["jax"] = delta(events.snap(), e0)
        res["ok"] = True
        phases[name] = res
        say(f"phase {name}: ok {res}")
        return res

    run_phase("kernel", lambda: phase_kernel(rng, size))
    device_mode = "on" if dev.platform == "tpu" else "force"
    res = run_phase("cluster", lambda: asyncio.run(
        run_cluster(rng, size, events, device_mode)))
    # the degraded leg ran inside the cluster's loop; it reports as its
    # own phase
    phases["degraded"] = {**res.pop("degraded"), "ok": True}
    run_phase("crush", lambda: phase_crush(rng, size))

    say(json.dumps({
        "ok": True, "device": device, "dry_run": args.cpu_dry_run,
        "seed": args.seed, "versions": versions,
        "native_simd": simd, "compile_cache_dir": cache_dir,
        "sizes": size, "phases": phases, "jax": events.snap(),
        "wall_s": round(time.monotonic() - T_START, 2),
        "claim": None}))
    # the verdict line: exactly these keys, the device as jax reports it
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

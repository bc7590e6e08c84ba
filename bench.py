#!/usr/bin/env python3
"""Benchmarks: EC encode/decode throughput, CRUSH mapping rate, and the
end-to-end EC pool axes.  REQUIRES a TPU: without one it exits non-zero
and prints no metric row.  (The metric set, cells and output contract
are ROADMAP S1's to redesign; `python chip_smoke.py` is the quick
on-chip proof.)

Contract: on success prints exactly ONE JSON line on stdout
  {"metric": ..., "value": N, "unit": "MB/s", "vs_baseline": N,
   "device": {...}, "extra": [...]}
Diagnostics go to stderr.  "extra" carries the secondary metrics in the
same {metric, value, unit} shape; every entry carries a "backend"
label, and a number measured on the host is only ever printed under a
host metric's name.

Shape:
  * the ORCHESTRATOR (no --stage argument) never imports jax: a parent
    that touched jax would hold the chip.  Each stage runs in its own
    subprocess, one at a time, with a timeout.
  * the first stage probes the device; anything but a TPU ends the run.
  * a stage that fails or times out ends the run with a non-zero exit.
  * the jax-free stages (cpu, crush_host, rgw_bucket_burst) run pinned
    to the CPU so they can never take the chip.

Reference harness equivalence:
- EC: ceph_erasure_code_benchmark --workload encode|decode --plugin isa
  --parameter technique=reed_sol_van -k 8 -m 4
  (/root/reference/src/test/erasure-code/ceph_erasure_code_benchmark.cc:
  46-63,179-187).  CPU baseline = the native GFNI/AVX-512 kernel
  (ceph_tpu/native/src/native.cc), the modern isa-l-class SIMD path;
  vs_baseline is TPU MB/s over that.
- CRUSH: osdmaptool --test-map-pgs (/root/reference/src/tools/
  osdmaptool.cc:73,328) over 128 hosts x 8 osds.  Baseline = the
  REFERENCE's own crush_do_rule (mapper.c) compiled -O3 -march=native at
  bench time from /root/reference sources via
  tests/golden/bench_ref_crush.c.  Where that tree is absent the CRUSH
  rows carry no vs_baseline.
"""

import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

import numpy as np

K, M = 8, 4
STRIPE = 1 << 20                       # 1 MiB of data per stripe
CHUNK = STRIPE // K                    # 128 KiB chunks
BATCH = 32                             # stripes per dispatch (batch the op
                                       # queue, survey §7 "hard parts")

CRUSH_N = int(os.environ.get("BENCH_CRUSH_N", "1000000"))
CRUSH_HOSTS, CRUSH_PER_HOST = 128, 8
REF = pathlib.Path("/root/reference")

DEADLINE = float(os.environ.get("BENCH_DEADLINE_SEC", "1140"))
T0 = time.monotonic()


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def remaining():
    return DEADLINE - (time.monotonic() - T0)


# --------------------------------------------------------------- test data

def _workload():
    """Deterministic generator matrix + folded data batch, identical in
    every stage subprocess (rng seeds are fixed)."""
    from ceph_tpu.ec import gf256
    gen = gf256.rs_vandermonde_matrix(K, M)
    rng = np.random.default_rng(0)
    # BATCH stripes folded along the lane axis: [K, BATCH * CHUNK] — the
    # cross-PG batch-collector layout (stripes share the generator, so
    # they concatenate on L and encode as ONE kernel launch)
    folded = rng.integers(0, 256, (K, BATCH * CHUNK), dtype=np.uint8)
    return gen, folded


def _decode_setup(gen, folded):
    """Survivor set for the 2-erasure decode workload (lost chunks 0, 3)."""
    from ceph_tpu import native
    from ceph_tpu.ec import gf256
    present = [1, 2, 4, 5, 6, 7, 8, 9]
    dec = gf256.decode_matrix(gen, present, [0, 3])
    par = native.gf_matrix_apply(gen[K:], folded) \
        if native.available() else gf256.host_apply(gen[K:], folded)
    full = np.concatenate([folded, par])
    surv = np.ascontiguousarray(full[present])
    return dec, surv


# ------------------------------------------------------------- stage: cpu

def _cpu_rate(mat, folded, label):
    """Native CPU apply of `mat` to folded [k, L] data: (simd, scalar)
    MB/s of INPUT data.  simd is the GFNI/AVX-512 kernel (the modern
    isa-l-class baseline, BASELINE.md row 2); scalar is the
    jerasure-style table sweep."""
    from ceph_tpu import native
    if not native.available():
        return None, None
    nbytes = folded.shape[0] * folded.shape[1]
    out = {}
    for kind, force in (("simd", False), ("scalar", True)):
        if kind == "simd" and not native.gf_simd_available():
            out[kind] = None
            continue
        iters = 8 if kind == "simd" else 2
        t0 = time.perf_counter()
        for _ in range(iters):
            native.gf_matrix_apply(mat, folded, force_scalar=force)
        dt = time.perf_counter() - t0
        out[kind] = iters * nbytes / dt / 1e6
        log(f"cpu {kind} {label}: {out[kind]:,.0f} MB/s")
    return out["simd"], out["scalar"]


def stage_cpu():
    gen, folded = _workload()
    enc_simd, enc_scalar = _cpu_rate(gen[K:], folded, "encode")
    dec, surv = _decode_setup(gen, folded)
    dec_simd, dec_scalar = _cpu_rate(dec, surv, "decode")
    return {"encode_simd": enc_simd, "encode_scalar": enc_scalar,
            "decode_simd": dec_simd, "decode_scalar": dec_scalar}


# ----------------------------------------------------------- stage: probe

def stage_probe():
    import jax
    devs = jax.devices()
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "n": len(devs)}


# ----------------------------------------------------------- stage: crush

def _bench_ref_crush():
    """Compile the reference crush_do_rule at -O3 and measure it.
    None when the reference tree is not on this machine."""
    src = REF / "src"
    harness = pathlib.Path(__file__).parent / "tests/golden/bench_ref_crush.c"
    if not (src / "crush/mapper.c").exists():
        log("reference tree unavailable: CRUSH rows carry no vs_baseline")
        return None
    with tempfile.TemporaryDirectory() as td:
        exe = pathlib.Path(td) / "bench_ref_crush"
        (pathlib.Path(td) / "acconfig.h").write_text(
            "#define HAVE_INTTYPES_H 1\n#define HAVE_STDINT_H 1\n"
            "#define HAVE_LINUX_TYPES_H 1\n")
        subprocess.run(
            ["gcc", "-O3", "-march=native", "-o", str(exe),
             "-I", td, str(harness),
             str(src / "crush/builder.c"), str(src / "crush/crush.c"),
             str(src / "crush/hash.c"),
             "-I", str(src), "-I", str(src / "crush"),
             f"-DMAPPER_C_PATH=\"{src}/crush/mapper.c\"", "-lm"],
            check=True, capture_output=True, timeout=120)
        out = subprocess.run([str(exe), "200000"], check=True,
                             capture_output=True, timeout=300)
        return json.loads(out.stdout)


def _crush_ref():
    """Reference rates from BENCH_CRUSH_REF (the orchestrator measured
    once and passed them down), measured here for a stage run by hand."""
    blob = os.environ.get("BENCH_CRUSH_REF")
    return json.loads(blob) if blob else _bench_ref_crush()


def _crush_workload():
    from ceph_tpu.crush.builder import (build_hierarchy, make_erasure_rule,
                                        make_replicated_rule)
    from ceph_tpu.crush.types import CrushMap
    n_osd = CRUSH_HOSTS * CRUSH_PER_HOST
    m = CrushMap()
    m.max_devices = n_osd
    build_hierarchy(m, n_osd, CRUSH_PER_HOST)
    rep = make_replicated_rule(m, "rep")
    ec = make_erasure_rule(m, "ec", size=6)
    # 3-level variant: same 1024 osds behind root->rack->host (16 racks)
    m3 = CrushMap()
    m3.max_devices = n_osd
    build_hierarchy(m3, n_osd, CRUSH_PER_HOST, hosts_per_rack=8)
    rep3 = make_replicated_rule(m3, "rep3")
    w = [0x10000] * n_osd
    return m, rep, ec, m3, rep3, w


def _stage_crush_engine(engine, backend_label):
    """1M mappings, firstn x3 + indep x6, on one kernel engine."""
    from ceph_tpu.crush.mapper import do_rule
    from ceph_tpu.ops.crush_kernel import batch_do_rule_arrays, warmup

    m, rep, ec, m3, rep3, w = _crush_workload()
    xs = np.arange(CRUSH_N)
    ref = _crush_ref()
    if ref:
        ref.setdefault("firstn3l_per_sec", ref["firstn_per_sec"])
        log(f"reference C crush_do_rule: "
            f"firstn {ref['firstn_per_sec']:.0f}/s, "
            f"indep {ref['indep_per_sec']:.0f}/s, "
            f"firstn3l {ref['firstn3l_per_sec']:.0f}/s")

    rates = {}
    for name, mm, rule, nr in (("firstn", m, rep, 3),
                               ("indep", m, ec, 6),
                               ("firstn3l", m3, rep3, 3)):
        if engine == "jax":
            t0 = time.perf_counter()
            warmup(mm, rule, nr, w, sizes=(len(xs),))
            log(f"crush {name} warmup (jit): "
                f"{time.perf_counter() - t0:.0f}s")
        best = 0.0
        for trial in range(3):       # trial 0 absorbs one-time concat jits
            t0 = time.perf_counter()
            osds, cnt = batch_do_rule_arrays(mm, rule, xs, nr, w,
                                             engine=engine)
            dt = time.perf_counter() - t0
            best = max(best, CRUSH_N / dt)
            log(f"crush {name} [{engine}] trial{trial}: "
                f"{CRUSH_N / dt:,.0f}/s")
        # bit-exactness spot check vs scalar host mapper
        for x in (0, 1234, CRUSH_N - 1):
            want = do_rule(mm, rule, x, nr, w)
            got = ([int(o) for o in osds[x, :cnt[x]]] if cnt is not None
                   else [int(o) for o in osds[x]])
            assert got == want, f"{engine} {name} mapping != host at x={x}"
        rates[name] = best
    sfx = "" if engine == "jax" else f"_{engine}"
    metrics = []
    for metric, key in (("crush_firstn3", "firstn"),
                        ("crush_indep6", "indep"),
                        ("crush_3level_firstn3", "firstn3l")):
        row = {"metric": f"{metric}_mappings_per_sec{sfx}",
               "value": round(rates[key]), "unit": "mappings/s",
               "backend": backend_label}
        if ref:
            row["vs_baseline"] = round(
                rates[key] / ref[f"{key}_per_sec"], 2)
        metrics.append(row)
    return {"metrics": metrics}


def stage_crush():
    """CRUSH jax engine on the device."""
    import jax
    return _stage_crush_engine("jax", jax.default_backend())


def stage_crush_host():
    """CRUSH numpy+native-C host engine: no jax import anywhere."""
    return _stage_crush_engine("host", "host_native")


# ---------------------------------------------------------- stage: tpu_ec

def _tpu_apply_rate(mat, folded):
    """Device MB/s (of input bytes) of the fused pallas kernel applying
    `mat` to a 256 MiB operand resident on the device: the best of 5
    calls, each timed to block_until_ready.  Returns (MB/s, output for
    `folded` as numpy for the bit-exact check)."""
    import jax
    import jax.numpy as jnp
    from ceph_tpu.ec import gf256
    from ceph_tpu.ec.kernel import _apply_bitmatrix_pallas

    bitmat = jnp.asarray(gf256.expand_to_bitmatrix(mat), jnp.int8)
    k = mat.shape[1]
    nbytes = 1 << 28
    d = jax.device_put(jnp.asarray(np.random.default_rng(7).integers(
        0, 256, (k, nbytes // k), dtype=np.uint8)))
    _apply_bitmatrix_pallas(bitmat, d).block_until_ready()  # compile + warm
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        _apply_bitmatrix_pallas(bitmat, d).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    del d
    out = np.asarray(_apply_bitmatrix_pallas(
        bitmat, jnp.asarray(folded, jnp.uint8)))
    return nbytes / best / 1e6, out


def stage_tpu_ec():
    import jax
    from ceph_tpu.ec import gf256
    from ceph_tpu.ec.kernel import autotune
    dev = jax.devices()[0]
    log(f"device: {dev.device_kind} ({dev.platform})")
    gen, folded = _workload()

    # sweep the fused-kernel variant space on the live chip and install
    # the winner before measuring (tile length x plane layout x pack
    # engine — ec/kernel.py TUNE_SPACE)
    tuned = autotune(gen[K:], length=1 << 24, trials=2)
    log(f"autotune winner: {tuned}")

    enc_rate, got = _tpu_apply_rate(gen[K:], folded)
    want = gf256.host_apply(gen[K:], folded[:, :65536])
    assert np.array_equal(got[:, :65536], want), \
        "TPU parity != host ground truth"
    log(f"tpu encode (pallas fused): {enc_rate:,.0f} MB/s")

    dec, surv = _decode_setup(gen, folded)
    # decode gets its OWN autotune pass, shape-bound: the rebuild
    # matrix's aspect ratio differs from the parity rows' and the
    # winning variant with it — install="shape" keys the winner to the
    # decode bitmat so the encode winner above stays installed
    dec_tuned = autotune(dec, length=1 << 24, trials=2, install="shape")
    log(f"decode autotune winner: {dec_tuned}")
    dec_rate, got = _tpu_apply_rate(dec, surv)
    assert np.array_equal(got[:, :65536], folded[[0, 3]][:, :65536]), \
        "TPU decode != original data"
    log(f"tpu decode: {dec_rate:,.0f} MB/s")
    return {"encode": enc_rate, "decode": dec_rate,
            "platform": dev.platform, "kind": dev.device_kind,
            "tuned": tuned, "decode_tuned": dec_tuned}


# ---------------------------------------------------------- stage: ec_e2e

def stage_ec_e2e():
    """End-to-end EC pool under load (VERDICT r3 ask #5): an in-process
    cluster takes `rados bench`-style concurrent writes on a k=2,m=2
    pool with the cross-PG device batch queue ON vs OFF, reporting
    p50/p99 latency and the perf-counter split proving where encoded
    bytes went (device vs host).  The iodepth axis (1 vs 16) isolates
    the per-PG op window's contribution: at iodepth 1 the window can
    never fill and throughput is pure serial latency; at 16 the
    counter-proven mean in-flight depth shows the pipelining engaged.
    Reference harness: /root/reference/src/common/obj_bencher.h:62
    driving an EC pool."""
    import asyncio

    from ceph_tpu.qa.cluster import Cluster, make_ctx

    N_OBJS, OBJ_SIZE, CONC = 192, 64 * 1024, 16

    def ctx_factory(batch_mode, shards=4, op_batching=True,
                    lanes=None, ext_min=None):
        def f(name):
            c = make_ctx(name)
            c.config.set("osd_ec_batch_device", batch_mode)
            if lanes is not None:
                # lane-backend axis (ISSUE 13): inline | thread |
                # process shard lanes, same run, same workload
                c.config.set("osd_shard_lanes", lanes)
            if ext_min is not None:
                # payload-sweep axis (ISSUE 20): 0 disables the
                # shared-memory extent path (everything rides the
                # ring inline — the pre-zero-copy transport)
                c.config.set("osd_lane_extent_min_bytes", ext_min)
            # co-located daemons skip TCP framing/crc/acks entirely
            # (messenger local fast path) — the bench cluster is one
            # process, so per-message socket round trips are pure
            # overhead the real system wouldn't pay either (it maps
            # co-located shards onto ICI collectives, SURVEY §2.4)
            c.config.set("ms_local_delivery", True)
            # per-op span tracing: every microsecond of the write path
            # is attributed to a named stage (common/tracer.py); the
            # run reports the per-stage p50/p99 breakdown + the
            # unattributed fraction
            c.config.set("op_tracing", True)
            # sharded data plane (ISSUE 10): shards=1 + op_batching
            # off reproduces the pre-shard plane bit-for-bit (the
            # axis baseline); inline lanes (no shard threads) win on
            # this GIL-bound 2-core container — see the shards axis
            c.config.set("osd_op_num_shards", shards)
            c.config.set("osd_shard_threads", False)
            c.config.set("objecter_op_batching", op_batching)
            return c
        return f

    async def run_once(batch_mode, iodepth=CONC, pg_num=8, shards=4,
                       op_batching=True, lanes=None,
                       n_objs=N_OBJS, obj_size=OBJ_SIZE,
                       ext_min=None):
        from ceph_tpu.msg import payload as payload_mod
        payload_mod.reset_counters()
        cl = Cluster(ctx_factory=ctx_factory(batch_mode, shards,
                                             op_batching, lanes,
                                             ext_min))
        admin = await cl.start(5)
        # pg_num 8 for the HEADLINE on/off runs (comparable with the
        # r1-r5 recorded series); the op-window axis runs pg_num 4 so
        # iodepth 16 over 4 windows yields per-PG depth ~4 and the
        # mean_inflight_depth evidence is readable
        await admin.pool_create("bpool", pg_num=pg_num,
                                pool_type="erasure", k=2, m=2)
        io = admin.open_ioctx("bpool")
        data = bytes(range(256)) * (obj_size // 256)
        lats = []
        sem = asyncio.Semaphore(iodepth)

        async def one(i):
            async with sem:
                t0 = time.perf_counter()
                await io.write_full(f"bench{i:05d}", data)
                lats.append(time.perf_counter() - t0)

        t0 = time.perf_counter()
        await asyncio.gather(*[one(i) for i in range(n_objs)])
        wall = time.perf_counter() - t0
        dev = host = 0
        # store group-commit counters (read BEFORE stop: umount drops
        # the commit thread): batches shared across concurrent txns +
        # fsyncs saved is the write-path pipelining evidence
        st = {"commit_batches": 0, "txns": 0, "fsyncs": 0,
              "fsyncs_saved": 0}
        writes = msgs = local = 0
        for osd in cl.osds.values():
            d = osd.ec_queue.perf.dump()
            dev += int(d.get("device_bytes", 0))
            host += int(d.get("host_bytes", 0))
            c = osd.store.commit_counters()
            for k in st:
                st[k] += int(c.get(k, 0))
            writes += osd.messenger._sock_writes
            msgs += osd.messenger._sock_write_msgs
            local += osd.messenger._local_msgs
        # per-PG op window evidence (achieved pipelining depth): one
        # aggregation lives in qa/cluster.py, shared with the tests
        win = cl.window_counters()
        # per-op tracer: stage breakdown vs the independently measured
        # e2e latencies — the unattributed fraction is the part of the
        # op path no named stage covers (read BEFORE stop).  Process
        # lanes: scrape each worker's stage histograms first (metrics
        # plane, FRAME_RPC), or the lane-side pipeline would read as
        # one unattributed hole
        await cl.refresh_lane_metrics()
        # zero-copy transport evidence (ISSUE 20): parent-side lane
        # counters (cork ratio, fastpath forwards, tx-pool extents)
        # plus each worker's view over the id-keyed RPC plane
        transport = {"corked_frames": 0, "cork_pushes": 0,
                     "fastpath_fwd": 0, "acks_sent": 0,
                     "acks_coalesced": 0, "ack_batches": 0,
                     "ext_allocs": 0, "ext_frees": 0, "ext_swept": 0,
                     "ext_alloc_full": 0}
        for osd in cl.osds.values():
            sc = osd.shards.counters()
            for k in ("acks_sent", "acks_coalesced", "ack_batches"):
                transport[k] += int(osd.perf_repack.dump().get(k, 0))
            for ek, v in (sc.get("extents") or {}).items():
                k = ek if ek in transport else None
                if k:
                    transport[k] += int(v)
            for ln in (sc.get("lanes") or {}).values():
                for k in ("corked_frames", "cork_pushes",
                          "fastpath_fwd"):
                    transport[k] += int(ln.get(k, 0))
            if osd.shards.process_lanes is not None:
                for lane in osd.shards.process_lanes:
                    if lane.dead:
                        continue
                    try:
                        lt = await lane.admin_rpc(
                            {"prefix": "lane_transport"})
                    except Exception:
                        continue
                    for k in ("corked_frames", "cork_pushes"):
                        transport[k] += int(
                            (lt.get("cork") or {}).get(k, 0))
                    for k in ("acks_sent", "acks_coalesced",
                              "ack_batches"):
                        transport[k] += int(
                            (lt.get("acks") or {}).get(k, 0))
                    for ek, v in (lt.get("extents") or {}).items():
                        if ek in transport:
                            transport[ek] += int(v)
        transport["frames_per_push"] = round(
            transport["corked_frames"] / transport["cork_pushes"], 2) \
            if transport["cork_pushes"] else 0.0
        bd = cl.stage_breakdown(measured_e2e_s=sum(lats))
        # lazy-payload guard: with ms_local_delivery on, in-process hops
        # must not serialize message bodies at all (read BEFORE stop)
        enc = payload_mod.counters()
        # sharded-plane evidence: handoff batching + sub-op inline
        # applies (osd_shard_handoff group), objecter corked batches
        shard_c = {}
        for osd in cl.osds.values():
            for k in ("handoff_ops", "handoff_wakeups",
                      "direct_local_ops", "subop_inline",
                      "subread_inline", "subread_queued"):
                shard_c[k] = shard_c.get(k, 0) \
                    + int(osd.shards.counters().get(k, 0))
        obj_batches = admin.objecter.batches_sent
        obj_batched_ops = admin.objecter.ops_batched
        await cl.stop()
        lats.sort()
        stage_p = {name: [d["p50_ms"], d["p99_ms"]]
                   for name, d in bd["stages"].items()}
        # the ISSUE 10 acceptance metric: combined queueing/delivery
        # share of e2e.  COMPARABLE with the recorded 0.47-0.49
        # series: the old monolithic queue_wait is exactly
        # queue_wait_ring + queue_wait_pump after the ISSUE 15 cause
        # split (throttle_wait/admit_wait were always separate stages
        # and stay excluded here; ring_wait is lane-hop time that was
        # previously UNATTRIBUTED, also excluded from this share).
        # The by-cause dict below reports the full taxonomy so the
        # next capture says WHICH seam to attack.
        from ceph_tpu.common.tracer import QUEUE_WAIT_CAUSES
        q_stages = ("dep_wait", "deliver", "ack_delivery",
                    "queue_wait_ring", "queue_wait_pump")
        qshare = sum(bd["stages"].get(s, {}).get("sum_s", 0.0)
                     for s in q_stages)
        qshare = qshare / bd["measured_s"] if bd["measured_s"] else 0.0
        q_by_cause = {
            s: round(bd["stages"].get(s, {}).get("sum_s", 0.0)
                     / bd["measured_s"], 3)
            for s in QUEUE_WAIT_CAUSES + ("admit_wait",)} \
            if bd["measured_s"] else {}
        return {
            "shards": shards,
            "lane_backend": lanes or "auto",
            "op_batching": op_batching,
            "queueing_delivery_share": round(qshare, 3),
            "queueing_share_by_cause": q_by_cause,
            "shard_counters": shard_c,
            "objecter_batches": obj_batches,
            "objecter_batched_ops": obj_batched_ops,
            "stage_p50_p99_ms": stage_p,
            "attributed_s": bd["attributed_s"],
            "unattributed_frac": bd["unattributed_frac"],
            "iodepth": iodepth,
            "pg_num": pg_num,
            "mean_inflight_depth": round(win["mean_inflight_depth"], 2),
            "max_inflight_depth": win["max_inflight_depth"],
            "ops_admitted": win["ops_admitted"],
            "obj_size": obj_size,
            "lane_transport": transport,
            "mb_s": round(n_objs * obj_size / wall / 1e6, 1),
            "p50_ms": round(lats[len(lats) // 2] * 1e3, 2),
            "p99_ms": round(lats[int(len(lats) * 0.99) - 1] * 1e3, 2),
            "device_bytes": dev, "host_bytes": host,
            "device_frac": round(dev / (dev + host), 3)
            if dev + host else 0.0,
            "store_txns": st["txns"],
            "store_commit_batches": st["commit_batches"],
            "store_txns_per_batch": round(
                st["txns"] / st["commit_batches"], 2)
            if st["commit_batches"] else 0.0,
            "store_fsyncs": st["fsyncs"],
            "store_fsyncs_saved": st["fsyncs_saved"],
            "msgs_per_sock_write": round(msgs / writes, 2)
            if writes else 0.0,
            "local_msgs": local,
            "msg_encode_calls": enc["msg_encode_calls"],
            "msg_encode_bytes": enc["msg_encode_bytes"],
        }

    async def run_reads(n_objs=128):
        """Read axis (ISSUE 10 satellite): sequential reads through
        the full pipeline, then DEGRADED reads after an OSD death (EC
        reconstructs the missing shard on the read path).  The write
        warm-up runs UNTRACED so the stage histograms carry only
        read-path samples."""
        from ceph_tpu.msg import payload as payload_mod
        payload_mod.reset_counters()
        cl = Cluster(ctx_factory=ctx_factory("off", 4, True))
        admin = await cl.start(5)
        await admin.pool_create("rpool", pg_num=4,
                                pool_type="erasure", k=2, m=2)
        io = admin.open_ioctx("rpool")
        data = bytes(range(256)) * (OBJ_SIZE // 256)
        ctxs = [o.ctx for o in cl.osds.values()] \
            + [m.ctx for m in cl.mons] + [c.ctx for c in cl.clients]
        for c in ctxs:
            c.tracer.enabled = False
        sem = asyncio.Semaphore(CONC)

        async def w(i):
            async with sem:
                await io.write_full(f"r{i:05d}", data)

        await asyncio.gather(*[w(i) for i in range(n_objs)])
        for c in ctxs:
            c.tracer.enabled = True

        async def read_all(lats):
            async def r(i):
                async with sem:
                    t0 = time.perf_counter()
                    got = await io.read(f"r{i:05d}")
                    lats.append(time.perf_counter() - t0)
                    assert len(got) == OBJ_SIZE
            t0 = time.perf_counter()
            await asyncio.gather(*[r(i) for i in range(n_objs)])
            return time.perf_counter() - t0

        seq_lats = []
        seq_wall = await read_all(seq_lats)
        bd = cl.stage_breakdown(measured_e2e_s=sum(seq_lats))
        stage_p = {name: [d["p50_ms"], d["p99_ms"]]
                   for name, d in bd["stages"].items()}
        seq_lats.sort()

        # degrade: kill one OSD and mark it down — reads on its PGs
        # re-target and EC-reconstruct from the survivors
        victim = max(cl.osds)
        await cl.kill_osd(victim)
        await admin.mon_command({"prefix": "osd down", "id": victim})
        while admin.monc.osdmap.is_up(victim):
            await asyncio.sleep(0.05)
        deg_lats = []
        deg_wall = await read_all(deg_lats)
        deg_lats.sort()
        await cl.stop()

        def pack(lats, wall):
            return {"mb_s": round(n_objs * OBJ_SIZE / wall / 1e6, 1),
                    "p50_ms": round(lats[len(lats) // 2] * 1e3, 2),
                    "p99_ms": round(
                        lats[int(len(lats) * 0.99) - 1] * 1e3, 2)}

        return {"n_objs": n_objs, "iodepth": CONC,
                "sequential": pack(seq_lats, seq_wall),
                "degraded": pack(deg_lats, deg_wall),
                "stage_p50_p99_ms": stage_p,
                "unattributed_frac": bd["unattributed_frac"]}

    async def run_recovery(n_objs=96, throttle=None):
        """Recovery axis (ISSUE 17/18, ec_e2e_recovery_rebuild_k2m2):
        kill an OSD while clients keep reading and measure the
        rebuild — recovery MB/s from the landing-side byte counter
        (osd.recovery_bytes), plus the client-visible degraded-read
        MB/s and p50/p99 DURING the rebuild window, with the per-stage
        degraded-read breakdown.  The PR-10 recorded degraded-read
        baseline is 14.6 MB/s (serial shard gather, host decode per
        read); the concurrent gather + batched decode path is what
        this axis judges.  `throttle` overlays recovery-throttle
        config (osd_recovery_sleep / osd_recovery_max_active) so the
        throttle-on and throttle-off arms run the same workload: the
        graceful-degradation claim is that throttling the rebuild
        buys back client tail latency."""
        from ceph_tpu.crush.constants import CRUSH_ITEM_NONE
        from ceph_tpu.msg import payload as payload_mod
        from ceph_tpu.osd.pglog import LB_MAX
        payload_mod.reset_counters()
        base_f = ctx_factory("on", 4, True)

        def rec_ctx(name):
            c = base_f(name)
            for k, v in (throttle or {}).items():
                c.config.set(k, v)
            return c

        cl = Cluster(ctx_factory=rec_ctx)
        admin = await cl.start(5)
        await admin.pool_create("recpool", pg_num=4,
                                pool_type="erasure", k=2, m=2)
        io = admin.open_ioctx("recpool")
        data = bytes(range(256)) * (OBJ_SIZE // 256)
        sem = asyncio.Semaphore(CONC)

        async def w(i):
            async with sem:
                await io.write_full(f"rc{i:05d}", data)

        await asyncio.gather(*[w(i) for i in range(n_objs)])

        def rec_bytes():
            return sum(int(o.perf_osd.dump().get("recovery_bytes", 0))
                       for o in cl.osds.values())

        def recovered():
            # rebuilt = every surviving pg re-peered AWAY from the
            # victim with no placement holes, nothing missing, every
            # backfill (primary bookkeeping included) run to
            # completion, and a shard replica actually instantiated
            # for every slot (pg_num x width PG objects).  The remap
            # check keeps the pre-peering instant (old acting sets,
            # trivially "clean") from reading as converged; the
            # presence floor keeps the post-remap instant (new target
            # has not created its replica yet, so no check can fail
            # on it) from doing the same; the active-state gate keeps
            # NEWBORN replicas (instantiated with last_backfill
            # already at LB_MAX, not yet marked backfill targets by
            # the primary's activation) from doing the same.
            pgs = [pg for o in cl.osds.values()
                   for pg in o.pgs.values()]
            if len(pgs) < 4 * 4:       # pg_num x (k+m)
                return False
            for pg in pgs:
                if pg.state != "active" \
                        or victim in pg.acting \
                        or CRUSH_ITEM_NONE in pg.acting \
                        or pg.missing.items \
                        or pg.info.last_backfill != LB_MAX \
                        or pg._backfilling \
                        or pg.peer_backfill_cursors:
                    return False
            return True

        base_bytes = rec_bytes()
        victim = max(cl.osds)
        await cl.kill_osd(victim)
        await admin.mon_command({"prefix": "osd down", "id": victim})
        while admin.monc.osdmap.is_up(victim):
            await asyncio.sleep(0.05)

        # client reads race the rebuild until it converges
        deg_lats = []
        stop = asyncio.Event()

        async def reader():
            i = 0
            while not stop.is_set():
                async def r(j):
                    async with sem:
                        t0 = time.perf_counter()
                        got = await io.read(f"rc{j:05d}")
                        deg_lats.append(time.perf_counter() - t0)
                        assert len(got) == OBJ_SIZE
                await asyncio.gather(
                    *[r((i + j) % n_objs) for j in range(CONC)])
                i += CONC

        rt = asyncio.get_running_loop().create_task(reader())
        t0 = time.perf_counter()
        while not recovered():
            if time.perf_counter() - t0 > 180:
                break
            await asyncio.sleep(0.02)
        rebuild_wall = time.perf_counter() - t0
        moved = rec_bytes() - base_bytes
        converged = recovered()
        stop.set()
        await rt
        read_wall = time.perf_counter() - t0
        # degraded-read breakdown: where client time went WHILE the
        # rebuild competed for the same loops/stores (queue_wait vs
        # device vs net), from the same tracer plane run_once uses
        bd = cl.stage_breakdown(measured_e2e_s=sum(deg_lats))
        deg_stage_p = {name: [d["p50_ms"], d["p99_ms"]]
                       for name, d in bd["stages"].items()}
        await cl.stop()
        deg_reads = len(deg_lats)
        deg_lats.sort()
        wall = rebuild_wall or 1e-9
        return {
            "n_objs": n_objs, "iodepth": CONC,
            "throttle": dict(throttle) if throttle else None,
            "converged": converged,
            "degraded_stage_p50_p99_ms": deg_stage_p,
            "rebuild_s": round(rebuild_wall, 2),
            "rebuild_mb_s": round(moved / wall / 1e6, 1),
            "recovery_bytes": moved,
            "degraded_reads": deg_reads,
            "degraded_read_mb_s": round(
                deg_reads * OBJ_SIZE / read_wall / 1e6, 1)
            if deg_reads else 0.0,
            "client_p50_ms": round(
                deg_lats[deg_reads // 2] * 1e3, 2) if deg_reads else 0,
            "client_p99_ms": round(
                deg_lats[int(deg_reads * 0.99) - 1] * 1e3, 2)
            if deg_reads else 0,
            "baseline_degraded_mb_s": 14.6,
        }

    on = asyncio.run(run_once("on"))
    log(f"ec_e2e batch=on:  {on}")
    off = asyncio.run(run_once("off"))
    log(f"ec_e2e batch=off: {off}")
    # op-window axis (pg_num 4 so the 16-deep client load concentrates
    # into per-PG depth ~4): iodepth 16 vs 1 isolates the per-PG
    # pipelining gain — at iodepth 1 the window can never fill and
    # throughput is the pure serial-latency floor
    win16 = asyncio.run(run_once("off", iodepth=16, pg_num=4))
    log(f"ec_e2e window axis iodepth=16 pg=4: {win16}")
    win1 = asyncio.run(run_once("off", iodepth=1, pg_num=4))
    log(f"ec_e2e window axis iodepth=1  pg=4: {win1}")
    # sharded-plane axis (ISSUE 10): the new data plane (4 shards,
    # corked client batching, ack-on-apply commits) vs the pre-shard
    # plane ("1 = today's behavior": single loop, unbatched client,
    # threaded commit handoff), same geometry and iodepth, measured
    # in the same process run.  win16 already IS the new plane at
    # this exact shape — reuse it as the shards=4 arm.
    sh4 = win16
    sh1 = asyncio.run(run_once("off", iodepth=16, pg_num=4, shards=1,
                               op_batching=False))
    log(f"ec_e2e shards=1 (legacy plane): {sh1}")
    reads = asyncio.run(run_reads())
    log(f"ec_e2e read axis: {reads}")
    # recovery axis (ISSUE 17/18, ec_e2e_recovery_rebuild_k2m2):
    # rebuild MB/s + client latency while the cluster is rebuilding a
    # killed OSD under read load, throttle-off vs throttle-on — the
    # osd_recovery_sleep/max_active knobs trade rebuild speed for
    # client tail latency, and the axis records both sides of that
    # trade in one run
    recovery = None
    recovery_throttled = None
    if remaining() >= 90:
        recovery = asyncio.run(run_recovery())
        log(f"ec_e2e recovery axis (throttle off): {recovery}")
    else:
        log("ec_e2e recovery axis: skipped (budget)")
    if remaining() >= 90:
        recovery_throttled = asyncio.run(run_recovery(
            throttle={"osd_recovery_max_active": 1,
                      "osd_recovery_sleep": 0.002}))
        log(f"ec_e2e recovery axis (throttle on): "
            f"{recovery_throttled}")
    else:
        log("ec_e2e recovery throttle arm: skipped (budget)")
    # lane-backend axis (ISSUE 13, ec_e2e_rados_write_lanes_k2m2):
    # process vs thread vs inline shard lanes at shards=4, same run.
    # Client-side MB/s + p50/p99 are the comparable numbers on every
    # arm; the tracer/window/shard counters live inside the lane
    # WORKERS under the process backend, so those fields honestly
    # read ~0 there (the parent hosts no PGs).  Thread lanes measured
    # ~0.6x of inline on this GIL-bound container in the PR-10 run —
    # the process arm is the escape that axis exists to judge.
    lane_axis = {}
    for lane_backend in ("inline", "thread", "process"):
        if remaining() < 60:
            log(f"ec_e2e lane axis: skipping {lane_backend} "
                f"(budget)")
            break
        r = asyncio.run(run_once("off", iodepth=16, pg_num=4,
                                 shards=4, lanes=lane_backend))
        lane_axis[lane_backend] = r
        log(f"ec_e2e lanes={lane_backend}: {r['mb_s']} MB/s "
            f"p50={r['p50_ms']} p99={r['p99_ms']}")
    if "inline" in lane_axis:
        base = lane_axis["inline"]["mb_s"] or 1.0
        for k, r in lane_axis.items():
            r["vs_inline"] = round(r["mb_s"] / base, 3)
    # payload-size sweep (ISSUE 20, zero-copy lane transport): the
    # lane_codec claim is that with shared-memory extents on, ring
    # codec cost stays FLAT with object size (the data bytes cross as
    # a 16-ish-byte handle; the one copy moves to extent_write/read).
    # 4 KB (under threshold: inline either way) vs 256 KB with
    # extents on vs 256 KB with extents off (the pre-zero-copy ring).
    payload_sweep = {}
    for label, osize, emin in (("4k", 4 * 1024, None),
                               ("256k", 256 * 1024, None),
                               ("256k_inline", 256 * 1024, 0)):
        if remaining() < 60:
            log(f"ec_e2e payload sweep: skipping {label} (budget)")
            break
        r = asyncio.run(run_once("off", iodepth=16, pg_num=4,
                                 shards=4, lanes="process",
                                 n_objs=96, obj_size=osize,
                                 ext_min=emin))
        payload_sweep[label] = r
        lc = (r.get("stage_p50_p99_ms") or {}).get("lane_codec") or [0, 0]
        tr = r.get("lane_transport") or {}
        log(f"ec_e2e lanes payload {label}: {r['mb_s']} MB/s "
            f"p50={r['p50_ms']} lane_codec_p50={lc[0]}ms "
            f"frames/push={tr.get('frames_per_push')} "
            f"acks_coalesced={tr.get('acks_coalesced')} "
            f"ext_allocs={tr.get('ext_allocs')}")
    return {"on": on, "off": off,
            "ec_e2e_lane_payload_sweep": payload_sweep,
            "window_iodepth16": win16, "window_iodepth1": win1,
            "shards4": sh4, "shards1": sh1, "reads": reads,
            "recovery": recovery,
            "ec_e2e_recovery_rebuild_k2m2": {
                "throttle_off": recovery,
                "throttle_on": recovery_throttled},
            "ec_e2e_rados_write_lanes_k2m2": lane_axis}


# ------------------------------------------------- stage: rgw_bucket_burst

def stage_rgw_bucket_burst():
    """Heavy-traffic S3 fairness axis (ISSUE 19): one bulk loader vs
    8 interactive clients PUTting into the same bucket, on a 2x2
    matrix — sharded (8 index shards) vs unsharded bucket index, and
    dmClock QoS (osd_op_queue=mclock) vs the static wpq.  Reports
    per-class p50/p99 (the fairness claim: interactive p99 improves
    under QoS while the loader keeps >= its reservation), the
    index-shard -> PG placement spread with per-PG op-window depth
    (the serialization evidence: unsharded pins every index op on ONE
    PG) and the cause-split queueing share.  Reference: cls_rgw bucket
    index shards + osd/scheduler/mClockScheduler.cc."""
    import asyncio

    from ceph_tpu.qa.cluster import Cluster, make_ctx

    # the loader must actually FLOOD the PG queues (a backlog is what
    # the scheduler arbitrates; an empty queue serves FIFO either way)
    N_BULK, BULK_SIZE, BULK_DEPTH = 256, 32 * 1024, 64
    N_INTER_CLIENTS, OPS_PER_CLIENT, INTER_SIZE = 8, 12, 2 * 1024
    PG_NUM, SHARDS = 16, 8

    def ctx_factory(qos, shards):
        def f(name):
            c = make_ctx(name)
            c.config.set("osd_op_queue", "mclock" if qos else "wpq")
            if qos:
                # the loader's class gets a real floor so "loader
                # keeps >= its reservation" is a measurable claim, not
                # vacuous (an unknown class rides default r=0)
                c.config.set(
                    "osd_qos_specs",
                    c.config["osd_qos_specs"] + ";bulk:r=5,w=5,l=0")
            c.config.set("rgw_bucket_index_shards", shards)
            c.config.set("ms_local_delivery", True)
            c.config.set("op_tracing", True)
            return c
        return f

    async def run_once(qos, shards):
        from ceph_tpu.common.qos import QOS_CLASS
        from ceph_tpu.services.rgw import S3Gateway, _shard_oids
        cl = Cluster(ctx_factory=ctx_factory(qos, shards))
        admin = await cl.start(4)
        await admin.pool_create(".rgw", pg_num=PG_NUM)
        gw = S3Gateway(admin, pool=".rgw", require_auth=False,
                       index_shards=shards)
        st, _, _ = await gw._put_bucket("burst")
        assert st == 200, f"put_bucket rc {st}"
        bulk_lats, inter_lats = [], []
        bulk_data = bytes(range(256)) * (BULK_SIZE // 256)
        inter_data = b"i" * INTER_SIZE

        async def put(key, body, lats):
            t0 = time.perf_counter()
            s, _, _ = await gw._put_object("burst", key, body, {})
            lats.append(time.perf_counter() - t0)
            assert s == 200, f"put {key} rc {s}"

        async def loader():
            # contextvar is task-local: every op this task (and its
            # gather children, which copy the context at creation)
            # issues — index prepare/complete, striper data write,
            # quota header reads — bills to the "bulk" class
            QOS_CLASS.set("bulk")
            sem = asyncio.Semaphore(BULK_DEPTH)

            async def one(i):
                async with sem:
                    await put(f"bulk/{i:05d}", bulk_data, bulk_lats)
            await asyncio.gather(*[one(i) for i in range(N_BULK)])

        async def interactive(c):
            QOS_CLASS.set("client")
            for i in range(OPS_PER_CLIENT):
                await put(f"user{c}/{i:04d}", inter_data, inter_lats)

        t0 = time.perf_counter()
        await asyncio.gather(loader(),
                             *[interactive(c)
                               for c in range(N_INTER_CLIENTS)])
        wall = time.perf_counter() - t0

        # index-spread evidence: which PG each index shard object maps
        # to (exact, from the osdmap), plus the achieved op-window
        # depth of those PGs (read BEFORE stop)
        layout = {"shards": shards, "gen": 0} if shards > 1 else None
        index_pgs = set()
        for oid in _shard_oids("burst", layout):
            pg, _, _ = admin.objecter.osdmap.object_to_acting(
                oid, gw.io._loc())
            index_pgs.add(str(pg))
        depth_by_pg = {}
        for osd in cl.osds.values():
            for pgid, pg in osd.pgs.items():
                if str(pgid) in index_pgs:
                    depth_by_pg[str(pgid)] = max(
                        depth_by_pg.get(str(pgid), 0),
                        pg.op_window.max_depth)
        # dmClock serve counters: per-class phase split summed over
        # every PG queue — the reservation-phase count is the proof
        # the floors actually fired (empty at wpq)
        qos_counters = {}
        for osd in cl.osds.values():
            for pg in osd.pgs.values():
                if not getattr(pg._op_queue, "QOS", False):
                    continue
                for k, c in pg._op_queue.counters().items():
                    agg = qos_counters.setdefault(
                        k, {"reservation": 0, "proportional": 0})
                    agg["reservation"] += c["reservation"]
                    agg["proportional"] += c["proportional"]
        await cl.refresh_lane_metrics()
        bd = cl.stage_breakdown(
            measured_e2e_s=sum(bulk_lats) + sum(inter_lats))
        from ceph_tpu.common.tracer import QUEUE_WAIT_CAUSES
        q_by_cause = {
            s: round(bd["stages"].get(s, {}).get("sum_s", 0.0)
                     / bd["measured_s"], 3)
            for s in QUEUE_WAIT_CAUSES + ("admit_wait",)} \
            if bd["measured_s"] else {}
        await cl.stop()

        def pct(lats):
            lats = sorted(lats)
            return {"p50_ms": round(lats[len(lats) // 2] * 1e3, 2),
                    "p99_ms": round(
                        lats[max(0, int(len(lats) * 0.99) - 1)] * 1e3,
                        2)}

        return {
            "qos": "mclock" if qos else "wpq",
            "index_shards": shards,
            "wall_s": round(wall, 2),
            "interactive": {**pct(inter_lats),
                            "clients": N_INTER_CLIENTS,
                            "ops": len(inter_lats)},
            "bulk": {**pct(bulk_lats), "ops": len(bulk_lats),
                     "ops_s": round(len(bulk_lats) / wall, 1)},
            "index_pgs": sorted(index_pgs),
            "n_index_pgs": len(index_pgs),
            "index_pg_window_depth": depth_by_pg,
            "max_index_pg_depth": max(depth_by_pg.values(), default=0),
            "qos_class_serves": qos_counters,
            "queueing_share_by_cause": q_by_cause,
        }

    out = {}
    for shards in (SHARDS, 1):
        for qos in (True, False):
            cell = asyncio.run(run_once(qos, shards))
            key = (f"{'sharded' if shards > 1 else 'unsharded'}"
                   f"_{cell['qos']}")
            out[key] = cell
            log(f"rgw_burst {key}: inter p99="
                f"{cell['interactive']['p99_ms']}ms bulk="
                f"{cell['bulk']['ops_s']} op/s "
                f"index_pgs={cell['n_index_pgs']} "
                f"depth={cell['max_index_pg_depth']}")
    return out


STAGES = {"cpu": stage_cpu, "probe": stage_probe,
          "crush": stage_crush, "crush_host": stage_crush_host,
          "tpu_ec": stage_tpu_ec, "ec_e2e": stage_ec_e2e,
          "rgw_bucket_burst": stage_rgw_bucket_burst}


# ------------------------------------------------------------ orchestrator

#: stages that own the chip (each alone, in its own process); the rest
#: are jax-free or host-only and run pinned to the CPU
CHIP_STAGES = ("probe", "tpu_ec", "crush", "ec_e2e")


class StageFailed(Exception):
    pass


def run_stage(name, budget, env_extra=None):
    """Run one stage in a subprocess and return its result.  stderr
    passes through; the stage's last stdout line is its JSON result.
    A stage that fails, times out or prints no result fails the run."""
    from ceph_tpu.common.envutil import cpu_child_env
    env = dict(os.environ) if name in CHIP_STAGES else cpu_child_env()
    env.update(env_extra or {})
    t0 = time.monotonic()
    try:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--stage", name],
            stdout=subprocess.PIPE, timeout=budget, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)) or ".")
    except subprocess.TimeoutExpired:
        raise StageFailed(f"{name}: timeout after {budget:.0f}s") from None
    if p.returncode != 0:
        raise StageFailed(f"{name}: rc={p.returncode}")
    lines = [l for l in p.stdout.decode(errors="replace").splitlines()
             if l.strip()]
    try:
        res = json.loads(lines[-1])
    except (IndexError, ValueError):
        raise StageFailed(f"{name}: unparseable output") from None
    log(f"stage {name}: ok in {time.monotonic() - t0:.0f}s")
    return res


def main():
    if len(sys.argv) >= 3 and sys.argv[1] == "--stage":
        name = sys.argv[2]
        if name in CHIP_STAGES:
            from ceph_tpu.common.envutil import enable_compile_cache
            log(f"compile cache: {enable_compile_cache()}")
        print(json.dumps(STAGES[name]()))
        return

    probe = run_stage("probe", 120)
    if probe.get("platform") != "tpu":
        raise StageFailed(f"no TPU: jax found {probe}")
    log(f"device: {probe}")

    # reference C measured ONCE here (pure gcc subprocess, no jax) and
    # handed to both crush stages
    ref = _bench_ref_crush()
    ref_env = {"BENCH_CRUSH_REF": json.dumps(ref)} if ref else {}

    cpu = run_stage("cpu", 240)
    crush_host = run_stage("crush_host", 300, ref_env)
    tpu = run_stage("tpu_ec", 480)
    crush = run_stage("crush", 900, ref_env)
    burst = run_stage("rgw_bucket_burst", 300)
    # the stage paces its optional axes against DEADLINE itself
    e2e = run_stage("ec_e2e", DEADLINE + 60)

    baseline = cpu.get("encode_simd") or cpu.get("encode_scalar")
    baseline_name = ("cpu_gfni_avx512_simd" if cpu.get("encode_simd")
                     else "cpu_scalar")
    value = tpu["encode"]

    extra = []
    if cpu.get("encode_simd") and cpu.get("encode_scalar"):
        extra.append({"metric": "ec_encode_cpu_simd_baseline",
                      "value": round(cpu["encode_simd"], 1), "unit": "MB/s",
                      "backend": "cpu_simd",
                      "vs_baseline": round(cpu["encode_simd"]
                                           / cpu["encode_scalar"], 2)})
    dec_base = cpu.get("decode_simd") or cpu.get("decode_scalar")
    extra.append({"metric": "ec_decode_rs_k8m4_2erasures",
                  "value": round(tpu["decode"], 1), "unit": "MB/s",
                  "backend": "tpu_pallas",
                  "vs_baseline": round(tpu["decode"] / dec_base, 2)})
    extra += crush_host["metrics"]
    extra += crush["metrics"]
    on, off = e2e["on"], e2e["off"]
    win16 = e2e.get("window_iodepth16")
    win1 = e2e.get("window_iodepth1")
    extra.append({
        "metric": "ec_e2e_rados_write_k2m2",
        "value": on["mb_s"], "unit": "MB/s",
        "vs_baseline": round(on["mb_s"] / off["mb_s"], 2)
        if off["mb_s"] else 1.0,
        "backend": "cluster+device_queue",
        "iodepth": on.get("iodepth", 16),
        "mean_inflight_depth": on.get("mean_inflight_depth", 0.0),
        "max_inflight_depth": on.get("max_inflight_depth", 0),
        "p50_ms": on["p50_ms"], "p99_ms": on["p99_ms"],
        "p50_ms_off": off["p50_ms"], "p99_ms_off": off["p99_ms"],
        "device_byte_fraction": on["device_frac"],
        # per-op tracer profile: stage -> [p50_ms, p99_ms], plus
        # the fraction of measured e2e no named stage covers
        "stage_p50_p99_ms": on.get("stage_p50_p99_ms", {}),
        "unattributed_frac": on.get("unattributed_frac", 0.0),
        "msg_encode_calls": on.get("msg_encode_calls", 0),
        "msg_encode_bytes": on.get("msg_encode_bytes", 0),
        "store_txns_per_commit_batch": on.get(
            "store_txns_per_batch", 0.0),
        "store_fsyncs": on.get("store_fsyncs", 0),
        "store_txns": on.get("store_txns", 0),
        "msgs_per_sock_write": on.get("msgs_per_sock_write", 0.0),
    })
    if win16 and win1:
        # the per-PG op-pipelining evidence: same pool geometry
        # (pg_num 4), batch off, iodepth 16 vs the serial floor —
        # vs_baseline IS the window speedup, and the mean depth is
        # the counter proof the window actually filled
        extra.append({
            "metric": "ec_e2e_op_window_speedup_k2m2_pg4",
            "value": win16["mb_s"], "unit": "MB/s",
            "vs_baseline": round(win16["mb_s"] / win1["mb_s"], 2)
            if win1["mb_s"] else 1.0,
            "backend": "cluster+op_window",
            "iodepth": 16,
            "mean_inflight_depth": win16.get(
                "mean_inflight_depth", 0.0),
            "max_inflight_depth": win16.get("max_inflight_depth", 0),
            "p50_ms": win16["p50_ms"], "p99_ms": win16["p99_ms"],
            "iodepth1_mb_s": win1["mb_s"],
            "iodepth1_p50_ms": win1["p50_ms"],
            "iodepth1_p99_ms": win1["p99_ms"],
        })
    sh4, sh1 = e2e.get("shards4"), e2e.get("shards1")
    if sh4 and sh1:
        # ISSUE 10 shards axis: new data plane (shards=4 inline
        # lanes + corked client batching + ack-on-apply) vs the
        # pre-shard plane (shards=1, unbatched, threaded commit),
        # same shape (k2m2, pg4, iodepth 16), same process run.
        # queueing_delivery_share = (dep_wait + queue_wait +
        # deliver + ack_delivery) / e2e, per arm.
        extra.append({
            "metric": "ec_e2e_rados_write_shards_k2m2",
            "value": sh4["mb_s"], "unit": "MB/s",
            "vs_baseline": round(sh4["mb_s"] / sh1["mb_s"], 2)
            if sh1["mb_s"] else 1.0,
            "backend": "cluster+sharded_plane",
            "iodepth": 16,
            "num_shards": sh4.get("shards", 4),
            "p50_ms": sh4["p50_ms"], "p99_ms": sh4["p99_ms"],
            "queueing_delivery_share": sh4.get(
                "queueing_delivery_share", 0.0),
            "shards1_mb_s": sh1["mb_s"],
            "shards1_p50_ms": sh1["p50_ms"],
            "shards1_p99_ms": sh1["p99_ms"],
            "shards1_queueing_delivery_share": sh1.get(
                "queueing_delivery_share", 0.0),
            "shard_counters": sh4.get("shard_counters", {}),
            "objecter_batched_ops": sh4.get(
                "objecter_batched_ops", 0),
        })
    reads = e2e.get("reads")
    if reads:
        # ISSUE 10 read axis: reads had NO captured number before
        # this round (ROADMAP open item).  value = sequential
        # read throughput; vs_baseline = degraded/sequential (the
        # EC-reconstruct cost of one dead OSD on the read path)
        seq, deg = reads["sequential"], reads["degraded"]
        extra.append({
            "metric": "ec_e2e_rados_read_k2m2",
            "value": seq["mb_s"], "unit": "MB/s",
            "vs_baseline": round(deg["mb_s"] / seq["mb_s"], 2)
            if seq["mb_s"] else 1.0,
            "backend": "cluster+sharded_plane",
            "iodepth": reads.get("iodepth", 16),
            "p50_ms": seq["p50_ms"], "p99_ms": seq["p99_ms"],
            "degraded_mb_s": deg["mb_s"],
            "degraded_p50_ms": deg["p50_ms"],
            "degraded_p99_ms": deg["p99_ms"],
            "stage_p50_p99_ms": reads.get("stage_p50_p99_ms", {}),
            "unattributed_frac": reads.get("unattributed_frac",
                                           0.0),
        })
    lanes = e2e.get("ec_e2e_rados_write_lanes_k2m2") or {}
    if lanes:
        # ISSUE 15 lane axis row: per-MODE stage breakdown +
        # queueing share BY CAUSE (throttle vs ring vs pump), so
        # the next multi-core capture explains itself — under
        # process lanes the stage histograms now include every
        # lane worker's slice via the metrics plane
        proc = lanes.get("process") or {}
        best = proc or lanes.get("inline") or {}
        extra.append({
            "metric": "ec_e2e_rados_write_lanes_k2m2",
            "value": best.get("mb_s", 0.0), "unit": "MB/s",
            "vs_baseline": best.get("vs_inline", 1.0),
            "backend": ("cluster+process_lanes" if proc
                        else "cluster+shard_lanes"),
            "iodepth": 16,
            "modes": {
                mode: {
                    "mb_s": r.get("mb_s", 0.0),
                    "p50_ms": r.get("p50_ms", 0.0),
                    "p99_ms": r.get("p99_ms", 0.0),
                    "vs_inline": r.get("vs_inline", 0.0),
                    "unattributed_frac": r.get(
                        "unattributed_frac", 0.0),
                    "queueing_delivery_share": r.get(
                        "queueing_delivery_share", 0.0),
                    "queueing_share_by_cause": r.get(
                        "queueing_share_by_cause", {}),
                    "stage_p50_p99_ms": r.get(
                        "stage_p50_p99_ms", {}),
                } for mode, r in lanes.items()},
            # ISSUE 20 zero-copy row: lane_codec p50 per payload
            # size (flat-with-size is the extent claim), corked
            # frames per ring push, replica-ack coalescing
            "payload_sweep": {
                label: {
                    "obj_size": r.get("obj_size", 0),
                    "mb_s": r.get("mb_s", 0.0),
                    "p50_ms": r.get("p50_ms", 0.0),
                    "p99_ms": r.get("p99_ms", 0.0),
                    "lane_codec_p50_ms": ((r.get(
                        "stage_p50_p99_ms") or {}).get(
                        "lane_codec") or [0.0, 0.0])[0],
                    "frames_per_push": (r.get(
                        "lane_transport") or {}).get(
                        "frames_per_push", 0.0),
                    "acks_coalesced": (r.get(
                        "lane_transport") or {}).get(
                        "acks_coalesced", 0),
                    "ext_allocs": (r.get(
                        "lane_transport") or {}).get(
                        "ext_allocs", 0),
                    "ext_frees": (r.get(
                        "lane_transport") or {}).get(
                        "ext_frees", 0),
                    "fastpath_fwd": (r.get(
                        "lane_transport") or {}).get(
                        "fastpath_fwd", 0),
                } for label, r in (e2e.get(
                    "ec_e2e_lane_payload_sweep") or {}).items()},
        })
    # ISSUE 19 fairness row.  value = interactive p99 on the
    # CONTENDED arm (unsharded: the bucket's single hot index PG
    # carries ~half of e2e as queue wait — the scenario a
    # scheduler exists for) with mclock; vs_baseline = that p99
    # over the same arm's wpq p99, so the QoS claim is < 1.0.
    # The sharded cells carry the complementary claim: index load
    # spread over >= 4 PGs removes the hot spot itself (their
    # queueing share collapses, and with no backlog to arbitrate
    # the two queue disciplines measure alike).  The full 2x2
    # matrix rides in cells, inspectable per arm.
    uq = burst.get("unsharded_mclock") or {}
    uw = burst.get("unsharded_wpq") or {}
    sq = burst.get("sharded_mclock") or {}
    uq_i = uq.get("interactive") or {}
    uw_i = uw.get("interactive") or {}
    extra.append({
        "metric": "rgw_bucket_burst_s3_qos",
        "value": uq_i.get("p99_ms", 0.0), "unit": "ms",
        "vs_baseline": round(uq_i.get("p99_ms", 0.0)
                             / uw_i["p99_ms"], 2)
        if uw_i.get("p99_ms") else 1.0,
        "backend": "cluster+dmclock+sharded_index",
        "bulk_ops_s": (uq.get("bulk") or {}).get("ops_s", 0.0),
        "qos_class_serves": uq.get("qos_class_serves", {}),
        "queueing_share_by_cause": uq.get(
            "queueing_share_by_cause", {}),
        "sharded_n_index_pgs": sq.get("n_index_pgs", 0),
        "sharded_max_index_pg_depth": sq.get(
            "max_index_pg_depth", 0),
        "sharded_queueing_share_by_cause": sq.get(
            "queueing_share_by_cause", {}),
        "cells": burst,
    })

    print(json.dumps({
        "metric": "ec_encode_rs_k8m4_1MiB_stripes",
        "value": round(value, 1),
        "unit": "MB/s",
        "vs_baseline": round(value / baseline, 2),
        "backend": "tpu_pallas",
        "baseline": baseline_name,
        "device": {"platform": probe["platform"], "kind": probe["kind"],
                   "count": probe["n"]},
        "extra": extra,
    }))


if __name__ == "__main__":
    try:
        main()
    except StageFailed as e:
        log(f"bench failed: {e}")
        sys.exit(1)

"""One run of one cell: deploy, prepare, warm, measure, verify, report.

    deploy    a single-process cluster (mon + OSDs + client on one loop:
              the one process owns the chip) from the configuration's
              file: a plain Context with the file's `options` and
              nothing else (no qa FAST_CFG, no lockdep, no test timers)
    prepare   the mix's objects, written once (bounded working set)
    degrade   where the mix kills OSDs: down, not out, every PG active
    warm      the seam's shapes (benchmark/warm.py); gc.freeze()
    measure   the load starts, ramps, and the window opens at t0; the
              metrics count the ops that COMPLETE inside
              [t0, t0 + seconds]; nothing is awaited at the close
    verify    after the window, against the plain reference
    report    a `diag` line (hazard counters), the numbers compared on
              stderr, and the result's one JSON object last on stdout
"""

from __future__ import annotations

import asyncio
import gc
import json
import shutil
import sys
import time
from types import SimpleNamespace
from typing import Optional

from benchmark import diag, peaks, stats, trace_reduce, warm

TRACE_MARK = "benchmark.traced_window"
TRACE_SECONDS = 5.0         # the traced sub-window of a --trace 1 run


class NoAccelerator(Exception):
    """jax found no TPU, or fewer chips than the cell asks for."""


def look_for_chip(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoAccelerator(
            f"cell needs {chips} TPU chip(s); jax found "
            f"{len(devs)} x {devs[0].platform}")
    return describe_device()


def describe_device() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.devices():
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    return peak


def ctx_factory(options: dict, op_tracing: bool, device_mode: str):
    from ceph_tpu.common.context import Context

    def make(name):
        ctx = Context(name)
        for key, val in options.items():
            ctx.config.set(key, val)
        ctx.config.set("osd_ec_batch_device", device_mode)
        ctx.config.set("op_tracing", op_tracing)
        return ctx
    return make


def stage_totals(cluster) -> dict:
    return {name: (h.count, h.sum)
            for name, h in cluster.stage_histograms().items()}


async def run_cell(man, workload: str, seed: int, seconds: float,
                   trace: bool, *, require_tpu: bool = True,
                   fault: Optional[str] = None, out=sys.stdout,
                   err=sys.stderr, trace_dir: Optional[str] = None) -> dict:
    """Run the cell once and return the result object (also printed,
    last, on `out`).  `require_tpu=False` is for the tests' toy
    rehearsals on the CPU; `fault` plants a fault of benchmark/faults.py
    under the timed path (the controls)."""
    cell = man.workload(workload)
    config = man.config(cell["config"])
    traffic = man.traffic(cell["traffic"])
    device = look_for_chip(cell["chips"]) if require_tpu \
        else describe_device()
    on_chip = device["platform"] == "tpu"

    from ceph_tpu.common.envutil import enable_compile_cache
    from ceph_tpu.qa.cluster import Cluster
    enable_compile_cache()
    events = diag.JaxEvents()
    gcw = diag.GcWatch()
    ticker = diag.Ticker()

    pool = config["pool"]
    k, m = int(pool["k"]), int(pool["m"])
    mode = config["options"].get("osd_ec_batch_device", "on")
    phases = {"imports": diag.process_age_s()}
    phase_from = time.monotonic()

    def phase(name):
        nonlocal phase_from
        now = time.monotonic()
        phases[name] = round(now - phase_from, 2)
        phase_from = now

    cluster = Cluster(ctx_factory=ctx_factory(
        config["options"], op_tracing=trace,
        device_mode=mode if on_chip else "force"))
    undo = None
    load = None
    try:
        admin = await cluster.start(int(config["osds"]))
        await admin.pool_create(pool["name"], pg_num=int(pool["pg_num"]),
                                pool_type=pool["type"], k=k, m=m)
        io = admin.open_ioctx(pool["name"])
        all_osds = list(cluster.osds.values())
        env = SimpleNamespace(
            cell=workload, seed=seed, traffic=traffic, config=config,
            cluster=cluster, admin=admin, io=io, k=k, m=m,
            pool_id=admin.monc.osdmap.lookup_pool(pool["name"]))
        phase("cluster_start")
        load = man.kind(traffic["kind"]).Load(env)
        phase("payloads_and_plan")
        await load.prepare()
        phase("prepare_objects")
        killed = await degrade(cluster, admin, load.kill_osds)
        phase("degrade")
        shapes = load.seam_shapes()
        warmed = await warm.enumerate_seam(
            next(iter(cluster.osds.values())).ec_queue, k,
            warm.seam_matrices(k, m, shapes, killed),
            shapes["lanes"], shapes["depth"])

        phase("warm_seam")
        # R5: the long-lived heap leaves the collector's sight
        gc.collect()
        gc.freeze()
        phase("gc_freeze")

        def counters():
            return diag.cluster_counters(all_osds, admin, cluster.mons)

        if fault:
            from benchmark import faults
            undo = faults.plant(fault, env)
        ticker.start()
        c_load = counters()
        load.start()
        await asyncio.sleep(load.ramp_s)

        tr = await start_trace(trace_dir or str(
            man.root / ".bench_trace"), counters) if trace else None
        # ---------------------------------------------------- the window
        c0, e0, pg0 = counters(), events.snap(), diag.pg_states(
            cluster.osds.values())
        st0 = stage_totals(cluster) if trace else {}
        rss0, load0 = diag.rss_bytes(), diag.loadavg_1m()
        gcw.armed = ticker.armed = load.keep_armed = True
        setup_s = diag.process_age_s()
        t0 = ticker.t0 = time.monotonic()
        phase("ramp")
        if tr is not None:
            await asyncio.sleep(min(TRACE_SECONDS, seconds))
            await stop_trace(tr, counters)
        await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
        t1 = time.monotonic()
        gcw.armed = ticker.armed = load.keep_armed = False
        c1, e1, pg1 = counters(), events.snap(), diag.pg_states(
            cluster.osds.values())
        st1 = stage_totals(cluster) if trace else {}
        rss1 = diag.rss_bytes()
        # ------------------------------------------------------ the close
        await load.stop()
        await ticker.stop()
        c2 = counters()
        mem_peak = memory_peak_bytes()
        if tr is not None:
            read_trace(tr)

        win = load.window(t0, seconds)
        compared = await load.verify()
        dc = diag.delta(c1, c0)
        compared.update(seam_evidence(diag.delta(c2, c_load), load))
        compared["ops_failed"] = (len(load.failed), 0)
    finally:
        if undo is not None:
            undo()
        gcw.close()
        await ticker.stop()
        gc.unfreeze()
        if load is not None:
            for t in load.tasks:
                t.cancel()
        await cluster.stop()

    correct = all(lim is None or val <= lim
                  for val, lim in compared.values())
    window_events = diag.delta(e1, e0)
    e2e = end_to_end(win, seconds, setup_s)
    series = diag.per_second(win["t_end"], win["bytes"], t0, seconds)
    diagnostics = {
        "workload": workload, "seed": seed, "seconds": seconds,
        "trace": int(trace), "late_close_ms": round(
            (t1 - t0 - seconds) * 1e3, 3),
        "window_jax": window_events, "gc": gcw.snap(),
        "ticker": ticker.snap(),
        "rss_gb": [round(rss0 / 1e9, 3), round(rss1 / 1e9, 3)],
        "seam": {k_: dc[k_] for k_ in diag.QUEUE_KEYS},
        "batch_fill": (dc["batch_fill_sum"] / dc["batch_fill_n"])
        if dc["batch_fill_n"] else None,
        "osdmap_epoch": [c0["osdmap_epoch"], c1["osdmap_epoch"]],
        "scrubs": dc["scrubs"],
        "failure_reports_held": [c0["failure_reports_held"],
                                 c1["failure_reports_held"]],
        "peering_events": sum(1 for pg, st in pg1.items()
                              if pg0.get(pg) != st),
        "per_second_MB": stats.series_summary([b / 1e6 for b in series]),
        "loadavg_1m_t0": load0, "setup_phases_s": phases, "warm": warmed,
        "killed_osds": killed,
        "ops_window": win["ops"], "attempted": load.attempted,
        "lat_ms": {kind: {f"p{q}": stats.percentile(win[kind + "_ms"], q)
                          for q in (50, 90, 95, 99, 100)}
                   for kind in ("read", "write") if win[kind + "_ms"]},
        "e2e": e2e,
    }
    print("diag " + json.dumps(diagnostics), file=out, flush=True)

    if trace:
        obs = SimpleNamespace(
            stages={s: (st1[s][0] - st0.get(s, (0, 0.0))[0],
                        st1[s][1] - st0.get(s, (0, 0.0))[1])
                    for s in st1},
            counters=dc, ops=win["ops"],
            compiles=window_events["compile_events"],
            peaks=peaks.peaks_of(device["kind"]) if on_chip else None,
            trace={"events": tr["events"], "window_s": tr["window_s"],
                   "counters": tr["counters"], "k": k,
                   "r": load.seam_rows()})
        metrics = {}
        for spec in man.metrics_of(workload, "per_layer"):
            val = man.reader(spec["name"])(obs)
            if val is not None:
                metrics[spec["name"]] = {"value": val,
                                         "unit": spec["unit"]}
    else:
        metrics = {spec["name"]: {"value": e2e[spec["name"]],
                                  "unit": spec["unit"]}
                   for spec in man.metrics_of(workload, "end_to_end")
                   if e2e.get(spec["name"]) is not None}

    device["memory_peak_bytes"] = mem_peak
    result = {"correct": bool(correct), "attempted": load.attempted,
              "failed": len(load.failed), "metrics": metrics,
              "device": device}
    if trace:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = tr["breakdown"]
    result["compared"] = {name: {"value": val, "limit": lim}
                          for name, (val, lim) in compared.items()}
    for line in load.failed[:5]:
        print(f"failed: {line}", file=err)
    for name, (val, lim) in compared.items():
        print(f"compared {name}: {val} limit "
              f"{'-' if lim is None else lim}", file=err)
    print(f"correct: {correct}", file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result


def end_to_end(win: dict, seconds: float, setup_s: float) -> dict:
    return {
        "goodput": stats.rate(win["user_bytes"] / 1e6, seconds),
        "op_rate": stats.rate(win["ops"], seconds),
        "write_p95": stats.percentile(win["write_ms"], 95),
        "read_p95": stats.percentile(win["read_ms"], 95),
        "setup_s": setup_s,
    }


def seam_evidence(dc: dict, load) -> dict:
    """The seam's counters over the whole load (ramp, window, close):
    the bytes took the device.  Every write that was acked crossed the
    seam once, whole."""
    written = sum(1 for r in load.is_read if not r) * load.size
    out = {"host_bytes": (dc["host_bytes"], 0),
           "device_fallbacks": (dc["device_fallbacks"], 0),
           "device_requests": (dc["device_requests"], None),
           "device_bytes": (dc["device_bytes"], None)}
    if written:
        out["device_bytes_short"] = (
            max(0, written - dc["device_bytes"]), 0)
    return out


async def degrade(cluster, admin, n_kill: int) -> list:
    """Kill the `n_kill` highest OSDs; wait until each is marked down
    (not out: no backfill) and every PG is active without it."""
    killed = []
    for _ in range(n_kill):
        victim = max(cluster.osds)
        await cluster.kill_osd(victim)
        await cluster.mark_down_and_wait(admin, victim)
        killed.append(victim)
    if not killed:
        return killed
    epoch, t0 = admin.monc.osdmap.epoch, time.monotonic()
    while any(o.osdmap.epoch < epoch
              or any(pg.state != "active"
                     or any(v in pg.acting for v in killed)
                     for pg in o.pgs.values())
              for o in cluster.osds.values()):
        if time.monotonic() - t0 > 120:
            raise RuntimeError("PGs did not re-peer in 120 s")
        await asyncio.sleep(0.05)
    return killed


async def start_trace(trace_dir: str, counters) -> dict:
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    loop = asyncio.get_running_loop()
    await loop.run_in_executor(
        None, lambda: jax.profiler.start_trace(
            trace_dir, profiler_options=opts))
    span = jax.profiler.TraceAnnotation(TRACE_MARK)
    span.__enter__()
    return {"dir": trace_dir, "span": span, "c0": counters(),
            "t0": time.monotonic()}


async def stop_trace(tr: dict, counters) -> None:
    """Close the traced sub-window (the load goes on)."""
    import jax
    tr["counters"] = diag.delta(counters(), tr["c0"])
    tr["span"].__exit__(None, None, None)
    await asyncio.get_running_loop().run_in_executor(
        None, jax.profiler.stop_trace)


def read_trace(tr: dict) -> None:
    """After the close: reduce the trace to numbers and delete it."""
    events = trace_reduce.load_events(tr["dir"])
    shutil.rmtree(tr["dir"], ignore_errors=True)
    try:
        w0, w1 = trace_reduce.window_of(events, TRACE_MARK)
    except LookupError:
        w0 = min(ev[3] for ev in events)
        w1 = max(ev[3] + ev[4] for ev in events)
    events = [ev for ev in events if ev[3] + ev[4] > w0 and ev[3] < w1]
    tr["events"] = events
    tr["window_s"] = (w1 - w0) / 1e9
    tr["busy_s"] = trace_reduce.busy_seconds(events)
    tr["breakdown"] = {
        "device_ops": trace_reduce.top_device_ops(events),
        "idle_gaps": trace_reduce.idle_gaps(events, w0, w1,
                                            skip=(TRACE_MARK,))}

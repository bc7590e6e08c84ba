"""In-window hazard counters (every run prints them): what can make one
run of a commit differ from the next, counted where it happens.

None of these is a metric of the result line; they are how a stray run
is explained."""

from __future__ import annotations

import asyncio
import gc
import os
import time
from typing import Dict, List, Optional

import jax


class JaxEvents:
    """jax.monitoring listener: executables built (persistent-cache hit
    or not) and the persistent cache's hits and misses.  Copied from
    chip_smoke.py."""

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

    def snap(self) -> Dict[str, int]:
        return {"compile_events": self.n[self.COMPILE],
                "cache_hits": self.n[self.HIT],
                "cache_misses": self.n[self.MISS]}


class GcWatch:
    """Collections per generation and their summed pause, from
    gc.callbacks, while `armed`."""

    def __init__(self):
        self.armed = False
        self.count = [0, 0, 0]
        self.pause_s = [0.0, 0.0, 0.0]
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if not self.armed:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            g = min(int(info.get("generation", 0)), 2)
            self.count[g] += 1
            self.pause_s[g] += time.perf_counter() - self._t

    def close(self):
        self.armed = False
        if self._cb in gc.callbacks:
            gc.callbacks.remove(self._cb)

    def snap(self) -> dict:
        return {"collections": list(self.count),
                "pause_ms": [round(p * 1e3, 3) for p in self.pause_s]}


class Ticker:
    """A 10 ms ticker on the loop: how late the loop let it run."""

    PERIOD = 0.010

    def __init__(self):
        self.armed = False
        self.worst = 0.0
        self.late_over_50ms = 0
        self.t0 = 0.0
        self.stalls: list = []       # (seconds into the window, ms late)
        self._task: Optional[asyncio.Task] = None

    def start(self):
        self._task = asyncio.get_running_loop().create_task(self._run())

    async def _run(self):
        due = time.monotonic() + self.PERIOD
        while True:
            await asyncio.sleep(max(0.0, due - time.monotonic()))
            late = time.monotonic() - due
            if self.armed:
                if late > self.worst:
                    self.worst = late
                if late > 0.050:
                    self.late_over_50ms += 1
                if late > 0.100:
                    self.stalls.append((round(due - self.t0, 2),
                                        round(late * 1e3, 1)))
            # a stalled loop skips ticks instead of bunching them
            due = max(due + self.PERIOD, time.monotonic())

    async def stop(self):
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None

    def snap(self) -> dict:
        return {"worst_late_ms": round(self.worst * 1e3, 3),
                "late_over_50ms": self.late_over_50ms,
                "stalls_over_100ms": self.stalls[:12]}


def rss_bytes() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def loadavg_1m() -> float:
    return os.getloadavg()[0]


def process_age_s() -> float:
    """Seconds since this process started, from /proc (the clock the
    kernel stamped the start with), so set-up counts the interpreter's
    own start and every import."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start_ticks = int(fields[19])           # field 22: starttime
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


QUEUE_KEYS = ("device_launches", "device_requests", "device_bytes",
              "host_requests", "host_bytes", "device_fallbacks")


def cluster_counters(osds, admin, mons) -> dict:
    """The program's own counts, summed over `osds` (a list that keeps a
    killed OSD), from the public perf dumps: the seam, scrubs, the map."""
    tot = {k: 0 for k in QUEUE_KEYS}
    fill_sum = fill_n = 0.0
    scrubs = 0
    for osd in osds:
        d = osd.ctx.perf.dump()
        q = d.get("ec_batch_queue", {})
        for k in QUEUE_KEYS:
            tot[k] += int(q.get(k, 0))
        bf = q.get("batch_fill") or {}
        fill_sum += float(bf.get("sum", 0.0))
        fill_n += float(bf.get("avgcount", 0))
        s = d.get("osd_scrub", {})
        scrubs += int(s.get("scrubs_light", 0)) + int(
            s.get("scrubs_deep", 0))
    tot["batch_fill_sum"] = fill_sum
    tot["batch_fill_n"] = fill_n
    tot["scrubs"] = scrubs
    omap = admin.monc.osdmap
    tot["osdmap_epoch"] = int(omap.epoch)
    # failure reports the mon is holding, where it shows them
    reports = 0
    for mon in mons:
        osdmon = getattr(mon, "osdmon", None)
        held = getattr(osdmon, "failure_reports", None)
        if held:
            reports += sum(len(v) for v in held.values())
    tot["failure_reports_held"] = reports
    return tot


def pg_states(live_osds) -> Dict[str, tuple]:
    """(state, acting) of every PG copy that leads: a change between the
    window's ends is a peering event."""
    out = {}
    for osd in live_osds:
        for pg in osd.pgs.values():
            if pg.is_primary():
                out[str(pg.pgid)] = (str(pg.state), tuple(pg.acting))
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float)) and k in before}


def per_second(t_end: List[float], amount: List[float], t0: float,
               seconds: float) -> List[float]:
    """Completed amount in each whole second of the window."""
    n = int(seconds)
    out = [0.0] * n
    for t, a in zip(t_end, amount):
        i = int(t - t0)
        if 0 <= i < n and t >= t0:
            out[i] += a
    return out

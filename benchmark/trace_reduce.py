"""From a device trace to numbers: busy time, a kernel's time, the
operations that took most time, and the idle gaps by what the host was
doing.  Works on plain event tuples so that it can be checked on a small
recorded trace (tests/benchmark); `load_events` is the only part that
touches the profiler's file.

An event is (plane, line, name, start_ns, dur_ns)."""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, str, str, int, int]

#: the device line that holds one event per executed HLO op / kernel
DEVICE_OP_LINE = "XLA Ops"


def load_events(trace_dir: str) -> List[Event]:
    """Every event of the newest .xplane.pb under `trace_dir`."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            int(ev.start_ns), int(ev.duration_ns)))
    return out


def is_device_plane(plane: str) -> bool:
    return plane.startswith("/device:") and "TPU" in plane.upper()


def device_ops(events: Iterable[Event]) -> Dict[str, List[Event]]:
    """Device op events by device plane."""
    out: Dict[str, List[Event]] = {}
    for ev in events:
        if is_device_plane(ev[0]) and ev[1] == DEVICE_OP_LINE:
            out.setdefault(ev[0], []).append(ev)
    return out


def union_ns(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted (start, end) intervals."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def device_seconds(events: Iterable[Event],
                   match: Optional[List[str]] = None) -> float:
    """Union of the device op intervals, averaged over the device planes
    that have any; with `match`, of the ops whose name holds any of it."""
    per_dev = device_ops(events)
    if not per_dev:
        return 0.0
    tot = 0
    for evs in per_dev.values():
        tot += sum(e - s for s, e in union_ns(
            (ev[3], ev[3] + ev[4]) for ev in evs
            if match is None or any(m in ev[2] for m in match)))
    return tot / len(per_dev) / 1e9


def busy_seconds(events: Iterable[Event]) -> float:
    """Seconds in which an operation ran on the device."""
    return device_seconds(events)


def kernel_seconds(events: Iterable[Event], match: List[str]) -> float:
    """Seconds in which a kernel named by `match` ran on the device."""
    return device_seconds(events, match)


_HLO = re.compile(r"^%?([\w.\-]+) = \(?(\w+\[[\d,]*\])")


def short_op_name(name: str) -> str:
    """The trace names a device op by its whole HLO line; keep the
    instruction's name and its output shape."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def top_device_ops(events: Iterable[Event], n: int = 10) -> List[list]:
    """[[name, seconds], ...]: the device ops that took most time."""
    tot: Dict[str, int] = {}
    for evs in device_ops(events).values():
        for ev in evs:
            key = short_op_name(ev[2])
            tot[key] = tot.get(key, 0) + ev[4]
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(events: List[Event], t0_ns: int, t1_ns: int,
              n: int = 10, skip: Tuple[str, ...] = ()) -> List[list]:
    """[[what the host was doing, seconds], ...] over the device's idle
    time inside [t0, t1]: each instant of a gap goes to the innermost
    (shortest) host event that covers it, or to "unattributed"."""
    busy = union_ns((ev[3], ev[3] + ev[4])
                    for evs in device_ops(events).values() for ev in evs)
    host = [ev for ev in events
            if not ev[0].startswith("/device:") and ev[4] > 0
            and ev[2] not in skip
            and ev[3] < t1_ns and ev[3] + ev[4] > t0_ns]
    # sweep: +1/-1 for busy, add/remove for host events
    marks = []
    for s, e in busy:
        marks.append((max(s, t0_ns), 0, 1, -1))
        marks.append((min(e, t1_ns), 0, -1, -1))
    for i, ev in enumerate(host):
        marks.append((max(ev[3], t0_ns), 1, 1, i))
        marks.append((min(ev[3] + ev[4], t1_ns), 1, -1, i))
    marks.sort(key=lambda m: (m[0], m[2]))
    tot: Dict[str, int] = {}
    active: Dict[int, int] = {}
    nbusy, prev = 0, t0_ns
    for t, what, sign, i in marks:
        t = min(max(t, t0_ns), t1_ns)
        if t > prev and nbusy == 0:
            if active:
                inner = min(active, key=active.get)
                name = host[inner][2]
            else:
                name = "unattributed"
            tot[name] = tot.get(name, 0) + (t - prev)
        prev = max(prev, t)
        if what == 0:
            nbusy += sign
        elif sign > 0:
            active[i] = host[i][4]
        else:
            active.pop(i, None)
    if t1_ns > prev and nbusy == 0:
        tot["unattributed"] = tot.get("unattributed", 0) + (t1_ns - prev)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def window_of(events: Iterable[Event], marker: str) -> Tuple[int, int]:
    """(start, end) in ns of the benchmark's own span `marker` (a
    TraceAnnotation around the traced window)."""
    for ev in events:
        if ev[2] == marker:
            return ev[3], ev[3] + ev[4]
    raise LookupError(f"no span {marker!r} in the trace")

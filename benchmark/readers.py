"""What the per-layer readers share.  A reader (benchmark/metrics/
<name>.py) is `read(obs) -> number or None`; `obs` is the traced run's
observation:

    obs.stages     {stage: (count, seconds)} the program's tracer added
                   over the window (stage histograms, merged over the
                   client and every daemon)
    obs.counters   the seam's and the cluster's counters over the window
    obs.ops        ops completed in the window
    obs.compiles   jax compile events inside the window
    obs.trace      None, or the device trace of the traced sub-window:
                   events, its length in s, the seam's counters over it,
                   the kernel's output rows r and the cell's k
    obs.peaks      the chip's published peaks

A reader that finds nothing to read returns None and the harness leaves
the metric out of the line; it never returns 0 for a share."""

from __future__ import annotations

from typing import Optional, Sequence

from benchmark import roofline, trace_reduce

#: kernel names as the device trace shows them
EC_APPLY_MATCH = ["apply_bitmatrix"]

#: every stage an op can wait in before the PG executes it
QUEUE_STAGES = ["throttle_wait", "ring_wait", "queue_wait_ring",
                "queue_wait_pump", "admit_wait", "dep_wait"]


def stage_ms_per_op(obs, stages: Sequence[str]) -> Optional[float]:
    """Mean milliseconds per completed op spent in `stages`."""
    secs = sum(obs.stages[s][1] for s in stages if s in obs.stages)
    n = sum(obs.stages[s][0] for s in stages if s in obs.stages)
    if not n or not obs.ops:
        return None
    return secs * 1e3 / obs.ops


def batch_fill(obs) -> Optional[float]:
    n = obs.counters.get("batch_fill_n", 0)
    return obs.counters["batch_fill_sum"] / n if n else None


def device_byte_fraction(obs) -> Optional[float]:
    dev = obs.counters.get("device_bytes", 0)
    host = obs.counters.get("host_bytes", 0)
    return 100.0 * dev / (dev + host) if dev + host else None


def ec_apply_busy(obs) -> Optional[float]:
    if obs.trace is None:
        return None
    secs = trace_reduce.kernel_seconds(obs.trace["events"], EC_APPLY_MATCH)
    return 100.0 * secs / obs.trace["window_s"] if secs > 0 else None


def ec_apply_roofline(obs) -> Optional[float]:
    if obs.trace is None:
        return None
    secs = trace_reduce.kernel_seconds(obs.trace["events"], EC_APPLY_MATCH)
    dev_bytes = obs.trace["counters"].get("device_bytes", 0)
    if secs <= 0 or not dev_bytes:
        return None
    k, r = obs.trace["k"], obs.trace["r"]
    lanes = dev_bytes // k          # device_bytes counts k * lanes
    return roofline.roofline_share(roofline.ec_apply_bytes(k, r, lanes),
                                   secs, obs.peaks["hbm_bytes_per_s"])


def window_compiles(obs) -> Optional[float]:
    return float(obs.compiles)

"""What the readers of the loop's time account share (common/tracer.py:
the loop sampler's `loop_wall` / `loop_cpu` per tick and, since PR 34,
its timing of the loop's selector per call: `evloop_idle`, a `select`
that was asked to block; `evloop_poll`, a `select(0)` between ready
callbacks), and the readers of two stages PR 34 named: the interval
`read_gather` and the chain stage `reply_wait`.

The account, over the window:

    loop_wall = evloop_idle + evloop_poll + the callbacks' wall
    idle share    = evloop_idle / loop_wall      asleep, nothing ready
    off-core share = (loop_wall - evloop_idle - loop_cpu) / loop_wall
                    runnable and not on a core: the GIL, the scheduler
    `osd.loop_cpu_share` + idle share + off-core share = 100

A program without the selector's stages (a parent commit) gives every
reader here nothing to read: None, never 0, never an exception."""

from __future__ import annotations

from typing import Optional

from benchmark import readers

EVLOOP = ("evloop_idle", "evloop_poll")


def _seconds(obs, stage: str) -> float:
    return obs.stages[stage][1] if stage in obs.stages else 0.0


def _wall(obs) -> float:
    """The sampler's wall seconds, or 0.0 where the program kept no
    account of its selector."""
    if not any(s in obs.stages for s in EVLOOP):
        return 0.0
    return _seconds(obs, "loop_wall")


def loop_idle_share(obs) -> Optional[float]:
    """Percent of the loop thread's wall time asleep in `select` with
    nothing ready."""
    wall = _wall(obs)
    if wall <= 0:
        return None
    return 100.0 * _seconds(obs, "evloop_idle") / wall


def loop_offcore_share(obs) -> Optional[float]:
    """Percent of the loop thread's wall time in which it was neither
    asleep in `select` nor on a core."""
    wall = _wall(obs)
    if wall <= 0 or "loop_cpu" not in obs.stages:
        return None
    return 100.0 * (wall - _seconds(obs, "evloop_idle")
                    - _seconds(obs, "loop_cpu")) / wall


def loop_poll_ms(obs) -> Optional[float]:
    """Milliseconds per completed op in `select(0)`: a call that waits
    for nothing of its own, so the syscall plus the GIL won back."""
    if _wall(obs) <= 0 or not obs.ops:
        return None
    return _seconds(obs, "evloop_poll") * 1e3 / obs.ops


def read_gather_ms(obs) -> Optional[float]:
    """Mean milliseconds per completed op from a read's first sub-read
    send to k shard streams in hand."""
    return readers.stage_ms_per_op(obs, ["read_gather"])


def reply_wait_ms(obs) -> Optional[float]:
    """Mean milliseconds per completed op a pipelined write waited to
    reply in order; 0.0 when no op waited.  None under a program whose
    tracer declares no such stage."""
    try:
        from ceph_tpu.common.tracer import CHAIN_STAGES
    except ImportError:
        return None
    if "reply_wait" not in CHAIN_STAGES or not obs.ops:
        return None
    return readers.stage_ms_per_op(obs, ["reply_wait"]) or 0.0

"""What the readers of the program's sections share (common/tracer.py:
`Tracer.section`, the seam's intervals, the loop sampler).

The `seam_*` and `loop_*` stages reach a reader twice: as totals in
`obs.stages`, like every other tracer stage, and as host events of the
same names in `obs.trace["events"]`, because a section is also a
`jax.profiler.TraceAnnotation`.  A program without them (a parent
commit) has neither, and every reader here then returns None."""

from __future__ import annotations

from typing import Optional

#: the loop sampler's two stages: time of the loop thread, not sections
LOOP_SAMPLER = ("loop_cpu", "loop_wall")


def _seconds(obs, stage: str) -> float:
    return obs.stages[stage][1] if stage in obs.stages else 0.0


def loop_cpu_share(obs) -> Optional[float]:
    """Percent of the loop thread's wall time in which it burned CPU;
    the rest is the loop waiting (executor, timer, GIL)."""
    wall = _seconds(obs, "loop_wall")
    if wall <= 0 or "loop_cpu" not in obs.stages:
        return None
    return 100.0 * _seconds(obs, "loop_cpu") / wall


def loop_named_share(obs) -> Optional[float]:
    """Percent of the loop thread's CPU seconds that lie in a `loop_*`
    section.  Sections time by the wall clock, so a section that waits
    for the GIL counts its wait: the share can pass 100."""
    cpu = _seconds(obs, "loop_cpu")
    named = [s for s in obs.stages
             if s.startswith("loop_") and s not in LOOP_SAMPLER]
    if cpu <= 0 or not named:
        return None
    return 100.0 * sum(_seconds(obs, s) for s in named) / cpu


def loop_longest_ms(obs) -> Optional[float]:
    """The longest single `loop_*` section of the traced sub-window,
    from the profiler's host events."""
    if obs.trace is None:
        return None
    longest = max((ev[4] for ev in obs.trace["events"]
                   if ev[2].startswith("loop_")
                   and not ev[0].startswith("/device:")), default=0)
    return longest / 1e6 if longest > 0 else None

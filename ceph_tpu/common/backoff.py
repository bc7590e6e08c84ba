"""Shared degraded-path retry/backoff policy (jittered exponential).

Every retry loop on a degraded path — recovery push rounds, EC gathers
starved by down shards, tier-client primary waits, cache writeback
against a down backend — shares ONE policy object instead of a
per-site hardcoded sleep: delays grow exponentially, carry
deterministic decorrelated jitter (so a storm of peers retrying the
same failure doesn't re-synchronize into thundering herds), cap at a
configurable maximum, and track a MONOTONIC overall deadline (MONO05:
no wall clock in op paths).  Every give-up is cause-tagged and counted
in a module census (and an optional perf group), so retry storms show
up in ``perf dump --cluster`` instead of only in warn logs.

Jitter is deliberately NOT ``random``: the schedule explorer
(devtools/schedule.py) replays whole clusters byte-identically from a
seed, so delay sequences must be a pure function of (cause, attempt).
A crc32-derived fraction gives decorrelation without nondeterminism.

Lint rule RETRY19 (devtools/rules.py) pins op-path retry loops in
osd/ and client/ modules to this helper (or an explicit waiver).
"""

from __future__ import annotations

import asyncio
import time
import zlib
from typing import Dict, Optional

__all__ = ["Backoff", "BackoffGiveUp", "GIVE_UPS", "RETRIES",
           "census_reset"]

#: module-wide retry/give-up census by cause tag — scraped by tests,
#: bench forensics and the admin socket without threading a perf
#: group into every call site
RETRIES: Dict[str, int] = {}
GIVE_UPS: Dict[str, int] = {}


def census_reset() -> None:
    RETRIES.clear()
    GIVE_UPS.clear()


class BackoffGiveUp(TimeoutError):
    """A Backoff exhausted its deadline/attempt budget.  A
    TimeoutError (``asyncio.TimeoutError`` is the same class) so
    callers that treated the old fixed ``wait_for`` timeout as "peer
    is gone" handle a give-up identically."""

    def __init__(self, cause: str, attempts: int, elapsed: float):
        super().__init__(
            f"{cause}: gave up after {attempts} attempts / "
            f"{elapsed:.1f}s")
        self.cause = cause
        self.attempts = attempts
        self.elapsed = elapsed


class Backoff:
    """One retry loop's policy state.

    ``cause`` tags the census rows and the give-up exception; ``base``/
    ``factor``/``cap`` shape the exponential; ``jitter`` is the maximum
    fraction shaved off a delay (0.25 = delays land in [0.75d, d]);
    ``timeout`` is the overall monotonic budget (None = retry forever —
    the caller's loop condition, e.g. an interval check, bounds it);
    ``max_attempts`` bounds rounds independently of time.

    ``reset()`` on progress: a path that moved work is alive, so both
    the delay ladder and the deadline restart.
    """

    __slots__ = ("cause", "base", "factor", "cap", "jitter",
                 "timeout", "max_attempts", "attempts", "_t0",
                 "_perf", "_perf_prefix", "_seed")

    def __init__(self, cause: str, *, base: float = 0.1,
                 factor: float = 2.0, cap: float = 5.0,
                 jitter: float = 0.25,
                 timeout: Optional[float] = None,
                 max_attempts: Optional[int] = None,
                 perf=None, perf_prefix: str = "backoff"):
        self.cause = cause
        self.base = base
        self.factor = factor
        self.cap = cap
        self.jitter = jitter
        self.timeout = timeout
        self.max_attempts = max_attempts
        self.attempts = 0
        self._t0 = time.monotonic()
        self._perf = perf
        self._perf_prefix = perf_prefix
        self._seed = zlib.crc32(cause.encode())

    # ------------------------------------------------------------ state
    def reset(self) -> None:
        """Progress was made: restart the ladder AND the deadline."""
        self.attempts = 0
        self._t0 = time.monotonic()

    def elapsed(self) -> float:
        return time.monotonic() - self._t0

    def remaining(self) -> float:
        """Monotonic budget left (inf when no overall timeout)."""
        if self.timeout is None:
            return float("inf")
        return max(0.0, self.timeout - self.elapsed())

    def expired(self) -> bool:
        if self.max_attempts is not None \
                and self.attempts >= self.max_attempts:
            return True
        return self.timeout is not None and self.remaining() <= 0.0

    def next_delay(self) -> float:
        """The delay the NEXT sleep() would use (pure, no side
        effects): capped exponential minus a deterministic jitter
        fraction derived from (cause, attempt)."""
        d = min(self.cap, self.base * (self.factor ** self.attempts))
        frac = ((self._seed ^ (self.attempts * 2654435761))
                % 1000) / 1000.0
        return d * (1.0 - self.jitter * frac)

    # ------------------------------------------------------------ waits
    def _count(self, kind: str) -> None:
        census = RETRIES if kind == "retries" else GIVE_UPS
        census[self.cause] = census.get(self.cause, 0) + 1
        if self._perf is not None:
            try:
                self._perf.inc(f"{self._perf_prefix}_{kind}")
            except KeyError:
                pass    # group exists but counter not registered

    def give_up(self) -> BackoffGiveUp:
        """Record and build the cause-tagged give-up (raised by the
        caller, so the raising line sits in the owning module)."""
        self._count("give_ups")
        return BackoffGiveUp(self.cause, self.attempts, self.elapsed())

    async def sleep(self) -> None:
        """One retry round: raise the cause-tagged give-up if the
        budget is spent, else sleep the next jittered delay."""
        if self.expired():
            raise self.give_up()
        delay = self.next_delay()
        self.attempts += 1
        self._count("retries")
        await asyncio.sleep(min(delay, self.remaining()))

    async def wait_for(self, awaitable, per_try: Optional[float] = None):
        """``asyncio.wait_for`` bounded by this policy's remaining
        budget (and optionally a per-attempt cap).  On timeout the
        cause-tagged give-up is raised instead of a bare
        ``TimeoutError`` — the fixed-magic-number replacement for the
        old ``await asyncio.wait_for(fut, 20.0)`` sites."""
        budget = self.remaining()
        if per_try is not None:
            budget = min(budget, per_try)
        if budget <= 0:
            raise self.give_up()
        try:
            return await asyncio.wait_for(awaitable, budget)
        except asyncio.TimeoutError:
            raise self.give_up() from None

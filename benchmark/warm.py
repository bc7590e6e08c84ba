"""R2: set-up enumerates the shapes the device seam will see, so that
nothing compiles inside the window.

The seam folds the requests pending at one moment into one launch, so a
window can present any width n * lanes for n = 1..depth, and the
program's eager slice/pad/concatenate glue compiles one small program
per width.  Which widths a run meets depends on timing; the set is
finite, so set-up walks it: for each n it issues n calls of the queue's
PUBLIC entry `apply(mat, chunks)` in one loop turn, which fold into one
group, and checks from the queue's counters that the group launched
whole.  The jit and persistent caches are process-wide, so one OSD's
queue warms all.  Compiled programs depend on shapes only (the matrix is
an operand), so widths are walked once per matrix SHAPE and every
further matrix is called once, which builds its operand."""

from __future__ import annotations

import asyncio
from typing import List

import numpy as np

from benchmark import reference


def seam_matrices(k: int, m: int, shapes: dict, lost: List[int]) -> list:
    """[(matrix, walk its widths?)] for this cell: the parity rows for
    encodes; on a degraded cluster one decode matrix per data chunk a
    placement can have lost (survivors: the first k that are left)."""
    gen = reference.generator(k, m)
    mats = []
    if shapes["encode"]:
        mats.append((np.ascontiguousarray(gen[k:]), True))
    if shapes["decode"]:
        first = True
        for chunk in range(k):
            present = [i for i in range(k + m) if i != chunk][:k]
            mats.append((reference.decode_matrix(k, m, present, [chunk]),
                         first))
            first = False
    return mats


async def enumerate_seam(queue, k: int, mats: list, lanes: int,
                         depth: int) -> dict:
    """Walk n = 1..depth for each matrix marked so; one call for the
    rest.  Returns what was launched and how many groups had to be
    retried because they did not launch whole."""
    rng = np.random.default_rng(12345)
    chunk = rng.integers(0, 256, (k, lanes), dtype=np.uint8)
    groups = retried = 0
    for mat, walk in mats:
        want = reference.apply(mat, chunk[:, :4096])
        for n in (range(1, depth + 1) if walk else (1,)):
            for attempt in range(4):
                before = _fill(queue)
                outs = await asyncio.gather(
                    *[queue.apply(mat, chunk) for _ in range(n)])
                launches, reqs = (a - b for a, b in
                                  zip(_fill(queue), before))
                for out in outs:
                    if not np.array_equal(out[:, :4096], want):
                        raise RuntimeError(
                            "seam warm-up: the device's answer differs "
                            "from the plain reference")
                if launches == 1 and reqs == n:
                    break
                retried += 1
            else:
                raise RuntimeError(
                    f"seam warm-up: {n} calls in one loop turn did not "
                    f"fold into one group in 4 attempts")
            groups += 1
    return {"groups": groups, "retried": retried}


def _fill(queue) -> tuple:
    bf = queue.perf.dump()["batch_fill"]
    return int(bf["avgcount"]), int(bf["sum"])

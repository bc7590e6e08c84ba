"""The comparison that decides `correct`, against the plain reference.

After the window: every object of the write ring is read back through
the served path and compared with the reference's last acked write
(`readback_mismatch`); the bytes of a seeded sample of window reads are
compared with what the reference says the object holds
(`window_read_mismatch`; in the degraded cell these came through
reconstruction); and for a seeded sample of objects every shard the
OSDs STORED (data and parity, on every OSD that is up) is compared with
the reference's own GF(2^8) encode (`shard_mismatch`).  Exact
comparisons: the limit of each is 0.  The seam's counters say whether
the bytes took the device (`host_bytes`, `device_fallbacks`,
`device_bytes_short`), limit 0 each."""

from __future__ import annotations

import asyncio
from typing import Dict, Tuple

import numpy as np

from benchmark import reference


async def readback_mismatch(io, want: Dict[str, bytes],
                            depth: int = 16) -> int:
    """Objects whose served bytes differ from `want`.  A read that
    fails or never comes counts as a mismatch."""
    sem, bad = asyncio.Semaphore(depth), 0

    async def one(name, data):
        nonlocal bad
        async with sem:
            try:
                got = await io.read(name, length=len(data), timeout=60.0)
            except Exception:
                bad += 1
                return
            if got != data:
                bad += 1
    await asyncio.gather(*[one(n, d) for n, d in want.items()])
    return bad


def shard_mismatch(env, sample: Dict[str, bytes]) -> Tuple[int, int]:
    """(shards compared, shards that differ) over the sample: what each
    up OSD of the object's acting set stored against the reference's
    encode of what the object should hold."""
    from ceph_tpu.client.objecter import ObjectLocator
    from ceph_tpu.store.types import CollectionId, ObjectId
    omap = env.admin.monc.osdmap
    loc = ObjectLocator(env.pool_id)
    seen = bad = 0
    for name, data in sample.items():
        pgid, acting = omap.object_to_acting(name, loc)[:2]
        want = reference.shards(data, env.k, env.m)
        for j, osd_id in enumerate(acting):
            osd = env.cluster.osds.get(osd_id)
            if osd is None:
                continue                    # a killed OSD holds nothing
            seen += 1
            try:
                raw = osd.store.read(
                    CollectionId.pg(env.pool_id, pgid.seed, j),
                    ObjectId(name, pool=env.pool_id))
            except Exception:
                bad += 1
                continue
            if not np.array_equal(np.frombuffer(raw, np.uint8), want[j]):
                bad += 1
    return seen, bad

"""Faults planted under the timed path, for the controls: each breaks
one guarantee the configurations state, and a run with it has to come
out `correct: false`.  Planted once set-up is done, just before the
load starts; never by the benchmark's own runs.

    stale_write    every 7th write is acked and never applied ("a step
                   that returns its state unchanged"): breaks "a read
                   returns the last acked bytes"
    answer_flip    every 5th read answers with one byte altered where
                   it is produced: breaks the same, on the read side
    seam_corrupt   every 2nd answer of the device seam has one byte
                   altered: stored parity is wrong (breaks "acked after
                   all k+m shards applied", as far as the shards must
                   be the code's), and a degraded read reconstructs the
                   wrong bytes
"""

from __future__ import annotations

import itertools


def plant(fault: str, env):
    """Plant `fault`; returns the function that removes it."""
    if fault == "stale_write":
        return _wrap(env.io, "write_full", _stale_write)
    if fault == "answer_flip":
        return _wrap(env.io, "read", _answer_flip)
    if fault == "seam_corrupt":
        undos = [_wrap(osd.ec_queue, "apply", _seam_corrupt)
                 for osd in env.cluster.osds.values()]
        return lambda: [u() for u in undos]
    raise ValueError(f"no fault {fault!r}")


def _wrap(obj, name, make):
    real = getattr(obj, name)
    setattr(obj, name, make(real))
    return lambda: setattr(obj, name, real)


def _stale_write(real):
    n = itertools.count(1)

    async def write_full(oid, data):
        if next(n) % 7 == 0:
            return None
        return await real(oid, data)
    return write_full


def _answer_flip(real):
    n = itertools.count(1)

    async def read(oid, length=0, offset=0, timeout=30.0):
        got = await real(oid, length=length, offset=offset,
                         timeout=timeout)
        if next(n) % 5 == 0 and got:
            got = bytes([got[0] ^ 0x01]) + got[1:]
        return got
    return read


def _seam_corrupt(real):
    n = itertools.count(1)

    async def apply(mat, chunks):
        out = await real(mat, chunks)
        if next(n) % 2 == 0:
            out = out.copy()
            out[0, 0] ^= 0x01
        return out
    return apply

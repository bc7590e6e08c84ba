"""Scrub: integrity detection + repair (osd/scrub.py).

Reference strategy analog: test/osd/osd-scrub-repair.sh — corrupt a
stored copy behind the cluster's back, scrub, prove detection and
repair for replicated and EC pools.
"""

import asyncio
import sys

import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/", 1)[0])
from test_osd import Cluster  # noqa: E402

from ceph_tpu.common.crc import crc32c_impl, crc32c_python  # noqa: E402
from ceph_tpu.osd.messages import MPGScrub  # noqa: E402
from ceph_tpu.osd.scrub import CRC_XATTR  # noqa: E402
from ceph_tpu.store.objectstore import Transaction  # noqa: E402


def find_copies(cl, name):
    """[(osd, cid, soid)] for every stored copy/shard of object `name`."""
    out = []
    for osd in cl.osds.values():
        for cid in osd.store.list_collections():
            for soid in osd.store.collection_list(cid):
                if soid.name == name:
                    out.append((osd, cid, soid))
    return out


def corrupt(osd, cid, soid, flip=0):
    """Flip one bit of the stored bytes WITHOUT touching xattrs —
    simulated silent media bit-rot."""
    data = bytearray(osd.store.read(cid, soid))
    data[flip] ^= 0x40
    osd.store.apply_transaction(
        Transaction().write(cid, soid, 0, bytes(data)))


def primary_pg(cl, pool_name, name):
    """(pg-on-primary, primary-osd) for the PG holding `name`."""
    for osd in cl.osds.values():
        for pg in osd.pgs.values():
            if not pg.is_primary():
                continue
            for soid in osd.store.collection_list(pg.cid):
                if soid.name == name:
                    return pg, osd
    raise AssertionError(f"no primary pg holds {name}")


async def run_scrub(pg, deep):
    pg.last_scrub_result = None
    pg.queue_op(MPGScrub(pg.pgid, deep=deep))
    for _ in range(400):
        if pg.last_scrub_result is not None:
            return pg.last_scrub_result
        await asyncio.sleep(0.05)
    raise AssertionError("scrub did not complete")


def test_deep_scrub_repairs_replica_bitrot():
    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("data", pg_num=4)
        io = admin.open_ioctx("data")
        payload = bytes(range(256)) * 32
        await io.write_full("obj", payload)
        pg, posd = primary_pg(cl, "data", "obj")
        # rot a NON-primary copy
        victims = [(o, c, s) for (o, c, s) in find_copies(cl, "obj")
                   if o is not posd]
        assert victims
        vosd, vcid, vsoid = victims[0]
        corrupt(vosd, vcid, vsoid)
        assert vosd.store.read(vcid, vsoid) != payload
        res = await run_scrub(pg, deep=True)
        assert res["errors"] >= 1 and res["repaired"] >= 1
        assert vosd.store.read(vcid, vsoid) == payload   # healed
        # second scrub: clean
        res = await run_scrub(pg, deep=True)
        assert res["errors"] == 0
        assert await io.read("obj") == payload
        await cl.stop()
    asyncio.run(run())


def test_deep_scrub_repairs_primary_bitrot():
    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("data", pg_num=4)
        io = admin.open_ioctx("data")
        payload = b"primary-rot" * 500
        await io.write_full("obj", payload)
        pg, posd = primary_pg(cl, "data", "obj")
        mine = [(o, c, s) for (o, c, s) in find_copies(cl, "obj")
                if o is posd]
        corrupt(*mine[0])
        res = await run_scrub(pg, deep=True)
        assert res["errors"] >= 1
        assert posd.store.read(mine[0][1], mine[0][2]) == payload
        assert await io.read("obj") == payload
        await cl.stop()
    asyncio.run(run())


def test_light_scrub_repairs_missing_replica_object():
    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("data", pg_num=4)
        io = admin.open_ioctx("data")
        await io.write_full("obj", b"x" * 4096)
        pg, posd = primary_pg(cl, "data", "obj")
        victims = [(o, c, s) for (o, c, s) in find_copies(cl, "obj")
                   if o is not posd]
        vosd, vcid, vsoid = victims[0]
        vosd.store.apply_transaction(Transaction().remove(vcid, vsoid))
        res = await run_scrub(pg, deep=False)     # light finds absence
        assert res["errors"] >= 1 and res["repaired"] >= 1
        assert vosd.store.read(vcid, vsoid) == b"x" * 4096
        await cl.stop()
    asyncio.run(run())


def test_deep_scrub_rebuilds_ec_shard():
    async def run():
        cl = Cluster()
        admin = await cl.start(6)
        await admin.pool_create("ecpool", pg_num=4, pool_type="erasure",
                                k=4, m=2)
        io = admin.open_ioctx("ecpool")
        rng = np.random.default_rng(5)
        payload = rng.integers(0, 256, 16384, dtype=np.uint8).tobytes()
        await io.write_full("obj", payload)
        pg, posd = primary_pg(cl, "ecpool", "obj")
        victims = [(o, c, s) for (o, c, s) in find_copies(cl, "obj")
                   if o is not posd]
        vosd, vcid, vsoid = victims[0]
        before = vosd.store.read(vcid, vsoid)
        corrupt(vosd, vcid, vsoid, flip=7)
        res = await run_scrub(pg, deep=True)
        assert res["errors"] >= 1 and res["repaired"] >= 1
        assert vosd.store.read(vcid, vsoid) == before    # shard rebuilt
        assert await io.read("obj") == payload
        res = await run_scrub(pg, deep=True)
        assert res["errors"] == 0
        await cl.stop()
    asyncio.run(run())


@pytest.mark.parametrize("size", [131072, 131072 + 4099],
                         ids=["stripe_aligned", "unaligned"])
def test_ec_write_records_each_shard_digest(size):
    """Every one of the six shards of a full write carries the `_crc` of
    exactly the bytes stored, by the byte-at-a-time python table (the
    write path's digest runs on the native kernel), and deep scrub,
    which recomputes it, agrees.  32 KiB shards: long enough for the
    kernel's interleaved streams."""
    async def run():
        cl = Cluster()
        admin = await cl.start(6)
        await admin.pool_create("ecpool", pg_num=4, pool_type="erasure",
                                k=4, m=2)
        io = admin.open_ioctx("ecpool")
        for osd in cl.osds.values():   # each OSD says which path it is on
            assert any(f"crc32c {crc32c_impl()}" in line
                       for line in osd.ctx.log.dump_recent(10000))
        payload = np.random.default_rng(size).integers(
            0, 256, size, dtype=np.uint8).tobytes()
        await io.write_full("obj", payload)
        copies = find_copies(cl, "obj")
        assert len(copies) == 6
        for osd, cid, soid in copies:
            stored = osd.store.read(cid, soid)
            assert len(stored) >= size // 4
            assert int(osd.store.getattr(cid, soid, CRC_XATTR)) == \
                crc32c_python(stored)
        pg, _ = primary_pg(cl, "ecpool", "obj")
        res = await run_scrub(pg, deep=True)
        assert res["errors"] == 0 and res["repaired"] == 0
        assert await io.read("obj") == payload
        await cl.stop()
    asyncio.run(run())


def test_ec_write_digests_come_from_the_device_threads_continuation():
    """The same guarantee on the path the chip runs: with the EC queue
    in `force` mode a full write's shard bytes and digests are made by
    the request's continuation on the ec-device thread (`finish_thread`
    counts every write, `finish_inline` none).  Each of the six shards
    of a whole-stripe payload (`split_data` views it) and of a padded
    one carries the `_crc` of exactly the bytes stored, the bytes are
    the host codec's encode, and deep scrub agrees."""
    from test_osd import FAST_CFG
    from ceph_tpu.ec.registry import factory
    saved = dict(FAST_CFG)
    FAST_CFG["osd_ec_batch_device"] = "force"
    FAST_CFG["osd_ec_batch_min_bytes"] = 1024
    sizes = {"aligned": 131072, "padded": 131072 + 4099,
             "aligned_small": 4 * 1024}

    async def run():
        cl = Cluster()
        admin = await cl.start(6)
        await admin.pool_create("ecpool", pg_num=4, pool_type="erasure",
                                k=4, m=2)
        io = admin.open_ioctx("ecpool")
        payloads = {name: np.random.default_rng(size).integers(
            0, 256, size, dtype=np.uint8).tobytes()
            for name, size in sizes.items()}
        await asyncio.gather(*[io.write_full(n, p)
                               for n, p in payloads.items()])
        seam = [osd.ec_queue.perf.dump() for osd in cl.osds.values()]
        assert sum(d["device_requests"] for d in seam) == len(sizes)
        assert sum(d["finish_thread"] for d in seam) == len(sizes)
        assert sum(d["finish_inline"] for d in seam) == 0
        assert sum(d["device_fallbacks"] for d in seam) == 0
        codec = factory("rs", {"k": "4", "m": "2", "backend": "host"})
        for name, payload in payloads.items():
            want = codec.encode(set(range(6)), payload)
            copies = find_copies(cl, name)
            assert len(copies) == 6
            for osd, cid, soid in copies:
                stored = osd.store.read(cid, soid)
                shard = int(cid.name[:-len("_head")].rsplit("s", 1)[1])
                assert stored == want[shard].tobytes(), (name, cid)
                assert int(osd.store.getattr(cid, soid, CRC_XATTR)) == \
                    crc32c_python(stored)
            pg, _ = primary_pg(cl, "ecpool", name)
            res = await run_scrub(pg, deep=True)
            assert res["errors"] == 0 and res["repaired"] == 0
            assert await io.read(name) == payload
        await cl.stop()
    try:
        asyncio.run(run())
    finally:
        FAST_CFG.clear()
        FAST_CFG.update(saved)


def test_deep_scrub_rebuilds_primary_own_ec_shard():
    async def run():
        cl = Cluster()
        admin = await cl.start(6)
        await admin.pool_create("ecpool", pg_num=4, pool_type="erasure",
                                k=4, m=2)
        io = admin.open_ioctx("ecpool")
        payload = bytes(range(256)) * 64
        await io.write_full("obj", payload)
        pg, posd = primary_pg(cl, "ecpool", "obj")
        mine = [(o, c, s) for (o, c, s) in find_copies(cl, "obj")
                if o is posd]
        before = posd.store.read(mine[0][1], mine[0][2])
        corrupt(*mine[0], flip=3)
        res = await run_scrub(pg, deep=True)
        assert res["errors"] >= 1 and res["repaired"] >= 1
        assert posd.store.read(mine[0][1], mine[0][2]) == before
        assert await io.read("obj") == payload
        await cl.stop()
    asyncio.run(run())


def test_pg_scrub_mon_command_path():
    """Operator path: `ceph pg deep-scrub <pgid>` routed mon -> primary."""
    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("data", pg_num=4)
        io = admin.open_ioctx("data")
        await io.write_full("obj", b"cmd-path" * 512)
        pg, posd = primary_pg(cl, "data", "obj")
        victims = [(o, c, s) for (o, c, s) in find_copies(cl, "obj")
                   if o is not posd]
        corrupt(*victims[0])
        pg.last_scrub_result = None
        ackm = await admin.mon_command(
            {"prefix": "pg deep-scrub",
             "pgid": str(pg.pgid.without_shard())})
        assert ackm.retcode == 0, ackm.outs
        for _ in range(400):
            if pg.last_scrub_result is not None:
                break
            await asyncio.sleep(0.05)
        assert pg.last_scrub_result is not None, "scrub never ran"
        assert pg.last_scrub_result["repaired"] >= 1
        assert victims[0][0].store.read(victims[0][1], victims[0][2]) \
            == b"cmd-path" * 512
        await cl.stop()
    asyncio.run(run())


def test_scrub_updates_info_stamps_and_perf():
    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("data", pg_num=4)
        io = admin.open_ioctx("data")
        await io.write_full("obj", b"stamps")
        pg, posd = primary_pg(cl, "data", "obj")
        assert pg.info.last_deep_scrub_stamp == 0
        await run_scrub(pg, deep=True)
        assert pg.info.last_deep_scrub_stamp > 0
        assert pg.info.last_scrub_stamp > 0
        assert posd.perf_scrub.dump()["scrubs_deep"] >= 1
        await cl.stop()
    asyncio.run(run())


def test_deep_scrub_repairs_clone_bitrot():
    """Snapshot clones scrub + repair like heads (keyed name\\x00snap):
    bit-rot in a replica's CLONE is detected by deep scrub and healed
    by re-pushing the base object (head + SnapSet + clones)."""
    async def run():
        cl = Cluster()
        admin = await cl.start(3)
        await admin.pool_create("data", pg_num=4)
        io = admin.open_ioctx("data")
        await io.write_full("obj", b"frozen" * 500)
        await io.snap_create("s1")
        sid = io.snap_lookup("s1")
        await io.write_full("obj", b"newer!" * 700)   # clones v1

        clones = [(o, c, s) for o, c, s in find_copies(cl, "obj")
                  if not s.is_head()]
        assert len(clones) == 3
        vosd, vcid, vsoid = clones[0]
        corrupt(vosd, vcid, vsoid)

        pg, posd = primary_pg(cl, "data", "obj")
        res = await run_scrub(pg, deep=True)
        assert res["errors"] >= 1, res
        assert res["repaired"] >= 1, res
        assert any("\x00" in i for i in res["inconsistent"]), res

        # the corrupted clone is bit-exact again on every copy...
        for o, c, s in find_copies(cl, "obj"):
            if not s.is_head():
                assert o.store.read(c, s) == b"frozen" * 500
        # ...and a re-scrub is clean
        res = await run_scrub(pg, deep=True)
        assert res["errors"] == 0, res
        # snapshot read serves the healed bytes
        sio = io.dup()
        sio.set_snap_read(sid)
        assert await sio.read("obj") == b"frozen" * 500
        await cl.stop()
    asyncio.run(run())


def test_deep_scrub_rebuilds_ec_clone_chunk():
    """EC clone chunks scrub + rebuild: bit-rot in one shard's CLONE
    chunk is detected and reconstructed by decoding over the peers'
    clone chunks (the erasure relation holds per clone)."""
    async def run():
        cl = Cluster()
        admin = await cl.start(4)
        await admin.pool_create("ec", pg_num=4, pool_type="erasure",
                                k=2, m=2)
        io = admin.open_ioctx("ec")
        await io.write_full("obj", b"frozen" * 600)
        await io.snap_create("s1")
        sid = io.snap_lookup("s1")
        await io.write_full("obj", b"newer!" * 400)   # clones chunks

        clones = [(o, c, s) for o, c, s in find_copies(cl, "obj")
                  if not s.is_head()]
        assert len(clones) == 4            # one clone chunk per shard
        vosd, vcid, vsoid = clones[0]
        want = vosd.store.read(vcid, vsoid)
        corrupt(vosd, vcid, vsoid)

        pg, posd = primary_pg(cl, "ec", "obj")
        res = await run_scrub(pg, deep=True)
        assert res["errors"] >= 1, res
        assert res["repaired"] >= 1, res

        # the corrupted clone chunk is bit-exact again
        assert vosd.store.read(vcid, vsoid) == want
        res = await run_scrub(pg, deep=True)
        assert res["errors"] == 0, res
        # and the snapshot read decodes the healed stripe
        sio = io.dup()
        sio.set_snap_read(sid)
        assert await sio.read("obj") == b"frozen" * 600
        await cl.stop()
    asyncio.run(run())

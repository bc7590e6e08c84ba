"""The device seam's launch windows (osd/ec_queue.py `_windows`, `_fold`,
`ECBatchQueue._run_group`): a group is folded on the host straight into
windows whose widths are lane buckets, host buffers kept from group to
group up to a bound, and each window is staged, launched and fetched
whole, so the programs that run depend on the matrix shape and the lane
bucket alone.

With `LANE_BUCKETS` patched small, random groups of mixed widths (wider
than the top bucket too, arrays and rows) compile nothing once each
bucket has run, every request's parity is `gf256.host_apply`'s bit for
bit, and the counters add up.  On a small in-process cluster with an EC
k=2 m=1 pool, objects of five sizes, one of them many windows wide, read
back as written and every stored shard is the plain reference's; and
the client's byte budget makes the second of two writes that do not fit
together wait, and both read back.  Nothing here is a number about
speed: the device is the CPU."""

import asyncio

import numpy as np
import pytest

from ceph_tpu.common.context import Context
from ceph_tpu.ec import gf256
from ceph_tpu.osd import ec_queue
from ceph_tpu.osd.ec_queue import ECBatchQueue

SMALL_BUCKETS = (256, 1024, 4096)


@pytest.fixture
def small_buckets(monkeypatch):
    monkeypatch.setattr(ec_queue, "LANE_BUCKETS", SMALL_BUCKETS)


class Compiles:
    """jax compile events, counted as benchmark/diag.py counts them."""

    def __init__(self):
        from benchmark import diag
        self.events = diag.JaxEvents()

    def now(self) -> int:
        return self.events.snap()["compile_events"]


# --------------------------------------------------------- the windows
@pytest.mark.parametrize("total,want", [
    (1, [1 << 14]),
    (1 << 14, [1 << 14]),
    (2048 * 3, [1 << 14]),              # three 4 KiB writes
    (1 << 15, [1 << 16]),               # a 64 KiB write
    (1 << 19, [1 << 20]),               # a 1 MiB write
    (1 << 21, [1 << 22]),               # a 4 MiB write
    (1 << 22, [1 << 22]),
    (1 << 25, [1 << 22] * 8),           # a 64 MiB write
    ((1 << 22) + 100, [1 << 22, 1 << 14]),
    ((1 << 25) + (1 << 15), [1 << 22] * 8 + [1 << 16]),
])
def test_windows_are_buckets_full_but_the_last(total, want):
    got = ec_queue._windows(total)
    assert got == want
    assert all(b in ec_queue.LANE_BUCKETS for b in got)
    assert sum(got[:-1]) < total <= sum(got)
    # the tail goes up to the bucket that holds it, no further
    tail = total - sum(got[:-1])
    assert got[-1] == ec_queue._bucket(tail)


def test_windows_of_small_buckets(small_buckets):
    assert ec_queue._windows(300) == [1024]
    assert ec_queue._windows(4096 + 1) == [4096, 256]
    assert ec_queue._windows(3 * 4096 + 2000) == [4096] * 4
    assert ec_queue._windows(0) == []


# ---------------------------------------------------- the queue alone
def _queue():
    q = ECBatchQueue(Context("osd.0"), mode="force", window_ms=5.0,
                     min_device_bytes=0)
    return q


def test_mixed_groups_compile_nothing_once_each_bucket_ran(small_buckets):
    """Warm each bucket once; then 40 groups of random widths, arrays
    and rows, some wider than the top bucket, compile nothing, and every
    request's parity is the host kernel's bit for bit."""
    compiles = Compiles()

    async def run():
        q = _queue()
        mat = gf256.rs_vandermonde_matrix(2, 1)[2:]
        rng = np.random.default_rng(41)
        for b in SMALL_BUCKETS:
            c = rng.integers(0, 256, (2, b), dtype=np.uint8)
            assert np.array_equal(await q.apply(mat, c),
                                  gf256.host_apply(mat, c))
        warm = q.perf.dump()
        before = compiles.now()
        most = 0
        for _ in range(40):
            widths = rng.integers(1, 3 * SMALL_BUCKETS[-1],
                                  rng.integers(1, 7))
            most = max(most, len(ec_queue._windows(int(widths.sum()))))
            ins = [rng.integers(0, 256, (2, int(w)), dtype=np.uint8)
                   for w in widths]
            given = [c if rng.random() < 0.5 else [c[0].copy(),
                                                   c[1].copy()]
                     for c in ins]
            outs = await asyncio.gather(*[q.apply(mat, g) for g in given])
            for c, o in zip(ins, outs):
                assert o.shape == (1, c.shape[1])
                assert np.array_equal(o, gf256.host_apply(mat, c))
        assert compiles.now() == before
        # the windows' host buffers are kept and reused, stale lanes and
        # all: of each width no more than the widest group needed
        assert set(q._window_bufs) <= {(2, b) for b in SMALL_BUCKETS}
        assert max(len(pool) for pool in q._window_bufs.values()) <= most
        d = q.perf.dump()
        assert d["host_requests"] == 0 and d["device_fallbacks"] == 0
        lanes = {key: d[key] - warm[key] for key in (
            "device_bytes", "device_lanes_launched", "device_pad_lanes",
            "device_launches")}
        assert lanes["device_lanes_launched"] - lanes["device_pad_lanes"] \
            == lanes["device_bytes"] // 2
        assert lanes["device_pad_lanes"] >= 0
        # more launches than groups: the wide ones took several windows
        groups = d["batch_fill"]["avgcount"] - warm["batch_fill"][
            "avgcount"]
        assert lanes["device_launches"] > groups
        await q.stop()
    asyncio.run(run())


def test_a_request_over_several_windows_comes_back_whole(small_buckets):
    """Requests that straddle windows get their pieces joined; a
    continuation sees them as one [r, L] result, once."""
    seen = []

    def finish(chunks, rows):
        seen.append(rows.shape)
        return rows.copy()

    async def run():
        q = _queue()
        mat = gf256.rs_vandermonde_matrix(4, 2)[4:]
        rng = np.random.default_rng(7)
        ins = [rng.integers(0, 256, (4, w), dtype=np.uint8)
               for w in (300, 5000, 9000, 17)]
        outs = await asyncio.gather(*[q.apply_then(mat, c, finish)
                                      for c in ins])
        for c, o in zip(ins, outs):
            assert np.array_equal(o, gf256.host_apply(mat, c))
        d = q.perf.dump()
        total = sum(c.shape[1] for c in ins)
        assert d["device_lanes_launched"] == sum(ec_queue._windows(total))
        assert d["device_pad_lanes"] == d["device_lanes_launched"] - total
        assert d["device_launches"] == len(ec_queue._windows(total))
        assert d["finish_thread"] == 4
        await q.stop()
    asyncio.run(run())
    assert sorted(seen) == sorted([(2, 300), (2, 5000), (2, 9000),
                                   (2, 17)])


def test_an_empty_request_comes_back_empty_alone_or_in_a_group(
        small_buckets):
    """An empty object's encode is a request of 0 lanes: alone it
    launches nothing, beside others it takes no lane of their windows,
    and neither is a device failure."""
    async def run():
        q = _queue()
        mat = gf256.rs_vandermonde_matrix(2, 1)[2:]
        empty = np.zeros((2, 0), np.uint8)
        out = await q.apply(mat, empty)
        assert out.shape == (1, 0)
        assert q.perf.dump()["device_launches"] == 0
        c = np.arange(2 * 300, dtype=np.uint8).reshape(2, 300)
        outs = await asyncio.gather(q.apply(mat, empty), q.apply(mat, c))
        assert outs[0].shape == (1, 0)
        assert np.array_equal(outs[1], gf256.host_apply(mat, c))
        d = q.perf.dump()
        assert d["device_fallbacks"] == 0 and d["host_requests"] == 0
        assert d["device_requests"] == 3
        assert d["device_lanes_launched"] == 1024       # [1024]
        await q.stop()
    asyncio.run(run())


def test_the_kept_windows_are_bounded_and_a_reused_tail_is_zeroed(
        small_buckets, monkeypatch):
    """The queue keeps at most WINDOW_POOL_BYTES of window buffers: a
    wider group makes the rest for itself alone.  A kept buffer that a
    narrower group reuses reaches the device with zeros past that
    group's last lane, not the bytes an earlier group left there."""
    import jax
    monkeypatch.setattr(ec_queue, "WINDOW_POOL_BYTES", 2 * 2 * 4096)
    staged = []
    put = jax.device_put

    def spy(x, *args, **kwargs):
        staged.append(np.array(x))
        return put(x, *args, **kwargs)
    monkeypatch.setattr(jax, "device_put", spy)

    async def run():
        q = _queue()
        mat = gf256.rs_vandermonde_matrix(2, 1)[2:]
        rng = np.random.default_rng(5)
        wide = rng.integers(1, 256, (2, 3 * 4096 + 1000), dtype=np.uint8)
        assert np.array_equal(await q.apply(mat, wide),
                              gf256.host_apply(mat, wide))
        assert [b.shape for b in staged] == [(2, 4096)] * 3 + [(2, 1024)]
        # the first two windows fit the pool, the other two do not
        assert {key: len(pool) for key, pool in q._window_bufs.items()
                if pool} == {(2, 4096): 2}
        assert q._window_bytes == 2 * 2 * 4096
        narrow = rng.integers(1, 256, (2, 2000), dtype=np.uint8)
        assert np.array_equal(await q.apply(mat, narrow),
                              gf256.host_apply(mat, narrow))
        assert staged[-1].shape == (2, 4096)
        assert np.array_equal(staged[-1][:, :2000], narrow)
        assert not staged[-1][:, 2000:].any()
        assert q._window_bytes == 2 * 2 * 4096
        await q.stop()
    asyncio.run(run())


def test_the_seam_traces_each_window_s_transfer_and_launch(small_buckets):
    """seam_h2d and seam_launch are sibling sections once per window,
    seam_window the interval over the two, and seam_fold and seam_d2h
    once per group."""
    async def run():
        q = _queue()
        q.ctx.config.set("op_tracing", True)
        mat = gf256.rs_vandermonde_matrix(2, 1)[2:]
        rng = np.random.default_rng(3)
        c = rng.integers(0, 256, (2, 3 * 4096 + 10), dtype=np.uint8)
        assert np.array_equal(await q.apply(mat, c),
                              gf256.host_apply(mat, c))
        hist = q.ctx.tracer.hist.histograms()
        for name in ("seam_window", "seam_h2d", "seam_launch"):
            assert hist[name].count == 4, name
        assert hist["seam_window"].sum >= \
            hist["seam_h2d"].sum + hist["seam_launch"].sum
        assert hist["seam_fold"].count == 1 and hist["seam_d2h"].count == 1
        await q.stop()
    asyncio.run(run())


# ----------------------------------------------------- the served path
def _ctx_factory(**client):
    from ceph_tpu.qa.cluster import make_ctx

    def make(name):
        ctx = make_ctx(name)
        ctx.config.set("ms_local_delivery", True)
        ctx.config.set("osd_ec_batch_device", "force")
        ctx.config.set("osd_ec_batch_min_bytes", 0)
        if name.startswith("client"):
            for key, val in client.items():
                ctx.config.set(key, val)
        return ctx
    return make


def _stored_shards(cl, admin, pool, name):
    from ceph_tpu.client.objecter import ObjectLocator
    from ceph_tpu.store.types import CollectionId, ObjectId
    pool_id = admin.monc.osdmap.lookup_pool(pool)
    pgid, acting = admin.monc.osdmap.object_to_acting(
        name, ObjectLocator(pool_id))[:2]
    return [np.frombuffer(cl.osds[osd].store.read(
        CollectionId.pg(pool_id, pgid.seed, j),
        ObjectId(name, pool=pool_id)), np.uint8)
        for j, osd in enumerate(acting)]


def test_five_sizes_through_the_served_path_match_the_reference(
        small_buckets):
    """Write and read objects of five sizes, the largest 16 windows of
    the top bucket, on an EC k=2 m=1 pool through the client: every read
    is what the model says was written, and every stored shard is the
    object's chunk or the host kernel's parity of them."""
    from ceph_tpu.qa.cluster import Cluster
    from ceph_tpu.qa.rados_model import ObjectModel
    sizes = (512, 2048, 8192, 24576, 131072)

    async def run():
        cl = Cluster(ctx_factory=_ctx_factory())
        try:
            admin = await cl.start(4)
            await admin.pool_create("ecs", pg_num=4, pool_type="erasure",
                                    k=2, m=1)
            io = admin.open_ioctx("ecs")
            rng = np.random.default_rng(5)
            model = ObjectModel()
            blobs = {f"o{size}_{i}": rng.integers(
                0, 256, size, dtype=np.uint8).tobytes()
                for size in sizes for i in range(3)}
            await asyncio.gather(*[io.write_full(n, b)
                                   for n, b in blobs.items()])
            for n, b in blobs.items():
                model.committed(n, b)
            # overwrite one of each size: the last acked write holds
            for size in sizes:
                n = f"o{size}_0"
                blobs[n] = rng.integers(0, 256, size,
                                        dtype=np.uint8).tobytes()
            await asyncio.gather(*[io.write_full(f"o{s}_0", blobs[
                f"o{s}_0"]) for s in sizes])
            for size in sizes:
                model.committed(f"o{size}_0", blobs[f"o{size}_0"])
            gen = gf256.rs_vandermonde_matrix(2, 1)
            for n, b in blobs.items():
                got = await io.read(n, length=len(b))
                assert model.check(n, got), n
                chunks = np.frombuffer(b, np.uint8).reshape(2, -1)
                want = [chunks[0], chunks[1],
                        gf256.host_apply(gen[2:], chunks)[0]]
                shards = _stored_shards(cl, admin, "ecs", n)
                assert len(shards) == 3
                for j in range(3):
                    assert np.array_equal(shards[j], want[j]), (n, j)
            seam = [o.ec_queue.perf.dump() for o in cl.osds.values()]
            assert sum(d["host_bytes"] for d in seam) == 0
            # every object written once, one of each size twice
            assert sum(d["device_bytes"] for d in seam) == sum(
                len(b) for b in blobs.values()) + sum(sizes)
            launched = sum(d["device_lanes_launched"] for d in seam)
            assert launched - sum(d["device_pad_lanes"] for d in seam) \
                == sum(d["device_bytes"] for d in seam) // 2
            # the largest object alone is 16 top-bucket windows
            assert max(d["device_launches"] for d in seam) >= 16
        finally:
            await cl.stop()
    asyncio.run(run())


def test_two_writes_over_the_byte_budget_both_ack_the_second_waits():
    """objecter_inflight_op_bytes 96k: two writes of 64 KiB sent in one
    step do not fit together; the second waits for the first's budget
    (one wait), both are acked, and both read back."""
    from ceph_tpu.qa.cluster import Cluster

    async def run():
        cl = Cluster(ctx_factory=_ctx_factory(
            objecter_inflight_op_bytes="96k"))
        try:
            admin = await cl.start(3)
            await admin.pool_create("ecs", pg_num=4, pool_type="erasure",
                                    k=2, m=1)
            io = admin.open_ioctx("ecs")
            await io.write_full("warm", b"x" * 256)
            ob = admin.objecter
            waits = ob.budget_stats()["throttle_waits"]
            rng = np.random.default_rng(9)
            a, b = (rng.integers(0, 256, 65536, dtype=np.uint8).tobytes()
                    for _ in range(2))
            await asyncio.gather(io.write_full("a", a),
                                 io.write_full("b", b))
            assert ob.budget_stats()["throttle_waits"] - waits == 1
            assert ob.budget_stats()["inflight_bytes"] == 0
            assert await io.read("a", length=65536) == a
            assert await io.read("b", length=65536) == b
            assert ob.budget_stats()["throttle_waits"] - waits == 1
        finally:
            await cl.stop()
    asyncio.run(run())

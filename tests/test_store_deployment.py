"""The durable deployment on the normal path: `Cluster` starts each OSD
on the store its configuration states (objectstore, objectstore_path);
an EC pool on BlockStore under the sharded data plane holds what the
plain reference says, served and on disk, and so do the files alone
when every OSD is abandoned without umount; the threaded commit
group's spans tile a transaction's wait for durability; and a
read-only mount leaves every byte of the directory as it was.

Toy sizes, a `tmp_path` store each, every cluster under a time limit of
its own (asyncio.wait_for)."""

import asyncio
import hashlib
import os
import pathlib
import shutil
import threading
import time
import types

import numpy as np
import pytest

from benchmark import reference
from ceph_tpu.common.context import Context
from ceph_tpu.qa.cluster import Cluster, make_ctx
from ceph_tpu.store.blockstore import BlockStore
from ceph_tpu.store.memstore import MemStore
from ceph_tpu.store.objectstore import ObjectStore, StoreError, Transaction
from ceph_tpu.store.types import CollectionId, ObjectId

LIMIT_S = 120.0


def ctx_factory(**over):
    def make(name):
        ctx = make_ctx(name)
        for key, val in over.items():
            ctx.config.set(key, val)
        return ctx
    return make


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, LIMIT_S))


def tree_digest(root) -> dict:
    """{relative path: sha1 of its bytes} over every file under root."""
    root = pathlib.Path(root)
    return {str(p.relative_to(root)): hashlib.sha1(
        p.read_bytes()).hexdigest()
        for p in sorted(root.rglob("*")) if p.is_file()}


# ------------------------------------------------ (i) the normal path
def test_cluster_deploys_the_configured_store_at_the_configured_path(
        tmp_path):
    async def go():
        cl = Cluster(ctx_factory=ctx_factory(
            objectstore="blockstore", objectstore_path=str(tmp_path),
            osd_op_num_shards=4))
        admin = await cl.start(3)
        try:
            for i, osd in cl.osds.items():
                assert type(osd.store) is BlockStore
                assert osd.store.path == str(tmp_path / f"osd.{i}")
                assert (tmp_path / f"osd.{i}" / "block").is_file()
                # a store with barriers is not asked to ack on apply:
                # it commits on its thread, named by the daemon's tracer
                assert osd.store.barriers == ("data", "kv")
                assert osd.store.ack_on_apply is False
                assert osd.store.tracer is osd.ctx.tracer
            await admin.pool_create("p", pg_num=4)
            io = admin.open_ioctx("p")
            await io.write_full("a", b"durable" * 100)
            assert await io.read("a") == b"durable" * 100
            for osd in cl.osds.values():
                c = osd.store.commit_counters()
                assert c["kv_syncs"] == c["commit_batches"] > 0
                assert c["data_fsyncs"] == c["data_groups"]
                assert c["acks_before_commit"] == 0
        finally:
            await cl.stop()
        # an absolute path is the deployment's own: left as it stands
        assert (tmp_path / "osd.0" / "block").is_file()
    run(go())


def test_relative_path_is_a_directory_of_this_process_removed_at_stop(
        tmp_path, monkeypatch):
    import tempfile
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    own = tmp_path / f"stores.{os.getpid()}"

    async def go():
        cl = Cluster(ctx_factory=ctx_factory(
            objectstore="blockstore", objectstore_path="stores"))
        await cl.start(2)
        try:
            for i, osd in cl.osds.items():
                assert osd.store.path == str(own / f"osd.{i}")
                assert (own / f"osd.{i}" / "block").is_file()
        finally:
            await cl.stop()
        assert not own.exists() and not list(tmp_path.iterdir())
    run(go())


def test_cluster_defaults_to_memstore_and_acks_on_apply():
    async def go():
        cl = Cluster(ctx_factory=ctx_factory(osd_op_num_shards=4))
        await cl.start(2)
        try:
            for osd in cl.osds.values():
                assert type(osd.store) is MemStore
                assert osd.store.barriers == ()
                assert osd.store.ack_on_apply is True
        finally:
            await cl.stop()
    run(go())


def test_ec_full_write_is_adopted_by_every_shard_store():
    """A 4 MiB `write_full` on k=4 m=2 over MemStore: each of the six
    shard OSDs keeps its 1 MiB shard by reference (no copy on write),
    and the read back is served from those same buffers."""
    names = ("adopted_writes", "adopted_bytes", "cow_copies",
             "reads_by_reference")

    def counts(cl):
        return {i: {k: osd.store.commit_counters()[k] for k in names}
                for i, osd in cl.osds.items()}

    async def go():
        cl = Cluster(ctx_factory=ctx_factory(osd_op_num_shards=4))
        admin = await cl.start(6)
        try:
            await admin.pool_create("ecpool", pg_num=4,
                                    pool_type="erasure", k=4, m=2)
            io = admin.open_ioctx("ecpool")
            payload = np.random.default_rng(29).integers(
                0, 256, 4 << 20, dtype=np.uint8).tobytes()
            base = counts(cl)
            await io.write_full("obj", payload)
            wrote = counts(cl)
            for i in cl.osds:
                assert type(cl.osds[i].store) is MemStore
                assert wrote[i]["adopted_writes"] > base[i]["adopted_writes"]
                assert wrote[i]["adopted_bytes"] \
                    - base[i]["adopted_bytes"] >= 1 << 20
                assert wrote[i]["cow_copies"] == base[i]["cow_copies"]
            assert await io.read("obj") == payload
            read = counts(cl)
            served = sum(read[i]["reads_by_reference"]
                         - wrote[i]["reads_by_reference"] for i in cl.osds)
            assert served >= 4          # k shards make the object
            assert all(read[i]["cow_copies"] == base[i]["cow_copies"]
                       for i in cl.osds)
        finally:
            await cl.stop()
    run(go())


def test_cluster_start_fails_loudly_without_a_directory():
    async def go():
        cl = Cluster(ctx_factory=ctx_factory(objectstore="blockstore"))
        try:
            with pytest.raises(StoreError, match="objectstore_path"):
                await cl.start(1)
        finally:
            await cl.stop()
    run(go())


def test_fresh_start_refuses_a_directory_that_no_store_made(tmp_path):
    (tmp_path / "osd.0").mkdir()
    (tmp_path / "osd.0" / "thesis.tex").write_bytes(b"not a store's")

    async def go():
        cl = Cluster(ctx_factory=ctx_factory(
            objectstore="blockstore", objectstore_path=str(tmp_path)))
        try:
            with pytest.raises(StoreError, match="not wiped"):
                await cl.start(1)
        finally:
            await cl.stop()
    run(go())
    assert (tmp_path / "osd.0" / "thesis.tex").read_bytes() \
        == b"not a store's"
    # an empty directory, or none, is nobody's: mkfs may have it
    empty = BlockStore(str(tmp_path / "osd.1"))
    (tmp_path / "osd.1").mkdir()
    empty.wipe()
    empty.wipe()
    assert not (tmp_path / "osd.1").exists() and not empty.made()


def test_cluster_fresh_start_wipes_a_stale_directory(tmp_path):
    stale = BlockStore(str(tmp_path / "osd.0"))
    stale.mkfs()
    stale.mount()
    cid = CollectionId("stale_head")
    stale.apply_transaction(Transaction().create_collection(cid).write(
        cid, ObjectId("old"), 0, b"x" * 8192))
    stale.umount()
    (tmp_path / "osd.0" / "junk").write_bytes(b"left by another run")

    async def go():
        cl = Cluster(ctx_factory=ctx_factory(
            objectstore="blockstore", objectstore_path=str(tmp_path)))
        await cl.start(1)
        try:
            store = cl.osds[0].store
            assert not (tmp_path / "osd.0" / "junk").exists()
            assert not store.collection_exists(cid)
        finally:
            await cl.stop()
    run(go())


@pytest.mark.parametrize("kind", ["memstore", "blockstore", "filestore",
                                  "kstore"])
def test_daemon_and_cluster_share_one_layout(kind, tmp_path):
    """tools/daemons.py and qa/cluster.py both go through for_osd."""
    cfg = Context("osd.7").config
    cfg.set("objectstore", kind)
    store = ObjectStore.for_osd(cfg, str(tmp_path), 7)
    assert type(store) is type(ObjectStore.create(kind, str(tmp_path)))
    assert store.path == ("" if kind == "memstore"
                          else str(tmp_path / "osd.7"))
    assert not list(tmp_path.iterdir())         # neither mkfs nor mount
    assert not store.made()
    # a daemon process has to find its objects after a restart
    durable = ObjectStore.for_osd(cfg, str(tmp_path), 7, durable=True)
    assert type(durable).__name__.lower() == (
        "filestore" if kind == "memstore" else kind)
    assert durable.path == str(tmp_path / "osd.7")
    durable.mkfs()
    assert durable.made()
    if kind != "memstore":
        with pytest.raises(StoreError, match="needs a directory"):
            ObjectStore.for_osd(cfg, "", 7)


# ------------------------- (ii) an EC pool on BlockStore, then the files
K, M, SIZE, N_OBJ = 2, 1, 64 * 1024, 12


def stored_shards(stores: dict, osdmap, pool_id: int, name: str):
    from ceph_tpu.client.objecter import ObjectLocator
    pgid, acting = osdmap.object_to_acting(
        name, ObjectLocator(pool_id))[:2]
    return [np.frombuffer(stores[osd_id].read(
        CollectionId.pg(pool_id, pgid.seed, j),
        ObjectId(name, pool=pool_id)), np.uint8)
        for j, osd_id in enumerate(acting)]


def test_ec_pool_on_blockstore_matches_the_reference_served_and_on_disk(
        tmp_path):
    pays = reference.payloads(2_600_000_001, 5, SIZE)
    rng = np.random.default_rng(26)

    async def go():
        cl = Cluster(ctx_factory=ctx_factory(
            objectstore="blockstore",
            objectstore_path=str(tmp_path / "live"),
            osd_op_num_shards=4))
        admin = await cl.start(K + M)
        try:
            await admin.pool_create("ec", pg_num=4, pool_type="erasure",
                                    k=K, m=M)
            io = admin.open_ioctx("ec")
            pool_id = admin.monc.osdmap.lookup_pool("ec")
            holds = {}
            # seeded writes, then two rounds of seeded overwrites
            for _round in range(3):
                picks = {f"obj{i}": int(rng.integers(len(pays)))
                         for i in range(N_OBJ)}
                await asyncio.gather(*[io.write_full(n, pays[p])
                                       for n, p in picks.items()])
                holds.update(picks)
            stores = {i: o.store for i, o in cl.osds.items()}
            omap = admin.monc.osdmap
            for name, p in holds.items():
                assert await io.read(name, length=SIZE) == pays[p], name
                want = reference.shards(pays[p], K, M)
                got = stored_shards(stores, omap, pool_id, name)
                assert len(got) == K + M
                for j in range(K + M):
                    assert np.array_equal(got[j], want[j]), (name, j)
            # every OSD abandoned without umount: its files as they are
            # now (no flush, no compaction), mounted read-only elsewhere
            left = {}
            for i, st in stores.items():
                dst = tmp_path / "abandoned" / f"osd.{i}"
                st.db.copy_files(str(dst / "db"))
                shutil.copyfile(os.path.join(st.path, "block"),
                                dst / "block")
                left[i] = BlockStore(str(dst))
                left[i].mount_read_only()
            for name, p in holds.items():
                want = reference.shards(pays[p], K, M)
                got = stored_shards(left, omap, pool_id, name)
                for j in range(K + M):
                    assert np.array_equal(got[j], want[j]), (name, j)
            for st in left.values():
                st.umount()
        finally:
            await cl.stop()
    run(go())


@pytest.mark.parametrize("d_off,d_end", [
    (0, 0), (-1, 0), (1, 0), (0, -1), (0, 1), (-1, 1), (1, -1)])
def test_overwrite_at_an_extents_edges_keeps_the_bytes_around_it(
        tmp_path, d_off, d_end):
    """An overwrite that covers a stored extent exactly, or misses or
    passes either edge by one allocation block: COW merges what
    survives of the old extents with the new bytes, and reads only the
    extents of which something survives."""
    from ceph_tpu.store.blockstore import MIN_ALLOC
    store = BlockStore(str(tmp_path / "s"))
    store.mkfs()
    store.mount()
    cid, oid = CollectionId("e_head"), ObjectId("o")
    blk = MIN_ALLOC
    rng = np.random.default_rng(7)
    parts = [rng.integers(0, 256, 4 * blk, np.uint8).tobytes()
             for _ in range(3)]
    try:
        txn = Transaction().create_collection(cid)
        for i, part in enumerate(parts):    # three extents of 4 blocks
            txn.write(cid, oid, i * 4 * blk, part)
        store.apply_transaction(txn)
        off = 4 * blk + d_off * blk
        end = 8 * blk + d_end * blk
        new = rng.integers(0, 256, end - off, np.uint8).tobytes()
        # an extent the write covers whole has no byte that survives,
        # and is not read; one it cuts is read (and its checksum held)
        read, real = [], store._pread_checked
        store._pread_checked = lambda ext: (
            read.append(ext.logical // (4 * blk)), real(ext))[1]
        store.apply_transaction(Transaction().write(cid, oid, off, new))
        del store._pread_checked
        cut = [i for i in range(3)
               if off < (i + 1) * 4 * blk and end > i * 4 * blk
               and not (off <= i * 4 * blk and end >= (i + 1) * 4 * blk)]
        assert sorted(read) == cut, (read, cut)
        want = bytearray(b"".join(parts))
        want[off:end] = new
        assert store.read(cid, oid) == bytes(want)
        # and a hole punched over the same range
        store.apply_transaction(Transaction().zero(
            cid, oid, off, end - off))
        want[off:end] = bytes(end - off)
        assert store.read(cid, oid) == bytes(want)
    finally:
        store.umount()


@pytest.mark.parametrize("compression", ["", "zlib"])
def test_reads_see_writes_whose_data_is_still_staged(tmp_path, compression):
    """With the kv-sync thread held at its gate nothing a transaction
    wrote is in the file: a read, the read-modify-write of a cut
    extent, a clone and a range clone are served from the in-flight
    table, a failed batch leaves nothing behind, a caller's buffer is
    the caller's again once the call has returned; and once the thread
    has run, the table is empty and the file answers the same."""
    from ceph_tpu.store.blockstore import MIN_ALLOC
    from ceph_tpu.store.objectstore import OP_WRITE, TxOp
    store = BlockStore(str(tmp_path / "s"), compression=compression)
    store.mkfs()
    store.mount()
    com = store._committer
    cid = CollectionId("e_head")
    oid, twin, part, buf_oid = (ObjectId(n) for n in (
        "o", "twin", "part", "buf"))
    blk = MIN_ALLOC
    rng = np.random.default_rng(3)

    def payload(n_blocks):      # compressible, and no two alike
        return np.repeat(rng.integers(0, 256, n_blocks * blk // 16,
                                      np.uint8), 16).tobytes()

    def hits():
        return store.commit_counters()["inflight_read_hits"]
    try:
        store.apply_transaction(Transaction().create_collection(cid)
                                .write(cid, oid, 0, payload(8)))
        assert not store._pending and not store._inflight
        assert hits() == 0
        com.gate = threading.Event()            # the thread is held
        want = {oid: payload(8)}
        store.queue_transactions([Transaction().write(
            cid, oid, 0, want[oid])])
        staged = len(store._pending)
        assert staged >= 1 and len(store._inflight) == staged
        if compression:     # what is staged is what the file will hold
            ext = store._get_onode(cid, oid).extents[0]
            assert ext.alg == compression and ext.disk_len < ext.length
        h = hits()
        assert store.read(cid, oid) == want[oid]
        assert hits() > h
        # a cut extent: its surviving bytes come from the table
        h, cut = hits(), payload(2)
        store.queue_transactions([Transaction().write(
            cid, oid, 3 * blk, cut)])
        want[oid] = want[oid][:3 * blk] + cut + want[oid][5 * blk:]
        assert hits() > h
        assert store.read(cid, oid) == want[oid]
        # clone and range clone, the second in the batch that wrote
        # its source
        h = hits()
        store.queue_transactions([Transaction().clone(cid, oid, twin)])
        want[twin] = want[oid]
        fresh = payload(4)
        store.queue_transactions([Transaction().write(
            cid, buf_oid, 0, fresh).clone_range(
                cid, buf_oid, part, blk, 2 * blk, 0)])
        want[buf_oid], want[part] = fresh, fresh[blk:3 * blk]
        assert hits() > h
        # a batch that fails takes its records and its entries back
        before = (len(store._pending), set(store._inflight))
        with pytest.raises(StoreError):
            store.queue_transactions([Transaction().write(
                cid, oid, 0, payload(4)).write(
                    CollectionId("missing"), oid, 0, b"x")])
        assert (len(store._pending), set(store._inflight)) == before
        # a buffer the caller may still change is copied, once
        buf = bytearray(payload(4))
        want[buf_oid] = bytes(buf)
        txn = Transaction()
        txn.ops.append(TxOp(OP_WRITE, cid, buf_oid, off=0,
                            length=len(buf), data=buf))
        store.queue_transactions([txn])
        buf[:] = bytes(len(buf))
        for name, data in want.items():
            assert store.read(cid, name) == data, name
        assert store.commit_counters()["deferred_writes"] == 1
        com.gate.set()
        com.gate = None
        store.sync()
        c = store.commit_counters()
        assert not store._pending and not store._inflight
        assert c["writes_after_data_sync"] == 0
        assert c["deferred_bytes"] >= (1 if compression else 8 * blk)
        h = hits()
        for name, data in want.items():         # now from the file
            assert store.read(cid, name) == data, name
        assert hits() == h
        store.umount()
        store.mount()
        for name, data in want.items():
            assert store.read(cid, name) == data, name
    finally:
        store.umount()


# ------------------------------------ (iii) the threaded group's spans
class SlowBarrierStore(BlockStore):
    """A write-out and barriers long enough that the gaps between spans
    do not count."""

    def _write_pending(self):
        time.sleep(0.002)
        return super()._write_pending()

    def _fsync_block(self):
        time.sleep(0.004)
        super()._fsync_block()


def _traced_store(path, op_tracing: bool):
    ctx = Context("osd.0")
    ctx.config.set("op_tracing", op_tracing)
    store = SlowBarrierStore(str(path))
    store.tracer = ctx.tracer
    store.mkfs()
    store.mount()
    real_kv = store._committer.kv_sync

    def slow_kv(upto):
        time.sleep(0.003)
        return real_kv(upto)
    store._committer.kv_sync = slow_kv
    return ctx, store


def _stage(ctx, name):
    h = ctx.tracer.hist.histograms().get(name)
    return (h.count, h.sum) if h is not None else (0, 0.0)


def test_store_stages_tile_a_transactions_wait_for_durability(tmp_path):
    ctx, store = _traced_store(tmp_path / "s", True)
    com = store._committer
    cid = CollectionId("t_head")
    store.apply_transaction(Transaction().create_collection(cid))
    n_txn, rounds = 5, 3
    before = {s: _stage(ctx, s) for s in (
        "store_commit_wait", "store_data_write", "store_data_sync",
        "store_kv_sync", "store_resume")}
    queue_s = 0.0

    async def one_round(r):
        nonlocal queue_s
        # the gate holds the thread: the pass's cork is ONE group
        com.gate = threading.Event()
        done, t_sub = [], []
        for i in range(n_txn):
            store.queue_transactions(
                [Transaction().write(cid, ObjectId(f"o{r}_{i}"), 0,
                                     b"d" * 8192)],
                on_commit=lambda: done.append(time.monotonic()))
            t_sub.append(time.monotonic())
        await asyncio.sleep(0.01)           # the cork ships; thread held
        t_open = time.monotonic()
        com.gate.set()
        while len(done) < n_txn:
            await asyncio.sleep(0.001)
        queue_s += sum(t_open - t for t in t_sub)

    async def go():
        for r in range(rounds):
            await one_round(r)
    try:
        run(go())
        d = {s: (_stage(ctx, s)[0] - before[s][0],
                 _stage(ctx, s)[1] - before[s][1]) for s in before}
        n = n_txn * rounds
        assert d["store_commit_wait"][0] == d["store_resume"][0] == n
        assert d["store_data_write"][0] == d["store_data_sync"][0] \
            == d["store_kv_sync"][0] == rounds
        # per transaction: wait for the thread (the gate: no gather
        # here, the group was whole when taken) + its group's three
        # sections + the wait for the loop
        tiled = queue_s + n_txn * (d["store_data_write"][1]
                                   + d["store_data_sync"][1]
                                   + d["store_kv_sync"][1]) \
            + d["store_resume"][1]
        whole = d["store_commit_wait"][1]
        assert d["store_data_write"][1] >= rounds * 0.002
        assert d["store_data_sync"][1] >= rounds * 0.004
        assert d["store_kv_sync"][1] >= rounds * 0.003
        assert abs(tiled - whole) <= 0.05 * whole, (tiled, whole, d)
    finally:
        store.umount()


def test_with_tracing_off_the_committers_spans_read_no_clock(
        tmp_path, monkeypatch):
    from ceph_tpu.common import tracer as tracer_mod
    ctx, store = _traced_store(tmp_path / "s", False)

    def boom():
        raise AssertionError("a clock was read with op_tracing off")
    monkeypatch.setattr(tracer_mod, "time", types.SimpleNamespace(
        monotonic=boom, thread_time=boom))
    cid = CollectionId("t_head")
    done = []

    async def go():
        store.queue_transactions(
            [Transaction().create_collection(cid).write(
                cid, ObjectId("o"), 0, b"d" * 8192)],
            on_commit=lambda: done.append(1))
        while not done:
            await asyncio.sleep(0.001)
    try:
        run(go())
        assert store._committer.tracer is ctx.tracer   # asked, and off
        c = store.commit_counters()
        assert c["kv_syncs"] >= 1
        assert c["deferred_writes"] >= 1        # the write-out ran too
        assert ctx.tracer._hist is None                 # nothing recorded
    finally:
        store.umount()


def test_the_write_out_is_an_annotation_on_the_kv_sync_thread(
        tmp_path, monkeypatch):
    """store_data_write is a section: the profiler's trace holds it by
    name on the thread that ran it, the store's kv-sync thread, ahead
    of the group's two barriers."""
    from ceph_tpu.common import tracer as tracer_mod
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append((self.name, threading.current_thread().name))

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(tracer_mod, "_annotation_cls", Annotation)
    ctx, store = _traced_store(tmp_path / "s", True)
    try:
        seen.clear()
        store.apply_transaction(
            Transaction().create_collection(CollectionId("t_head")).write(
                CollectionId("t_head"), ObjectId("o"), 0, b"d" * 8192))
        assert seen == [(name, "kv_sync_thread") for name in (
            "store_data_write", "store_data_sync", "store_kv_sync")]
        assert threading.current_thread().name != "kv_sync_thread"
        assert "store_data_write" in tracer_mod.AUX_STAGES
        assert _stage(ctx, "store_data_write")[0] >= 1
    finally:
        store.umount()


def test_inline_commit_groups_record_no_store_span(tmp_path):
    """The path the MemStore cells take: one section, loop_store_commit,
    and none of the threaded group's spans."""
    ctx = Context("osd.0")
    ctx.config.set("op_tracing", True)
    store = MemStore()
    store.tracer, store.ack_on_apply = ctx.tracer, True
    store.mkfs()
    store.mount()
    cid = CollectionId("t_head")
    done = []

    async def go():
        store.queue_transactions(
            [Transaction().create_collection(cid)],
            on_commit=lambda: done.append(1))
        while not done:
            await asyncio.sleep(0.001)
    try:
        run(go())
        stages = set(ctx.tracer.hist.histograms())
        assert "loop_store_commit" in stages
        assert not {s for s in stages if s.startswith("store_")}
    finally:
        store.umount()


def test_commit_thread_counts_acks_posted_before_their_barriers():
    """The order is counted where it happens: a group whose completion
    records leave before its barriers have returned, and a group of
    data-writing transactions that issues no data barrier."""
    from ceph_tpu.store.commit import KVSyncThread

    class EarlyAck(KVSyncThread):
        def _commit(self, group):
            self._complete(group)       # the records leave first
            super()._commit(group)

    for cls, early in ((KVSyncThread, 0), (EarlyAck, 5)):
        acked, synced = [], []
        com = cls("t_order", data_sync=lambda: synced.append("data"),
                  kv_sync=lambda upto: synced.append("kv"))
        com.start()
        for i in range(5):
            com.submit(seq=i + 1, wrote_data=True,
                       on_commit=lambda i=i: acked.append(i))
        com.flush()
        com.stop()
        c = com.counters()
        assert acked == list(range(5))      # each acked once, in order
        assert c["acks_before_commit"] == early
        assert c["data_groups"] == c["data_fsyncs"] == c["kv_syncs"] \
            == c["commit_batches"] > 0
    no_barrier = KVSyncThread("t_nodata", kv_sync=lambda upto: None)
    no_barrier.start()
    no_barrier.submit(seq=1, wrote_data=True)
    no_barrier.flush()
    no_barrier.stop()
    c = no_barrier.counters()
    assert (c["data_groups"], c["data_fsyncs"]) == (1, 0)


# --------------------------------------------- (iv) the read-only mount
@pytest.fixture
def abandoned(tmp_path):
    """A store's directory as a crash left it: WAL records not yet
    compacted, and a torn tail after them."""
    live = BlockStore(str(tmp_path / "live"))
    live.mkfs()
    live.mount()
    cid = CollectionId("1.0_head")
    txn = Transaction().create_collection(cid)
    for i in range(4):
        txn.write(cid, ObjectId(f"o{i}", pool=1), 0, bytes([i]) * 10000)
    txn.omap_setkeys(cid, ObjectId("o0", pool=1), {b"k": b"v"})
    live.apply_transaction(txn)
    dst = tmp_path / "abandoned"
    shutil.copytree(tmp_path / "live", dst)     # no umount
    live.umount()
    with open(dst / "db" / "wal", "ab") as f:
        f.write(b"\x01\x02\x03 torn")
    return dst, cid


def test_read_only_mount_leaves_every_byte_as_it_was(abandoned):
    dst, cid = abandoned
    before = tree_digest(dst)
    assert os.path.getsize(dst / "db" / "wal") > 0
    ro = BlockStore(str(dst))
    ro.mount_read_only()
    try:
        assert sorted(o.name for o in ro.collection_list(cid)) == [
            "o0", "o1", "o2", "o3"]
        for i in range(4):
            assert ro.read(cid, ObjectId(f"o{i}", pool=1)) \
                == bytes([i]) * 10000
        assert ro.omap_get(cid, ObjectId("o0", pool=1))[1] == {b"k": b"v"}
        assert ro.statfs()["used"] >= 4 * 8192
        with pytest.raises(StoreError, match="read-only"):
            ro.queue_transactions([Transaction().touch(
                cid, ObjectId("new", pool=1))])
        with pytest.raises(StoreError, match="already mounted"):
            ro.mount_read_only()
    finally:
        ro.umount()
    assert tree_digest(dst) == before
    # the read-write mount is what repairs: it truncates the torn tail
    rw = BlockStore(str(dst))
    rw.mount()
    rw.umount()
    assert tree_digest(dst) != before


@pytest.mark.parametrize("op", ["list", "list-pgs", "statfs", "info"])
def test_objectstore_tool_reads_without_writing(abandoned, op, capsys):
    from ceph_tpu.tools import objectstore_tool
    dst, _cid = abandoned
    before = tree_digest(dst)
    argv = ["--data-path", str(dst), "--op", op]
    if op == "info":
        argv += ["--pgid", "1.0", "--object", "o1"]
    assert objectstore_tool.main(argv) == 0
    assert capsys.readouterr().out.strip()
    assert tree_digest(dst) == before

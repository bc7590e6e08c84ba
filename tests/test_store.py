"""Store layer tests: KeyValueDB, Transaction codec, MemStore, FileStore.

Models the reference's store test strategy (test/objectstore/store_test.cc —
value-parameterized over backends) plus journal replay/crash tests
(DeterministicOpSequence / run_seed_to.sh analog).
"""

import array
import collections
import os

import numpy as np

import pytest

from ceph_tpu.store import (
    CollectionId, FileDB, FileStore, MemDB, MemStore, NoSuchCollection,
    NoSuchObject, ObjectId, ObjectStore, Transaction,
)
from ceph_tpu.crush.hashfn import ceph_str_hash_rjenkins


# ---------------------------------------------------------------- kv layer

@pytest.fixture(params=["mem", "file"])
def kvdb(request, tmp_path):
    if request.param == "mem":
        db = MemDB()
    else:
        db = FileDB(str(tmp_path / "kv"))
    yield db
    db.close()


def test_kv_basic(kvdb):
    t = kvdb.create_transaction()
    t.set("p", "a", b"1").set("p", "b", b"2").set("q", "a", b"3")
    kvdb.submit(t)
    assert kvdb.get("p", "a") == b"1"
    assert kvdb.get("q", "a") == b"3"
    assert kvdb.get("p", "zzz") is None
    assert [k for k, _ in kvdb.iterate("p")] == [b"a", b"b"]

    t2 = kvdb.create_transaction().rmkey("p", "a")
    kvdb.submit(t2)
    assert kvdb.get("p", "a") is None

    kvdb.submit(kvdb.create_transaction().rmkeys_by_prefix("p"))
    assert kvdb.keys("p") == []
    assert kvdb.get("q", "a") == b"3"


def test_kv_iterate_range(kvdb):
    t = kvdb.create_transaction()
    for i in range(10):
        t.set("x", f"k{i}", str(i).encode())
    kvdb.submit(t)
    got = [k for k, _ in kvdb.iterate("x", start=b"k3", end=b"k7")]
    assert got == [b"k3", b"k4", b"k5", b"k6"]


def test_filedb_replay(tmp_path):
    path = str(tmp_path / "kv")
    db = FileDB(path)
    db.submit(db.create_transaction().set("p", "a", b"1"))
    db.submit(db.create_transaction().set("p", "b", b"2"))
    # simulate crash: do NOT close/compact
    db._wal.close()
    db2 = FileDB(path)
    assert db2.get("p", "a") == b"1"
    assert db2.get("p", "b") == b"2"
    db2.close()
    # clean reopen after compact
    db3 = FileDB(path)
    assert db3.get("p", "b") == b"2"
    db3.close()


def test_filedb_torn_tail(tmp_path):
    path = str(tmp_path / "kv")
    db = FileDB(path)
    db.submit(db.create_transaction().set("p", "a", b"1"))
    db._wal.close()
    with open(os.path.join(path, "wal"), "ab") as f:
        f.write(b"\x01\x02garbage-torn-record")
    db2 = FileDB(path)
    assert db2.get("p", "a") == b"1"
    # regression: commits made AFTER torn-tail recovery must survive the
    # next replay (the tail must be truncated, not appended past)
    db2.submit(db2.create_transaction().set("p", "b", b"2"))
    db2._wal.close()
    db3 = FileDB(path)
    assert db3.get("p", "a") == b"1"
    assert db3.get("p", "b") == b"2"
    db3.close()


def test_filedb_reads_dont_block_on_group_fsync(tmp_path):
    """ISSUE 4 satellite (ROADMAP known hazard): the WAL group fsync on
    the commit thread must NOT hold the memory lock — event-loop reads
    (get/iterate) proceed for the whole barrier duration."""
    import threading
    import time as _time

    db = FileDB(str(tmp_path / "kv"))
    db.submit(db.create_transaction().set("p", "seed", b"v"))
    for i in range(8):
        db.submit_deferred(
            db.create_transaction().set("p", f"d{i}", str(i).encode()))

    entered, release = threading.Event(), threading.Event()
    orig = db._wal.append_many

    def slow_append(recs, sync=True):
        entered.set()
        assert release.wait(10), "test wedged: releaser never ran"
        orig(recs, sync=sync)

    db._wal.append_many = slow_append
    flusher = threading.Thread(target=db.log_deferred, args=(db.seq,))
    flusher.start()
    assert entered.wait(10)

    # the "fsync" is in flight and will stay stuck until `release`:
    # reads must complete NOW (they only need the memory lock)
    done = threading.Event()

    def reader():
        for _ in range(50):
            assert db.get("p", "seed") == b"v"
            assert db.get("p", "d0") == b"0"       # deferred: visible
            assert [k for k, _ in db.iterate("p", start=b"d")][0] == b"d0"
        done.set()

    r = threading.Thread(target=reader)
    r.start()
    assert done.wait(5.0), \
        "db.get/iterate stalled behind the WAL group fsync"
    release.set()
    flusher.join(10)
    r.join(5)
    db._wal.append_many = orig
    # durability unaffected: reopen sees every record
    db.close()
    db2 = FileDB(str(tmp_path / "kv"))
    assert db2.get("p", "d7") == b"7"
    db2.close()


def test_filedb_concurrent_submit_and_log_deferred(tmp_path):
    """Seq order on the WAL survives submit() racing log_deferred()
    across threads (the _io lock serializes appenders; _mu only guards
    memory)."""
    import threading

    db = FileDB(str(tmp_path / "kv"))
    stop = threading.Event()

    def committer():
        while not stop.is_set():
            db.log_deferred(db.seq)

    t = threading.Thread(target=committer)
    t.start()
    try:
        for i in range(200):
            if i % 3 == 0:
                db.submit(db.create_transaction()
                          .set("s", f"k{i:03d}", b"sync"))
            else:
                db.submit_deferred(db.create_transaction()
                                   .set("s", f"k{i:03d}", b"def"))
    finally:
        stop.set()
        t.join(10)
    db.close()
    db2 = FileDB(str(tmp_path / "kv"))
    assert len(db2.keys("s")) == 200
    db2.close()


def test_memdb_remove_prefix_high_bytes():
    # regression: keys whose suffix starts with many 0xff bytes must be
    # removed by rmkeys_by_prefix and must not desync the sorted index
    db = MemDB()
    hot = b"\xff" * 12
    db.submit(db.create_transaction().set("p", hot, b"v")
              .set("p", b"normal", b"n").set("q", b"other", b"o"))
    db.submit(db.create_transaction().rmkeys_by_prefix("p"))
    assert db.get("p", hot) is None
    assert db.keys("p") == []
    assert db.get("q", b"other") == b"o"
    assert [k for k, _ in db.iterate("q")] == [b"other"]


# ------------------------------------------------------------- object ids

def test_object_id_hash_matches_reference_rjenkins():
    # golden values from compiling /root/reference/src/common/ceph_hash.cc
    golden = {
        b"": 0xBD49D10D, b"foo": 0x7FC1F406, b"object_12345": 0x1632FBC1,
        b"aaaaaaaaaaa": 0x17A6E6E2, b"bbbbbbbbbbbb": 0xB15A9932,
        b"ccccccccccccccccccccccc": 0x39658A70,
        b"dddddddddddddddddddddddd": 0x11360A09,
        b"hello world this is long": 0xA83AA0EE,
    }
    for s, want in golden.items():
        assert ceph_str_hash_rjenkins(s) == want


def test_object_id_roundtrip_and_order():
    a = ObjectId("obj1", pool=3)
    b = ObjectId.from_bytes(a.to_bytes())
    assert a == b and hash(a) == hash(b)
    # locator key overrides name for placement
    c = ObjectId("other", key="obj1")
    assert c.hash32 == a.hash32
    ids = sorted([ObjectId(f"o{i}") for i in range(20)])
    assert ids == sorted(ids, key=lambda o: o.sort_key())


def test_collection_id():
    c = CollectionId.pg(3, 0x1A, shard=2)
    assert c.is_pg()
    assert CollectionId.from_bytes(c.to_bytes()) == c
    assert not CollectionId.meta().is_pg()


# ------------------------------------------------------------ transaction

def test_transaction_roundtrip():
    cid = CollectionId.pg(1, 0)
    oid = ObjectId("a", pool=1)
    t = Transaction()
    t.create_collection(cid)
    t.write(cid, oid, 0, b"hello")
    t.setattr(cid, oid, "_", b"oi")
    t.omap_setkeys(cid, oid, {b"k": b"v"})
    t.clone(cid, oid, oid.with_snap(4))
    t2 = Transaction.from_bytes(t.to_bytes())
    assert len(t2.ops) == 5
    assert [o.op for o in t2.ops] == [o.op for o in t.ops]
    assert t2.ops[1].data == b"hello"
    assert t2.ops[3].kv == {b"k": b"v"}
    assert t2.ops[4].oid2.snap == 4


# ------------------------------------------------------------- stores

@pytest.fixture(params=["memstore", "filestore", "blockstore",
                        "kstore"])
def store(request, tmp_path):
    s = ObjectStore.create(request.param, str(tmp_path / "store"))
    s.mkfs()
    s.mount()
    yield s
    s.umount()


CID = CollectionId.pg(1, 0)
OID = ObjectId("obj", pool=1)


def _mkcoll(s):
    t = Transaction().create_collection(CID)
    s.apply_transaction(t)


def test_store_write_read(store):
    _mkcoll(store)
    store.apply_transaction(Transaction().write(CID, OID, 0, b"hello world"))
    assert store.read(CID, OID) == b"hello world"
    assert store.read(CID, OID, 6, 5) == b"world"
    store.apply_transaction(Transaction().write(CID, OID, 6, b"there"))
    assert store.read(CID, OID) == b"hello there"
    # sparse write past EOF zero-fills
    store.apply_transaction(Transaction().write(CID, OID, 20, b"x"))
    assert store.read(CID, OID, 11, 9) == b"\x00" * 9
    assert store.stat(CID, OID)["size"] == 21


def test_store_zero_truncate_remove(store):
    _mkcoll(store)
    store.apply_transaction(Transaction().write(CID, OID, 0, b"abcdef"))
    store.apply_transaction(Transaction().zero(CID, OID, 1, 3))
    assert store.read(CID, OID) == b"a\x00\x00\x00ef"
    store.apply_transaction(Transaction().truncate(CID, OID, 2))
    assert store.read(CID, OID) == b"a\x00"
    store.apply_transaction(Transaction().remove(CID, OID))
    assert not store.exists(CID, OID)
    with pytest.raises(NoSuchObject):
        store.read(CID, OID)


def test_store_xattr_omap(store):
    _mkcoll(store)
    store.apply_transaction(
        Transaction().touch(CID, OID)
        .setattrs(CID, OID, {"_": b"meta", "snapset": b"ss"})
        .omap_setheader(CID, OID, b"hdr")
        .omap_setkeys(CID, OID, {b"a": b"1", b"b": b"2"}))
    assert store.getattr(CID, OID, "_") == b"meta"
    assert store.getattrs(CID, OID) == {"_": b"meta", "snapset": b"ss"}
    hdr, omap = store.omap_get(CID, OID)
    assert hdr == b"hdr" and omap == {b"a": b"1", b"b": b"2"}
    store.apply_transaction(Transaction().rmattr(CID, OID, "snapset")
                            .omap_rmkeys(CID, OID, [b"a"]))
    assert store.getattrs(CID, OID) == {"_": b"meta"}
    assert store.omap_get(CID, OID)[1] == {b"b": b"2"}
    assert store.omap_get_values(CID, OID, [b"b", b"zz"]) == {b"b": b"2"}


def test_store_clone_and_rename(store):
    _mkcoll(store)
    snap = OID.with_snap(5)
    store.apply_transaction(Transaction().write(CID, OID, 0, b"v1")
                            .clone(CID, OID, snap))
    store.apply_transaction(Transaction().write(CID, OID, 0, b"v2"))
    assert store.read(CID, snap) == b"v1"
    assert store.read(CID, OID) == b"v2"
    cid2 = CollectionId.pg(1, 1)
    store.apply_transaction(Transaction().create_collection(cid2)
                            .collection_move_rename(CID, OID, cid2, OID))
    assert store.read(cid2, OID) == b"v2"
    assert not store.exists(CID, OID)


def test_store_collections_and_listing(store):
    _mkcoll(store)
    oids = [ObjectId(f"o{i}", pool=1) for i in range(10)]
    t = Transaction()
    for o in oids:
        t.touch(CID, o)
    store.apply_transaction(t)
    listed = store.collection_list(CID)
    assert set(listed) == set(oids)
    assert listed == sorted(listed, key=lambda o: o.sort_key())
    # pagination resumes after cursor
    first = store.collection_list(CID, max_count=4)
    rest = store.collection_list(CID, start=first[-1])
    assert first + rest == listed
    with pytest.raises(NoSuchCollection):
        store.collection_list(CollectionId.pg(9, 9))


def test_store_callbacks_order(store):
    _mkcoll(store)
    events = []
    store.queue_transactions(
        [Transaction().write(CID, OID, 0, b"x")],
        on_applied=lambda: events.append("applied"),
        on_commit=lambda: events.append("commit"))
    # applied fires inline (state readable immediately); commit may ride
    # the group-commit thread — sync() drains it (and with no event loop
    # captured the callback runs on the commit thread before sync returns)
    assert events[0] == "applied"
    store.sync()
    assert events == ["applied", "commit"]


# ------------------------------------------------------- filestore replay

def test_filestore_crash_replay(tmp_path):
    path = str(tmp_path / "fs")
    s = FileStore(path)
    s.mkfs()
    s.mount()
    _mkcoll(s)
    s.apply_transaction(Transaction().write(CID, OID, 0, b"durable")
                        .omap_setkeys(CID, OID, {b"k": b"v"}))
    # crash: no umount/checkpoint
    s._wal.close()

    s2 = FileStore(path)
    s2.mount()
    assert s2.read(CID, OID) == b"durable"
    assert s2.omap_get(CID, OID)[1] == {b"k": b"v"}
    s2.apply_transaction(Transaction().write(CID, OID, 0, b"DURABLE"))
    s2.umount()  # clean: checkpoint + truncate wal

    s3 = FileStore(path)
    s3.mount()
    assert s3.read(CID, OID) == b"DURABLE"
    assert os.path.getsize(os.path.join(path, "wal")) == 0
    s3.umount()


def test_filestore_checkpoint_midstream(tmp_path):
    path = str(tmp_path / "fs")
    s = FileStore(path)
    s.mkfs()
    s.mount()
    _mkcoll(s)
    for i in range(5):
        s.apply_transaction(
            Transaction().write(CID, ObjectId(f"o{i}", pool=1), 0,
                                bytes([i]) * 100))
    s.checkpoint()
    s.apply_transaction(Transaction().write(CID, ObjectId("after", pool=1),
                                            0, b"post-ckpt"))
    s._wal.close()  # crash after checkpoint + one more txn
    s2 = FileStore(path)
    s2.mount()
    assert s2.read(CID, ObjectId("o3", pool=1)) == b"\x03" * 100
    assert s2.read(CID, ObjectId("after", pool=1)) == b"post-ckpt"
    s2.umount()


def test_store_apply_is_total(store):
    # regression: destructive ops on missing targets are no-ops; a journaled
    # transaction can never fail halfway through apply (poison WAL record)
    _mkcoll(store)
    missing = ObjectId("missing", pool=1)
    t = (Transaction().write(CID, OID, 0, b"x")
         .rmattr(CID, missing, "a").omap_rmkeys(CID, missing, [b"k"])
         .omap_clear(CID, missing).remove(CID, missing)
         .clone(CID, missing, ObjectId("c", pool=1))
         .remove(CollectionId.pg(9, 9), missing))
    store.apply_transaction(t)      # must not raise
    assert store.read(CID, OID) == b"x"
    assert not store.exists(CID, missing)


def test_filestore_no_poison_wal(tmp_path):
    # a txn containing destructive ops on missing targets must not prevent
    # future mounts (it is replayed from the WAL on mount)
    path = str(tmp_path / "fs")
    s = FileStore(path)
    s.mkfs()
    s.mount()
    _mkcoll(s)
    s.apply_transaction(Transaction().write(CID, OID, 0, b"ok")
                        .rmattr(CID, ObjectId("ghost", pool=1), "x"))
    s._wal.close()  # crash before checkpoint: WAL replays on mount
    s2 = FileStore(path)
    s2.mount()
    assert s2.read(CID, OID) == b"ok"
    s2.umount()


def test_filestore_commits_after_torn_tail_survive(tmp_path):
    path = str(tmp_path / "fs")
    s = FileStore(path)
    s.mkfs()
    s.mount()
    _mkcoll(s)
    s.apply_transaction(Transaction().write(CID, OID, 0, b"one"))
    s._wal.close()
    with open(os.path.join(path, "wal"), "ab") as f:
        f.write(b"torn-half-record\x00\x01")
    s2 = FileStore(path)
    s2.mount()
    assert s2.read(CID, OID) == b"one"
    s2.apply_transaction(Transaction().write(CID, OID, 0, b"two"))
    s2._wal.close()  # crash again
    s3 = FileStore(path)
    s3.mount()
    assert s3.read(CID, OID) == b"two"
    s3.umount()


def test_mkfs_required(tmp_path):
    s = FileStore(str(tmp_path / "nofs"))
    with pytest.raises(Exception):
        s.mount()


# ----------------------------------------------------------- blockstore

def test_blockstore_remount_preserves_data(tmp_path):
    from ceph_tpu.store.blockstore import BlockStore
    path = str(tmp_path / "bs")
    s = BlockStore(path)
    s.mkfs()
    s.mount()
    s.apply_transaction(Transaction().create_collection(CID)
                        .write(CID, OID, 0, b"A" * 10000)
                        .setattr(CID, OID, "x", b"v")
                        .omap_setkeys(CID, OID, {b"k": b"v"}))
    s.umount()
    s2 = BlockStore(path)
    s2.mount()
    assert s2.read(CID, OID) == b"A" * 10000
    assert s2.getattr(CID, OID, "x") == b"v"
    assert s2.omap_get(CID, OID)[1] == {b"k": b"v"}
    s2.umount()


def test_blockstore_crash_no_umount_recovers(tmp_path):
    """Abandon the store without umount (crash): the kv WAL replays and
    the allocator rebuild must reclaim any leaked COW blocks."""
    from ceph_tpu.store.blockstore import BlockStore
    path = str(tmp_path / "bs")
    s = BlockStore(path)
    s.mkfs()
    s.mount()
    s.apply_transaction(Transaction().create_collection(CID))
    for i in range(10):
        s.apply_transaction(
            Transaction().write(CID, ObjectId(f"o{i}", pool=1), 0,
                                bytes([i]) * 5000))
    # overwrite churn creates freed+reallocated extents
    for i in range(10):
        s.apply_transaction(
            Transaction().write(CID, ObjectId(f"o{i}", pool=1), 100,
                                bytes([0xF0 | (i & 0xF)]) * 1000))
    # NO umount — reopen like after a crash
    s2 = BlockStore(path)
    s2.mount()
    for i in range(10):
        got = s2.read(CID, ObjectId(f"o{i}", pool=1))
        want = bytearray(bytes([i]) * 5000)
        want[100:1100] = bytes([0xF0 | (i & 0xF)]) * 1000
        assert got == bytes(want), i
    # allocator accounting is consistent: used <= device, free+used=total
    fs = s2.statfs()
    assert fs["used"] + fs["free"] == fs["total"]
    s2.umount()


def test_blockstore_detects_bit_rot(tmp_path):
    """Flip one bit in the raw block file: the per-extent crc must turn
    the read into an error instead of returning rot (bluestore csum)."""
    import os as _os
    from ceph_tpu.store.blockstore import BlockStore, StoreError
    path = str(tmp_path / "bs")
    s = BlockStore(path)
    s.mkfs()
    s.mount()
    s.apply_transaction(Transaction().create_collection(CID)
                        .write(CID, OID, 0, b"precious-bytes" * 100))
    ext = s._get_onode(CID, OID).extents[0]
    s.umount()
    with open(_os.path.join(path, "block"), "r+b") as f:
        f.seek(ext.disk + 7)
        b = f.read(1)
        f.seek(ext.disk + 7)
        f.write(bytes([b[0] ^ 0x40]))
    s2 = BlockStore(path)
    s2.mount()
    with pytest.raises(StoreError, match="csum"):
        s2.read(CID, OID)
    s2.umount()


def test_blockstore_cow_overwrite_moves_blocks(tmp_path):
    """Overwrites land in fresh blocks (COW) and the old ones return to
    the allocator after commit."""
    from ceph_tpu.store.blockstore import BlockStore
    path = str(tmp_path / "bs")
    s = BlockStore(path)
    s.mkfs()
    s.mount()
    s.apply_transaction(Transaction().create_collection(CID)
                        .write(CID, OID, 0, b"1" * 8192))
    before = {(e.disk, e.length) for e in s._get_onode(CID, OID).extents}
    s.apply_transaction(Transaction().write(CID, OID, 0, b"2" * 8192))
    after = {(e.disk, e.length) for e in s._get_onode(CID, OID).extents}
    assert before.isdisjoint(after)
    assert s.read(CID, OID) == b"2" * 8192
    # freed space is reusable: total device should not balloon
    for _ in range(20):
        s.apply_transaction(Transaction().write(CID, OID, 0, b"x" * 8192))
    assert s.statfs()["total"] <= 8192 * 4 + 4 * 4096
    s.umount()


# --------------------------------------------------- objectstore tool

def test_objectstore_tool_list_info_export_import(tmp_path, capsys):
    from ceph_tpu.store.blockstore import BlockStore
    from ceph_tpu.tools import objectstore_tool as ost
    src = str(tmp_path / "src")
    s = BlockStore(src)
    s.mkfs()
    s.mount()
    cid = CollectionId.pg(1, 4)
    s.apply_transaction(Transaction().create_collection(cid))
    for i in range(3):
        o = ObjectId(f"obj{i}", pool=1)
        s.apply_transaction(Transaction().write(cid, o, 0, b"D" * 100)
                            .setattr(cid, o, "_", b"m")
                            .omap_setkeys(cid, o, {b"k": bytes([i])}))
    s.umount()

    assert ost.main(["--data-path", src, "--op", "list-pgs"]) == 0
    assert "1.4" in capsys.readouterr().out
    assert ost.main(["--data-path", src, "--op", "list",
                     "--pgid", "1.4"]) == 0
    assert capsys.readouterr().out.count("obj") == 3
    assert ost.main(["--data-path", src, "--op", "info", "--pgid", "1.4",
                     "--object", "obj1"]) == 0
    import json as _json
    info = _json.loads(capsys.readouterr().out)
    assert info["size"] == 100 and info["omap_keys"] == 1

    exp = str(tmp_path / "pg.export")
    assert ost.main(["--data-path", src, "--op", "export",
                     "--pgid", "1.4", "--file", exp]) == 0
    capsys.readouterr()

    # import into a DIFFERENT backend (filestore)
    dst = str(tmp_path / "dst")
    d = ObjectStore.create("filestore", dst)
    d.mkfs()
    assert ost.main(["--data-path", dst, "--type", "filestore",
                     "--op", "import", "--file", exp]) == 0
    capsys.readouterr()
    d2 = ObjectStore.create("filestore", dst)
    d2.mount()
    oids = d2.collection_list(cid)
    assert {o.name for o in oids} == {"obj0", "obj1", "obj2"}
    for o in oids:
        assert d2.read(cid, o) == b"D" * 100
        assert d2.getattr(cid, o, "_") == b"m"
    d2.umount()

    # surgical remove
    assert ost.main(["--data-path", src, "--op", "remove",
                     "--pgid", "1.4", "--object", "obj0"]) == 0
    capsys.readouterr()
    assert ost.main(["--data-path", src, "--op", "list",
                     "--pgid", "1.4"]) == 0
    assert capsys.readouterr().out.count("obj") == 2


# ----------------------------------------------------------- compressor

def test_compressor_plugins_roundtrip():
    from ceph_tpu.compressor import CompressorError, create, plugin_names
    data = b"compressible " * 1000 + bytes(range(256))
    for name in ("zlib", "bz2", "lzma"):
        c = create(name)
        z = c.compress(data)
        assert len(z) < len(data)
        assert c.decompress(z) == data
    with pytest.raises(CompressorError):
        create("snappy")            # gated: native lib absent
    with pytest.raises(CompressorError):
        create("nope")
    assert "zlib" in plugin_names()
    with pytest.raises(CompressorError):
        create("zlib").decompress(b"not compressed data")


def test_blockstore_compression_roundtrip_and_savings(tmp_path):
    from ceph_tpu.store.blockstore import BlockStore
    path = str(tmp_path / "bsz")
    s = BlockStore(path, compression="zlib")
    s.mkfs()
    s.mount()
    payload = b"squeeze me please " * 4096           # ~72 KiB, redundant
    s.apply_transaction(Transaction().create_collection(CID)
                        .write(CID, OID, 0, payload))
    on = s._get_onode(CID, OID)
    assert any(e.alg == "zlib" for e in on.extents)
    assert sum(e.disk_len for e in on.extents) < len(payload) // 4
    assert s.read(CID, OID) == payload
    # incompressible data stays raw
    import os as _os
    rnd = _os.urandom(32768)
    OID2 = ObjectId("rand", pool=1)
    s.apply_transaction(Transaction().write(CID, OID2, 0, rnd))
    assert all(e.alg == "" for e in s._get_onode(CID, OID2).extents)
    assert s.read(CID, OID2) == rnd
    s.umount()
    # remount without compression configured still reads both (per-
    # extent alg tags), and mixed writes compose
    s2 = BlockStore(path)
    s2.mount()
    assert s2.read(CID, OID) == payload
    s2.apply_transaction(Transaction().write(CID, OID, 100, b"RAW"))
    want = bytearray(payload)
    want[100:103] = b"RAW"
    assert s2.read(CID, OID) == bytes(want)
    s2.umount()


# -------------------------------------------------------------- kstore

def test_kstore_remount_preserves_everything(tmp_path):
    """All state (data stripes, xattrs, omap) lives in the KV WAL and
    survives umount/mount (os/kstore/KStore.cc role)."""
    from ceph_tpu.store.kstore import KStore, STRIPE
    p = str(tmp_path / "ks")
    s = KStore(p)
    s.mkfs(); s.mount()
    t = Transaction()
    t.create_collection(CID)
    big = bytes(range(256)) * ((STRIPE * 2 + 777) // 256 + 1)
    t.write(CID, OID, 0, big)
    t.setattr(CID, OID, "_", b"oi-bytes")
    t.omap_setkeys(CID, OID, {b"a": b"1", b"b": b"2"})
    t.omap_setheader(CID, OID, b"HDR")
    s.apply_transaction(t)
    s.umount()

    s2 = KStore(p)
    s2.mount()
    assert s2.read(CID, OID) == big
    # partial read across a stripe boundary
    assert s2.read(CID, OID, STRIPE - 100, 200) == big[STRIPE - 100:
                                                       STRIPE + 100]
    assert s2.getattr(CID, OID, "_") == b"oi-bytes"
    hdr, omap = s2.omap_get(CID, OID)
    assert hdr == b"HDR" and omap == {b"a": b"1", b"b": b"2"}
    assert s2.collection_list(CID) == [OID]
    s2.umount()


def test_kstore_small_overwrite_wals_only_touched_stripes(tmp_path):
    """A 100-byte overwrite inside a multi-stripe object must not
    rewrite every stripe record (the store's reason to stripe)."""
    from ceph_tpu.store.kstore import KStore, STRIPE, P_DATA
    s = KStore("")
    s.mount()
    t = Transaction()
    t.create_collection(CID)
    t.write(CID, OID, 0, b"x" * (STRIPE * 4))
    s.apply_transaction(t)

    seen = []
    orig = s.db.submit
    def spy(kvt, sync=True):
        seen.extend(k for kind, k, _ in kvt.ops
                    if kind == 0 and k.startswith(b"D"))
        return orig(kvt, sync=sync)
    s.db.submit = spy
    t2 = Transaction()
    t2.write(CID, OID, STRIPE + 5, b"y" * 100)
    s.apply_transaction(t2)
    assert len(seen) == 1            # exactly one stripe rewritten
    got = s.read(CID, OID, STRIPE, 200)
    assert got[5:105] == b"y" * 100
    s.umount()


def test_kstore_clone_and_rename_carry_omap(tmp_path):
    from ceph_tpu.store.kstore import KStore
    s = KStore("")
    s.mount()
    o2 = ObjectId("obj2", pool=1)
    t = Transaction()
    t.create_collection(CID)
    t.write(CID, OID, 0, b"payload")
    t.omap_setkeys(CID, OID, {b"k": b"v"})
    t.clone(CID, OID, o2)
    s.apply_transaction(t)
    assert s.read(CID, o2) == b"payload"
    assert s.omap_get(CID, o2)[1] == {b"k": b"v"}
    # rename within the collection
    o3 = ObjectId("obj3", pool=1)
    t2 = Transaction()
    t2.try_rename(CID, o2, o3)
    s.apply_transaction(t2)
    assert s.read(CID, o3) == b"payload"
    assert not s.exists(CID, o2)
    s.umount()


def test_kstore_rename_replaces_existing_destination():
    """try_rename onto an existing object must REPLACE it wholesale —
    stale destination omap/data must not merge in (review finding)."""
    from ceph_tpu.store.kstore import KStore, STRIPE
    s = KStore("")
    s.mount()
    a = ObjectId("a", pool=1)
    b = ObjectId("b", pool=1)
    t = Transaction()
    t.create_collection(CID)
    t.write(CID, b, 0, b"Z" * (STRIPE * 2))
    t.omap_setkeys(CID, b, {b"old": b"1"})
    t.write(CID, a, 0, b"payload")
    t.omap_setkeys(CID, a, {b"k": b"v"})
    s.apply_transaction(t)
    t2 = Transaction()
    t2.try_rename(CID, a, b)
    s.apply_transaction(t2)
    assert s.omap_get(CID, b)[1] == {b"k": b"v"}
    assert s.read(CID, b) == b"payload"
    # extend past the first stripe: old b's bytes must not resurface
    t3 = Transaction()
    t3.write(CID, b, STRIPE + 5, b"!")
    s.apply_transaction(t3)
    assert s.read(CID, b, STRIPE, 5) == b"\x00" * 5
    s.umount()


def _random_txn(rng):
    """One seeded transaction touching data/xattr/omap."""
    from ceph_tpu.store.objectstore import Transaction
    from ceph_tpu.store.types import CollectionId, ObjectId
    cid = CollectionId("seq")
    oid = ObjectId(f"o{rng.integers(0, 6)}")
    t = Transaction()
    kind = int(rng.integers(0, 4))
    if kind == 0:
        t.write(cid, oid, int(rng.integers(0, 512)),
                bytes(rng.integers(0, 256, int(rng.integers(1, 2048)),
                                   dtype=np.uint8)))
    elif kind == 1:
        t.setattr(cid, oid, f"a{int(rng.integers(0, 3))}",
                  bytes(rng.integers(0, 256, 16, dtype=np.uint8)))
    elif kind == 2:
        t.omap_setkeys(cid, oid, {
            f"k{int(rng.integers(0, 4))}".encode():
            bytes(rng.integers(0, 256, 32, dtype=np.uint8))})
    else:
        t.truncate(cid, oid, int(rng.integers(0, 256)))
    return t


def _store_fingerprint(s):
    """Canonical digest of every object's data/xattrs/omap."""
    out = {}
    for cid in s.list_collections():
        for oid in s.collection_list(cid):
            o = (bytes(s.read(cid, oid, 0, -1)),
                 tuple(sorted(s.getattrs(cid, oid).items())),
                 tuple(sorted(s.omap_get(cid, oid)[1].items())))
            out[(cid.name, oid.name)] = o
    return out


def test_deterministic_crash_replay_sweep(tmp_path):
    """DeterministicOpSequence / filestore_kill_at role
    (test/objectstore/DeterministicOpSequence.cc, run_seed_to.sh):
    a seeded transaction sequence is killed at EVERY injection point
    — before-journal and after-journal-before-apply of each batch —
    and the remounted store must equal a clean replay of the exact
    transaction-boundary prefix: after-journal kills recover the txn,
    before-journal kills lose it, never anything in between."""
    from ceph_tpu.store.filestore import FileStore, KilledAt
    from ceph_tpu.store.objectstore import Transaction
    from ceph_tpu.store.types import CollectionId

    SEQ = 12
    seed = 1234

    def build_txns():
        rng = np.random.default_rng(seed)
        txns = [Transaction()]
        txns[0].create_collection(CollectionId("seq"))
        txns += [_random_txn(rng) for _ in range(SEQ)]
        return txns

    _fp_cache = {}

    def clean_prefix_fingerprint(m):
        """Fingerprint after applying the first m txns cleanly
        (cached: each prefix replays exactly once, in a FRESH dir —
        the oracle must not depend on op idempotence)."""
        if m not in _fp_cache:
            d = tmp_path / f"clean{m}"
            s = FileStore(str(d))
            s.mkfs(); s.mount()
            for t in build_txns()[:m]:
                s.queue_transactions([t])
            _fp_cache[m] = _store_fingerprint(s)
            s.umount()
        return _fp_cache[m]

    for n in range(1, SEQ + 2):
        for mode, survivors in (("after", n), ("before", n - 1)):
            d = tmp_path / f"kill_{mode}_{n}"
            s = FileStore(str(d))
            s.mkfs(); s.mount()
            s.kill_at = n if mode == "after" else -n
            died = False
            try:
                for t in build_txns():
                    s.queue_transactions([t])
            except KilledAt:
                died = True
            assert died, (mode, n)
            # crash: no umount/checkpoint — remount replays the WAL
            s2 = FileStore(str(d))
            s2.mount()
            assert _store_fingerprint(s2) == \
                clean_prefix_fingerprint(survivors), (mode, n)
            s2.umount()


# ------------------------------------------------- group-commit pipeline

def test_group_commit_callbacks_fire_in_submission_order(store):
    """on_commit callbacks fire in submission order even when the commit
    thread drains many queued batches in one group (ISSUE 1 invariant:
    repop acks / pglog last_complete ride these callbacks)."""
    import threading
    _mkcoll(store)
    committer = getattr(store, "_committer", None)
    if committer is not None:
        # hold the thread so every batch below lands in ONE group
        committer.gate = threading.Event()
    order = []
    n = 24
    for i in range(n):
        store.queue_transactions(
            [Transaction().write(CID, ObjectId(f"seq{i}", pool=1), 0,
                                 bytes([i]) * 128)],
            on_commit=lambda i=i: order.append(i))
    if committer is not None:
        committer.gate.set()
    store.sync()
    assert order == list(range(n))


def test_blockstore_group_commit_shares_fsyncs(tmp_path):
    """N concurrent transaction batches commit with fewer than N fsyncs:
    the kv-sync thread issues ONE data barrier + ONE atomic kv submit
    per group (BlueStore kv_sync_thread recipe)."""
    import threading
    from ceph_tpu.store.blockstore import BlockStore
    s = BlockStore(str(tmp_path / "bs"))
    s.mkfs()
    s.mount()
    _mkcoll(s)
    base = s.commit_counters()
    s._committer.gate = threading.Event()
    n = 16
    done = []
    for i in range(n):
        s.queue_transactions(
            [Transaction().write(CID, ObjectId(f"grp{i}", pool=1), 0,
                                 bytes([i]) * 4096)],
            on_commit=lambda i=i: done.append(i))
    s._committer.gate.set()
    s.sync()
    c = s.commit_counters()
    txns = c["txns"] - base["txns"]
    fsyncs = c["fsyncs"] - base["fsyncs"]
    batches = c["commit_batches"] - base["commit_batches"]
    assert txns == n and done == list(range(n))
    assert batches < n                # grouping engaged
    assert 1 <= fsyncs < n            # shared barriers, not per-txn
    assert c["fsyncs_saved"] > base["fsyncs_saved"]
    # group-committed state is really durable: crash-reopen sees it all
    s2 = BlockStore(str(tmp_path / "bs"))   # no umount (power cut)
    s2.mount()
    for i in range(n):
        assert s2.read(CID, ObjectId(f"grp{i}", pool=1)) == \
            bytes([i]) * 4096
    s2.umount()
    s.umount()


COMMIT_POINTS = ["before_data_write", "before_data_sync", "before_kv"]


@pytest.mark.parametrize("point", COMMIT_POINTS)
def test_blockstore_crash_ordering_data_before_metadata(tmp_path, point):
    """Fault-inject a power cut on the commit thread: a kv batch must
    never be visible (replayable) before its data blocks are written
    and fsync'd.  The trace hook proves the write-out strictly precedes
    the data barrier and that the kv submit; a crash at any of the
    three points leaves the object invisible on replay and fires NO
    commit callback."""
    from ceph_tpu.store.blockstore import BlockStore, StoreError
    path = str(tmp_path / "bs")
    s = BlockStore(path)
    s.mkfs()
    s.mount()
    s.apply_transaction(Transaction().create_collection(CID))  # durable
    stages = []
    s._committer.trace = lambda pt, n: stages.append(pt)
    s._committer.crash_at = point
    done = []
    s.queue_transactions([Transaction().write(CID, OID, 0, b"doomed")],
                         on_commit=lambda: done.append(1))
    # sync fails LOUDLY: durability can no longer be promised
    with pytest.raises(StoreError):
        s.sync()
    assert s._committer.dead
    assert done == []                 # never committed, never acked
    # and so do new writes (no silent phantom acceptance)
    with pytest.raises(StoreError):
        s.queue_transactions([Transaction().write(
            CID, ObjectId("after", pool=1), 0, b"x")])
    # applied state WAS readable in memory (apply/commit split) ...
    assert s.read(CID, OID) == b"doomed"
    # ... and the thread went write-out, data barrier, kv submit, as
    # far as the cut let it
    assert stages == COMMIT_POINTS[:COMMIT_POINTS.index(point) + 1]
    # power cut: abandon without umount (umount would flush), reopen
    s2 = BlockStore(path)
    s2.mount()
    assert s2._coll_exists(CID)       # the durable prefix survives
    with pytest.raises(NoSuchObject):
        s2.read(CID, OID)             # the un-fsync'd batch never lands
    s2.umount()


# ------------------------------------ write-behind: order and power cuts

def _write_behind_store(path):
    from ceph_tpu.store.blockstore import BlockStore
    s = BlockStore(path)
    s.mkfs()
    s.mount()
    s.apply_transaction(Transaction().create_collection(CID))
    return s


def _write_behind_load(s, n, acked, model, seed=11):
    """n transactions, each an overwrite of the one hot object (whole,
    or cutting its extent) and a write of one of many others; the
    model holds every object's bytes after each transaction."""
    rng = np.random.default_rng(seed)
    hot = ObjectId("hot", pool=1)
    state = dict(model[-1]) if model else {}
    for i in range(n):
        txn = Transaction()
        size = int(rng.integers(1, 5)) * 4096
        off = int(rng.integers(0, 3)) * 4096 if i % 3 else 0
        data = rng.integers(0, 256, size, np.uint8).tobytes()
        old = bytearray(state.get(hot, b""))
        if len(old) < off + size:
            old.extend(bytes(off + size - len(old)))
        old[off:off + size] = data
        state[hot] = bytes(old)
        txn.write(CID, hot, off, data)
        other = ObjectId(f"many{int(rng.integers(0, 12))}", pool=1)
        state[other] = rng.integers(0, 256, 8192, np.uint8).tobytes()
        txn.remove(CID, other).write(CID, other, 0, state[other])
        s.queue_transactions(
            [txn], on_commit=lambda k=len(model): acked.append(k))
        model.append(dict(state))       # applied: the store took it


def test_write_behind_group_writes_then_syncs_then_commits(
        tmp_path, monkeypatch):
    """Every commit group: before_data_write -> every pwrite of every
    one of its transactions has RETURNED -> the block file's fsync ->
    the kv WAL's -> the callbacks; nothing is written on the caller's
    thread, and the thread's counters say the same."""
    import threading
    s = _write_behind_store(str(tmp_path / "bs"))
    com = s._committer
    events, offsets_of, caller = [], {}, threading.get_ident()
    real_pwrite, real_fsync, real_piece = os.pwrite, os.fsync, \
        s._store_piece

    def pwrite(fd, data, off):
        assert threading.get_ident() != caller
        n = real_pwrite(fd, data, off)
        events.append(("pwritten", off))
        return n

    def fsync(fd):
        real_fsync(fd)
        events.append(("fsync", "block" if fd == s._fd else "kv"))

    def piece(logical, chunk, d_off, d_len, b):
        offsets_of.setdefault(len(model), []).append(d_off)
        return real_piece(logical, chunk, d_off, d_len, b)

    monkeypatch.setattr(os, "pwrite", pwrite)
    monkeypatch.setattr(os, "fsync", fsync)
    s._store_piece = piece
    com.trace = lambda point, n: events.append((point, n))
    acked, model = [], []
    real_complete = com._complete
    com._complete = lambda group: (
        events.append(("acks", len(acked))), real_complete(group))
    try:
        base = s.commit_counters()
        for burst in (7, 1, 12, 3, 9):
            com.gate = threading.Event()    # the burst lands in few groups
            _write_behind_load(s, burst, acked, model, seed=burst)
            com.gate.set()
            _write_behind_load(s, 5, acked, model, seed=100 + burst)
            s.sync()
        n_txn = len(model)
        assert acked == list(range(n_txn))
        # cut the thread's log into groups at each before_data_write
        starts = [i for i, ev in enumerate(events)
                  if ev[0] == "before_data_write"]
        assert len(starts) >= 5
        # blocks are reused as overwrites free them: count per offset
        written, owed, first = collections.Counter(), \
            collections.Counter(), 0
        for g, start in enumerate(starts):
            end = starts[g + 1] if g + 1 < len(starts) else len(events)
            names = [ev[0] for ev in events[start:end]]
            size = events[start][1]
            # one of each, in this order, with the pwrites all between
            # the first two
            order = [names.index(p) for p in (
                "before_data_write", "before_data_sync", "fsync",
                "before_kv", "committed", "acks")]
            assert order == sorted(order), names
            assert names.count("fsync") == 2
            assert [ev[1] for ev in events[start:end]
                    if ev[0] == "fsync"] == ["block", "kv"]
            i_sync = names.index("before_data_sync")
            assert set(names[1:i_sync]) <= {"pwritten"}
            assert "pwritten" not in names[i_sync:]
            written.update(ev[1] for ev in events[start:end]
                           if ev[0] == "pwritten")
            # the group is transactions first .. first + size - 1
            for k in range(first, first + size):
                owed.update(offsets_of[k])
            assert not owed - written, (g, owed - written)
            first += size
        assert first == n_txn
        c = s.commit_counters()
        n_rec = sum(len(v) for v in offsets_of.values())
        assert c["deferred_writes"] - base["deferred_writes"] == n_rec \
            == sum(written.values())
        assert c["writes_after_data_sync"] == 0
        assert c["data_groups"] == c["data_fsyncs"]
        assert not s._pending and not s._inflight
        for oid, want in model[-1].items():
            assert s.read(CID, oid) == want
    finally:
        s.umount()


@pytest.mark.parametrize("point", COMMIT_POINTS)
def test_write_behind_power_cut_keeps_every_acked_write(tmp_path, point):
    """A power cut at each point of a group under a load of interleaved
    overwrites: a fresh mount holds exactly what the acked transactions
    wrote, every checksum good.  before_data_write leaves the group's
    data in memory alone; before_data_sync and before_kv leave it in
    the file, written and unreferenced."""
    import threading
    from ceph_tpu.store.blockstore import BlockStore, StoreError
    path = str(tmp_path / "bs")
    s = _write_behind_store(path)
    com = s._committer
    acked, model = [], []
    _write_behind_load(s, 10, acked, model)
    s.sync()
    # one group still commits with the cut armed, the next is cut; the
    # gate makes each burst one group, staged after the group before
    # it was done
    com.crash_at, com.crash_skip = point, 1
    for n_done in (14, None):
        com.gate = threading.Event()
        written = s.commit_counters()["deferred_writes"]
        staged = s._staged_n
        _write_behind_load(s, 4, acked, model, seed=n_done or 5)
        com.gate.set()
        if n_done is not None:
            s.sync()
            assert acked == list(range(n_done))
    with pytest.raises(StoreError):
        s.sync()
    assert com.dead
    assert acked == list(range(14)) and len(model) == 18
    n_acked = len(acked)
    c = s.commit_counters()
    assert c["writes_after_data_sync"] == 0
    assert c["acks_before_commit"] == 0
    n_cut = s._staged_n - staged
    assert n_cut >= 8
    if point == "before_data_write":    # never reached the file
        assert len(s._pending) == len(s._inflight) == n_cut
        assert c["deferred_writes"] == written
    else:                               # in the file, and unreferenced
        assert not s._pending and not s._inflight
        assert c["deferred_writes"] == written + n_cut
    # memory still serves what was applied, acked or not
    for oid, want in model[-1].items():
        assert s.read(CID, oid) == want
    # the cut: no umount (it would flush); what the files hold
    s2 = BlockStore(path)
    s2.mount()
    try:
        for oid, want in model[n_acked - 1].items():
            assert s2.read(CID, oid) == want, oid
        assert {o.name for o in s2.collection_list(CID)} == {
            o.name for o in model[n_acked - 1]}
    finally:
        s2.umount()


@pytest.mark.parametrize("fault", [False, True],
                         ids=["sound", "fsync_before_the_drain"])
def test_writes_after_data_sync_counts_a_barrier_ahead_of_its_data(
        tmp_path, fault):
    """The control for the counter: a commit thread whose data barrier
    runs BEFORE the staged records are written reads every record of
    every group late; the sound one reads 0."""
    s = _write_behind_store(str(tmp_path / "bs"))
    com = s._committer
    if fault:
        write, sync = com.data_write, com.data_sync
        # the fsync first (nothing drained: the count as it stood) ...
        com.data_write = lambda: (sync(), s._written_n)[1]
        com.data_sync = write       # ... and the write-out after it
    acked, model = [], []
    try:
        base = s.commit_counters()["deferred_writes"]
        _write_behind_load(s, 12, acked, model)
        s.sync()
        c = s.commit_counters()
        assert acked == list(range(12))
        late = c["writes_after_data_sync"]
        if fault:
            assert 0 < late <= c["deferred_writes"] - base
        else:
            assert late == 0
        for oid, want in model[-1].items():
            assert s.read(CID, oid) == want
    finally:
        s.umount()


# ------------------------------------- data kept by reference (MemStore)
# MemStore (and FileStore, which applies through it) holds an object's
# data as the immutable buffer it was written with: adopted by
# reference, handed back by reference, copied once when part of it is
# about to change.  The comparisons of contents run on every store; the
# identities and the counters on the two that adopt.

@pytest.fixture(params=["memstore", "filestore"])
def mstore(request, tmp_path):
    s = ObjectStore.create(request.param, str(tmp_path / "store"))
    s.mkfs()
    s.mount()
    yield s
    s.umount()


MIB = 1 << 20
SNAP = OID.with_snap(7)
OTHER = ObjectId("other", pool=1)


def _payload(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _raw_write(cid, oid, off, data):
    """A write whose op carries `data` AS GIVEN (Transaction.write makes
    it bytes first): what a store must never keep by reference."""
    from ceph_tpu.store.objectstore import OP_WRITE, TxOp
    t = Transaction()
    t.ops.append(TxOp(OP_WRITE, cid, oid, off=off, length=len(data),
                      data=data))
    return t


_CHANGEABLE = {
    "bytearray": lambda raw: bytearray(raw),
    "memoryview": lambda raw: memoryview(bytearray(raw)),
    "numpy": lambda raw: np.frombuffer(raw, np.uint8).copy(),
    "array": lambda raw: array.array("B", raw),
}


# (the WAL's encoder takes no ndarray, so none reaches a store in an op)
@pytest.mark.parametrize("kind,via", [
    (k, v) for k in sorted(_CHANGEABLE) for v in ("builder", "txop")
    if (k, v) != ("numpy", "txop")])
def test_store_copies_a_source_its_caller_can_still_change(
        mstore, kind, via):
    _mkcoll(mstore)
    raw = _payload(4096, seed=3)
    src = _CHANGEABLE[kind](raw)
    if via == "builder":
        t = Transaction().write(CID, OID, 0, src)
    else:
        t = _raw_write(CID, OID, 0, src)
    mstore.queue_transactions([t])
    for i in range(2048):               # the caller reuses its buffer
        src[i] = 0
    got = mstore.read(CID, OID)
    assert type(got) is bytes and got == raw
    assert mstore.read(CID, OID, 100, 50) == raw[100:150]


def test_adopted_bytes_are_read_back_by_identity(mstore):
    _mkcoll(mstore)
    data = _payload(MIB)
    mstore.queue_transactions([Transaction().truncate(CID, OID, 0)
                               .write(CID, OID, 0, data)])
    assert mstore.read(CID, OID) is data
    assert mstore.read(CID, OID, 0, MIB) is data
    assert mstore.read(CID, OID, 0, MIB + 4096) is data
    for off, length in ((0, MIB - 1), (1, -1), (4096, 8192), (MIB, 10)):
        part = mstore.read(CID, OID, off, length)
        want = data[off:] if length < 0 else data[off:off + length]
        assert type(part) is bytes and part == want and part is not data
    assert mstore.stat(CID, OID)["size"] == MIB
    # a clone shares the buffer; the next whole write replaces only
    # the head's
    data2 = _payload(MIB // 2, seed=1)
    mstore.apply_transaction(Transaction().clone(CID, OID, SNAP)
                             .truncate(CID, OID, 0)
                             .write(CID, OID, 0, data2))
    assert mstore.read(CID, SNAP) is data
    assert mstore.read(CID, OID) is data2


def _mut_partial_write(t, model):
    t.write(CID, OID, 1000, b"\xa5" * 3000)
    model[1000:4000] = b"\xa5" * 3000


def _mut_write_past_end(t, model):
    t.write(CID, OID, len(model) + 100, b"tail")
    model.extend(bytes(100) + b"tail")


def _mut_zero(t, model):
    t.zero(CID, OID, 10, 5000)
    model[10:5010] = bytes(5000)


def _mut_truncate_down(t, model):
    t.truncate(CID, OID, 777)
    del model[777:]


def _mut_truncate_up(t, model):
    t.truncate(CID, OID, len(model) + 333)
    model.extend(bytes(333))


def _mut_clone_range_into(t, model):
    t.write(CID, OTHER, 0, b"0123456789" * 100)
    t.clone_range(CID, OTHER, OID, 5, 500, 2000)
    model[2000:2500] = (b"0123456789" * 100)[5:505]


def _mut_clone_range_onto_itself(t, model):
    t.clone_range(CID, OID, OID, 0, 1024, 512)
    model[512:1536] = bytes(model[0:1024])


_MUTATIONS = [_mut_partial_write, _mut_write_past_end, _mut_zero,
              _mut_truncate_down, _mut_truncate_up, _mut_clone_range_into,
              _mut_clone_range_onto_itself]


@pytest.mark.parametrize("mutate", _MUTATIONS,
                         ids=[m.__name__[5:] for m in _MUTATIONS])
def test_a_change_to_part_of_an_object_copies_on_write(store, mutate):
    """Adopt, clone, then change part of the head: the head equals a
    plain bytearray model, and neither the ORIGINAL bytes object nor
    the clone taken before the change moved."""
    _mkcoll(store)
    data = _payload(8192, seed=5)
    kept = bytes(bytearray(data))       # a copy nothing else refers to
    store.apply_transaction(Transaction().write(CID, OID, 0, data)
                            .clone(CID, OID, SNAP))
    model = bytearray(kept)
    t = Transaction()
    mutate(t, model)
    store.apply_transaction(t)
    assert store.read(CID, OID) == bytes(model)
    assert store.stat(CID, OID)["size"] == len(model)
    assert data == kept
    assert store.read(CID, SNAP) == kept
    if isinstance(store, MemStore):
        assert store.read(CID, SNAP) is data
    # and once more on the now-mutable buffer
    t = Transaction()
    mutate(t, model)
    store.apply_transaction(t)
    assert store.read(CID, OID) == bytes(model)
    assert store.read(CID, SNAP) == kept


def test_truncate_zero_then_a_shorter_write_leaves_no_tail(store):
    _mkcoll(store)
    long_, short = _payload(6000, seed=1), _payload(2500, seed=2)
    store.apply_transaction(Transaction().write(CID, OID, 0, long_))
    store.apply_transaction(Transaction().truncate(CID, OID, 0)
                            .write(CID, OID, 0, short))
    assert store.read(CID, OID) == short
    assert store.read(CID, OID, 2000, 4000) == short[2000:]
    assert store.stat(CID, OID)["size"] == 2500
    # the same without the truncate keeps the old tail, on every store
    store.apply_transaction(Transaction().write(CID, OID, 0, long_))
    store.apply_transaction(Transaction().write(CID, OID, 0, short))
    assert store.read(CID, OID) == short + long_[2500:]


_SEQ_NAMES = [ObjectId(f"seq{i}", pool=1) for i in range(3)]


def _random_data_txn(rng, model, sources):
    """1-4 random data ops on three objects, applied to `model`
    ({oid: bytearray}, absent = no such object) as they are built."""
    def splice(oid, off, chunk):
        m = model.setdefault(oid, bytearray())
        end = off + len(chunk)
        if len(m) < end:
            m.extend(bytes(end - len(m)))
        m[off:end] = chunk

    t = Transaction()
    for _ in range(int(rng.integers(1, 5))):
        oid = _SEQ_NAMES[int(rng.integers(0, 3))]
        oid2 = _SEQ_NAMES[int(rng.integers(0, 3))]
        kind = int(rng.integers(0, 9))
        size = len(model.get(oid, b""))
        if kind <= 2:
            # a write: whole (with or without the truncate an EC full
            # write sends), or anywhere; as bytes or as a buffer the
            # caller goes on to change
            chunk = _payload(int(rng.integers(0, 700)),
                             seed=int(rng.integers(0, 2**31)))
            off = 0 if kind < 2 else int(rng.integers(0, size + 50))
            if kind == 0:
                t.truncate(CID, oid, 0)
                model[oid] = bytearray()
            if rng.integers(0, 3) == 0:
                src = bytearray(chunk)
                t.ops.extend(_raw_write(CID, oid, off, src).ops)
                sources.append((src, None))
            else:
                t.write(CID, oid, off, chunk)
                sources.append((chunk, bytes(bytearray(chunk))))
            splice(oid, off, chunk)
        elif kind == 3:
            off, n = int(rng.integers(0, size + 20)), int(rng.integers(0, 300))
            t.zero(CID, oid, off, n)
            splice(oid, off, bytes(n))
        elif kind == 4:
            n = int(rng.choice([0, size, int(rng.integers(0, size + 200))]))
            t.truncate(CID, oid, n)
            m = model.setdefault(oid, bytearray())
            if n < len(m):
                del m[n:]
            else:
                m.extend(bytes(n - len(m)))
        elif kind == 5:
            t.clone(CID, oid, oid2)
            if oid in model:
                model[oid2] = bytearray(model[oid])
        elif kind in (6, 7):
            off, n = int(rng.integers(0, size + 10)), int(rng.integers(0, 400))
            dst = 0 if kind == 6 else int(rng.integers(0, 300))
            t.clone_range(CID, oid, oid2, off, n, dst)
            if oid in model:
                splice(oid2, dst, bytes(model[oid][off:off + n]))
        else:
            t.remove(CID, oid)
            model.pop(oid, None)
    return t


@pytest.mark.parametrize("block", range(20))
def test_random_data_ops_match_a_bytearray_model(mstore, block):
    """500 seeded op sequences (25 per case): after every transaction
    each object reads as the plain bytearray model says, whole and in
    part, and no bytes object handed to the store ever changed."""
    _mkcoll(mstore)
    for seed in range(block * 25, block * 25 + 25):
        rng = np.random.default_rng(seed)
        model, sources = {}, []
        t = Transaction()
        for oid in _SEQ_NAMES:
            t.remove(CID, oid)
        mstore.apply_transaction(t)
        for _ in range(int(rng.integers(3, 8))):
            mstore.queue_transactions(
                [_random_data_txn(rng, model, sources)])
            for src, _ in sources:
                if type(src) is bytearray:
                    src[:] = b"\xee" * len(src)     # the caller's reuse
            for oid in _SEQ_NAMES:
                if oid not in model:
                    assert not mstore.exists(CID, oid), (seed, oid)
                    continue
                want = bytes(model[oid])
                assert mstore.read(CID, oid) == want, (seed, oid)
                assert mstore.stat(CID, oid)["size"] == len(want)
                a = int(rng.integers(0, len(want) + 2))
                n = int(rng.integers(0, len(want) + 2))
                assert mstore.read(CID, oid, a, n) == want[a:a + n]
        for src, kept in sources:
            assert kept is None or src == kept, seed


def test_filestore_replays_and_snapshots_adopted_objects(tmp_path):
    """WAL replay, then the snapshot, give back the bytes of an adopted
    object, of one copied on write and of a clone that shares a buffer;
    and the snapshot of adopted objects is, byte for byte, the snapshot
    of the same contents held as bytearrays."""
    whole, part = _payload(65536, seed=8), _payload(5000, seed=9)
    cowed = bytearray(whole)
    cowed[100:5100] = part

    def load(path, adopt):
        s = FileStore(path)
        s.mkfs()
        s.mount()
        _mkcoll(s)
        if adopt:
            s.apply_transaction(Transaction().write(CID, OID, 0, whole)
                                .clone(CID, OID, SNAP))
            s.apply_transaction(Transaction().write(CID, OTHER, 0, whole))
        else:       # the same contents through partial writes
            s.apply_transaction(
                Transaction().write(CID, OID, 1000, whole[1000:])
                .write(CID, OID, 0, whole[:1000])
                .clone(CID, OID, SNAP))
            s.apply_transaction(
                Transaction().write(CID, OTHER, 1, whole[1:])
                .write(CID, OTHER, 0, whole[:1]))
        s.apply_transaction(Transaction().write(CID, OTHER, 100, part))
        return s

    def check(s):
        assert s.read(CID, OID) == whole
        assert s.read(CID, SNAP) == whole
        assert s.read(CID, OTHER) == bytes(cowed)

    a = load(str(tmp_path / "a"), adopt=True)
    assert a.read(CID, OID) is whole and a.read(CID, SNAP) is whole
    check(a)
    a._wal.close()                      # crash: the WAL alone
    a2 = FileStore(str(tmp_path / "a"))
    a2.mount()
    check(a2)
    a2.umount()                         # clean: snapshot, empty WAL
    a3 = FileStore(str(tmp_path / "a"))
    a3.mount()
    assert os.path.getsize(os.path.join(a3.path, "wal")) == 0
    check(a3)
    # what a mount decoded is held by reference too
    assert a3.read(CID, OID) is a3.read(CID, OID)
    a3.umount()

    b = load(str(tmp_path / "b"), adopt=False)
    assert b.commit_counters()["adopted_writes"] == 0
    b.umount()
    with open(os.path.join(a3.path, "checkpoint"), "rb") as fa, \
            open(os.path.join(b.path, "checkpoint"), "rb") as fb:
        assert fa.read() == fb.read()


def test_store_counts_what_it_adopts_and_what_it_copies(mstore):
    _mkcoll(mstore)
    names = ("adopted_writes", "adopted_bytes", "copied_write_bytes",
             "cow_copies", "cow_bytes", "reads_by_reference",
             "read_copied_bytes")

    def delta(fn):
        base = mstore.commit_counters()
        fn()
        now = mstore.commit_counters()
        return {k: now[k] - base[k] for k in names if now[k] != base[k]}

    data = _payload(MIB)
    assert delta(lambda: mstore.queue_transactions(
        [Transaction().truncate(CID, OID, 0).write(CID, OID, 0, data)])) \
        == {"adopted_writes": 1, "adopted_bytes": MIB}
    assert delta(lambda: mstore.read(CID, OID)) == {"reads_by_reference": 1}
    assert delta(lambda: mstore.read(CID, OID, 4096, 8192)) \
        == {"read_copied_bytes": 8192}
    assert delta(lambda: mstore.queue_transactions(
        [Transaction().write(CID, OID, 4096, b"x" * 100)])) \
        == {"cow_copies": 1, "cow_bytes": MIB, "copied_write_bytes": 100}
    # the buffer is the store's own now: no second copy, and a whole
    # read of it is one copy
    assert delta(lambda: mstore.queue_transactions(
        [Transaction().write(CID, OID, 0, b"y" * 100)])) \
        == {"copied_write_bytes": 100}
    assert delta(lambda: mstore.read(CID, OID)) == {"read_copied_bytes": MIB}
    # a whole write from a buffer the caller can change: copied, once
    assert delta(lambda: mstore.queue_transactions(
        [_raw_write(CID, OTHER, 0, bytearray(1000))])) \
        == {"copied_write_bytes": 1000}
    assert delta(lambda: mstore.read(CID, OTHER)) == {"reads_by_reference": 1}

"""Toy-size rehearsals of `rb_write_4m_qd16_blockstore` on the CPU: the
kind `closed_loop_restart` on a BlockStore deployment through the same
harness the chip runs, traced and not; its controls (completion records
posted before the barriers have run: the pure reordering has to come
out `correct: false` by `acks_before_commit`, and with the barriers
held behind the acks by `crash_image_mismatch` too); and the kind's
refusal when the deployed store is not the configured one, which is
what a program that ignores the `objectstore` option meets.

Nothing here is a number about speed: the device is the CPU."""

import asyncio
import io
import json
import os
import pathlib
import threading
import time

import pytest

from benchmark import harness, manifest

REPO = pathlib.Path(__file__).resolve().parents[2]
TOY = REPO / "tests" / "benchmark" / "toy" / "manifest_restart.json"
CELL = "toy_write_blockstore"
STORE_METRICS = {f"store.{m}.goodput" for m in (
    "apply_ms", "commit_wait_ms", "data_sync_ms", "kv_sync_ms",
    "resume_ms", "group_txns")}
NEW_COMPARED = {"crash_image_shards": None, "crash_image_mismatch": 0,
                "data_fsyncs": None, "kv_syncs": None,
                "commit_groups": None, "groups_without_kv_sync": 0,
                "groups_without_data_sync": 0, "acks_before_commit": 0,
                "osds_without_data_fsync": 0, "block_bytes_start": None,
                "block_bytes_close": None, "crash_image_ms": None,
                "store_fs_ram": None}


def toy_manifest(tmp_path, **options):
    """The toy manifest with the configuration's directory put under
    this test's tmp_path (the file holds a path that does not exist)."""
    man = manifest.Manifest(path=TOY)
    cfg = man.config("toy_k4m2_blockstore")
    assert not pathlib.Path(cfg["options"]["objectstore_path"]).exists()
    cfg["options"].update(objectstore_path=str(tmp_path / "osds"),
                          **options)
    man.config = lambda name: cfg
    return man


def run_toy(man, tmp_path, *, trace=False, seed=2_600_000_011):
    out, err = io.StringIO(), io.StringIO()
    result = asyncio.run(asyncio.wait_for(harness.run_cell(
        man, CELL, seed, 1.0, trace, require_tpu=False, out=out, err=err,
        trace_dir=str(tmp_path / "trace")), 240.0))
    lines = out.getvalue().strip().splitlines()
    assert json.loads(lines[-1]) == json.loads(json.dumps(result))
    return result, json.loads(lines[-2][len("diag "):]), err.getvalue()


def check_mirror(real):
    """The toy cell reports what `real`'s cell reports, under the real
    readers: the same end-to-end metrics and the same per-layer set."""
    toy = manifest.Manifest(path=TOY)
    cell = "rb_write_4m_qd16_blockstore"
    for section in ("end_to_end", "per_layer"):
        assert [m["name"] for m in toy.metrics_of(CELL, section)] == [
            m["name"] for m in real.metrics_of(cell, section)]
    assert STORE_METRICS <= {
        m["name"] for m in real.metrics_of(cell, "per_layer")}
    ctl = {m["name"] for m in real.metrics_of("rb_write_4m_qd16",
                                              "per_layer")}
    new = {m["name"] for m in real.metrics_of(cell, "per_layer")}
    assert new - ctl == STORE_METRICS and ctl <= new
    t_real = real.traffic(real.workload(cell)["traffic"])
    t_ctl = real.traffic("rb_write_4m_qd16")
    for key in ("depth", "object_size", "read_ratio", "write_objects",
                "write_select", "payloads", "ramp_s", "check_shards"):
        assert t_real[key] == t_ctl[key], key
    assert t_real["kind"] == "closed_loop_restart"
    cfg = real.config("radosbench_ec_k4m2_blockstore")
    # a plain relative name: the cluster puts it under the run's own
    # temp directory, one per process, and removes it at the end
    path = cfg["options"]["objectstore_path"]
    assert path and pathlib.Path(path).name == path
    kind = real.kind("closed_loop_restart")
    own = pathlib.Path(kind.store_dir(path))
    assert own.is_absolute() and REPO not in own.parents
    assert own.name.endswith(f".{os.getpid()}")
    assert kind.store_dir("/abs/dir") == "/abs/dir"
    assert cfg["options"]["objectstore"] == cfg["objectstore"] \
        == "blockstore"


def test_toy_manifest_mirrors_the_cells_entries():
    check_mirror(manifest.Manifest())


def test_rehearsal_is_correct_and_prints_the_new_numbers(tmp_path):
    result, diag, err = run_toy(toy_manifest(tmp_path), tmp_path)
    assert result["correct"] is True, err
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"goodput", "write_p95", "setup_s"}
    cmp_ = result["compared"]
    for name, limit in NEW_COMPARED.items():
        assert cmp_[name]["limit"] == limit
        assert any(ln.startswith(f"compared {name}: ")
                   for ln in err.splitlines()), name
    # every known object of the ring, every shard (k + m = 6 of each):
    # at most `depth` of the 16 names had a write in flight
    assert (16 - 4) * 6 <= cmp_["crash_image_shards"]["value"] <= 16 * 6
    assert cmp_["crash_image_mismatch"]["value"] == 0
    assert cmp_["readback_mismatch"]["value"] == 0
    assert cmp_["shard_mismatch"]["value"] == 0
    assert cmp_["commit_groups"]["value"] == cmp_["kv_syncs"]["value"] > 0
    assert cmp_["data_fsyncs"]["value"] > 0
    assert cmp_["acks_before_commit"]["value"] == 0
    assert cmp_["groups_without_data_sync"]["value"] == 0
    assert cmp_["store_fs_ram"]["value"] in (0, 1)
    assert cmp_["block_bytes_start"]["value"] > 0
    assert diag["window_jax"]["compile_events"] == 0
    assert diag["osdmap_epoch"][0] == diag["osdmap_epoch"][1]
    assert err.strip().splitlines()[-1] == "correct: True"
    # the stores are where the configuration says, and the image's
    # copies are gone
    left = sorted(p.name for p in (tmp_path / "osds").iterdir())
    assert left == [f"osd.{i}" for i in range(6)]


def test_traced_rehearsal_reports_the_store_metrics(tmp_path):
    man = toy_manifest(tmp_path)
    result, _diag, err = run_toy(man, tmp_path, trace=True)
    assert result["correct"] is True, err
    got = set(result["metrics"])
    declared = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    silent = {n for n in declared if n.startswith("kernel.")}
    assert got == declared - silent and STORE_METRICS <= got
    for name in STORE_METRICS:
        assert result["metrics"][name]["value"] > 0, name
    m = result["metrics"]
    # a transaction waits at least for its group's two barriers
    assert m["store.commit_wait_ms.goodput"]["value"] >= \
        m["store.kv_sync_ms.goodput"]["value"]
    assert m["store.group_txns.goodput"]["value"] >= 1.0
    assert m["seam.device_byte_fraction.goodput"]["value"] == 100.0
    named = {name for name, _s in result["breakdown"]["idle_gaps"]}
    assert {"store_data_sync", "store_kv_sync"} & named


def plant_early_ack(hold_s: float, lag: int):
    """A stand-in for benchmark/faults.py's `plant`: every store's
    commit group posts its completion records FIRST, and its barriers
    run `lag` groups later, groups `hold_s` apart.  (0.0, 0) is the
    pure reordering: nothing held, the records leave and the same
    group's barriers follow at once.  One crash image is one instant,
    and barriers that trail their acks by less than the ack's way back
    to the client are behind it at every instant a client can name: the
    image sees the fault only where it holds them, (0.15, 2); the
    commit thread's own count sees both.  Planted where the harness
    plants a fault: set-up done, just before the load."""
    def plant(fault, env):
        assert fault == "early_ack"
        undos = []
        for osd in env.cluster.osds.values():
            com, lock, owed = osd.store._committer, threading.Lock(), []
            real = com._commit

            def early(group, com=com, real=real, lock=lock, owed=owed):
                with lock:
                    if "off" in owed:           # the fault is removed
                        return real(group)
                    com._complete(group)
                    owed.append(group)
                    due = owed.pop(0) if len(owed) > lag else None
                time.sleep(hold_s)
                if due is not None:
                    real(due)

            def undo(com=com, real=real, lock=lock, owed=owed):
                with lock:
                    due, owed[:] = list(owed), ["off"]
                    del com._commit
                for group in due:
                    real(group)
            com._commit = early
            undos.append(undo)
        return lambda: [u() for u in undos]
    return plant


@pytest.mark.parametrize("hold_s,lag", [(0.0, 0), (0.15, 2)],
                         ids=["pure_reordering", "barriers_held"])
def test_control_acks_before_the_barriers_is_not_correct(
        tmp_path, monkeypatch, hold_s, lag):
    """The served read-back cannot see an ack that ran ahead of its
    barriers (memory holds the write); the commit thread's count does,
    and where the barriers are held the crash image does too."""
    from benchmark import faults
    monkeypatch.setattr(faults, "plant", plant_early_ack(hold_s, lag))
    out, err = io.StringIO(), io.StringIO()
    result = asyncio.run(asyncio.wait_for(harness.run_cell(
        toy_manifest(tmp_path), CELL, 2_600_000_012, 1.0, False,
        require_tpu=False, fault="early_ack", out=out, err=err), 240.0))
    cmp_ = result["compared"]
    assert result["correct"] is False, err.getvalue()
    assert cmp_["acks_before_commit"]["value"] > 0
    if lag:
        assert cmp_["crash_image_mismatch"]["value"] > 0
    assert cmp_["readback_mismatch"]["value"] == 0
    assert cmp_["groups_without_data_sync"]["value"] == 0
    assert cmp_["groups_without_kv_sync"]["value"] == 0
    assert err.getvalue().strip().splitlines()[-1] == "correct: False"


def test_kind_refuses_a_deployment_that_is_not_the_configured_one(
        tmp_path):
    """What a program that ignores `objectstore` meets (the parent of
    the PR that added the cell): MemStore under a configuration that
    states blockstore.  No result line, an error that says why."""
    man = toy_manifest(tmp_path, objectstore="memstore")
    out = io.StringIO()
    with pytest.raises(RuntimeError, match="refusing to measure"):
        asyncio.run(asyncio.wait_for(harness.run_cell(
            man, CELL, 1, 1.0, False, require_tpu=False, out=out,
            err=io.StringIO()), 240.0))
    assert out.getvalue() == ""

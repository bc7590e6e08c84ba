"""Toy-size rehearsals of `cos_mix_sizes` on the CPU: the kind
`closed_loop_sizes` through the same harness the chip runs, traced and
not, with the seam's lane buckets patched small so that the largest
class takes many launch windows; its plan by hand (every block of every
worker holds the histogram exactly); its comparison catching one flipped
byte in a stored parity shard of the largest class; the two per-layer
readers it brings; and the real manifest's new entries and files against
what the cell is defined by.

Nothing here is a number about speed: the device is the CPU."""

import asyncio
import io
import json
import pathlib
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import harness, manifest
import test_benchmark_loop_account as loop_account_tests
import test_benchmark_manifest as manifest_tests
import test_benchmark_spans as spans_tests

REPO = pathlib.Path(__file__).resolve().parents[2]
TOY = REPO / "tests" / "benchmark" / "toy" / "manifest_sizes.json"
CELL, REAL, REAL_CONTROL = "toy_sizes", "cos_mix_sizes", "cos_mix_64k_w8"
CONFIG = "cosbench_sizes_ec_k2m1"
NEW_METRICS = ("seam.window_ms.goodput", "seam.windows_per_group.goodput")
#: the seam's buckets in the toy runs: the largest class, 128 KiB, is
#: sixteen windows of the top one
SMALL_BUCKETS = (256, 1024, 4096)
LIMIT_0 = ("window_read_mismatch", "readback_mismatch", "shard_mismatch",
           "device_bytes_short", "host_bytes", "device_fallbacks",
           "ops_failed")
PRINTED = ("throttle_waits", "inflight_bytes_peak", "inflight_ops_peak",
           "device_lanes_launched", "device_pad_lanes", "bytes_written",
           "readback_objects", "shards_checked")


@pytest.fixture
def toy(monkeypatch):
    """The toy manifest with the real cell's per-layer entries, each
    naming the toy cell, and the seam's buckets patched small."""
    from ceph_tpu.osd import ec_queue
    monkeypatch.setattr(ec_queue, "LANE_BUCKETS", SMALL_BUCKETS)
    real = manifest.Manifest()
    doc = json.loads(TOY.read_text())
    for spec in real.metrics_of(REAL, "per_layer"):
        doc["per_layer"].append(dict(spec, workloads=[CELL]))
    man = manifest.Manifest(path=TOY)
    man.doc, man.per_layer = doc, {m["name"]: m for m in doc["per_layer"]}
    return man


def run_toy(man, *, trace=False, seed=4_100_000_011, seconds=1.5,
            tmp_path=None):
    out, err = io.StringIO(), io.StringIO()
    result = asyncio.run(asyncio.wait_for(harness.run_cell(
        man, CELL, seed, seconds, trace, require_tpu=False, out=out,
        err=err, trace_dir=str(tmp_path / "trace") if tmp_path else None),
        240.0))
    lines = out.getvalue().strip().splitlines()
    assert json.loads(lines[-1]) == json.loads(json.dumps(result))
    return result, json.loads(lines[-2][len("diag "):]), err.getvalue()


def bare_load(man, traffic, seed, scale=1):
    """A Load without a cluster; `scale` divides every object size (the
    plan does not depend on them, the payloads' memory does)."""
    t = json.loads(json.dumps(man.traffic(traffic)))
    for c in t["classes"]:
        c["object_size"] //= scale
    env = SimpleNamespace(cell="c", seed=seed, k=2, m=1, traffic=t)
    return man.kind("closed_loop_sizes").Load(env)


# ------------------------------------------------------------- the plan
def check_blocks(load):
    """Every block of every worker's plan holds each class's per_block
    exactly and `read_ratio` of its ops as reads; every class is read
    `read_ratio` of its ops over each group of blocks; reads name the
    class's read range, writes the worker's own slice of its ring."""
    kind = manifest.Manifest().kind("closed_loop_sizes")
    per_block = [c.per_block for c in load.classes]
    share = 1.0 - load.read_ratio
    g = kind.blocks_per_group(load.block_ops, per_block, share)
    for w, plan in enumerate(load.plan):
        n = len(plan["cls"])
        assert n % (load.block_ops * g) == 0
        assert n >= kind.PLAN_BLOCKS * load.block_ops
        cls = plan["cls"].reshape(-1, load.block_ops)
        rd = plan["is_read"].reshape(-1, load.block_ops)
        for c, k in enumerate(load.classes):
            assert ((cls == c).sum(axis=1) == k.per_block).all()
            writes = ((cls == c) & ~rd).sum(axis=1).reshape(-1, g)
            assert (writes.sum(axis=1) == round(
                k.per_block * g * share)).all()
            at = plan["cls"] == c
            reads = plan["obj"][at & plan["is_read"]]
            assert reads.min() >= 0 and reads.max() < k.n_read
            mine = plan["obj"][at & ~plan["is_read"]]
            assert set(mine.tolist()) <= set(range(w, k.n_write,
                                                   load.depth))
            pay = plan["pay"][at]
            assert pay.min() >= 0 and pay.max() < len(k.payloads)
        assert (rd.sum(axis=1) == round(load.block_ops
                                        * load.read_ratio)).all()


def test_every_block_of_every_workers_plan_holds_the_histogram():
    real = manifest.Manifest()
    load = bare_load(real, REAL, 2_500_000_000, scale=1024)
    assert [c.per_block for c in load.classes] == [20, 15, 10, 4, 1]
    assert load.block_ops == 50 and load.depth == 8
    check_blocks(load)
    # in a group of five blocks exactly one of {a 4 MiB, the 64 MiB op}
    # is written in each block: 4 and 1 writes a group
    plan = load.plan[3]
    cls = plan["cls"].reshape(-1, 50)
    rd = plan["is_read"].reshape(-1, 50)
    big = ((cls >= 3) & ~rd).sum(axis=1)
    assert (big == 1).all()


def test_the_plan_repeats_for_a_seed_and_names_for_every_seed():
    real = manifest.Manifest()
    a, b = (bare_load(real, REAL, 7, scale=1024) for _ in range(2))
    c = bare_load(real, REAL, 2_500_000_000, scale=1024)
    for w in range(8):
        for key in ("cls", "is_read", "obj", "pay", "keep"):
            assert np.array_equal(a.plan[w][key], b.plan[w][key]), key
        assert not np.array_equal(a.plan[w]["cls"], c.plan[w]["cls"])
    for x, y, z in zip(a.classes, b.classes, c.classes):
        assert x.payloads == y.payloads and x.payloads != z.payloads
        assert (x.read_names, x.write_names) == (z.read_names,
                                                 z.write_names)
    names = [n for k in a.classes for n in k.read_names + k.write_names]
    assert len(set(names)) == len(names) == 4096 + 2048 + 256 + 64 + 16 \
        + 512 + 256 + 64 + 16 + 8
    assert a.classes[4].write_names[0] == "benchmark_data_c_64m_objectw0"
    # no class's payloads begin another's
    heads = {p[:16] for k in a.classes for p in k.payloads}
    assert len(heads) == sum(len(k.payloads) for k in a.classes)


def test_writes_are_dealt_so_every_class_keeps_its_share():
    kind = manifest.Manifest().kind("closed_loop_sizes")
    rng = np.random.default_rng(1)
    assert kind.blocks_per_group(50, [20, 15, 10, 4, 1], 0.2) == 5
    out = kind.writes_per_block(rng, 50, [20, 15, 10, 4, 1], 0.2)
    assert out.shape == (5, 5)
    assert (out.sum(axis=1) == 10).all()
    assert out.sum(axis=0).tolist() == [20, 15, 10, 4, 1]
    assert (out[:, :3] == [4, 3, 2]).all()
    # a share that comes to a whole number over no group of 100 blocks
    with pytest.raises(ValueError, match="whole number"):
        kind.writes_per_block(rng, 1, [1], 1 / 101.0)


def test_a_mix_whose_blocks_do_not_add_up_is_refused():
    real = manifest.Manifest()
    t = json.loads(json.dumps(real.traffic(REAL)))
    t["block_ops"] = 49
    for c in t["classes"]:
        c["object_size"] //= 1024
    env = SimpleNamespace(cell="c", seed=1, k=2, m=1, traffic=t)
    with pytest.raises(ValueError, match="per_block"):
        real.kind("closed_loop_sizes").Load(env)


# -------------------------------------------------- the toy rehearsals
def test_toy_cell_is_correct_compiles_nothing_and_the_budget_acts(toy):
    result, diag, err = run_toy(toy)
    assert result["correct"] is True, err
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"goodput", "setup_s"}
    cmp_ = result["compared"]
    for name in LIMIT_0:
        assert cmp_[name] == {"value": 0, "limit": 0}, name
    for name in PRINTED:
        assert cmp_[name]["limit"] is None, name
    # the toy's budget of 200k: two 128 KiB ops do not fit together
    assert cmp_["throttle_waits"]["value"] > 0
    assert cmp_["inflight_bytes_peak"]["value"] <= 200 * 1024
    # every class read, kept and written in the window
    load_classes = ("1k", "8k", "32k", "128k")
    for name in load_classes:
        assert cmp_[f"window_reads_kept_{name}"]["value"] > 0, name
        for kind in ("read", "write"):
            assert cmp_[f"{kind}_{name}_ops"]["value"] > 0
            assert 0 < cmp_[f"{kind}_{name}_p50_ms"]["value"] <= \
                cmp_[f"{kind}_{name}_p95_ms"]["value"]
    # the seam's bytes over the load: what the kind wrote, no less, and
    # the launched lanes less the pad are the requests' own
    assert cmp_["device_bytes"]["value"] >= cmp_["bytes_written"]["value"]
    lanes = cmp_["device_lanes_launched"]["value"]
    pad = cmp_["device_pad_lanes"]["value"]
    assert 0 <= pad < lanes
    assert lanes - pad == cmp_["device_bytes"]["value"] // 2
    # a shard of every class compared, two of the largest among them
    assert cmp_["shards_checked"]["value"] == 3 * (2 + 2 + 2 + 2)
    # nothing compiled in the window, though groups of every width met
    assert diag["window_jax"]["compile_events"] == 0
    assert diag["window_jax"]["cache_misses"] == 0


def test_traced_toy_cell_reports_the_cells_per_layer_metrics(toy,
                                                            tmp_path):
    result, _diag, err = run_toy(toy, trace=True, tmp_path=tmp_path)
    assert result["correct"] is True, err
    declared = {m["name"] for m in toy.metrics_of(CELL, "per_layer")}
    # the device-trace readers find no TPU plane on the CPU
    silent = {n for n in declared if n.startswith("kernel.")}
    assert set(result["metrics"]) == declared - silent
    got = result["metrics"]
    assert got["seam.window_ms.goodput"]["value"] > 0
    # the largest class alone is sixteen windows of the top bucket
    assert got["seam.windows_per_group.goodput"]["value"] > 1.0
    assert got["launch.window_compiles.goodput"]["value"] == 0
    assert got["seam.device_byte_fraction.goodput"]["value"] == 100.0


def test_verify_catches_one_flipped_byte_in_a_largest_class_parity_shard(
        toy, monkeypatch):
    """A stored parity shard of the largest class reads with one byte
    flipped (a client read never touches parity on a healthy pool): the
    comparison's shard check alone has to catch it."""
    from ceph_tpu.store.memstore import MemStore
    real_read = MemStore.read

    def read(self, cid, oid, *args, **kw):
        raw = real_read(self, cid, oid, *args, **kw)
        if cid.name.endswith("s2_head") and "_128k_objectw" in oid.name:
            raw = bytearray(raw)
            raw[len(raw) // 2] ^= 0x40
            raw = bytes(raw)
        return raw
    monkeypatch.setattr(MemStore, "read", read)
    result, _diag, err = run_toy(toy, seconds=0.8)
    assert result["correct"] is False
    cmp_ = result["compared"]
    assert cmp_["shard_mismatch"]["value"] == 2      # both sampled
    assert cmp_["readback_mismatch"]["value"] == 0
    assert cmp_["window_read_mismatch"]["value"] == 0
    assert err.strip().splitlines()[-1] == "correct: False"


def test_shard_check_computes_parity_a_block_at_a_time(monkeypatch):
    """The reference's parity of a large object, a block of lanes at a
    time, is its parity in one go; a flipped byte anywhere fails it."""
    from benchmark import reference
    kind = manifest.Manifest().kind("closed_loop_sizes")
    monkeypatch.setattr(kind, "PARITY_BLOCK_LANES", 1000)
    rng = np.random.default_rng(4)
    chunks = rng.integers(0, 256, (2, 4500), dtype=np.uint8)
    gen = reference.generator(2, 1)
    parity = reference.apply(gen[2:], chunks)[0]
    assert kind._same_shard(parity, chunks, gen, 2, 2)
    assert kind._same_shard(chunks[1].copy(), chunks, gen, 1, 2)
    for at in (0, 999, 1000, 4499):
        bad = parity.copy()
        bad[at] ^= 1
        assert not kind._same_shard(bad, chunks, gen, 2, 2)
    assert not kind._same_shard(parity[:-1], chunks, gen, 2, 2)


# -------------------------------------------------------- the readers
def test_the_two_seam_readers_on_hand_made_and_parent_observations():
    real = manifest.Manifest()
    hand = SimpleNamespace(ops=200, stages={
        "seam_window": (90, 1.2), "seam_launch": (90, 0.5),
        "seam_h2d": (90, 0.6), "seam_fold": (30, 0.1)})
    assert real.reader("seam.window_ms.goodput")(hand) == \
        pytest.approx(6.0)                       # 1.2 s / 200 ops
    assert real.reader("seam.windows_per_group.goodput")(hand) == 3.0
    # a parent commit has no seam_window: nothing to read, never 0
    parent = SimpleNamespace(ops=200, stages={
        "seam_launch": (30, 0.5), "seam_fold": (30, 0.1)})
    for name in NEW_METRICS:
        assert real.reader(name)(parent) is None
        assert real.reader(name)(SimpleNamespace(ops=0, stages={})) is None


# ------------------------------------------------- files and entries
def test_the_cell_and_its_configuration_are_what_the_cell_is_defined_by():
    real = manifest.Manifest()
    cell = real.workload(REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, REAL, 1)
    cfg, base = real.config(CONFIG), real.config("cosbench_64k_ec_k2m1")
    assert real.configs[CONFIG]["source"] == cfg["source"]
    assert len(cfg["source"]) <= 200 and "h()" in cfg["source"]
    assert "k=2 m=1" in cfg["source"]
    for key in ("osds", "objectstore", "chips", "workers", "read_ratio",
                "write_ratio", "reduced", "reduced_why"):
        assert cfg[key] == base[key], key
    assert cfg["pool"] == base["pool"]
    assert cfg["options"] == dict(
        base["options"], objecter_inflight_ops=1024,
        objecter_inflight_op_bytes="100m", osd_ec_batch_min_bytes=0)
    assert set(cfg["options_why"]) == {
        "objecter_inflight_ops", "objecter_inflight_op_bytes",
        "osd_ec_batch_min_bytes"}
    text = " ".join(cfg["guarantees"])
    for phrase in ("acked only after all k+m=3 shards are applied",
                   "the last acked write, at the object's own length",
                   "no durability"):
        assert phrase in text, phrase
    # the traffic says what the configuration says
    t = real.traffic(REAL)
    assert t["depth"] == cfg["workers"]
    assert t["read_ratio"] * 100 == cfg["read_ratio"]
    assert [c["object_size"] for c in t["classes"]] == cfg["object_sizes"]
    weights = [c["per_block"] * 100 // t["block_ops"] for c in t["classes"]]
    assert weights == cfg["size_weights"] == [40, 30, 20, 8, 2]
    assert cfg["sizes"] == "h(" + ",".join(
        f"{s // 1024}|{s // 1024}|{w}" for s, w in zip(
            cfg["object_sizes"], weights)) + ")KB"
    # the data set the configuration states: 1.64 GiB of reads, 0.64 GiB
    # of ring, 613 MiB of payloads
    reads = sum(c["object_size"] * c["read_objects"] for c in t["classes"])
    ring = sum(c["object_size"] * c["write_objects"] for c in t["classes"])
    pays = sum(c["object_size"] * c["payloads"] for c in t["classes"])
    assert (round(reads / 2 ** 30, 2), round(ring / 2 ** 30, 2),
            pays // 2 ** 20) == (1.64, 0.64, 613)
    assumed = " ".join(cfg["assumed"])
    for phrase in ("no public source fixes them", "1,864 KiB", "70%",
                   "blocks of 50 ops", "1.64 GiB", "0.64 GiB", "613 MiB",
                   "osd_ec_batch_min_bytes 0"):
        assert phrase in assumed, phrase


def test_the_cell_reports_goodput_and_the_goodput_cells_layer_metrics():
    real = manifest.Manifest()
    assert [m["name"] for m in real.metrics_of(REAL, "end_to_end")] == [
        "goodput", "setup_s"]
    got = {m["name"] for m in real.metrics_of(REAL, "per_layer")}
    goodput_cells = [w for w in real.end_to_end["goodput"]["workloads"]
                     if w != REAL]
    shared = set.intersection(*[{m["name"] for m in real.metrics_of(
        w, "per_layer")} for w in goodput_cells])
    assert shared <= got
    for name in ("ec.encode_ms.goodput", "osd.replica_rtt_ms.goodput",
                 "ec.read_gather_ms.goodput") + NEW_METRICS:
        assert name in got, name
    for name in NEW_METRICS:
        assert real.per_layer[name] == {
            "name": name, "unit": real.per_layer[name]["unit"],
            "better": "lower", "source": "program_span",
            "layer": "Device seam", "moves": "goodput",
            "workloads": [REAL]}
        assert callable(real.reader(name))
    assert real.per_layer["seam.window_ms.goodput"]["unit"] == "ms"
    # appended last, after every entry the benchmark had
    names = [m["name"] for m in real.doc["per_layer"]]
    assert names[-2:] == list(NEW_METRICS)
    assert real.doc["workloads"][-1]["name"] == REAL
    assert real.doc["configs"][-1]["name"] == CONFIG


def test_the_real_manifest_passes_every_check_of_the_append_test():
    real = manifest.Manifest()
    manifest_tests.check_manifest(real)
    loop_account_tests.check_the_nine_are_declared(real)
    for name in loop_account_tests.NEW:
        loop_account_tests.check_entry(real, name)
    for name in spans_tests.NEW:
        spans_tests.check_entry(real, name)
    assert REAL in real.per_layer["ec.read_gather_ms.goodput"]["workloads"]

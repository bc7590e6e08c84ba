"""Toy-size rehearsals of `ycsb_a_1k_zipf` on the CPU: the kind
`closed_loop_records` through the same harness the chip runs, traced
and not; the selector (Zipf 0.99, a fixed rank-to-name permutation) and
the object model (`RecordOrder`) by hand; the padded reference against
the program's own split + encode at small sizes; the controls (a stale
write, a flipped answer, and a read sent ahead of the write submitted
before it each have to come out `correct: false`); and the real
manifest's new entries and files against what ISSUE 32 names.

Nothing here is a number about speed: the device is the CPU."""

import asyncio
import io
import json
import pathlib
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import faults, harness, manifest, reference, reference_padded

REPO = pathlib.Path(__file__).resolve().parents[2]
TOY = REPO / "tests" / "benchmark" / "toy" / "manifest_records.json"
CELL = "toy_records"
REAL, REAL_CONTROL = "ycsb_a_1k_zipf", "cos_mix_64k_w8"
#: the two stages of the cell that lie inside `osd.queue_ms`, and the
#: wait to reply in order, which was cut out of the first of them
QUEUE_METRICS = {"osd.dep_wait_ms.op_rate", "osd.admit_wait_ms.op_rate"}
NEW_METRICS = QUEUE_METRICS | {"osd.reply_wait_ms.op_rate"}
LIMIT_0 = ("write_order_violations", "read_order_violations",
           "read_length_mismatch", "readback_mismatch", "shard_mismatch",
           "host_bytes", "device_fallbacks", "device_bytes_short",
           "ops_failed")
PRINTED = ("overlapping_writes", "reads_behind_a_write", "order_tested",
           "hottest_object_ops", "hottest_pg_share", "throttle_waits",
           "inflight_ops_peak", "unknown_reads", "unknown_objects",
           "device_lanes_launched", "same_object_waits",
           "window_full_waits", "chain_peak")


@pytest.fixture(scope="module")
def toy():
    return manifest.Manifest(path=TOY)


def run_toy(man, cell, *, trace=False, fault=None, seed=3_200_000_011,
            seconds=1.5, tmp_path=None):
    out, err = io.StringIO(), io.StringIO()
    result = asyncio.run(asyncio.wait_for(harness.run_cell(
        man, cell, seed, seconds, trace, require_tpu=False, fault=fault,
        out=out, err=err,
        trace_dir=str(tmp_path / "trace") if tmp_path else None), 240.0))
    lines = out.getvalue().strip().splitlines()
    assert json.loads(lines[-1]) == json.loads(json.dumps(result))
    return result, json.loads(lines[-2][len("diag "):]), err.getvalue()


def bare_load(man, traffic, seed, **over):
    env = SimpleNamespace(cell="c", seed=seed, k=2, m=1,
                          traffic=dict(man.traffic(traffic), **over))
    return man.kind("closed_loop_records").Load(env)


# ------------------------------------------------------ files and entries
def test_the_mix_and_the_configuration_hold_what_the_cell_is_defined_by():
    real = manifest.Manifest()
    cell = real.workload(REAL)
    assert cell["chips"] == 1 and cell["config"] == "ycsb_a_1k_ec_k2m1"
    mix = dict(real.traffic(cell["traffic"]))
    assert mix.pop("what") and mix.pop("prepare_depth") >= 1
    assert mix == {
        "kind": "closed_loop_records", "depth": 64, "object_size": 1000,
        "read_ratio": 0.5, "records": 32768, "select": "zipfian",
        "zipf_constant": 0.99, "payloads": 256, "ramp_s": 3.0,
        "check_shards": 64, "check_hottest": 8}
    cfg, base = real.config(cell["config"]), real.config(
        "cosbench_64k_ec_k2m1")
    for key in ("osds", "objectstore", "chips"):
        assert cfg[key] == base[key], key
    assert cfg["pool"] == dict(base["pool"], name=cfg["pool"]["name"])
    assert cfg["options"] == dict(
        base["options"], objecter_inflight_ops=1024,
        objecter_inflight_op_bytes="100m", osd_ec_batch_min_bytes=0)
    want = {"recordcount": 32768, "fieldcount": 10, "fieldlength": 100,
            "object_size": 1000, "threads": 64, "readproportion": 0.5,
            "updateproportion": 0.5, "clients": 1,
            "requestdistribution": {"name": "zipfian", "constant": 0.99}}
    for key, val in want.items():
        assert cfg[key] == val, key
    assert cfg["fieldcount"] * cfg["fieldlength"] == cfg["object_size"]
    entry = real.configs[cell["config"]]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert "workloads/workloada" in cfg["source"]
    assert "k=2 m=1" in cfg["source"]
    # the traffic file says what the configuration says
    t = real.traffic(cell["traffic"])
    assert (t["depth"], t["records"], t["object_size"]) == (
        cfg["threads"], cfg["recordcount"], cfg["object_size"])
    assert t["read_ratio"] == cfg["readproportion"]
    assert t["zipf_constant"] == cfg["requestdistribution"]["constant"]


def test_the_configuration_states_its_guarantees_cuts_and_settings():
    cfg = manifest.Manifest().config("ycsb_a_1k_ec_k2m1")
    text = " ".join(cfg["guarantees"])
    for phrase in ("acked only after all k+m=3 shards are applied",
                   "apply in the order submitted",
                   "exactly the bytes of the last write submitted before "
                   "it to that object",
                   "1,000 bytes, not its padded stripe",
                   "no durability"):
        assert phrase in text, phrase
    assert cfg["reduced"] == ["osds", "objectstore", "clients",
                              "recordcount"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    why = cfg["reduced_why"]
    assert "one Rados handle, one Objecter, one op budget" in why["clients"]
    for phrase in ("10^6 to 10^8", "32,768", "6.5%", "8.7%"):
        assert phrase in why["recordcount"], phrase
    assumed = " ".join(cfg["assumed"])
    for phrase in ("write_full", "writes one field", "rejects partial writes",
                   "plain Zipf(0.99)", "FIXED permutation",
                   "scrambled zipfian", "do not depend on the seed",
                   "64 threads", "cos_write_64k_w64's depth",
                   "CHUNK_ALIGN 128 = 512 bytes",
                   "osd_pool_erasure_code_stripe_width 4096",
                   "2 KiB chunks: not modelled",
                   "osd_ec_batch_min_bytes 0", "host_bytes 0",
                   "does not answer it"):
        assert phrase in assumed, phrase
    # the figures the file quotes, from the distribution itself
    kind = manifest.Manifest().kind("closed_loop_records")
    for n, share in ((32768, 0.087), (10 ** 6, 0.065)):
        assert kind.zipf_cdf(n, 0.99)[0] == pytest.approx(share, abs=5e-4)
    cdf = kind.zipf_cdf(32768, 0.99)
    assert cdf[9] == pytest.approx(0.256, abs=1e-3)
    assert cdf[99] == pytest.approx(0.459, abs=1e-3)


def check_mirror(real):
    """The toy cell reports what `real`'s cell reports, and the cell, its
    configuration and its own metrics are declared, found by name
    wherever in their lists they sit."""
    toy = manifest.Manifest(path=TOY)
    for section in ("end_to_end", "per_layer"):
        assert [m["name"] for m in toy.metrics_of(CELL, section)] == [
            m["name"] for m in real.metrics_of(REAL, section)]
    assert [m["name"] for m in real.metrics_of(REAL, "end_to_end")] == [
        "op_rate", "write_p95", "read_p95", "setup_s"]
    ctl = {m["name"] for m in real.metrics_of(REAL_CONTROL, "per_layer")}
    new = {m["name"] for m in real.metrics_of(REAL, "per_layer")}
    assert new - ctl == NEW_METRICS and ctl <= new
    for name in NEW_METRICS:
        assert real.per_layer[name] == {
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "OSD / PG",
            "moves": "op_rate", "workloads": [REAL]}
    assert "ycsb_a_1k_ec_k2m1" in real.configs
    cell = real.workloads[REAL]
    assert (cell["config"], cell["traffic"]) == ("ycsb_a_1k_ec_k2m1", REAL)


def test_toy_manifest_mirrors_the_cells_entries():
    check_mirror(manifest.Manifest())


def test_kind_and_reference_import_nothing_of_the_program_to_compare():
    for rel in ("benchmark/reference_padded.py",
                "benchmark/kinds/closed_loop_records.py"):
        top = [ln for ln in (REPO / rel).read_text().splitlines()
               if ln.startswith(("import ", "from "))]
        assert top and not any("ceph_tpu" in ln for ln in top), rel
    # the reference not even inside a function
    assert not [ln for ln in (REPO / "benchmark/reference_padded.py")
                .read_text().splitlines()
                if ln.strip().startswith(("import ceph_tpu",
                                          "from ceph_tpu"))]


# ------------------------------------------------------------ the selector
def test_selector_draws_zipf_and_the_plan_repeats_for_a_seed(toy):
    kind = toy.kind("closed_loop_records")
    n = 32768
    cdf = kind.zipf_cdf(n, 0.99)
    share = np.diff(np.concatenate([[0.0], cdf]))
    assert share[0] / share[1] == pytest.approx(2 ** 0.99)
    draws = 400_000
    ranks = kind.draw_ranks(np.random.default_rng(5), cdf, draws)
    assert ranks.min() == 0 and ranks.max() < n
    seen = np.bincount(ranks, minlength=n)[:100] / draws
    # each of the first 100 ranks within five standard errors of its share
    sigma = np.sqrt(share[:100] * (1 - share[:100]) / draws)
    assert np.all(np.abs(seen - share[:100]) < 5 * sigma)
    assert seen.sum() == pytest.approx(cdf[99], abs=0.005)
    # one seed, one plan; another seed, another plan, the same names
    a, b = (bare_load(toy, "toy_records", 7) for _ in range(2))
    c = bare_load(toy, "toy_records", 2_500_000_000)
    for key in ("plan_read", "plan_rank", "plan_pay"):
        assert np.array_equal(getattr(a, key), getattr(b, key)), key
    assert a.payloads == b.payloads and a.payloads != c.payloads
    assert not np.array_equal(a.plan_rank, c.plan_rank)
    assert a.names == c.names and len(set(a.names)) == 64
    assert a.names[0] == "benchmark_data_c_object0"
    assert np.array_equal(a.record_of, c.record_of)
    assert sorted(a.record_of) == list(range(64))
    assert not np.array_equal(a.record_of, np.arange(64))   # scattered
    assert 0.47 < a.plan_read.mean() < 0.53
    assert a.plan_rank.shape == (8, kind.PLAN_OPS)
    # reads and updates share the ONE range; no worker owns a record
    hot = int(a.record_of[0])
    assert all(hot in a.record_of[a.plan_rank[w]] for w in range(8))
    # the seam sees the CHUNK (the padded geometry), not size // k
    assert a.seam_shapes() == {"lanes": 512, "depth": 8, "encode": True,
                               "decode": False}
    assert a.seam_rows() == 1
    with pytest.raises(ValueError, match="selector"):
        bare_load(toy, "toy_records", 7, select="latest")
    with pytest.raises(ValueError, match="between 0 and 1"):
        bare_load(toy, "toy_records", 7, read_ratio=1.0)


def test_real_names_and_hot_records_are_the_same_for_every_seed():
    real = manifest.Manifest()
    loads = []
    for seed in (1, 2_500_000_000):
        env = SimpleNamespace(cell=REAL, seed=seed, k=2, m=1,
                              traffic=real.traffic(REAL))
        loads.append(real.kind("closed_loop_records").Load(env))
    a, b = loads
    assert a.names == b.names and len(set(a.names)) == 32768
    assert a.names[5] == "benchmark_data_ycsb_a_1k_zipf_object5"
    assert np.array_equal(a.record_of, b.record_of)
    assert len(a.payloads) == 256 and len(a.payloads[0]) == 1000
    assert a.depth == 64 and a.ramp_s == 3.0


# -------------------------------------------------------- the object model
def test_record_order_counts_what_a_hand_made_history_says(toy):
    kind = toy.kind("closed_loop_records")
    order = kind.RecordOrder(np.array([5, 6, 7, 8]))
    assert isinstance(order, toy.kind("open_loop").WriteOrder)
    # object 0: a read before any write is decided by the set-up
    assert order.submit_read(0) == (kind.SETUP, 5)
    order.read_reply(kind.SETUP, True)
    # write 10 in flight; a read behind it is decided by IT, and the
    # write after that read does not change what the read must return
    order.submit_write(0, 10, 1)
    assert order.submit_read(0) == (10, 1)
    order.submit_write(0, 12, 2)
    assert order.submit_read(0) == (12, 2)
    order.ack(0, 10, 1)
    order.read_reply(10, True)
    order.ack(0, 12, 2)
    order.read_reply(12, False)         # answered with something else
    assert (order.reads_behind, order.overlapping) == (2, 1)
    assert order.violations == 0 and order.holds[0] == 2
    # object 1: the later write is acked first (a write-order violation)
    order.submit_write(1, 20, 3)
    order.submit_write(1, 21, 4)
    order.ack(1, 21, 4)
    order.ack(1, 20, 3)
    assert order.violations == 1 and order.holds[1] == 4
    # object 2: a read decided by a write that FAILS is unknown, not a
    # violation, whatever it returned; one decided by a write that is
    # never answered likewise
    order.submit_write(2, 30, 1)
    order.read_reply(order.submit_read(2)[0], False)
    order.fail(2, 30)
    order.submit_write(3, 40, 2)
    order.read_reply(order.submit_read(3)[0], True)
    # a read with no write in flight on its object is not "behind" one
    assert order.submit_read(1) == (21, 4)
    order.read_reply(21, True)
    assert order.reads_behind == 4
    order.close()
    assert order.read_verdict() == (1, 2)
    assert order.unknown() == {2, 3} and not order.flying
    assert list(order.holds) == [2, 4, 7, 8]
    assert list(order.initial) == [5, 6, 7, 8]


# ----------------------------------------------------- the padded reference
@pytest.mark.parametrize("k,m", [(2, 1), (4, 2)])
@pytest.mark.parametrize("length", [1, 127, 128, 1000, 1024, 1025])
def test_padded_reference_is_the_programs_split_and_encode(k, m, length):
    from ceph_tpu.ec.registry import factory
    codec = factory("rs", {"k": str(k), "m": str(m),
                           "technique": "reed_sol_van", "backend": "host"})
    data = np.random.default_rng(length * 31 + k).integers(
        0, 256, length, dtype=np.uint8).tobytes()
    want = reference_padded.shards(data, k, m)
    chunk = reference_padded.chunk_size(length, k)
    assert chunk == codec.get_chunk_size(length)
    assert chunk % 128 == 0 and k * chunk >= length > k * (chunk - 128)
    assert len(want) == k + m and all(len(s) == chunk for s in want)
    got = codec.encode(set(range(k + m)), data)
    for i in range(k + m):
        assert np.array_equal(np.asarray(got[i]), want[i]), i
    split = codec.split_data(data)
    assert np.array_equal(split, np.stack(want[:k]))
    # zero fill, and a read trims back to the object's length
    joined = b"".join(s.tobytes() for s in want[:k])
    assert joined[:length] == data and not any(joined[length:])
    if length % (k * 128) == 0:
        # a whole stripe: the unpadded reference says the same
        for a, b in zip(reference.shards(data, k, m), want):
            assert np.array_equal(a, b)


def test_the_unpadded_reference_is_not_what_a_pool_stores_of_a_record():
    data = bytes(range(256)) * 3 + bytes(232)
    assert len(data) == 1000
    plain = reference.shards(data, 2, 1)        # it DOES split: 2 x 500
    padded = reference_padded.shards(data, 2, 1)
    assert [len(s) for s in plain] == [500] * 3
    assert [len(s) for s in padded] == [512] * 3
    assert reference_padded.chunk_size(1000, 2) == 512
    assert padded[1][-24:].tolist() == [0] * 24


# ------------------------------------------------------------- rehearsals
def test_rehearsal_is_correct_and_puts_the_order_to_the_test(toy):
    result, diag, err = run_toy(toy, CELL)
    assert result["correct"] is True, err
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"op_rate", "write_p95", "read_p95",
                                      "setup_s"}
    assert set(diag["lat_ms"]) == {"read", "write"}
    cmp_ = result["compared"]
    for name in LIMIT_0:
        assert cmp_[name] == {"value": 0, "limit": 0}, name
    for name in PRINTED:
        assert cmp_[name]["limit"] is None, name
        assert any(ln.startswith(f"compared {name}: ")
                   for ln in err.splitlines()), name
    assert cmp_["readback_objects"]["value"] == 64
    assert cmp_["shards_checked"]["value"] >= 3 * 16
    assert cmp_["reads_checked"]["value"] > 0
    # eight workers on 64 records with Zipf 0.99 meet all the time
    assert cmp_["overlapping_writes"]["value"] > 0
    assert cmp_["reads_behind_a_write"]["value"] > 0
    assert cmp_["order_tested"]["value"] == 1
    assert cmp_["hottest_object_ops"]["value"] > result["attempted"] / 64
    assert 1 / 8 <= cmp_["hottest_pg_share"]["value"] <= 1.0
    # the program's own counters: same-object waits and the chain
    assert cmp_["same_object_waits"]["value"] > 0
    assert 2 <= cmp_["chain_peak"]["value"] <= 8
    # every launch is one bucket; 1,024 bytes a write were asked for
    writes = cmp_["device_requests"]["value"]
    assert cmp_["device_bytes"]["value"] == writes * 1024
    lanes = cmp_["device_lanes_launched"]["value"]
    assert lanes > 0 and lanes % 16384 == 0
    # the pad share, from the two numbers on the line
    pad = 1 - (cmp_["device_bytes"]["value"] / 2) / lanes
    assert 0.9 < pad < 1.0
    assert diag["window_jax"]["compile_events"] == 0
    assert diag["warm"]["groups"] == 8
    assert diag["seam"]["host_bytes"] == 0
    assert err.strip().splitlines()[-1] == "correct: True"


def test_traced_rehearsal_reports_the_two_new_per_layer_metrics(
        toy, tmp_path):
    result, _diag, err = run_toy(toy, CELL, trace=True, tmp_path=tmp_path)
    assert result["correct"] is True, err
    got = set(result["metrics"])
    declared = {m["name"] for m in toy.metrics_of(CELL, "per_layer")}
    silent = {n for n in declared if n.startswith("kernel.")}
    assert got == declared - silent and NEW_METRICS <= got
    for name in NEW_METRICS:
        assert result["metrics"][name]["unit"] == "ms"
        assert result["metrics"][name]["value"] >= 0
    for name in QUEUE_METRICS:
        assert result["metrics"][name]["value"] > 0
    # both lie inside osd.queue_ms
    assert sum(result["metrics"][n]["value"] for n in QUEUE_METRICS) <= \
        result["metrics"]["osd.queue_ms.op_rate"]["value"] + 1e-9
    assert result["metrics"]["seam.device_byte_fraction.op_rate"][
        "value"] == 100.0


def test_new_readers_read_their_stage_alone_or_nothing(toy):
    obs = SimpleNamespace(ops=100, stages={
        "dep_wait": (40, 0.5), "admit_wait": (100, 0.2),
        "queue_wait_pump": (100, 1.0), "reply_wait": (10, 0.3)})
    assert toy.reader("osd.dep_wait_ms.op_rate")(obs) == pytest.approx(5.0)
    assert toy.reader("osd.admit_wait_ms.op_rate")(obs) == \
        pytest.approx(2.0)
    assert toy.reader("osd.reply_wait_ms.op_rate")(obs) == \
        pytest.approx(3.0)
    for name in QUEUE_METRICS:
        assert toy.reader(name)(SimpleNamespace(ops=100, stages={})) is None
    # no op waited to reply: a plain 0, not nothing
    assert toy.reader("osd.reply_wait_ms.op_rate")(
        SimpleNamespace(ops=100, stages={})) == 0.0
    for name in NEW_METRICS:
        assert toy.reader(name)(SimpleNamespace(ops=0, stages={})) is None


@pytest.mark.parametrize("fault,caught_by", [
    ("stale_write", "read_order_violations readback_mismatch"),
    ("answer_flip", "read_order_violations readback_mismatch"),
])
def test_control_comes_out_not_correct(toy, fault, caught_by):
    result, _diag, err = run_toy(toy, CELL, fault=fault)
    assert result["correct"] is False, err
    over = [n for n in caught_by.split()
            if result["compared"][n]["value"] > 0
            and result["compared"][n]["limit"] == 0]
    assert over
    assert err.strip().splitlines()[-1] == "correct: False"


def plant_swap(hold_s: float):
    """The control: under the timed path a write is held back `hold_s`
    before it reaches the client, so a read of the same object that its
    worker submitted AFTER it is sent ahead of it."""
    def plant(fault, env):
        assert fault == "swap"
        real = env.io.write_full

        async def write_full(oid, data):
            await asyncio.sleep(hold_s)
            return await real(oid, data)
        env.io.write_full = write_full
        return lambda: setattr(env.io, "write_full", real)
    return plant


def test_control_a_read_sent_ahead_of_the_write_before_it_is_not_correct(
        toy, monkeypatch):
    monkeypatch.setattr(faults, "plant", plant_swap(0.05))
    result, _diag, err = run_toy(toy, CELL, fault="swap", seconds=2.0)
    cmp_ = result["compared"]
    assert result["correct"] is False, err
    assert cmp_["read_order_violations"]["value"] > 0
    assert cmp_["reads_behind_a_write"]["value"] >= \
        cmp_["read_order_violations"]["value"]
    # nothing else is at fault: every op was answered, the bytes took
    # the device, every record holds SOME write's bytes whole
    for name in ("ops_failed", "host_bytes", "read_length_mismatch",
                 "shard_mismatch"):
        assert cmp_[name]["value"] == 0, name
    assert err.strip().splitlines()[-1] == "correct: False"

"""Toy-size rehearsals of `cos_mix_64k_w8_open` on the CPU: the kind
`open_loop` through the same harness the chip runs, traced and not; the
schedule and the object model (`WriteOrder`) by hand; a planted block of
the loop, which an open loop shows in every op that was DUE inside it
and a closed loop on the same block does not; the control (same-object
writes reordered under the timed path has to come out `correct:
false`); and the kind's refusal under a client that keeps no op budget.

Nothing here is a number about speed: the device is the CPU."""

import asyncio
import io
import json
import pathlib
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import faults, harness, manifest

REPO = pathlib.Path(__file__).resolve().parents[2]
TOY = REPO / "tests" / "benchmark" / "toy" / "manifest_open.json"
CELL, CONTROL = "toy_mix_open", "toy_mix"
REAL, REAL_CONTROL = "cos_mix_64k_w8_open", "cos_mix_64k_w8"
PRINTED = ("overlapping_writes", "sched_late_p99_ms", "due_in_window",
           "completed_in_window", "unanswered_at_close",
           "inflight_ops_peak", "throttle_waits", "unknown_objects",
           "outstanding_peak", "write_order_tested")


@pytest.fixture(scope="module")
def toy():
    return manifest.Manifest(path=TOY)


@pytest.fixture
def loads(toy, monkeypatch):
    """Every Load the harness constructs, of either kind."""
    made = []
    for name in ("open_loop", "closed_loop"):
        kind = toy.kind(name)

        class Spy(kind.Load):
            def __init__(self, env):
                super().__init__(env)
                made.append(self)
        monkeypatch.setattr(kind, "Load", Spy)
    return made


def run_toy(man, cell, *, trace=False, fault=None, seed=3_000_000_011,
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
    return man.kind("open_loop").Load(env)


# ------------------------------------------------------ files and entries
def test_the_mix_and_the_configuration_hold_what_the_cell_is_defined_by():
    real = manifest.Manifest()
    cell = real.workload(REAL)
    assert cell["chips"] == 1 and cell["config"] == \
        "cosbench_64k_ec_k2m1_open"
    t = real.traffic(cell["traffic"])
    want = {"kind": "open_loop", "arrivals": "poisson",
            "object_size": 65536, "read_ratio": 0.8, "read_objects": 4096,
            "read_select": "uniform", "write_objects": 4096,
            "write_select": "uniform", "payloads": 256, "ramp_s": 3.0,
            "keep_reads": 1024, "keep_prob": 0.05, "check_shards": 64,
            "warm_depth": 64}
    for key, val in want.items():
        assert t[key] == val, key
    assert isinstance(t["rate_ops_s"], (int, float)) and t["rate_ops_s"] > 0
    # the control's shapes, not one of them changed
    ctl = real.traffic(REAL_CONTROL)
    for key in ("object_size", "read_ratio", "read_objects",
                "write_objects", "payloads", "ramp_s", "keep_reads",
                "keep_prob", "check_shards"):
        assert t[key] == ctl[key], key
    cfg, base = real.config(cell["config"]), real.config(
        "cosbench_64k_ec_k2m1")
    for key in ("osds", "objectstore", "object_size", "pool",
                "read_ratio", "write_ratio"):
        assert cfg[key] == base[key], key
    assert cfg["options"] == dict(
        base["options"], objecter_inflight_ops=1024,
        objecter_inflight_op_bytes="100m")
    assert set(base["guarantees"]) < set(cfg["guarantees"])
    text = " ".join(cfg["guarantees"])
    assert "apply in the order submitted" in text
    assert "none dropped, shed or thinned" in text
    assert cfg["reduced"] == ["osds", "objectstore", "clients"]
    assert cfg["clients"] == 1 and cfg["reduced_why"]["clients"]
    assert len(cfg["source"]) <= 200 and cfg["assumed"]


def check_mirror(real):
    """The toy cell reports what `real`'s cell reports."""
    toy = manifest.Manifest(path=TOY)
    for section in ("end_to_end", "per_layer"):
        assert [m["name"] for m in toy.metrics_of(CELL, section)] == [
            m["name"] for m in real.metrics_of(REAL, section)]
    ctl = {m["name"] for m in real.metrics_of(REAL_CONTROL, "per_layer")}
    new = {m["name"] for m in real.metrics_of(REAL, "per_layer")}
    assert new - ctl == {"client.throttle_wait_ms.op_rate"} and ctl <= new
    # the tails did not repeat inside half their bounds (PERF.md section
    # 7): the cell is judged on the rate it keeps up with, and every
    # run's tails are on its `diag` line
    assert [m["name"] for m in real.metrics_of(REAL, "end_to_end")] == [
        "op_rate", "setup_s"]


def test_toy_manifest_mirrors_the_cells_entries():
    check_mirror(manifest.Manifest())


# ------------------------------------------------- schedule, names, model
def test_schedule_and_names_repeat_for_a_seed_and_names_for_every_seed(toy):
    a, b = (bare_load(toy, "toy_mix_open", 7) for _ in range(2))
    c = bare_load(toy, "toy_mix_open", 2_500_000_000)
    for key in ("due_at", "plan_read", "plan_rd", "plan_wr", "plan_pay"):
        assert np.array_equal(getattr(a, key), getattr(b, key)), key
    assert a.payloads == b.payloads
    assert not np.array_equal(a.due_at, c.due_at)
    assert not np.array_equal(a.plan_wr, c.plan_wr)
    assert a.payloads != c.payloads
    assert (a.read_names, a.write_names) == (c.read_names, c.write_names)
    assert a.read_names[0] == "benchmark_data_c_object0"
    assert a.write_names[0] == "benchmark_data_c_objectw0"
    assert len(set(a.read_names) | set(a.write_names)) == 32
    # Poisson arrivals at the file's rate; writes over the WHOLE ring
    n = len(a.due_at)
    assert abs(n / a.due_at[-1] / a.rate - 1) < 0.02
    gaps = np.diff(a.due_at)
    assert 0.9 < gaps.std() / gaps.mean() < 1.1     # exponential: cv 1
    assert set(a.plan_wr) == set(range(16))
    assert 0.78 < a.plan_read.mean() < 0.82
    even = bare_load(toy, "toy_mix_open", 7, arrivals="even",
                     rate_ops_s=100.0)
    assert np.allclose(np.diff(even.due_at), 0.01)
    # the plan wraps and the clock goes on
    even.t_start = 5.0
    assert even.due_time(0) == pytest.approx(5.01)
    assert even.due_time(n) == pytest.approx(5.01 + n * 0.01)
    assert even.seam_shapes() == {"lanes": 32768, "depth": 8,
                                  "encode": True, "decode": False}
    with pytest.raises(ValueError, match="whole range"):
        bare_load(toy, "toy_mix_open", 7, write_select="ring")
    with pytest.raises(ValueError, match="arrival process"):
        bare_load(toy, "toy_mix_open", 7, arrivals="bursty")


def test_write_order_counts_what_a_hand_made_ack_order_says(toy):
    order = toy.kind("open_loop").WriteOrder(np.zeros(4, np.int64))
    # object 0: two writes in flight together, acked as submitted
    order.submit(0, 10)
    order.submit(0, 11)
    order.ack(0, 10, 5)
    order.ack(0, 11, 6)
    assert (order.overlapping, order.violations) == (1, 0)
    assert order.holds[0] == 6
    # object 1: the later submitted is acked FIRST, twice over
    order.submit(1, 20)
    order.submit(1, 21)
    order.submit(1, 22)
    order.ack(1, 22, 3)
    order.ack(1, 21, 2)
    order.ack(1, 20, 1)
    assert (order.overlapping, order.violations) == (3, 2)
    assert order.holds[1] == 3      # the last SUBMITTED among the acked
    # object 2: one at a time never overlaps
    for seq, pay in ((30, 7), (31, 8)):
        order.submit(2, seq)
        order.ack(2, seq, pay)
    assert (order.overlapping, order.violations) == (3, 2)
    # object 3: a failed write before the last acked one is covered by
    # it; a failed or unanswered one after it leaves the object unknown
    order.submit(3, 40)
    order.submit(3, 41)
    order.fail(3, 40)
    order.ack(3, 41, 4)
    assert order.unknown() == set() and order.violations == 2
    order.submit(3, 42)
    order.submit(0, 43)
    order.fail(0, 43)
    order.close()                   # 42 was never answered
    assert order.unknown() == {0, 3} and not order.flying
    assert list(order.holds) == [6, 3, 8, 4]


def test_kind_refuses_a_client_that_keeps_no_op_budget(toy):
    env = SimpleNamespace(cell="c", seed=1, k=2, m=1,
                          traffic=toy.traffic("toy_mix_open"),
                          admin=SimpleNamespace(objecter=SimpleNamespace()))
    with pytest.raises(RuntimeError, match="refusing to measure"):
        toy.kind("open_loop").Load(env)


# ------------------------------------------------------------- rehearsals
def test_rehearsal_is_correct_and_counts_from_the_due_time(toy, loads):
    result, diag, err = run_toy(toy, CELL)
    assert result["correct"] is True, err
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"op_rate", "setup_s"}
    assert set(diag["lat_ms"]) == {"read", "write"}
    assert result["device"]["platform"] == "cpu"
    cmp_ = result["compared"]
    for name in ("readback_mismatch", "window_read_mismatch",
                 "shard_mismatch", "write_order_violations", "host_bytes",
                 "device_fallbacks", "device_bytes_short", "ops_failed"):
        assert cmp_[name] == {"value": 0, "limit": 0}, name
    for name in PRINTED:
        assert cmp_[name]["limit"] is None
        assert any(ln.startswith(f"compared {name}: ")
                   for ln in err.splitlines()), name
    assert cmp_["readback_objects"]["value"] == 16
    assert cmp_["throttle_waits"]["value"] == 0
    assert 1 <= cmp_["inflight_ops_peak"]["value"] <= 1024
    # the load's own peak is the generator's count; the client's is
    # read as it stands (set-up included), never reset
    assert 1 <= cmp_["outstanding_peak"]["value"] <= \
        cmp_["inflight_ops_peak"]["value"]
    assert cmp_["write_order_tested"]["value"] == int(
        cmp_["overlapping_writes"]["value"] > 0)
    # the offered rate is what completes while the system keeps up
    load = loads[0]
    due = cmp_["due_in_window"]["value"]
    assert abs(due - load.rate * 1.5) < 0.3 * load.rate * 1.5
    assert abs(cmp_["completed_in_window"]["value"] - due) <= 0.1 * due
    assert diag["ops_window"] == cmp_["completed_in_window"]["value"]
    # every op was submitted at or after its due time, and its latency
    # counts from then
    assert len(load.due) == load.attempted and min(load.late) >= 0.0
    assert all(la >= 0 for la in load.lat)
    assert diag["window_jax"]["compile_events"] == 0
    assert diag["warm"]["groups"] == 8          # warm_depth widths
    assert err.strip().splitlines()[-1] == "correct: True"


def test_traced_rehearsal_reports_the_cells_per_layer_metrics(toy, tmp_path):
    result, _diag, err = run_toy(toy, CELL, trace=True, tmp_path=tmp_path)
    assert result["correct"] is True, err
    got = set(result["metrics"])
    declared = {m["name"] for m in toy.metrics_of(CELL, "per_layer")}
    silent = {n for n in declared if n.startswith("kernel.")}
    assert got == declared - silent
    # nobody waited for budget: the reader says 0.0, not nothing
    assert result["metrics"]["client.throttle_wait_ms.op_rate"] == {
        "value": 0.0, "unit": "ms"}
    assert result["metrics"]["seam.device_byte_fraction.op_rate"][
        "value"] == 100.0


def test_throttle_wait_reader_reads_the_stage_or_nothing(toy):
    read = toy.reader("client.throttle_wait_ms.op_rate")
    obs = SimpleNamespace(stages={"client_throttle_wait": (3, 0.5),
                                  "client_submit": (10, 0.1)}, ops=100)
    assert read(obs) == pytest.approx(5.0)
    assert read(SimpleNamespace(stages={}, ops=100)) == 0.0
    assert read(SimpleNamespace(stages={}, ops=0)) is None


def plant_block(seconds: float, after: float, seen: dict):
    """A stand-in for benchmark/faults.py's `plant`: the loop stands
    still for `seconds`, once, `after` seconds into the load."""
    def plant(fault, env):
        assert fault == "block"

        def block():
            seen["t0"] = time.monotonic()
            time.sleep(seconds)
            seen["t1"] = time.monotonic()
        handle = asyncio.get_running_loop().call_later(after, block)
        return handle.cancel
    return plant


def test_a_block_of_the_loop_shows_in_the_ops_due_inside_it(
        toy, loads, monkeypatch):
    """Coordinated omission, both ways round: the open loop charges a
    200 ms block to every op that was due inside it (latency at least
    what was left of the block); the closed loop on the same block has
    at most `depth` slow ops, and sends nothing meanwhile."""
    seen = {}
    monkeypatch.setattr(faults, "plant", plant_block(0.2, 0.8, seen))
    result, _diag, err = run_toy(toy, CELL, fault="block")
    assert result["correct"] is True, err
    load, (b0, b1) = loads[0], (seen["t0"], seen["t1"])
    assert b1 - b0 >= 0.2
    inside = [(te - la, la) for te, la in zip(load.t_end, load.lat)
              if b0 < te - la < b1]             # (due, latency)
    assert len(inside) >= 0.5 * load.rate * 0.2
    for due, lat in inside:
        assert lat >= (b1 - due) - 1e-6
    slow_open = sum(1 for la in load.lat if la >= 0.1)
    assert slow_open >= 0.3 * load.rate * 0.2
    assert result["compared"]["sched_late_p99_ms"]["value"] >= 0.0
    assert max(load.late) >= 0.15               # the generator was late

    seen.clear()
    result, _diag, err = run_toy(toy, CONTROL, fault="block")
    assert result["correct"] is True, err
    closed, (b0, b1) = loads[1], (seen["t0"], seen["t1"])
    started_inside = [1 for te, la in zip(closed.t_end, closed.lat)
                      if b0 < te - la < b1]
    assert not started_inside                   # never sent: never late
    slow_closed = sum(1 for la in closed.lat if la >= 0.1)
    assert slow_closed <= closed.depth < slow_open


def plant_reorder(hold_s: float):
    """The control: under the timed path a write is held back `hold_s`
    before it is submitted unless another write to its object is
    already held, so a second write to that object passes it: the
    later submitted is applied, and acked, first."""
    def plant(fault, env):
        assert fault == "reorder"
        real, held = env.io.write_full, set()

        async def write_full(oid, data):
            if oid not in held:
                held.add(oid)
                try:
                    await asyncio.sleep(hold_s)
                finally:
                    held.discard(oid)
            return await real(oid, data)
        env.io.write_full = write_full
        return lambda: setattr(env.io, "write_full", real)
    return plant


def test_control_reordered_same_object_writes_is_not_correct(
        toy, monkeypatch):
    monkeypatch.setattr(faults, "plant", plant_reorder(0.15))
    result, _diag, err = run_toy(toy, CELL, fault="reorder", seconds=2.5)
    cmp_ = result["compared"]
    assert result["correct"] is False, err
    assert cmp_["write_order_violations"]["value"] > 0
    assert cmp_["overlapping_writes"]["value"] >= \
        cmp_["write_order_violations"]["value"]
    # nothing else is at fault: the bytes took the device, every shard
    # is the reference's encode of SOME write
    assert cmp_["ops_failed"]["value"] == 0
    assert cmp_["host_bytes"]["value"] == 0
    assert cmp_["window_read_mismatch"]["value"] == 0
    assert err.strip().splitlines()[-1] == "correct: False"

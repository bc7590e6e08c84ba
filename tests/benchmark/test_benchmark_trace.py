"""The reduction from a device trace to numbers, on a hand-made trace
whose answers are worked out below and on a small slice recorded on the
v5e (tests/benchmark/recorded/: events as the profiler gave them, cut to
44 ms around a burst of launches)."""

import json
import pathlib
from types import SimpleNamespace

import pytest

from benchmark import readers, trace_reduce as tr

DEV, OPS = "/device:TPU:0", "XLA Ops"
HAND = [
    (DEV, OPS, "%slice = u8[4,1024]{1,0} slice(...)", 0, 10),
    (DEV, OPS, "%_apply_bitmatrix_pallas_jit.1 = u8[2,1024]{1,0} "
               "custom-call(...)", 20, 30),
    (DEV, OPS, "%pad.1 = u8[4,2048]{1,0} pad(...)", 45, 15),
    (DEV, "XLA Modules", "jit__apply(1)", 20, 30),     # not an op line
    ("/host:CPU", "python", "outer", 5, 20),
    ("/host:CPU", "python", "inner", 12, 6),
    ("/host:CPU", "pjrt", "fetch", 70, 10),
    ("/host:CPU", "python", "marker", 0, 100),
]


def test_busy_is_the_union_of_the_device_op_intervals():
    # [0,10) + [20,60): the kernel and the pad overlap by 5 ns
    assert tr.busy_seconds(HAND) == pytest.approx(50e-9)
    assert tr.busy_seconds([e for e in HAND if e[0] != DEV]) == 0.0


def test_kernel_time_matches_by_name():
    assert tr.kernel_seconds(HAND, ["apply_bitmatrix"]) == \
        pytest.approx(30e-9)
    assert tr.kernel_seconds(HAND, ["no_such_kernel"]) == 0.0


def test_top_device_ops_are_named_short_and_sorted():
    top = tr.top_device_ops(HAND)
    assert top == [["_apply_bitmatrix_pallas_jit.1 u8[2,1024]",
                    pytest.approx(30e-9)],
                   ["pad.1 u8[4,2048]", pytest.approx(15e-9)],
                   ["slice u8[4,1024]", pytest.approx(10e-9)]]


def test_idle_gaps_go_to_the_innermost_host_event():
    gaps = dict(tr.idle_gaps(HAND, 0, 100, skip=("marker",)))
    # idle: [10,20) and [60,100)
    assert gaps["outer"] == pytest.approx(4e-9)      # [10,12) + [18,20)
    assert gaps["inner"] == pytest.approx(6e-9)      # [12,18)
    assert gaps["fetch"] == pytest.approx(10e-9)     # [70,80)
    assert gaps["unattributed"] == pytest.approx(30e-9)
    assert sum(gaps.values()) == pytest.approx(50e-9)
    assert tr.window_of(HAND, "marker") == (0, 100)
    with pytest.raises(LookupError):
        tr.window_of(HAND, "absent")


def test_roofline_reader_counts_the_requests_own_bytes():
    # one request of k=4 rows x 1024 lanes: device_bytes = 4096, least
    # traffic (4 + 2) * 1024 = 6144 B; at 819e9 B/s that is 7.5 ns
    # against 30 ns of kernel time
    obs = SimpleNamespace(
        trace={"events": HAND, "window_s": 100e-9, "k": 4, "r": 2,
               "counters": {"device_bytes": 4096}},
        peaks={"hbm_bytes_per_s": 819e9})
    assert readers.ec_apply_roofline(obs) == pytest.approx(
        100 * (6144 / 819e9) / 30e-9)
    assert readers.ec_apply_busy(obs) == pytest.approx(30.0)
    # nothing to read: nothing returned, never 0
    obs.trace["events"] = [e for e in HAND if "apply" not in e[2]]
    assert readers.ec_apply_roofline(obs) is None
    assert readers.ec_apply_busy(obs) is None
    obs.trace = None
    assert readers.ec_apply_roofline(obs) is None


def test_stage_and_counter_readers():
    obs = SimpleNamespace(
        stages={"ec_encode": (10, 0.5), "replica_rtt": (10, 0.3)},
        counters={"batch_fill_sum": 30.0, "batch_fill_n": 10,
                  "device_bytes": 300, "host_bytes": 100},
        ops=10, compiles=0)
    assert readers.stage_ms_per_op(obs, ["ec_encode"]) == 50.0
    assert readers.stage_ms_per_op(obs, ["ec_encode", "replica_rtt"]) \
        == pytest.approx(80.0)
    assert readers.stage_ms_per_op(obs, ["decode_rebuild"]) is None
    assert readers.batch_fill(obs) == 3.0
    assert readers.device_byte_fraction(obs) == 75.0
    assert readers.window_compiles(obs) == 0.0
    obs.counters = {"batch_fill_sum": 0.0, "batch_fill_n": 0}
    assert readers.batch_fill(obs) is None
    assert readers.device_byte_fraction(obs) is None


RECORDED = sorted((pathlib.Path(__file__).parent / "recorded").glob(
    "*.json"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.stem)
def test_recorded_slice_from_the_chip(path):
    rec = json.loads(path.read_text())
    events = [tuple(e) for e in rec["events"]]
    t0, t1 = rec["t0"], rec["t1"]
    busy = tr.busy_seconds(events)
    kern = tr.kernel_seconds(events, readers.EC_APPLY_MATCH)
    assert 0 < kern <= busy < (t1 - t0) / 1e9
    assert busy == pytest.approx(rec["want"]["busy_s"])
    assert kern == pytest.approx(rec["want"]["kernel_s"])
    top = tr.top_device_ops(events)
    assert top[0][0].startswith("_apply_bitmatrix_pallas_jit")
    assert all(len(name) <= 80 for name, _ in top)
    gaps = tr.idle_gaps(events, t0, t1)
    idle = sum(s for _, s in tr.idle_gaps(events, t0, t1, n=10**6))
    clipped = sum(
        min(e, t1) - max(s, t0) for s, e in tr.union_ns(
            (ev[3], ev[3] + ev[4]) for evs in tr.device_ops(
                events).values() for ev in evs)
        if min(e, t1) > max(s, t0))
    assert idle == pytest.approx((t1 - t0 - clipped) / 1e9)
    assert len(gaps) <= 10

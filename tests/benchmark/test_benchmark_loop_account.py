"""The per-layer metrics that read the loop's time account and the two
stages the tracer names beside it (common/tracer.py: the loop sampler's
`evloop_idle` / `evloop_poll` beside `loop_wall` / `loop_cpu`, the
interval `read_gather`, the chain stage `reply_wait`;
benchmark/loop_account.py and the `osd.loop_idle_share`,
`osd.loop_offcore_share`, `osd.loop_poll_ms`, `ec.read_gather_ms`,
`osd.reply_wait_ms` readers under benchmark/metrics/): each reader's
arithmetic on a hand-made observation, what it does on a program without
the stage (a parent commit: None, never 0, never an exception), the
nine entries BENCHMARK.json declares for them, and traced toy cells on
the CPU in which every one of them finds something to read, the hops
and the read path record under their names, and the three shares of
the loop's wall sum to 100.

The toy manifest here is built in a tmp dir from the tests' own one
plus those entries; no file of the benchmark is edited.  Nothing here
is a number about speed."""

import json
import pathlib
from types import SimpleNamespace

import pytest

from benchmark import harness, loop_account, manifest
from test_benchmark_rehearsal import TOY, run_toy

REPO = pathlib.Path(__file__).resolve().parents[2]
REAL = manifest.Manifest()
LOOP_BASES = ("osd.loop_idle_share", "osd.loop_offcore_share",
              "osd.loop_poll_ms")
NEW = [f"{b}.{sfx}" for b in LOOP_BASES + ("ec.read_gather_ms",)
       for sfx in ("goodput", "op_rate")] + ["osd.reply_wait_ms.op_rate"]
READ_CELLS = {"goodput": ["rb_seq_degraded_4m_qd16"],
              "op_rate": ["cos_mix_64k_w8", "cos_mix_64k_w8_open",
                          "ycsb_a_1k_zipf"]}
#: the cells each of the nine named while it waited, undeclared, as data
#: under benchmark/pending/; a cell appended since follows them
WAITED_CELLS = {
    **{f"{b}.{sfx}": cells for b in LOOP_BASES for sfx, cells in (
        ("goodput", ["rb_write_4m_qd16", "rb_seq_degraded_4m_qd16",
                     "rb_write_4m_qd16_blockstore"]),
        ("op_rate", ["cos_mix_64k_w8", "cos_write_64k_w64",
                     "cos_mix_64k_w8_open", "ycsb_a_1k_zipf"]))},
    **{f"ec.read_gather_ms.{sfx}": cells
       for sfx, cells in READ_CELLS.items()},
    "osd.reply_wait_ms.op_rate": ["ycsb_a_1k_zipf"]}
#: the toy cells that stand for them
TOY_CELLS = {"osd.loop": {"goodput": ["toy_write", "toy_seq_degraded"],
                          "op_rate": ["toy_mix"]},
             "ec.read_gather_ms": {"goodput": ["toy_seq_degraded"],
                                   "op_rate": ["toy_mix"]},
             "osd.reply_wait_ms": {"op_rate": ["toy_mix"]}}

#: a hand-made traced observation: 200 ops in a window of 51 s
HAND = SimpleNamespace(
    ops=200,
    stages={
        "loop_wall": (510, 51.0), "loop_cpu": (510, 30.6),
        "evloop_idle": (900, 10.2), "evloop_poll": (40000, 2.0),
        "loop_read": (800, 1.0), "loop_msg": (2400, 3.0),
        "read_gather": (160, 0.8),
        "dep_wait": (200, 4.0), "reply_wait": (20, 0.5),
    })
WANT = {
    "osd.loop_idle_share": 20.0,            # 10.2 / 51
    "osd.loop_offcore_share": 20.0,         # (51 - 10.2 - 30.6) / 51
    "osd.loop_poll_ms": 10.0,               # 2.0 s / 200 ops
    "ec.read_gather_ms": 4.0,               # 0.8 s / 200 ops
    "osd.reply_wait_ms": 2.5,               # 0.5 s / 200 ops
}
#: what a parent commit gives the same readers: the sampler's two
#: stages and its sections, no account of the selector, no read_gather
PARENT = SimpleNamespace(
    ops=200, stages={"loop_wall": (510, 51.0), "loop_cpu": (510, 30.6),
                     "loop_ec_host": (200, 1.0), "dep_wait": (220, 4.5),
                     "op_exec": (160, 2.0)})


def base_of(name):
    return name.rsplit(".", 1)[0]


@pytest.mark.parametrize("name", NEW)
def test_reader_arithmetic_on_a_hand_made_observation(name):
    got = REAL.reader(name)(HAND)
    assert got == pytest.approx(WANT[base_of(name)])


def test_the_three_shares_sum_to_100_by_construction():
    from benchmark import spans
    total = spans.loop_cpu_share(HAND) + loop_account.loop_idle_share(
        HAND) + loop_account.loop_offcore_share(HAND)
    assert total == pytest.approx(100.0)
    # a loop that never slept in the window: idle is a plain 0, and the
    # account still closes
    busy = SimpleNamespace(ops=10, stages={
        "loop_wall": (10, 1.0), "loop_cpu": (10, 0.7),
        "evloop_poll": (500, 0.1)})
    assert loop_account.loop_idle_share(busy) == 0.0
    assert loop_account.loop_offcore_share(busy) == pytest.approx(30.0)
    assert loop_account.loop_poll_ms(busy) == pytest.approx(10.0)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_on_a_program_without_the_stage(
        name, monkeypatch):
    if name.startswith("osd.reply_wait_ms"):
        # a parent's tracer declares no such stage: nothing to read,
        # however many ops completed
        from ceph_tpu.common import tracer
        monkeypatch.setattr(tracer, "CHAIN_STAGES", tuple(
            s for s in tracer.CHAIN_STAGES if s != "reply_wait"))
    assert REAL.reader(name)(PARENT) is None
    untraced = SimpleNamespace(ops=0, stages={}, trace=None)
    assert REAL.reader(name)(untraced) is None


def test_reply_wait_is_a_plain_zero_when_no_op_waited():
    read = REAL.reader("osd.reply_wait_ms.op_rate")
    assert read(SimpleNamespace(ops=100, stages={})) == 0.0
    assert read(SimpleNamespace(ops=0, stages={})) is None
    # and dep_wait's reader no longer sees the reply-order wait
    assert REAL.reader("osd.dep_wait_ms.op_rate")(HAND) == \
        pytest.approx(20.0)


def check_entry(man, name):
    """The entry `man` declares for `name` names every cell of its
    suffix (a read metric: every one whose mix reads) and no other."""
    spec = man.per_layer[name]
    assert set(spec) == {"name", "unit", "better", "source", "layer",
                         "moves", "workloads"}
    assert manifest.NAME_RE.match(name) and manifest.UNIT_RE.match(
        spec["unit"])
    assert man.find(f"metrics/{name}.py").is_file()
    base, sfx = name.rsplit(".", 1)
    assert spec["moves"] == sfx and spec["source"] == "program_span"
    assert spec["better"] == "lower"
    assert spec["unit"] == ("%" if base.endswith("_share") else "ms")
    assert spec["layer"] == ("EC backend" if base.startswith("ec.")
                             else "OSD / PG")
    of_suffix = [w["name"] for w in man.doc["workloads"]
                 if sfx in {m["name"] for m in
                            man.metrics_of(w["name"], "end_to_end")}]
    if base in LOOP_BASES:
        assert spec["workloads"] == of_suffix
    elif base == "ec.read_gather_ms":
        # the cells that read (cos_write_64k_w64 does not)
        assert spec["workloads"] == [
            w for w in of_suffix
            if man.traffic(man.workloads[w]["traffic"])["read_ratio"] > 0]
    else:
        assert spec["workloads"] == ["ycsb_a_1k_zipf"]


def check_the_nine_are_declared(man):
    """Each of the nine once, as it waited: its layer, what it moves,
    and the cells it named first in its list."""
    names = [m["name"] for m in man.doc["per_layer"]]
    layers = {m["layer"] for m in man.doc["per_layer"]
              if m["name"] not in NEW}
    moved = {m["name"] for m in man.doc["end_to_end"]}
    for name in NEW:
        assert names.count(name) == 1, name
        spec = man.per_layer[name]
        base, sfx = name.rsplit(".", 1)
        assert spec["moves"] == sfx and spec["moves"] in moved
        assert spec["layer"] == ("EC backend" if base.startswith("ec.")
                                 else "OSD / PG")
        assert spec["layer"] in layers
        cells = WAITED_CELLS[name]
        assert spec["workloads"][:len(cells)] == cells, name
        assert set(spec["workloads"]) <= set(man.workloads)


@pytest.mark.parametrize("name", NEW)
def test_pending_entry_names_the_cells_the_metric_is_for(name):
    check_entry(REAL, name)


def test_the_nine_are_declared_once_each_as_they_waited():
    check_the_nine_are_declared(REAL)


@pytest.fixture(scope="module")
def toy_with_account(tmp_path_factory):
    doc = json.loads(TOY.read_text())
    for name in NEW:
        spec = dict(REAL.per_layer[name])
        base = base_of(name)
        cells = TOY_CELLS["osd.loop" if base in LOOP_BASES else base]
        spec["workloads"] = cells[spec["moves"]]
        doc["per_layer"].append(spec)
    for name in ("osd.loop_cpu_share.goodput", "osd.loop_cpu_share.op_rate",
                 "osd.loop_named_share.goodput",
                 "osd.loop_named_share.op_rate"):
        spec = dict(REAL.per_layer[name])
        spec["workloads"] = TOY_CELLS["osd.loop"][spec["moves"]]
        doc["per_layer"].append(spec)
    path = tmp_path_factory.mktemp("account") / "manifest.json"
    path.write_text(json.dumps(doc))
    return manifest.Manifest(path=path)


#: the stages a traced toy cell has to record, by cell
HOPS = ("loop_msg", "loop_pump", "loop_admit", "loop_client_reply")
RECORDS = {"toy_mix": HOPS + ("loop_read", "loop_sub_read", "read_gather",
                              "evloop_idle", "evloop_poll"),
           "toy_seq_degraded": HOPS + ("loop_read", "loop_sub_read",
                                       "read_gather", "evloop_idle",
                                       "evloop_poll"),
           "toy_write": HOPS + ("evloop_idle", "evloop_poll")}


@pytest.mark.parametrize("workload", ["toy_mix", "toy_seq_degraded",
                                      "toy_write"])
def test_traced_toy_cell_closes_the_account_and_names_the_hops(
        toy_with_account, workload, tmp_path, monkeypatch):
    seen = []
    totals = harness.stage_totals

    def keep(cluster):
        seen.append(totals(cluster))
        return seen[-1]
    monkeypatch.setattr(harness, "stage_totals", keep)
    result, _diag, err = run_toy(toy_with_account, workload, trace=True,
                                 tmp_path=tmp_path)
    assert result["correct"] is True, err
    sfx = "op_rate" if workload == "toy_mix" else "goodput"
    got = result["metrics"]
    for base in LOOP_BASES:
        assert f"{base}.{sfx}" in got, (base, sorted(got))
    shares = [got[f"{b}.{sfx}"]["value"] for b in (
        "osd.loop_cpu_share", "osd.loop_idle_share",
        "osd.loop_offcore_share")]
    assert sum(shares) == pytest.approx(100.0, abs=1e-6), shares
    assert 0.0 <= shares[1] < 100.0 and shares[0] > 0.0
    assert got[f"osd.loop_poll_ms.{sfx}"]["value"] > 0
    if workload != "toy_write":
        gather = got[f"ec.read_gather_ms.{sfx}"]
        assert gather["value"] > 0 and gather["unit"] == "ms"
    else:
        assert f"ec.read_gather_ms.{sfx}" not in got
    if workload == "toy_mix":
        # every worker writes its own slice: no write ever waits to
        # reply in order, and the reader says so with a plain 0
        assert got["osd.reply_wait_ms.op_rate"]["value"] == 0.0
    # over the window every stage recorded under its name
    before, after = seen[0], seen[1]
    for stage in RECORDS[workload]:
        assert after[stage][0] > before.get(stage, (0, 0.0))[0], stage
    # and the new names carry a part of the loop's CPU the old ones did
    # not: the named share counts them by their prefix, with no edit
    named = got[f"osd.loop_named_share.{sfx}"]["value"]
    assert 0.0 < named < 150.0
    secs = {s: after[s][1] - before.get(s, (0, 0.0))[1] for s in after}
    wall = secs["loop_wall"]
    rest = wall - secs["evloop_idle"] - secs["evloop_poll"] - sum(
        v for s, v in secs.items() if s.startswith("loop_")
        and s not in ("loop_wall", "loop_cpu"))
    # the callbacks' wall without a name: what is left is not negative
    # beyond the sampler's tick (the window's edges fall between ticks)
    assert rest > -0.25, (rest, secs)

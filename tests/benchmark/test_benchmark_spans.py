"""The per-layer metrics that read the program's sections, intervals and
loop sampler (common/tracer.py `Tracer.section`; benchmark/spans.py and
the `seam.*_ms`, `ec.host_ms`, `osd.loop_*` readers under
benchmark/metrics/): each reader's arithmetic on a hand-made
observation, what it does on a program that has no such stage (a parent
commit: None, never 0, never an exception), and a traced toy cell on the
CPU in which every one of them finds something to read.

The toy manifest is built in a tmp dir from the tests' own one plus the
new entries of BENCHMARK.json; no file of the benchmark is edited.
Nothing here is a number about speed."""

import json
import pathlib
from types import SimpleNamespace

import pytest

from benchmark import manifest
from test_benchmark_rehearsal import TOY, run_toy

REPO = pathlib.Path(__file__).resolve().parents[2]
REAL = manifest.Manifest()
BASES = ("seam.pending_ms", "seam.stage_ms", "seam.launch_ms",
         "seam.fetch_ms", "seam.resume_ms", "ec.host_ms",
         "osd.loop_cpu_share", "osd.loop_named_share",
         "osd.loop_longest_ms")
NEW = [f"{b}.{sfx}" for b in BASES for sfx in ("goodput", "op_rate")]
TOY_CELLS = {"goodput": ["toy_write", "toy_seq_degraded"],
             "op_rate": ["toy_mix"]}

HOST, DEV = "/host:CPU", "/device:TPU:0"
#: a hand-made traced observation: 200 ops in the window
HAND = SimpleNamespace(
    ops=200,
    stages={
        "ec_encode": (200, 12.0),
        "seam_apply": (200, 10.0),
        "seam_pending": (200, 4.0),
        "seam_fold": (100, 0.5), "seam_h2d": (100, 0.3),
        "seam_launch": (100, 1.0),
        "seam_d2h": (100, 0.6), "seam_split": (100, 0.1),
        "seam_resume": (200, 2.0),
        "loop_ec_host": (400, 3.0),
        "loop_store_apply": (1200, 1.5),
        "loop_submit": (200, 0.5),
        "loop_wall": (510, 51.0), "loop_cpu": (510, 10.0),
    },
    trace={"events": [
        (HOST, "python", "loop_ec_host", 0, 4_000_000),
        (HOST, "python", "loop_store_apply", 5_000_000, 61_500_000),
        (HOST, "ec-device_0", "seam_d2h", 0, 90_000_000),
        (HOST, "python", "benchmark.traced_window", 0, 5_000_000_000),
        (DEV, "XLA Ops", "%loop_fusion = u8[2,64]", 0, 99_000_000),
    ]})
WANT = {
    "seam.pending_ms": 20.0,            # 4.0 s / 200 ops
    "seam.stage_ms": 4.0,               # (0.5 + 0.3) s / 200
    "seam.launch_ms": 5.0,
    "seam.fetch_ms": 3.5,               # (0.6 + 0.1) s / 200
    "seam.resume_ms": 10.0,
    "ec.host_ms": 15.0,
    "osd.loop_cpu_share": 100 * 10.0 / 51.0,
    "osd.loop_named_share": 100 * (3.0 + 1.5 + 0.5) / 10.0,
    "osd.loop_longest_ms": 61.5,        # the host's longest loop_* event
}
#: what a parent commit gives the same readers: the stages it has
PARENT = SimpleNamespace(
    ops=200, stages={"ec_encode": (200, 12.0), "op_total": (200, 30.0)},
    trace={"events": [e for e in HAND.trace["events"]
                      if not e[2].startswith(("loop_", "seam_"))]})


@pytest.mark.parametrize("name", NEW)
def test_reader_arithmetic_on_a_hand_made_observation(name):
    got = REAL.reader(name)(HAND)
    assert got == pytest.approx(WANT[name.rsplit(".", 1)[0]])


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_on_a_program_without_the_stage(name):
    assert REAL.reader(name)(PARENT) is None
    untraced = SimpleNamespace(ops=0, stages={}, trace=None)
    assert REAL.reader(name)(untraced) is None


def test_named_share_leaves_the_samplers_stages_out_and_needs_both():
    from benchmark import spans
    only_sampler = SimpleNamespace(
        stages={"loop_wall": (10, 1.0), "loop_cpu": (10, 0.5)})
    assert spans.loop_named_share(only_sampler) is None
    assert spans.loop_cpu_share(only_sampler) == pytest.approx(50.0)
    no_sampler = SimpleNamespace(stages={"loop_submit": (3, 0.1)})
    assert spans.loop_named_share(no_sampler) is None
    assert spans.loop_cpu_share(no_sampler) is None


def check_entry(man, name):
    """The entry `man` declares for `name` names every cell of its
    suffix and no other."""
    spec = man.per_layer[name]
    base, sfx = name.rsplit(".", 1)
    assert spec["moves"] == sfx and spec["source"] == "program_span"
    assert spec["workloads"] == [
        w["name"] for w in man.doc["workloads"]
        if sfx in {m["name"] for m in
                   man.metrics_of(w["name"], "end_to_end")}]
    assert spec["layer"] == {"seam": "Device seam", "ec": "EC backend",
                             "osd": "OSD / PG"}[base.split(".")[0]]
    assert spec["unit"] == ("%" if base.endswith("_share") else "ms")
    assert spec["better"] == ("higher" if base == "osd.loop_named_share"
                              else "lower")


@pytest.mark.parametrize("name", NEW)
def test_new_metric_is_declared_for_the_cells_of_its_suffix(name):
    check_entry(REAL, name)


@pytest.fixture(scope="module")
def toy_with_spans(tmp_path_factory):
    doc = json.loads(TOY.read_text())
    for name in NEW:
        spec = dict(REAL.per_layer[name])
        spec["workloads"] = TOY_CELLS[spec["moves"]]
        doc["per_layer"].append(spec)
    path = tmp_path_factory.mktemp("spans") / "manifest.json"
    path.write_text(json.dumps(doc))
    return manifest.Manifest(path=path)


@pytest.mark.parametrize("workload", ["toy_write", "toy_seq_degraded",
                                      "toy_mix"])
def test_traced_toy_cell_reports_every_new_metric(toy_with_spans, workload,
                                                  tmp_path):
    result, _diag, err = run_toy(toy_with_spans, workload, trace=True,
                                 tmp_path=tmp_path)
    assert result["correct"] is True, err
    sfx = "op_rate" if workload == "toy_mix" else "goodput"
    got = {b: result["metrics"].get(f"{b}.{sfx}") for b in BASES}
    for base, m in got.items():
        assert m is not None, (base, sorted(result["metrics"]))
        assert m["value"] > 0 and m["unit"] in ("ms", "%")
    assert got["osd.loop_cpu_share"]["value"] <= 100.0
    # the sections lie in the profiler's trace too: the idle device's
    # gaps carry their names
    named = [n for n, _s in result["breakdown"]["idle_gaps"]
             if n.startswith(("loop_", "seam_"))]
    assert named, result["breakdown"]["idle_gaps"]
    # the seam's stages lie below the stage they divide
    whole = result["metrics"][
        ("ec.decode_ms." if "degraded" in workload else "ec.encode_ms.")
        + sfx]["value"]
    parts = sum(got[b]["value"] for b in BASES[:5])
    assert 0 < parts < 1.5 * whole + 1.0, (parts, whole)

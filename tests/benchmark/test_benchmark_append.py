"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric by appending entries to BENCHMARK.json and adding files, and
edits no file the benchmark has.  So every check of this directory that
reads BENCHMARK.json has to hold on it with one of each appended, as it
holds on BENCHMARK.json as committed: the manifest's shape
(test_benchmark_manifest.py), each toy manifest's mirror of its cell
(test_benchmark_open.py, _records.py, _restart.py) and the declarations
of the per-layer metrics (test_benchmark_spans.py, _loop_account.py).
None of them may find an entry by where it sits in its list.

The appended copy lives in a tmp dir: the benchmark's paths copied
there, the dummy's files added to them, its own BENCHMARK.json."""

import json
import pathlib
import shutil

import pytest

from benchmark import manifest
import test_benchmark_loop_account as loop_account_tests
import test_benchmark_manifest as manifest_tests
import test_benchmark_open as open_tests
import test_benchmark_records as records_tests
import test_benchmark_restart as restart_tests
import test_benchmark_spans as spans_tests

REPO = pathlib.Path(__file__).resolve().parents[2]
#: the dummy is a copy of this cell under new names
CONTROL = "cos_mix_64k_w8"
CONFIG, TRAFFIC, CELL = "appended_k2m1", "appended_mix", "appended_mix"
METRIC = "appended.ops_seen.op_rate"


def append_dummy(root: pathlib.Path) -> dict:
    """Write a dummy configuration, traffic mix and per-layer reader under
    `root`/benchmark and return `root`'s BENCHMARK.json with their
    entries, and the one-chip cell that runs them, appended.

    The cell copies `CONTROL` and is appended to every list of cells that
    names it, as a PR that adds a cell beside its control does; nothing
    else of the manifest changes."""
    committed = manifest.Manifest(path=root / "BENCHMARK.json", root=root)
    doc, control = committed.doc, committed.workloads[CONTROL]
    cell = dict(control, name=CELL, config=CONFIG, traffic=TRAFFIC,
                chips=1, why="a dummy cell appended by a test")
    config = dict(committed.configs[control["config"]], name=CONFIG,
                  file=f"benchmark/configs/{CONFIG}.json")
    bench = root / "benchmark"
    shutil.copy(root / committed.configs[control["config"]]["file"],
                root / config["file"])
    shutil.copy(bench / "traffic" / f"{control['traffic']}.json",
                bench / "traffic" / f"{TRAFFIC}.json")
    (bench / "metrics" / f"{METRIC}.py").write_text(
        "def read(obs):\n    return float(obs.ops) if obs.ops else None\n")
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CONTROL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    doc["configs"].append(config)
    doc["workloads"].append(cell)
    doc["per_layer"].append({
        "name": METRIC, "unit": "ops", "better": "higher",
        "source": "program_counter", "layer": "Client",
        "moves": "op_rate", "workloads": [CELL]})
    return doc


@pytest.fixture(scope="module")
def appended(tmp_path_factory):
    root = tmp_path_factory.mktemp("appended")
    ignore = shutil.ignore_patterns("__pycache__", "_throwaway")
    for rel in manifest.Manifest().doc["paths"]:
        shutil.copytree(REPO / rel, root / rel, ignore=ignore)
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    doc = append_dummy(root)
    (root / "BENCHMARK.json").write_text(json.dumps(doc, indent=1) + "\n")
    return manifest.Manifest(path=root / "BENCHMARK.json", root=root)


@pytest.fixture(params=["as_committed", "appended"])
def man(request):
    if request.param == "appended":
        return request.getfixturevalue("appended")
    return manifest.Manifest()


def test_the_copy_differs_by_appended_entries_alone(appended):
    """Every list of the committed manifest is the head of its list in
    the copy, and the dummy's entries reach its cell."""
    real = manifest.Manifest()

    def heads(a, b):
        if isinstance(a, dict):
            assert set(a) == set(b)
            for key in a:
                heads(a[key], b[key])
        elif isinstance(a, list):
            assert len(b) >= len(a)
            for x, y in zip(a, b):
                heads(x, y)
        else:
            assert a == b
    heads(real.doc, appended.doc)
    for sec, name in (("configs", CONFIG), ("workloads", CELL),
                      ("per_layer", METRIC)):
        assert [e["name"] for e in appended.doc[sec]] == [
            e["name"] for e in real.doc[sec]] + [name]
    got = {m["name"] for m in appended.metrics_of(CELL, "per_layer")}
    assert got == {m["name"] for m in real.metrics_of(
        CONTROL, "per_layer")} | {METRIC}
    assert [m["name"] for m in appended.metrics_of(CELL, "end_to_end")] \
        == [m["name"] for m in real.metrics_of(CONTROL, "end_to_end")]


def test_every_manifest_check_holds(man):
    manifest_tests.check_manifest(man)


@pytest.mark.parametrize("cell_tests", [open_tests, records_tests,
                                        restart_tests],
                         ids=lambda mod: mod.__name__)
def test_every_toy_manifest_mirrors_its_cell(man, cell_tests):
    cell_tests.check_mirror(man)


def test_every_declared_per_layer_metric_names_its_cells(man):
    loop_account_tests.check_the_nine_are_declared(man)
    for name in loop_account_tests.NEW:
        loop_account_tests.check_entry(man, name)
    for name in spans_tests.NEW:
        spans_tests.check_entry(man, name)

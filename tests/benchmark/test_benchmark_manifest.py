"""BENCHMARK.json against the contract, as far as a CPU can check it:
names, units, files, which cell reports what, and that the benchmark's
files keep clear of the unit-test deployment.

Each check of the manifest's shape is a function of a `Manifest`, so
that test_benchmark_append.py can hold a manifest with entries appended
to every one of them: a later PR adds a cell by appending, and no check
here finds an entry by where it sits in a list."""

import json
import pathlib
import re

import pytest

from benchmark import manifest

REPO = pathlib.Path(__file__).resolve().parents[2]
MAN = manifest.Manifest()
DOC = MAN.doc
WIDTH_WORDS = re.compile(r"(_dim|_rank)$")


def all_metrics(man):
    return man.doc["end_to_end"] + man.doc["per_layer"]


def check_top_level_keys(man):
    doc = man.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= doc["run_seconds"] <= 51 and isinstance(
        doc["run_seconds"], int)
    assert len(json.dumps(doc)) < 64 * 1024
    # the full check of 24 cells has to fit into 43,200 s
    runs = 2 + 14 * 24
    assert runs * (doc["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def check_command_and_paths(man):
    doc = man.doc
    assert 1 <= len(doc["paths"]) <= 16
    for p in doc["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert not p.startswith("/") and ".." not in p
        assert (man.root / p).is_dir()
    assert len(doc["command"]) <= 32
    for word in doc["command"]:
        assert not word.startswith("/") and ".." not in word


def check_names_units_and_keys(entry):
    assert manifest.NAME_RE.fullmatch(entry["name"])
    if "unit" in entry:
        assert manifest.UNIT_RE.fullmatch(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]


def check_names_are_unique(man):
    doc = man.doc
    for sec in ("configs", "workloads"):
        names = [e["name"] for e in doc[sec]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in all_metrics(man)]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(pairs) == len(set(pairs))


def check_config(man, entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert any(entry["file"].startswith(p + "/") for p in man.doc["paths"])
    cfg = json.loads((man.root / entry["file"]).read_text())
    for key in ("source", "guarantees", "assumed", "reduced", "options",
                "pool"):
        assert cfg[key], key
    assert cfg["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert key in cfg and not WIDTH_WORDS.search(key)
    assert any(w["config"] == entry["name"] for w in man.doc["workloads"])
    # the deployment, not the unit tests' settings
    assert "lockdep" not in cfg["options"]
    assert cfg["options"]["osd_scrub_interval"] >= 86400
    assert cfg["options"]["osd_heartbeat_grace"] >= 20


def check_cell(man, cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    assert cell["config"] in man.configs
    traffic = man.traffic(cell["traffic"])
    assert hasattr(man.kind(traffic["kind"]), "Load")
    e2e = [m["name"] for m in man.metrics_of(cell["name"], "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = man.metrics_of(cell["name"], "per_layer")
    assert layer
    for m in layer:
        assert callable(man.reader(m["name"]))


def check_four_chips(man):
    cells = man.doc["workloads"]
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)


def check_end_to_end_bound(man, metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "bound", "source"}
    assert 0.01 <= metric["bound"] <= 0.25
    assert metric["source"] in ("host_clock", "device_trace")
    for cell in metric.get("workloads", []):
        assert cell in man.workloads


def check_per_layer_metric(man, metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "source", "layer", "moves"}
    assert metric["moves"] in man.end_to_end
    cells = metric.get("workloads") or [
        w["name"] for w in man.doc["workloads"]]
    for cell in cells:
        assert cell in man.workloads
        reported = {m["name"] for m in man.metrics_of(cell, "end_to_end")}
        assert metric["moves"] in reported, (metric["name"], cell)
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def check_layers(man):
    layers = {m["layer"] for m in man.doc["per_layer"]}
    assert layers == {"Client", "Messenger", "OSD / PG", "EC backend",
                      "Device seam", "EC kernel", "Launch"}


def check_manifest(man):
    """Every check above, over every entry of `man`."""
    check_top_level_keys(man)
    check_command_and_paths(man)
    for entry in man.doc["configs"] + man.doc["workloads"] + all_metrics(
            man):
        check_names_units_and_keys(entry)
    check_names_are_unique(man)
    for entry in man.doc["configs"]:
        check_config(man, entry)
    for cell in man.doc["workloads"]:
        check_cell(man, cell)
    check_four_chips(man)
    for metric in man.doc["end_to_end"]:
        check_end_to_end_bound(man, metric)
    for metric in man.doc["per_layer"]:
        check_per_layer_metric(man, metric)
    check_layers(man)


def test_top_level_keys_are_exactly_the_contracts():
    check_top_level_keys(MAN)


def test_command_and_paths_stay_inside_the_benchmark():
    check_command_and_paths(MAN)


@pytest.mark.parametrize("entry", DOC["configs"] + DOC["workloads"]
                         + all_metrics(MAN), ids=lambda e: e["name"])
def test_names_units_and_keys(entry):
    check_names_units_and_keys(entry)


def test_names_are_unique():
    check_names_are_unique(MAN)


@pytest.mark.parametrize("entry", DOC["configs"], ids=lambda e: e["name"])
def test_config_file_states_source_guarantees_assumed_reduced(entry):
    check_config(MAN, entry)


@pytest.mark.parametrize("cell", DOC["workloads"], ids=lambda e: e["name"])
def test_cell_has_its_files_and_reports_enough(cell):
    check_cell(MAN, cell)


def test_at_most_half_of_the_cells_take_four_chips():
    check_four_chips(MAN)


@pytest.mark.parametrize("metric", DOC["end_to_end"],
                         ids=lambda e: e["name"])
def test_end_to_end_bounds(metric):
    check_end_to_end_bound(MAN, metric)


@pytest.mark.parametrize("metric", DOC["per_layer"],
                         ids=lambda e: e["name"])
def test_per_layer_metric_moves_what_each_of_its_cells_reports(metric):
    check_per_layer_metric(MAN, metric)


def test_metrics_of_one_layer_name_it_letter_for_letter():
    check_layers(MAN)


def test_no_benchmark_file_starts_from_the_unit_test_deployment():
    for path in (REPO / "benchmark").rglob("*.py"):
        text = path.read_text()
        assert "make_ctx" not in text and "FAST_CFG" not in text.replace(
            "no qa FAST_CFG", ""), path


def test_object_names_do_not_depend_on_the_seed():
    from types import SimpleNamespace
    kind = MAN.kind("closed_loop")
    traffic = dict(MAN.traffic("cos_mix_64k_w8"), object_size=64,
                   payloads=2)
    names = []
    for seed in (1, 2_500_000_000):
        env = SimpleNamespace(cell="c", seed=seed, traffic=traffic,
                              k=2, m=1)
        load = kind.Load(env)
        names.append((load.read_names, load.write_names))
        assert len(set(load.read_names) | set(load.write_names)) == 8192
    assert names[0] == names[1]
    assert names[0][0][0] == "benchmark_data_c_object0"

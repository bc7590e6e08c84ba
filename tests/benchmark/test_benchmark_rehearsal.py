"""Toy-size rehearsals of a whole run on the CPU: every traffic mix the
cells use, traced and not, through the same harness the chip runs; the
controls (a fault planted under the timed path has to come out `correct:
false`); the refusal to measure without a TPU; and the proof that a
cell, a configuration, a traffic kind and a per-layer metric are each
added with new files and entries alone.

Nothing here is a number about speed: the device is the CPU and every
line says so."""

import asyncio
import io
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from benchmark import harness, manifest

REPO = pathlib.Path(__file__).resolve().parents[2]
TOY = REPO / "tests" / "benchmark" / "toy" / "manifest.json"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_toy(man, workload, *, trace=False, fault=None, seed=2_500_000_011,
            seconds=1.0, tmp_path=None):
    out, err = io.StringIO(), io.StringIO()
    result = asyncio.run(harness.run_cell(
        man, workload, seed, seconds, trace, require_tpu=False,
        fault=fault, out=out, err=err,
        trace_dir=str(tmp_path / "trace") if tmp_path else None))
    lines = out.getvalue().strip().splitlines()
    assert json.loads(lines[-1]) == json.loads(json.dumps(result))
    diag = json.loads(lines[-2][len("diag "):])
    return result, diag, err.getvalue()


@pytest.fixture(scope="module")
def toy():
    return manifest.Manifest(path=TOY)


@pytest.mark.parametrize("workload", ["toy_write", "toy_seq_degraded",
                                      "toy_mix"])
def test_rehearsal_prints_the_contracts_line_and_compiles_nothing(
        toy, workload):
    result, diag, err = run_toy(toy, workload)
    assert RESULT_KEYS <= set(result)
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, err
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    want = {m["name"] for m in toy.metrics_of(workload, "end_to_end")}
    assert set(result["metrics"]) == want and "setup_s" in want
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    # R2: set-up walked every width the loop then presented
    assert diag["window_jax"]["compile_events"] == 0
    assert diag["window_jax"]["cache_misses"] == 0
    assert diag["warm"]["groups"] >= 4
    # the hazards of a sound run: no map change, scrub or peering
    assert diag["osdmap_epoch"][0] == diag["osdmap_epoch"][1]
    assert diag["scrubs"] == 0 and diag["peering_events"] == 0
    assert diag["seam"]["host_bytes"] == 0
    assert diag["seam"]["device_fallbacks"] == 0
    assert diag["killed_osds"] == ([5] if "degraded" in workload else [])
    # every number compared is printed beside its limit, last on stderr
    tail = err.strip().splitlines()
    assert tail[-1] == "correct: True"
    for name, c in result["compared"].items():
        assert any(ln.startswith(f"compared {name}: ") for ln in tail)
        assert c["limit"] is None or c["value"] <= c["limit"]


@pytest.mark.parametrize("workload", ["toy_write", "toy_seq_degraded",
                                      "toy_mix"])
def test_traced_rehearsal_reports_the_per_layer_metrics(toy, workload,
                                                       tmp_path):
    result, diag, err = run_toy(toy, workload, trace=True,
                                tmp_path=tmp_path)
    assert result["correct"] is True, err
    got = set(result["metrics"])
    declared = {m["name"] for m in toy.metrics_of(workload, "per_layer")}
    assert got <= declared
    # the device-trace readers find no TPU plane on the CPU and return
    # nothing (never 0 for a share); every other reader reads
    silent = {n for n in declared if n.startswith("kernel.")}
    assert got == declared - silent
    sfx = "op_rate" if workload == "toy_mix" else "goodput"
    assert result["metrics"][f"seam.device_byte_fraction.{sfx}"][
        "value"] == 100.0
    assert result["metrics"][f"launch.window_compiles.{sfx}"]["value"] == 0
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(result["breakdown"]["idle_gaps"]) <= 10
    assert not (tmp_path / "trace").exists()      # read, then deleted


@pytest.mark.parametrize("workload,fault,caught_by", [
    ("toy_write", "stale_write", "readback_mismatch device_bytes_short"),
    ("toy_write", "seam_corrupt", "shard_mismatch"),
    ("toy_mix", "stale_write", "readback_mismatch device_bytes_short"),
    ("toy_mix", "answer_flip", "window_read_mismatch"),
    ("toy_mix", "seam_corrupt", "shard_mismatch"),
    ("toy_seq_degraded", "answer_flip", "window_read_mismatch"),
    ("toy_seq_degraded", "seam_corrupt", "window_read_mismatch"),
])
def test_control_comes_out_not_correct(toy, workload, fault, caught_by):
    result, _diag, err = run_toy(toy, workload, fault=fault)
    assert result["correct"] is False
    # on a ring this small a dropped write is often overwritten before
    # the read-back; the seam's count of the bytes then still misses it
    over = [n for n in caught_by.split()
            if result["compared"][n]["value"] > 0
            and result["compared"][n]["limit"] == 0]
    assert over
    assert err.strip().splitlines()[-1] == "correct: False"


def test_run_refuses_to_measure_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "rb_write_4m_qd16", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_refuses_an_unknown_workload():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "nope",
         "--seed", "1", "--seconds", "1"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_a_cell_config_kind_and_metric_are_added_with_new_files_alone(
        tmp_path):
    """A later PR's view: a directory of NEW files (a configuration, a
    traffic mix, a traffic kind, a per-layer metric's reader) plus
    entries in the manifest; no file of benchmark/ is edited."""
    before = {p: p.read_bytes() for p in (REPO / "benchmark").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    new = REPO / "tests" / "benchmark" / "_throwaway"
    shutil.rmtree(new, ignore_errors=True)
    try:
        for sub in ("configs", "traffic", "kinds", "metrics"):
            (new / sub).mkdir(parents=True)
        cfg = json.loads((REPO / "tests/benchmark/toy/configs/"
                          "toy_k2m1.json").read_text())
        cfg["name"] = "new_k3m2"
        cfg["pool"].update(k=3, m=2, name="newpool")
        (new / "configs/new_k3m2.json").write_text(json.dumps(cfg))
        (new / "traffic/new_mix.json").write_text(json.dumps({
            "kind": "new_kind", "depth": 3, "object_size": 3 * 32768,
            "read_ratio": 0.5, "read_objects": 9, "write_objects": 9,
            "write_select": "uniform", "payloads": 4, "ramp_s": 0.2,
            "keep_reads": 16, "keep_prob": 0.5, "check_shards": 3}))
        (new / "kinds/new_kind.py").write_text(
            "from benchmark.manifest import Manifest\n"
            "_base = Manifest().kind('closed_loop')\n\n\n"
            "class Load(_base.Load):\n"
            "    new_kind_ran = True\n")
        (new / "metrics/new.ops_seen.op_rate.py").write_text(
            "def read(obs):\n    return float(obs.ops)\n")
        doc = json.loads(TOY.read_text())
        doc["paths"] = ["tests/benchmark/_throwaway"] + doc["paths"]
        doc["configs"].append({
            "name": "new_k3m2", "source": "throw-away", "reduced": [],
            "file": "tests/benchmark/_throwaway/configs/new_k3m2.json",
            "why": "throw-away"})
        doc["workloads"].append({
            "name": "new_cell", "config": "new_k3m2",
            "traffic": "new_mix", "chips": 1, "why": "throw-away"})
        for m in doc["end_to_end"]:
            if m["name"] in ("op_rate", "write_p95", "read_p95"):
                m["workloads"].append("new_cell")
        doc["per_layer"].append({
            "name": "new.ops_seen.op_rate", "unit": "ops",
            "better": "higher", "source": "program_counter",
            "layer": "Client", "moves": "op_rate",
            "workloads": ["new_cell"]})
        (new / "manifest.json").write_text(json.dumps(doc))
        man = manifest.Manifest(path=new / "manifest.json")
        result, _diag, err = run_toy(man, "new_cell")
        assert result["correct"] is True, err
        assert set(result["metrics"]) == {"op_rate", "write_p95",
                                          "read_p95", "setup_s"}
        result, _diag, err = run_toy(man, "new_cell", trace=True,
                                     tmp_path=tmp_path)
        assert result["metrics"]["new.ops_seen.op_rate"]["value"] > 0
        assert man.kind("new_kind").Load.new_kind_ran
    finally:
        shutil.rmtree(new, ignore_errors=True)
    after = {p: p.read_bytes() for p in (REPO / "benchmark").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert after == before

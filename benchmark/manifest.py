"""BENCHMARK.json and the files its entries name.

Whatever belongs to one configuration, one traffic mix, one traffic
kind or one per-layer metric is a file of its own, found by the name in
the manifest under one of its `paths`:

    <file of the config entry>         the deployment, as it is run
    traffic/<traffic>.json             the mix's parameters
    kinds/<kind>.py                    the loop a mix's "kind" names
    metrics/<per-layer metric>.py      that metric's reader

A later PR adds a cell, a configuration, a kind or a metric by adding
files and entries; nothing here is edited for it."""

from __future__ import annotations

import importlib.util
import json
import pathlib
import re
from typing import Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class ManifestError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


class Manifest:
    def __init__(self, path: Optional[pathlib.Path] = None,
                 root: Optional[pathlib.Path] = None):
        self.root = pathlib.Path(root or ROOT)
        self.path = pathlib.Path(path or self.root / "BENCHMARK.json")
        try:
            self.doc = json.loads(self.path.read_text())
        except (OSError, ValueError) as e:
            raise ManifestError(f"cannot read {self.path}: {e}") from e
        self.dirs = [self.root / p for p in self.doc["paths"]]
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.workloads = {w["name"]: w for w in self.doc["workloads"]}
        self.end_to_end = {m["name"]: m for m in self.doc["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.doc["per_layer"]}

    # ------------------------------------------------------------ lookup
    def find(self, rel: str) -> pathlib.Path:
        """The first file `rel` under the manifest's paths."""
        for d in self.dirs:
            p = d / rel
            if p.is_file():
                return p
        raise ManifestError(
            f"no {rel} under {[str(d) for d in self.dirs]}")

    def workload(self, name: str) -> dict:
        if name not in self.workloads:
            raise ManifestError(
                f"no workload {name!r}; BENCHMARK.json has "
                f"{sorted(self.workloads)}")
        return self.workloads[name]

    def config(self, name: str) -> dict:
        entry = self.configs[name]
        return json.loads((self.root / entry["file"]).read_text())

    def traffic(self, name: str) -> dict:
        return json.loads(self.find(f"traffic/{name}.json").read_text())

    def kind(self, name: str):
        return load_module(self.find(f"kinds/{name}.py"), f"kind_{name}")

    def reader(self, metric: str):
        mod = load_module(self.find(f"metrics/{metric}.py"),
                          "metric_" + re.sub(r"\W", "_", metric))
        return mod.read

    # -------------------------------------------------- which metric where
    def metrics_of(self, workload: str, section: str) -> List[dict]:
        """The metrics of `section` that cell `workload` reports: those
        that list it, and those that list no cells at all (for a
        per-layer metric: every cell that reports what it moves)."""
        out = []
        for m in self.doc[section]:
            cells = m.get("workloads")
            if cells is not None:
                if workload in cells:
                    out.append(m)
            elif section == "end_to_end" or m["moves"] in {
                    e["name"] for e in
                    self.metrics_of(workload, "end_to_end")}:
                out.append(m)
        return out


_loaded: Dict[str, object] = {}


def load_module(path: pathlib.Path, name: str):
    """Import a file by path (metric files carry dots in their names)."""
    key = str(path)
    if key not in _loaded:
        spec = importlib.util.spec_from_file_location(
            f"benchmark._found.{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[key] = mod
    return _loaded[key]

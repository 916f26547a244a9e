"""Where a cell's files are: everything is found by the names in
``BENCHMARK.json``, so a later PR adds files and entries and edits none.

    configs/<config>.json          the deployment as it is run (the
                                   manifest's ``file``), naming its
                                   plain reference, a module beside it
    traffic/<cell>.json            the cell's traffic mix: parameters
                                   for the one general generator, the
                                   driver that feeds them to the
                                   program, the limits of ``correct``
    drivers/<driver>.py            one entry point of the program
    metrics/<metric>.py            one per-layer metric's reader
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import Any, Dict, List

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> Dict[str, Any]:
    with open(path, encoding="ascii") as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` as a module.  A metric's name may hold dots,
    so its reader is loaded by path; drivers and references are plain
    module names (``BENCH`` is on ``sys.path``)."""
    path = os.path.join(BENCH, kind, name + ".py")
    if name.isidentifier() and os.path.isfile(path):
        return importlib.import_module(f"{kind}.{name}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r}: {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its files loaded."""

    def __init__(self, manifest: Dict[str, Any], name: str,
                 root: str = ROOT):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                           f"{sorted(cells)}")
        self.manifest = manifest
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg = {c["name"]: c for c in manifest["configs"]}[
            self.entry["config"]]
        self.config = load_json(os.path.join(root, cfg["file"]))
        self.traffic = load_json(os.path.join(BENCH, "traffic",
                                              name + ".json"))
        self.driver = load_module("drivers", self.traffic["driver"])
        self.reference = load_module("configs", self.config["reference"])

    def _reported(self, metric: Dict[str, Any]) -> bool:
        return "workloads" not in metric \
            or self.name in metric["workloads"]

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.manifest["end_to_end"]
                if self._reported(m)]

    def per_layer(self) -> List[Dict[str, Any]]:
        return [m for m in self.manifest["per_layer"]
                if self._reported(m)]

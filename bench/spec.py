"""Find every part of a cell by name, from ``BENCHMARK.json`` and data files.

Nothing here is specific to one cell: a configuration is
``configs/<config>.json``, a traffic mix ``traffic/<traffic>.json``, a
cell's server settings and limits ``cells/<workload>.json``, a per-layer
metric's reader ``metrics/<metric>.py``, a family's module
``references/<reference>.py`` (weight table, plain reference and counts;
its contract is ``references/__init__.py``'s docstring) and the device
peaks ``peaks.json``.  A new cell, mix, metric or family is new files and
new entries, never an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""
    name: str
    chips: int
    config: Dict[str, Any]        # configs/<config>.json
    traffic: Dict[str, Any]       # traffic/<traffic>.json
    settings: Dict[str, Any]      # cells/<workload>.json
    family: Any                   # references/<config's reference>.py
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


class Bench:
    """``BENCHMARK.json`` and the directory that holds the named files."""

    def __init__(self, root: str = ROOT, bench_dir: str = BENCH_DIR,
                 spec: Optional[Dict[str, Any]] = None):
        self.root = root
        self.dir = bench_dir
        self.spec = spec if spec is not None else _load_json(
            os.path.join(root, "BENCHMARK.json"))

    def _path(self, *parts: str) -> str:
        return os.path.join(self.dir, *parts)

    def workload(self, name: str) -> Dict[str, Any]:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return _load_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def cell(self, name: str) -> Cell:
        w = self.workload(name)
        config = self.config(w["config"])
        e2e = self.spec["end_to_end"]
        e2e_names = {m["name"] for m in e2e}
        per_layer = [m for m in self.spec["per_layer"]
                     if m["moves"] in e2e_names]
        return Cell(name=name, chips=int(w["chips"]),
                    config=config,
                    traffic=_load_json(self._path(
                        "traffic", w["traffic"] + ".json")),
                    settings=_load_json(self._path("cells", name + ".json")),
                    family=self.reference(config["reference"]),
                    end_to_end=e2e, per_layer=per_layer)

    def metric_reader(self, metric: str) -> Callable[[Any], Optional[float]]:
        """``metrics/<metric>.py``'s ``read(run) -> float | None``."""
        mod = _load_module(self._path("metrics", metric + ".py"),
                           "bench_metric_" + metric.replace(".", "_"))
        return mod.read

    def reference(self, name: str):
        """``references/<name>.py``: the family's module."""
        return _load_module(self._path("references", name + ".py"),
                            "bench_reference_" + name)

    def peaks(self, device_kind: str) -> Dict[str, Any]:
        return device_peaks(device_kind, self._path("peaks.json"))


def device_peaks(device_kind: str,
                 path: str = os.path.join(BENCH_DIR, "peaks.json")
                 ) -> Dict[str, Any]:
    """The published peaks of ``device_kind``; an unknown kind is an error,
    never a default."""
    table = _load_json(path)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; have {sorted(table)}")
    return table[device_kind]

"""Find a cell's files by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files behind those names are:

* ``bench/configs/<config>.json`` -- the entry's ``file``: model keys,
  DSA settings, serving dtype and the engine settings the cell fixes;
* ``bench/traffic/<traffic>.json`` -- parameters for ``traffic.Traffic``;
* ``bench/checks/<cell>.json`` -- the numbers the output check compares
  and their limits;
* ``bench/metrics/<metric>.py`` -- one reader per metric, ``read(ctx)``.

Adding a cell, mix, configuration or metric adds files and entries only.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]


class SpecError(Exception):
    pass


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its configuration, traffic, check
    and metric entries resolved."""

    def __init__(self, name: str, root: Path = ROOT):
        self.root = Path(root)
        bm_path = self.root / "BENCHMARK.json"
        if not bm_path.is_file():
            raise SpecError(f"no BENCHMARK.json at {self.root}")
        self.benchmark = load_json(bm_path)
        cells = {w["name"]: w for w in self.benchmark["workloads"]}
        if name not in cells:
            raise SpecError(f"unknown workload {name!r}; known: "
                            f"{sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.benchmark["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = load_json(self.root / self.config_entry["file"])
        self.traffic_name = self.entry["traffic"]
        self.traffic = load_json(self.root / "bench" / "traffic"
                                 / f"{self.traffic_name}.json")
        self.check = load_json(self.root / "bench" / "checks"
                               / f"{name}.json")

    def metrics(self, trace: bool) -> List[Dict[str, Any]]:
        """The metric entries this cell reports: its end-to-end metrics in
        a ``--trace 0`` run, its per-layer metrics in a ``--trace 1``
        run."""
        e2e = [m for m in self.benchmark["end_to_end"]
               if self.name in m.get("workloads", [self.name])]
        if not trace:
            return e2e
        mine = {m["name"] for m in e2e}
        return [m for m in self.benchmark["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]

    def reader(self, metric: str) -> Callable[[Dict[str, Any]],
                                              Optional[float]]:
        return load_reader(self.root, metric)


def load_reader(root: Path, metric: str):
    path = Path(root) / "bench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise SpecError(f"no reader for metric {metric!r} at {path}")
    mod_name = "bench_metric_" + "".join(
        c if c.isalnum() else "_" for c in metric)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read

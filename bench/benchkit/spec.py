"""Find a cell's files by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files behind those names are:

* ``bench/configs/<config>.json`` -- the entry's ``file``: model keys,
  DSA settings, serving dtype and the engine settings the cell fixes;
* ``bench/traffic/<traffic>.json`` -- parameters for ``traffic.Traffic``;
* ``bench/checks/<cell>.json`` -- the numbers the output check compares
  and their limits;
* ``bench/metrics/<metric>.py`` -- one reader per metric, ``read(ctx)``;
* ``bench/archs/<model_type>.py`` -- everything that knows one
  architecture, found by the configuration's ``model_type``.  Such a
  module provides

  - ``read_shapes(s, cfg)``: set the architecture's sizes on the
    ``model.Shapes`` ``s`` from its configuration keys;
  - ``model_config(s, cfg, name, dsa)``: the program's ``ModelConfig``;
  - ``weights(s, key, dtype)``: the weights in the pytree the program
    serves, drawn from ``key`` (traced inside one jitted call);
  - ``embed(params, ids)``, ``layer_params(params, layer)`` and
    ``head_params(params)``: the parts of that pytree the reference reads;
  - ``layer_kind(s, layer)``: a name for the kind of layer ``layer`` is;
    layers of one kind share one reference program;
  - ``reference_layer(s, kind, ops, S, n)``: that kind's layer over a
    prompt of ``S`` positions and ``n`` decode positions, returning the
    new hidden states and the cached state as the host pool names it,
    ``{name: (T, heads, width)}`` (``k`` and ``v``, or ``k`` alone for a
    latent cache), built from ``reference``'s shared helpers (norm,
    rotary embedding, blocked causal attention, DSA decode attention,
    which take the softmax scale, any number of query heads per key head
    and values narrower than keys);
  - ``reference_head(s, ops)``: the final norm and the logits;
  - ``layer_counts(s, layer)``: the operations of layer ``layer`` (see
    ``flops``).

Adding a cell, mix, configuration, metric or architecture adds files and
entries only.
"""
from __future__ import annotations

import importlib.util
import json
import re
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
        from benchkit.model import Shapes
        self.shapes = Shapes(self.config, self.root)

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


def _load(path: Path, prefix: str, name: str):
    mod_name = prefix + "".join(c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, metric: str):
    path = Path(root) / "bench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise SpecError(f"no reader for metric {metric!r} at {path}")
    return _load(path, "bench_metric_", metric).read


def load_arch(root: Path, model_type: str):
    """The module ``bench/archs/<model_type>.py`` under ``root``."""
    archs = Path(root) / "bench" / "archs"
    path = (archs / f"{model_type}.py").resolve()
    if not (re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]*", str(model_type))
            and path.is_file()):
        known = sorted(p.stem for p in archs.glob("*.py"))
        raise SpecError(f"no architecture module for model_type "
                        f"{model_type!r} under {archs}; known: {known}")
    return _load(path, "bench_arch_", model_type)

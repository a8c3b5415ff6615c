"""Find a cell's configuration, traffic mix, generator and metric readers by
the names ``BENCHMARK.json`` gives them.

Every definition is a file of its own under the benchmark directory:

  configs/<config>.json      a deployment (generator, sizes, guarantees,
                             the control that breaks one of them)
  traffic/<traffic>.json     a traffic mix (query, keys, arrivals, warm-up)
  generators/<kind>.py       ``generate(cfg, seed) -> Deployment``
  queries/<kind>.py          ``build(q)``, and the plain reference:
                             ``answers(dep, q, params)``, ``served(q, result)``
  keys/<kind>.py             ``draw(spec, q, dep, rng, warm_rng, n_streams,
                             length, n_warm) -> (streams, warm-up)``
  arrivals/<kind>.py         ``shape``, ``drive``, ``in_window``, ``missing``
  controls/<kind>.py         ``broken(dep, spec) -> Deployment``
  metrics/<metric>.py        ``read(window) -> float | None`` and its LAYER

so a later change adds a cell, a mix or a metric by adding a file and an
entry, and edits none. ``Registry(root)`` reads the tree under ``root``
(the checkout), which tests point at a directory of their own.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parent.parent


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]  # metrics this cell reports with --trace 0
    per_layer: List[Dict[str, Any]]  # metrics this cell reports with --trace 1


class Registry:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench_dir = self.root / "bench"
        with open(self.root / "BENCHMARK.json") as f:
            self.spec = json.load(f)

    # -------------------------------------------------------------- files
    def _json(self, kind: str, name: str) -> Dict[str, Any]:
        path = self.bench_dir / kind / f"{name}.json"
        with open(path) as f:
            data = json.load(f)
        if data.get("name") != name:
            raise ValueError(f"{path}: 'name' is {data.get('name')!r}, not {name!r}")
        return data

    def _module(self, kind: str, name: str) -> ModuleType:
        path = self.bench_dir / kind / f"{name}.py"
        if not path.is_file():
            raise FileNotFoundError(f"no {kind} file {path}")
        mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
        return mod

    def config(self, name: str) -> Dict[str, Any]:
        return self._json("configs", name)

    def traffic(self, name: str) -> Dict[str, Any]:
        return self._json("traffic", name)

    def generator(self, kind: str) -> ModuleType:
        return self._module("generators", kind)

    def metric(self, name: str) -> ModuleType:
        return self._module("metrics", name)

    def query(self, kind: str) -> ModuleType:
        return self._module("queries", kind)

    def keys(self, kind: str) -> ModuleType:
        return self._module("keys", kind)

    def arrivals(self, kind: str) -> ModuleType:
        return self._module("arrivals", kind)

    def control(self, kind: str) -> ModuleType:
        return self._module("controls", kind)

    # -------------------------------------------------------------- cells
    def cell(self, name: str) -> Cell:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                break
        else:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        e2e = [m for m in self.spec["end_to_end"]
               if name in m.get("workloads", [name])]
        reported = {m["name"] for m in e2e}

        def reports(m):
            if "workloads" in m:
                return name in m["workloads"]
            return m["moves"] in reported

        per_layer = [m for m in self.spec["per_layer"] if reports(m)]
        return Cell(
            name=name, chips=int(w["chips"]),
            config=self.config(w["config"]), traffic=self.traffic(w["traffic"]),
            end_to_end=e2e, per_layer=per_layer,
        )

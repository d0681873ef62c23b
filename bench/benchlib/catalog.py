"""Cells, configurations, traffic mixes and metric readers, found by name.

``BENCHMARK.json`` names every cell with its configuration and traffic,
and every metric.  Each of those lives in a file of its own under the
benchmark's directory, so a new one is a new file plus a new entry:

* ``configs/<config>.json``: the model, its sizes, batch, optimizer
  and the limits of the correctness check;
* ``traffic/<traffic>.json``: the dataset the pipeline serves and the
  cache tier in front of it;
* ``reference/<reference>.py``: the plain reference a configuration
  names under ``"reference"``, with ``check_steps(config, traffic,
  seeds, rows, ids, *, precision="f32", use_rows=0)``, which follows
  the set-up steps, and ``train_flops_per_sample(sizes)``;
* ``metrics/<metric>.py``: a reader with ``read(run) -> float | None``.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional, Sequence

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    read: Callable


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Metric]
    per_layer: List[Metric]
    run_seconds: int
    reference: Optional[ModuleType] = None


def _load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, kind: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(path: Path, name: str) -> Callable:
    return load_module(path, "metric", name).read


class Catalog:
    """Everything ``BENCHMARK.json`` names, looked up by name in
    ``dirs`` (first match wins; the benchmark's own directory last)."""

    def __init__(self, spec_path: Optional[Path] = None,
                 dirs: Sequence[Path] = ()):
        self.spec_path = Path(spec_path or ROOT / "BENCHMARK.json")
        self.dirs = [Path(d) for d in dirs] + [BENCH_DIR]
        self.spec = _load_json(self.spec_path)

    def find(self, kind: str, filename: str) -> Path:
        for d in self.dirs:
            if (d / kind / filename).is_file():
                return d / kind / filename
        raise FileNotFoundError(f"no {kind}/{filename} in "
                                f"{[str(d) for d in self.dirs]}")

    def config(self, name: str) -> Dict:
        return _load_json(self.find("configs", f"{name}.json"))

    def traffic(self, name: str) -> Dict:
        return _load_json(self.find("traffic", f"{name}.json"))

    def reference(self, config: Dict) -> ModuleType:
        """The module ``reference/<name>.py`` that ``config["reference"]``
        names; a configuration without one cannot be checked."""
        name = config.get("reference")
        try:
            path = self.find("reference", f"{name}.py") if name else None
        except FileNotFoundError:
            path = None
        if path is None:
            raise LookupError(
                f"configuration {config.get('name')!r} names reference "
                f"{name!r}; no reference/<name>.py for it in "
                f"{[str(d) for d in self.dirs]}")
        return load_module(path, "reference", name)

    def _metric(self, entry: Dict) -> Metric:
        return Metric(entry["name"], entry["unit"], entry["better"],
                      entry["source"],
                      load_reader(self.find("metrics", f"{entry['name']}.py"),
                                  entry["name"]))

    def cell(self, workload: str) -> Cell:
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in {self.spec_path}; "
                           f"known: {sorted(cells)}")
        w = cells[workload]
        e2e = [m for m in self.spec["end_to_end"]
               if workload in m.get("workloads", [workload])]
        e2e_names = {m["name"] for m in e2e}
        layer = [m for m in self.spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
        config = self.config(w["config"])
        return Cell(w["name"], int(w["chips"]), config,
                    self.traffic(w["traffic"]),
                    [self._metric(m) for m in e2e],
                    [self._metric(m) for m in layer],
                    int(self.spec["run_seconds"]), self.reference(config))

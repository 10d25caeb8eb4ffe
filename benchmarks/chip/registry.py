"""Finds what a cell is made of, by name, in data files.

A configuration is `configs/<name>.json`, a traffic mix `traffic/<name>.json`,
a per-layer metric `metrics/<name>.py` (a module with `read(run) -> float |
None`), the plain reference model a configuration names under its key
`"reference"` `references/<name>.py` (the contract is in `reference.py`'s
docstring), and the limits of a cell's `correct` `limits/<cell>.json`. Each
is looked up in the search roots in order (the benchmark's own directory
first), so a later cell adds files and edits none.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Sequence

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]                   # the checkout
BENCHMARK = ROOT / "BENCHMARK.json"


class Registry:
    def __init__(self, roots: Sequence[Path] = (HERE,),
                 benchmark: Path = BENCHMARK):
        self.roots = [Path(r) for r in roots]
        self.benchmark_path = Path(benchmark)

    def _find(self, sub: str, name: str, suffix: str) -> Path:
        for r in self.roots:
            p = r / sub / f"{name}{suffix}"
            if p.is_file():
                return p
        raise KeyError(f"no {sub}/{name}{suffix} under "
                       f"{[str(r) for r in self.roots]}")

    def _json(self, sub: str, name: str) -> Dict:
        return json.loads(self._find(sub, name, ".json").read_text())

    def config(self, name: str) -> Dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> Dict:
        return self._json("traffic", name)

    def limits(self, cell: str) -> Dict:
        return self._json("limits", cell)

    def _module(self, sub: str, name: str) -> ModuleType:
        path = self._find(sub, name, ".py")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{sub}_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metric(self, name: str) -> ModuleType:
        """The reader module of a per-layer metric: `read(run)` returns the
        value or None when the run holds nothing to read; an optional
        `KERNELS` tuple names kernels the trace reduction must sum."""
        return self._module("metrics", name)

    def reference(self, name: str) -> ModuleType:
        """The plain reference model module `references/<name>.py`."""
        return self._module("references", name)

    def model(self, cfg: Dict) -> ModuleType:
        """The reference model of a configuration: the module its
        `"reference"` key names, else the dense model of `reference.py`."""
        if "reference" in cfg:
            return self.reference(cfg["reference"])
        from benchmarks.chip import reference
        return reference

    def benchmark(self) -> Dict:
        return json.loads(self.benchmark_path.read_text())

    def workload(self, name: str) -> Dict:
        for w in self.benchmark()["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.benchmark_path}")

    def metrics_of(self, cell: str, table: str) -> List[Dict]:
        """The `end_to_end` or `per_layer` entries the cell reports."""
        return [m for m in self.benchmark()[table]
                if cell in m.get("workloads", [cell])]

"""Finds a cell's configuration, traffic mix and metric readers by the names
``BENCHMARK.json`` gives them.  Nothing here lists them: a new one is a new
file plus a new entry.

- configuration ``<c>``: ``bench/configs/<c>.json`` (the sizes as run; the
  entry's ``file``) and ``bench/configs/<c>.py`` (the deployment beside its
  plain reference), which defines ``Deployment``;
- traffic mix ``<t>``: ``bench/traffic/<t>.json``, read by the one
  generator in :mod:`bench.harness.traffic`;
- metric ``<m>``: ``bench/metrics/<m>.py``, which defines ``read(run)``.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_module(path: Path, kind: str) -> ModuleType:
    """Import a file whose name may hold ``-`` or ``.``."""
    name = f"bench_{kind}_" + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config_entry(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return json.loads((self.root / self.config_entry(name)["file"]).read_text())

    def deployment_module(self, name: str) -> ModuleType:
        path = self.root / self.config_entry(name)["file"]
        return load_module(path.with_suffix(".py"), "config")

    def mix(self, name: str) -> dict:
        return json.loads((self.root / "bench" / "traffic" / f"{name}.json").read_text())

    def metrics(self, cell: str, trace: bool) -> List[dict]:
        """The metrics a run of ``cell`` reports: end to end without the
        trace, per layer with it; each metric without a ``workloads`` list
        applies to every cell."""
        group = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py", "metric")

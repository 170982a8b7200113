"""``BENCHMARK.json`` and the files it names. The harness holds no list
of configurations, traffic mixes, drivers or per-layer metrics: each is
a file of its own, found by the name the manifest gives."""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Dict, List

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class Manifest:
    def __init__(self, root: Path):
        self.root = Path(root)
        self.data = json.loads((self.root / "BENCHMARK.json").read_text())
        self.bench = self.root / self.data["paths"][0]

    # ------------------------------------------------------------ entries

    def workload(self, name: str) -> Dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have: "
                       f"{[w['name'] for w in self.data['workloads']]})")

    def config_entry(self, name: str) -> Dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def metrics_for(self, kind: str, workload: str) -> List[Dict]:
        """The ``end_to_end`` or ``per_layer`` entries a cell reports:
        those with no ``workloads`` key, or with the cell in it."""
        return [m for m in self.data[kind]
                if "workloads" not in m or workload in m["workloads"]]

    # -------------------------------------------------------------- files

    def config(self, name: str) -> Dict:
        return json.loads(
            (self.root / self.config_entry(name)["file"]).read_text())

    def traffic(self, name: str) -> Dict:
        path = self.bench / "traffic" / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"no traffic file {path}")
        return json.loads(path.read_text())

    def driver(self, name: str) -> ModuleType:
        return _load(self.bench / "drivers" / f"{name}.py", f"driver {name}")

    def reference(self, name: str) -> ModuleType:
        return _load(self.bench / "reference" / f"{name}.py",
                     f"reference {name}")

    def layer_metric(self, name: str) -> ModuleType:
        return _load(self.bench / "layer_metrics" / f"{name}.py",
                     f"per-layer metric {name}")


def _load(path: Path, what: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"{what}: no file {path}")
    mod_name = "perfbench_" + re.sub(r"\W", "_", f"{path.parent.name}_{path.stem}")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module

"""Find a cell's pieces by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, and the harness finds it by
the name ``BENCHMARK.json`` gives:

* ``bench/configs/<config>.json``  the configuration as it is run;
* ``bench/mixes/<mix>.json``       the traffic parameters, whose ``kind``
                                   names a generator ``bench/traffic/<kind>.py``;
* ``bench/metrics/<metric>.py``    one reducer per metric;
* ``bench/work/<name>.py``         operations and bytes of one kernel or step;
* ``bench/reference/<name>.py``    the plain reference a configuration names;
* ``bench/limits/<cell>.json``     the limits ``correct`` is judged by.

Adding a cell, configuration, mix or metric therefore adds files and
entries and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Dict

ROOT = Path(__file__).resolve().parents[2]       # the checkout


class SpecError(Exception):
    """A name that BENCHMARK.json or a cell refers to has no file."""


class Spec:
    """``BENCHMARK.json`` and the ``bench/`` tree beside it."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench = self.root / "bench"
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise SpecError(f"no BENCHMARK.json in {self.root}")
        self.doc = json.loads(path.read_text())

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise SpecError(f"no config {name!r} in BENCHMARK.json")

    def mix(self, name: str) -> Dict[str, Any]:
        return self._json("mixes", name)

    def limits(self, cell: str) -> Dict[str, Any]:
        return self._json("limits", cell)

    def metrics(self, cell: str, trace: bool):
        """The metric entries a run of ``cell`` reports: its end-to-end
        metrics untraced, its per-layer metrics traced."""
        key = "per_layer" if trace else "end_to_end"
        return [m for m in self.doc[key]
                if cell in m.get("workloads", [cell])]

    def module(self, kind: str, name: str) -> ModuleType:
        """Import ``bench/<kind>/<name>.py`` (metrics, traffic, work,
        reference) under a name of its own."""
        path = self.bench / kind / f"{name}.py"
        if not path.is_file():
            raise SpecError(f"no {kind} module {name!r} at {path}")
        mod_name = f"bench_{kind}_{name}_{abs(hash(str(path)))}"
        if mod_name in sys.modules:
            return sys.modules[mod_name]
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
        return mod

    def _json(self, kind: str, name: str) -> Dict[str, Any]:
        path = self.bench / kind / f"{name}.json"
        if not path.is_file():
            raise SpecError(f"no {kind} file {name!r} at {path}")
        return json.loads(path.read_text())

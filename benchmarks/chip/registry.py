"""Finds the benchmark's parts by the names in `BENCHMARK.json`.

Everything that belongs to one configuration, one cell, one traffic mix,
one traffic kind, one per-layer metric or one kernel sits in a file of its
own under this directory, named after it:

    configs/<config>.json     the deployment as it is run
    cells/<cell>.json         the cell's correctness limits
    traffic/<mix>.json        a traffic mix: its kind and parameters
    traffic/<kind>.py         the load generator of one traffic kind
    metrics/<metric>.py       the reader of one per-layer metric
    kernels/<kernel>.py       operations and bytes of one kernel call

so a later change adds a cell, a mix or a metric by adding files and
entries, and edits no file that exists.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]

__all__ = ["Registry"]


class Registry:
    """The benchmark rooted at `repo` (a checkout holding BENCHMARK.json)
    with its files under `repo/benchmarks/chip`."""

    def __init__(self, repo: Path = REPO):
        self.repo = Path(repo)
        self.root = self.repo / "benchmarks" / "chip"
        self.spec = json.loads((self.repo / "BENCHMARK.json").read_text())
        self._modules: dict = {}

    def _json(self, kind: str, name: str) -> dict:
        path = self.root / kind / f"{name}.json"
        if not path.is_file():
            raise KeyError(f"no {kind} file named {name!r} ({path})")
        return json.loads(path.read_text())

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def cell(self, name: str) -> dict:
        return self._json("cells", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def module(self, kind: str, name: str):
        """The Python file `<kind>/<name>.py`, loaded by its path (names
        may hold `.` and `-`, which `import` cannot)."""
        key = (kind, name)
        if key not in self._modules:
            path = self.root / kind / f"{name}.py"
            if not path.is_file():
                raise KeyError(f"no {kind} module named {name!r} ({path})")
            mod_name = f"chipbench_{kind}_{name}".replace(".", "_").replace(
                "-", "_")
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[mod_name] = mod
            spec.loader.exec_module(mod)
            self._modules[key] = mod
        return self._modules[key]

    def end_to_end(self, cell: str) -> list:
        """The end-to-end metrics `cell` reports."""
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list:
        """The per-layer metrics a traced run of `cell` reads: those that
        list it, and those without a list whose end-to-end metric it
        reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.spec["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

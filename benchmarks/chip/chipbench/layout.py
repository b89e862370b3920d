"""Find a cell's configuration, traffic mix and metric readers by name.

Everything is data: ``BENCHMARK.json`` at the repository root names the
cells and metrics; a configuration is the file its entry names, and
``traffic/<mix>.json``, ``limits/<cell>.json`` (the correctness limit),
``metrics/<name>.py`` and ``archs/<model_type>.py`` (see ``arch.py``) are
looked up in the directories that ``paths`` lists, in order.  A new cell,
mix, metric or architecture is new files and new entries, never an edit.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from types import ModuleType

from . import arch


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    arch: ModuleType      # its model_type's module (see arch.py)
    traffic: dict         # the traffic file's contents
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list


class Layout:
    def __init__(self, root) -> None:
        self.root = pathlib.Path(root)
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise FileNotFoundError(f"no BENCHMARK.json in {self.root}")
        self.bench = json.loads(path.read_text())
        self.dirs = [self.root / p for p in self.bench["paths"]]

    def find(self, sub: str, filename: str) -> pathlib.Path:
        for d in self.dirs:
            p = d / sub / filename
            if p.is_file():
                return p
        raise FileNotFoundError(f"{sub}/{filename} is in none of "
                                f"{self.bench['paths']}")

    def cell(self, workload: str) -> Cell:
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"unknown workload {workload!r}; known: "
                           f"{sorted(cells)}")
        w = cells[workload]
        cfg_entry = {c["name"]: c for c in self.bench["configs"]}[w["config"]]
        config = json.loads((self.root / cfg_entry["file"]).read_text())
        traffic = json.loads(self.find("traffic", w["traffic"] + ".json")
                             .read_text())
        e2e = [m for m in self.bench["end_to_end"]
               if workload in m.get("workloads", [workload])]
        names = {m["name"] for m in e2e}
        per_layer = [m for m in self.bench["per_layer"]
                     if (workload in m["workloads"] if "workloads" in m
                         else m["moves"] in names)]
        return Cell(workload, int(w["chips"]), config,
                    arch.load(config["model_type"], self.dirs), traffic, e2e,
                    per_layer)

    def metric(self, name: str) -> ModuleType:
        """The reader module ``metrics/<name>.py``; it defines
        ``read(run) -> float | None`` (None: nothing to read)."""
        return arch.load_module(self.find("metrics", name + ".py"),
                                "chipbench_metric_" + name)
